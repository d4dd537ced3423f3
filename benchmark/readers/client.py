"""The harness's own clock around its calls into the API.

Metric file: ``{"reader": "client", "value": "register" | "latency",
"reduce": "median" | "p95" | ..., "scale": 1000}``: the seconds
``jobs.register`` took, or the seconds from register sent to done,
over the jobs registered in the window (a job that was never done
counts with the wait it was given).
"""

from . import reduce_values


def read(metric: dict, ctx: dict):
    window = [r for r in ctx["records"]
              if ctx["t0"] <= r.t_send < ctx["t1"] and r.t_ack is not None]
    if metric["value"] == "register":
        values = [r.t_ack - r.t_send for r in window]
    elif metric["value"] == "latency":
        values = [(r.t_done if r.t_done is not None else ctx["drained_at"])
                  - r.t_send for r in window]
    else:
        raise ValueError(f"unknown value {metric['value']!r}")
    out = reduce_values(values, metric["reduce"])
    return None if out is None else out * metric.get("scale", 1.0)
