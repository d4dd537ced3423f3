"""The device trace, reduced by ``trace_reduce``.

Metric file: ``{"reader": "trace", "value": "idle_share"}`` for the
device's idle share of the traced window, or ``{"value": "launch",
"programs": [regex, ...], "reduce": ..., "scale": 1000}`` for the
device time of one launch of the programs whose names match.
"""

import re

from . import reduce_values


def launches_of(trace: dict, patterns: list) -> list:
    out = []
    for name, durations in trace["launches"].items():
        if any(re.search(p, name) for p in patterns):
            out.extend(durations)
    return out


def read(metric: dict, ctx: dict):
    trace = ctx["trace"]
    if metric["value"] == "idle_share":
        out = trace["idle_share"]
    elif metric["value"] == "launch":
        out = reduce_values(launches_of(trace, metric["programs"]),
                            metric["reduce"])
    else:
        raise ValueError(f"unknown value {metric['value']!r}")
    return None if out is None else out * metric.get("scale", 1.0)
