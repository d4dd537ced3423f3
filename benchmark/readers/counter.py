"""Counters read at the window's first and last instant.

Metric file: ``{"reader": "counter", "counters": [path, ...],
"reduce": "delta" | "ratio", "scale": 100}``. ``delta`` is the growth
of the first counter over the window; ``ratio`` is the growth of the
first over the growth of the second, and nothing where the second did
not grow. A path is ``module:object.attribute`` (``()`` calls, a dict
is indexed), or ``harness:...`` for the harness's own counters.
"""


def read(metric: dict, ctx: dict):
    grown = []
    for path in metric["counters"]:
        before, after = ctx["counters"].get(path, (None, None))
        if before is None or after is None:
            return None
        grown.append(after - before)
    if metric["reduce"] == "delta":
        out = grown[0]
    elif metric["reduce"] == "ratio":
        if grown[1] <= 0:
            return None
        out = grown[0] / grown[1]
    else:
        raise ValueError(f"unknown reduction {metric['reduce']!r}")
    return out * metric.get("scale", 1.0)
