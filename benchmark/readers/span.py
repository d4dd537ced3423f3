"""Host spans of the program's tracer, inside the measured window.

Metric file: ``{"reader": "span", "span": name, "value": "duration" |
"exclusive", "reduce": ..., "scale": 1000}``; or, for an interval that
runs from one span's start to the end of the next span of another name
that starts after it, ``{"span": first, "until": last, ...}``.
``exclusive`` is a span's duration less its same-thread children
(``eval.schedule`` less ``wave.park`` and what is under it).
"""

import bisect

from . import reduce_values


def read(metric: dict, ctx: dict):
    rows = [r for r in ctx["spans"] if r[0] == metric["span"]]
    if "until" in metric:
        # each span with the first ``until`` span that starts once it
        # has ended (the applier's passes carry no trace id: they are
        # paired by their order in time)
        lasts = sorted((r[4], r[4] + r[5]) for r in ctx["spans"]
                       if r[0] == metric["until"])
        starts = [s for s, _e in lasts]
        values = []
        for r in rows:
            i = bisect.bisect_left(starts, r[4] + r[5] - 1e-6)
            if i < len(lasts):
                values.append(lasts[i][1] - r[4])
    elif metric.get("value") == "exclusive":
        values = [max(r[5] - r[6], 0.0) for r in rows]
    else:
        values = [r[5] for r in rows]
    out = reduce_values(values, metric["reduce"])
    return None if out is None else out * metric.get("scale", 1.0)
