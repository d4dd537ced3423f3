"""Thread CPU of a span, or the wall it spent off the CPU.

Metric file: ``{"reader": "span_cpu", "span": name, "attrs": {key:
[value, ...]}, "exclude": [name, ...], "value": "cpu" | "offcpu",
"reduce": "median", "scale": 1000}``. The rows of ``span`` whose
attribute ``key`` is one of its values, each less the subtrees of its
same-thread descendants named in ``exclude`` (the waits under a
scheduling run: parked at the rendezvous, the launch it fired, the
applier's answer). ``cpu`` is the thread's CPU seconds over what is
left; ``offcpu`` is the wall seconds over it less that CPU: waiting for
the interpreter lock and for other locks.

Rows that lack the attribute, or whose CPU clock was not read (the
tracer samples whole span trees where reading it is dear, and marks
the others unread), are skipped: a program from before the attributes
reads nothing.
"""

from __future__ import annotations

from . import reduce_values


def _left(row: tuple, kids: dict, exclude: tuple):
    """(wall, cpu) of ``row`` less its excluded subtrees; None where an
    excluded subtree's CPU was not read."""
    wall, cpu = row[5], row[7]
    todo = list(kids.get(row[2], ()))
    while todo:
        r = todo.pop()
        if r[9] != row[9]:
            continue
        if r[0] in exclude:
            if r[7] is None:
                return None
            wall -= r[5]
            cpu -= r[7]
        else:
            todo.extend(kids.get(r[2], ()))
    return wall, cpu


def read(metric: dict, ctx: dict):
    rows = ctx["spans"]
    want = metric.get("attrs", {})
    picked = [r for r in rows if r[0] == metric["span"]
              and r[7] is not None and r[10]
              and all(r[10].get(k) in v for k, v in want.items())]
    if not picked:
        return None
    exclude = tuple(metric.get("exclude", ()))
    kids: dict = {}
    if exclude:
        for r in rows:
            kids.setdefault(r[3], []).append(r)
    values = []
    for r in picked:
        left = _left(r, kids, exclude)
        if left is None:
            continue
        wall, cpu = left
        if metric["value"] == "cpu":
            values.append(max(cpu, 0.0))
        elif metric["value"] == "offcpu":
            values.append(max(wall - cpu, 0.0))
        else:
            raise ValueError(f"unknown value {metric['value']!r}")
    out = reduce_values(values, metric["reduce"])
    return None if out is None else out * metric.get("scale", 1.0)
