"""The device's wait between launches, cut by the stage that held the
next launch.

Metric file: ``{"reader": "gap", "label": <label>, "scale": 1000}``:
the label's seconds over the number of gaps, a mean, so that the eight
labels sum to the mean gap.

From host spans alone, over the whole measured window. A launch is a
``wave.launch`` span with its ``kernel.dispatch`` (or
``kernel.compile``) and ``kernel.execute`` children; launches are taken
in the order of their calls. Each launch after the first makes one gap:
from the latest ``kernel.execute`` end of the launches before it to its
own call, or none (0) where one of them was still executing. A gap is
cut into labels that partition it; at each instant the first rule that
holds wins:

1. ``gc``: a ``gc.collect`` span is open (it stops every thread).
2. ``launch``: inside the next launch's own ``wave.launch`` (its
   assembly).
3. ``schedule``: from the start of the releaser's ``eval.schedule`` on:
   the evaluation that completed the rendezvous (the record's
   ``released_by``; the launch's own trace id where that is absent),
   until it fired the launch, or finished and let it fire.
4. ``dequeue``: from the releaser's enqueue (its ``eval.e2e`` start) to
   its ``eval.schedule`` start: the broker and the worker's batch.
5. Before the releaser's enqueue:

   - ``api``: an ``http.*`` span, or an ``fsm.entry`` of any type but
     plan results, is open;
   - ``commit``: ``plan.queue_wait``, ``plan.evaluate``,
     ``plan.group_commit``, ``plan.commit``, a ``raft.*`` span or an
     ``fsm.entry`` of plan results is open;
   - ``evals``: another evaluation's ``eval.schedule`` is open: the last
     launch's members copying their results back, building and
     submitting their plans, and purge evaluations;
   - ``client``: none of these: event delivery and the client's
     turn-around.

A program whose launch record carries no ``release`` (one from before
these attributes) reads nothing.
"""

from __future__ import annotations

import bisect
import math
import sys

from ..trace_reduce import union

LABELS = ("gc", "launch", "schedule", "dequeue", "api", "commit", "evals",
          "client")
#: the applier's spans (rule 5, ``commit``), besides ``raft.*``
COMMIT_SPANS = ("plan.queue_wait", "plan.evaluate", "plan.group_commit",
                "plan.commit")
PLAN_RESULTS = "ApplyPlanResultsRequestType"
CALLS = ("kernel.dispatch", "kernel.compile")


class _Union:
    """Merged intervals, and whether an instant lies inside one."""

    def __init__(self, intervals: list) -> None:
        self.spans = union(intervals)
        self.starts = [s for s, _e in self.spans]

    def covers(self, t: float) -> bool:
        i = bisect.bisect_right(self.starts, t) - 1
        return i >= 0 and t < self.spans[i][1]

    def edges(self, lo: float, hi: float) -> list:
        """The boundaries of the intervals that fall inside (lo, hi)."""
        out = []
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        for s, e in self.spans[i:]:
            if s >= hi:
                break
            out.extend(x for x in (s, e) if lo < x < hi)
        return out


def launches(rows: list) -> list:
    """(call start, execute end, wave.launch row) of every launch that
    has both children, in the order of the calls."""
    own = {r[2]: r for r in rows if r[0] == "wave.launch"}
    calls: dict = {}
    ready: dict = {}
    for r in rows:
        if r[3] not in own:
            continue
        if r[0] in CALLS:
            calls[r[3]] = min(calls.get(r[3], math.inf), r[4])
        elif r[0] == "kernel.execute":
            ready[r[3]] = max(ready.get(r[3], -math.inf), r[4] + r[5])
    return sorted((calls[i], ready[i], own[i]) for i in own
                  if i in calls and i in ready)


def _starts_by_trace(rows: list, name: str) -> dict:
    out: dict = {}
    for r in rows:
        if r[0] == name and r[1]:
            out.setdefault(r[1], []).append(r[4])
    for starts in out.values():
        starts.sort()
    return out


def _latest(starts: list, at: float):
    """The latest of sorted ``starts`` at or before ``at``; None."""
    i = bisect.bisect_right(starts, at)
    return starts[i - 1] if i else None


def split(rows: list):
    """(gaps, {label: seconds}) over the launches in ``rows``; None where
    the launch record carries no ``release``."""
    order = launches(rows)
    if not any((r[10] or {}).get("release") for _c, _e, r in order):
        return None
    gc = _Union([(r[4], r[4] + r[5]) for r in rows if r[0] == "gc.collect"])
    api, commit, evals = [], [], []
    for r in rows:
        name = r[0]
        if name == "eval.schedule":
            evals.append((r[4], r[4] + r[5]))
        elif name.startswith("http."):
            api.append((r[4], r[4] + r[5]))
        elif name == "fsm.entry":
            plan = (r[10] or {}).get("type") == PLAN_RESULTS
            (commit if plan else api).append((r[4], r[4] + r[5]))
        elif name in COMMIT_SPANS or name.startswith("raft."):
            commit.append((r[4], r[4] + r[5]))
    api, commit, evals = _Union(api), _Union(commit), _Union(evals)
    schedules = _starts_by_trace(rows, "eval.schedule")
    enqueues = _starts_by_trace(rows, "eval.e2e")
    seconds = dict.fromkeys(LABELS, 0.0)
    gaps = 0
    last_ready = None
    for call, ready, rec in order:
        if last_ready is not None:
            gaps += 1
            if call > last_ready:
                _label(last_ready, call, rec, (gc, api, commit, evals),
                       schedules, enqueues, seconds)
        last_ready = ready if last_ready is None else max(last_ready, ready)
    return gaps, seconds


def _label(lo: float, hi: float, rec: tuple, unions: tuple,
           schedules: dict, enqueues: dict, seconds: dict) -> None:
    """Add the labelled pieces of the gap [lo, hi) before the launch
    ``rec`` to ``seconds``. ``unions``: where ``gc.collect``, the API,
    the commit path and scheduling runs are open."""
    gc, api, commit, evals = unions
    releaser = (rec[10] or {}).get("released_by") or rec[1]
    scheduled = _latest(schedules.get(releaser, []), hi)
    if scheduled is None:
        scheduled = math.inf
    enqueued = _latest(enqueues.get(releaser, []), scheduled)
    if enqueued is None:
        enqueued = scheduled
    assembling = rec[4]
    cuts = {lo, hi}
    cuts.update(x for x in (assembling, scheduled, enqueued) if lo < x < hi)
    for u in unions:
        cuts.update(u.edges(lo, hi))
    cuts = sorted(cuts)
    for x, y in zip(cuts, cuts[1:]):
        mid = (x + y) / 2.0
        if gc.covers(mid):
            label = "gc"
        elif mid >= assembling:
            label = "launch"
        elif mid >= scheduled:
            label = "schedule"
        elif mid >= enqueued:
            label = "dequeue"
        elif api.covers(mid):
            label = "api"
        elif commit.covers(mid):
            label = "commit"
        elif evals.covers(mid):
            label = "evals"
        else:
            label = "client"
        seconds[label] += y - x


def read(metric: dict, ctx: dict):
    memo = ctx.get("gap_split")
    if memo is None or memo[0] is not ctx["spans"]:
        out = split(ctx["spans"])
        memo = (ctx["spans"], out)
        ctx["gap_split"] = memo
        if out is not None and out[0]:
            gaps, seconds = out
            print(f"gap: {gaps} gaps, mean "
                  f"{sum(seconds.values()) / gaps * 1e3:.3f} ms: "
                  + ", ".join(f"{k} {v / gaps * 1e3:.3f}"
                              for k, v in seconds.items()),
                  file=sys.stderr, flush=True)
    out = memo[1]
    if out is None or not out[0]:
        return None
    gaps, seconds = out
    return seconds[metric["label"]] / gaps * metric.get("scale", 1.0)
