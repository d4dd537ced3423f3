"""What decides ``correct``: the answers the API served, held against
the plain reference and the configuration's guarantees.

Everything judged here was read over HTTP once the window had closed;
the cluster is the generator's plain record and the jobs are the
harness's own records. Each number compared has a limit of its own
(``limits`` of the workload file, set from readings on the chip:
PERF.md section 2), and every run prints each beside its limit.

The node choice is judged wave by wave. The evaluations a worker takes
in one batch run against one snapshot and say so (``SnapshotIndex`` of
``/v1/job/<id>/evaluations``); the device program places the batch's
members one after the other over a shared capacity carry, in one
launch or in several, each launch from a committed state: the stamped
snapshot, or one near it (after the wave before's stamp, before the
member's own plan commit). So for a
sampled wave the reference rebuilds what every node held at those
indices from the run's own allocations, finds an order of the members
and the launches under which each one's answers hold (the API shows
neither: ``explain_wave``), and replays every placement step over
EVERY node of the cluster in float64: feasibility, the scores, the
best of them.

Numbers (``kind`` max: the value may not pass the limit; min: it may
not fall below):

- ``jobs_never_done``: acknowledged registers that were not done two
  minutes past the close although, by the reference, one more of their
  allocations still fits somewhere. (A job the scheduler blocked
  because nothing fits any more got the right answer; it is counted
  under ``failed`` and printed as ``jobs_blocked_rightly``.)
- ``alloc_count_wrong``: done jobs whose served allocations are not
  exactly the ``count`` distinct names asked for.
- ``overcommitted_nodes``: nodes that at some raft index held more
  cpu, memory, disk or devices than they have, resident usage included.
- ``constraint_violations``: allocations outside the job's
  datacenters or node class, or sharing a node under distinct_hosts.
- ``readback_mismatch``: sampled allocations whose full record
  (``/v1/allocation/<id>``) disagrees with the job's listing.
- ``infeasible_chosen``: sampled placements on a node that, by the
  reference, could not take them in the snapshot they were made in.
- ``chosen_short_of_best``: the widest gap by which the reference's
  score of a chosen node lies below its best over every feasible node
  of the cluster, at that step.
- ``topk_short_of_best``: the same for the recorded candidates: the
  widest gap between the reference's i-th best feasible score and the
  i-th best of the nodes the program recorded.
- ``score_max_abs_diff``: the widest gap between a recorded score and
  the reference's, over every recorded candidate of every sampled step.
- ``placements_compared``, ``scores_compared``: how many there were.
- ``jobs_not_replayable``: the window's done jobs whose wave the
  reference cannot replay, because an evaluation of it placed its plan
  in more than one piece (``waves_of``): answers the applier refuses
  would otherwise escape the comparison by being refused.
"""

from __future__ import annotations

import re

import numpy as np

from . import reference
from .reference import DIMS

_INDEX = re.compile(r"\[(\d+)\]$")

#: the limits of an exact comparison
EXACT = ("jobs_never_done", "alloc_count_wrong", "overcommitted_nodes",
         "constraint_violations", "readback_mismatch", "infeasible_chosen")
#: the gaps a member's answers are judged by when the order is looked for
GAPS = ("score_max_abs_diff", "chosen_short_of_best", "topk_short_of_best")
#: the score of a node that cannot take the placement: below any real
#: one, and finite, so that a gap to it can be printed
NO_SCORE = -1.0e6


class RunAlloc:
    __slots__ = ("id", "job", "step", "node", "create", "stop")

    def __init__(self, id, job, step, node, create, stop):
        self.id, self.job, self.step = id, job, step
        self.node, self.create, self.stop = node, create, stop


def fetch_run_allocs(api, records: list, node_index: dict):
    """Every allocation the API lists for the run's jobs."""
    by_job: dict = {}
    unknown_nodes = 0
    for rec in records:
        out = []
        for a in api.jobs.allocations(rec.id):
            m = _INDEX.search(a["Name"])
            node = node_index.get(a["NodeID"], -1)
            unknown_nodes += node < 0
            stopped = a["DesiredStatus"] != "run"
            out.append(RunAlloc(a["ID"], rec.id, int(m.group(1)) if m else -1,
                                node, a["CreateIndex"],
                                a["ModifyIndex"] if stopped else None))
        by_job[rec.id] = out
    return by_job, unknown_nodes


def count_wrong(records: list, by_job: dict, stop_when_done: bool) -> int:
    wrong = 0
    for rec in records:
        allocs = by_job[rec.id]
        if not stop_when_done:
            allocs = [a for a in allocs if a.stop is None]
        steps = {a.step for a in allocs}
        if (len(allocs) != rec.plain["count"]
                or steps != set(range(rec.plain["count"]))):
            wrong += 1
    return wrong


def overcommitted(plain: dict, records: list, by_job: dict) -> int:
    """Sweep the run's allocations in raft-index order (at one index,
    stops before placements) over the resident usage."""
    used = {d: plain[f"used_{d}"].copy() for d in DIMS}
    ask = {r.id: r.plain for r in records}
    events = []
    for jid, allocs in by_job.items():
        for a in allocs:
            if a.node < 0:
                continue
            events.append((a.create, 1, a.node, jid))
            if a.stop is not None:
                events.append((a.stop, 0, a.node, jid))
    events.sort(key=lambda e: (e[0], e[1]))
    over = set()
    for _index, add, node, jid in events:
        sign = 1.0 if add else -1.0
        for d in DIMS:
            used[d][node] += sign * ask[jid][d]
            if add and used[d][node] > plain[f"cap_{d}"][node] + 1e-9:
                over.add(node)
    return len(over)


def usage_at(plain: dict, records: list, by_job: dict, index=None) -> dict:
    """What every node held at raft ``index`` (None: now): the resident
    usage and each allocation of the run committed by then and not yet
    stopped."""
    used = {d: plain[f"used_{d}"].copy() for d in DIMS}
    ask = {r.id: r.plain for r in records}
    for jid, allocs in by_job.items():
        for a in allocs:
            if a.node < 0:
                continue
            if index is None:
                live = a.stop is None
            else:
                live = a.create <= index and (a.stop is None
                                              or a.stop > index)
            if live:
                for d in DIMS:
                    used[d][a.node] += ask[jid][d]
    return used


def could_place_more(plain: dict, rec, records: list, by_job: dict) -> bool:
    """Whether one more allocation of ``rec``'s job fits anywhere once
    everything the run committed is in place. Jobs that stay only ever
    fill the cluster, so a job that fits now fitted when the scheduler
    gave up on it: then the blocked evaluation was the wrong answer.
    Where nothing fits, blocking it was the right one."""
    used = usage_at(plain, records, by_job)
    own = np.zeros(len(plain["node_ids"]))
    for a in by_job[rec.id]:
        if a.node >= 0 and a.stop is None:
            own[a.node] += 1
    return bool(reference.feasible_of_all(
        plain, reference.static_mask(plain, rec.plain), used, rec.plain,
        own).any())


def constraint_violations(plain: dict, records: list, by_job: dict) -> int:
    bad = 0
    for rec in records:
        job = rec.plain
        live = [a for a in by_job[rec.id] if a.node >= 0]
        for a in live:
            if plain["datacenter"][a.node] not in job["datacenters"]:
                bad += 1
            if job["node_class"] and \
                    plain["node_class"][a.node] != job["node_class"]:
                bad += 1
        if job["distinct_hosts"]:
            nodes = [a.node for a in live]
            bad += len(nodes) - len(set(nodes))
    return bad


# -- waves -------------------------------------------------------------------

def waves_of(api, records: list, by_job: dict) -> dict:
    """{snapshot index: [job record, ...]}: the waves the reference can
    replay. Every evaluation of the run that schedules (a deregister
    places nothing) is grouped by the snapshot it ran against; a wave
    is kept where each of its evaluations is a job's one register
    evaluation, complete, and its allocations are the ``count`` asked
    for, committed at one index (a plan partly refused and placed again
    took capacity in its wave that the API no longer shows)."""
    groups: dict = {}
    for rec in records:
        evals = [e for e in api.jobs.evaluations(rec.id)
                 if e.get("TriggeredBy") != "job-deregister"]
        allocs = by_job[rec.id]
        clean = (len(evals) == 1 and evals[0].get("Status") == "complete"
                 and evals[0].get("TriggeredBy") == "job-register"
                 and rec.t_done is not None
                 and len({a.create for a in allocs}) == 1
                 and all(a.node >= 0 for a in allocs)
                 and sorted(a.step for a in allocs)
                 == list(range(rec.plain["count"])))
        for e in evals:
            groups.setdefault(e.get("SnapshotIndex"), []).append(
                (rec, clean))
    return {snap: [rec for rec, _ok in members]
            for snap, members in groups.items()
            if snap and all(ok for _rec, ok in members)}


def sample_waves(waves: dict, window: list, n: int, seed: int,
                 always=()) -> list:
    """Snapshot indices of ``n`` replayable waves that hold a job of
    the window, drawn from the seed, the wave of the window's job with
    the most allocations among them; and the waves of ``always`` (the
    job that was sent alone)."""
    in_window = {r.id for r in window}
    holds = [s for s in sorted(waves)
             if any(r.id in in_window for r in waves[s])]
    picked = []
    if holds:
        longest = max(holds, key=lambda s: max(
            r.plain["count"] for r in waves[s] if r.id in in_window))
        rest = [s for s in holds if s != longest]
        rng = np.random.default_rng([seed, 2])
        picked = [longest] + [rest[i] for i in
                              rng.permutation(len(rest))[:max(n - 1, 0)]]
    for rec in always:
        picked += [s for s in waves if rec in waves[s] and s not in picked]
    return picked


def fetch_wave(api, members: list, by_job: dict) -> list:
    """[(job record, [(RunAlloc, full allocation), ...] by step)], the
    full allocations as ``/v1/allocation/<id>`` serves them."""
    out = []
    for rec in members:
        allocs = sorted(by_job[rec.id], key=lambda a: a.step)
        out.append((rec, [(a, api.allocations.info(a.id)) for a in allocs]))
    return out


def replay_member(plain: dict, node_index: dict, rack_attr: str, rec,
                  allocs: list, used: dict, served=None) -> dict:
    """One member's placement steps, in step order, over every node of
    the cluster, given what every node holds when its first step runs
    (``used``; not changed). Returns the member's numbers and
    ``placed``, the usage once its last step has run.

    ``served``, when given, stands in the program's place: a function
    (job, node, prior_nodes, spread_values, seen) -> score used instead
    of the recorded score (the control)."""
    job = rec.plain
    n = len(plain["node_ids"])
    used = {d: used[d].copy() for d in DIMS}
    static_ok = reference.static_mask(plain, job)
    own = np.zeros(n)
    codes = counts = values = None
    if job["spread"]:
        values = reference.spread_values_of(plain, job["spread"], rack_attr)
        names, codes = np.unique(np.array(values), return_inverse=True)
        counts = np.zeros(len(names))
    out = dict.fromkeys(GAPS, 0.0)
    out.update(readback_mismatch=0, infeasible_chosen=0,
               placements_compared=0, scores_compared=0)
    prior: list = []
    for a, full in allocs:
        if (full["NodeID"] != plain["node_ids"][a.node]
                or full["JobID"] != rec.id
                or not full["Name"].endswith(f"[{a.step}]")):
            out["readback_mismatch"] += 1
        feasible = reference.feasible_of_all(plain, static_ok, used, job, own)
        score = reference.scores_of_all(plain, used, job, own, codes, counts)
        if not feasible[a.node]:
            out["infeasible_chosen"] += 1
        ranked = np.where(feasible, score, NO_SCORE)
        out["chosen_short_of_best"] = max(
            out["chosen_short_of_best"], float(ranked.max() - score[a.node]))
        meta = (full.get("Metrics") or {}).get("ScoreMeta") or []
        cand = [(node_index.get(m[0], -1), float(m[2])) for m in meta]
        cand = [(node, s) for node, s in cand if node >= 0]
        if cand:
            nodes = np.array([node for node, _s in cand])
            k = len(nodes)
            best_k = -np.sort(-np.partition(ranked, n - k)[n - k:])
            theirs = -np.sort(-ranked[nodes])
            out["topk_short_of_best"] = max(
                out["topk_short_of_best"], float(np.max(best_k - theirs)))
        for node, recorded in cand:
            if served is not None:
                k_own = own[node]
                recorded = served(job, node, prior, values, (
                    used["cpu"][node] - plain["used_cpu"][node]
                    - k_own * job["cpu"],
                    used["mem"][node] - plain["used_mem"][node]
                    - k_own * job["mem"]))
            out["score_max_abs_diff"] = max(
                out["score_max_abs_diff"], abs(recorded - score[node]))
            out["scores_compared"] += 1
        out["placements_compared"] += 1
        for d in DIMS:
            used[d][a.node] += job[d]
        own[a.node] += 1
        if codes is not None:
            counts[codes[a.node]] += 1
        prior.append(a.node)
    out["placed"] = used
    return out


#: how many member replays a run's searches for its waves' orders may
#: cost, all together (a tenth of a second each at 10,000 nodes)
SEARCH_REPLAYS = 400


def explain_wave(plain: dict, node_index: dict, rack_attr: str,
                 fetched: list, states: list, usage_of, limit: float,
                 budget: list):
    """[(member, state)], an order of the wave's members under which
    each one's answers hold: ``state`` is None where the member ran in
    one launch with the member before it, over their shared capacity
    carry, or the raft index of the state it started a launch from.
    None where nothing explains the answers.

    A batch's members run as one launch, one after the other, or as
    several (a member whose wait for the others runs out fires with
    those parked so far), and a member whose plan the applier sends
    back places again alone against a refreshed state: every launch
    starts from a committed state, the stamped snapshot or another of
    ``states`` that was committed before the member's own plan. The
    API shows none of this, so it is searched for, depth first: each
    member left is tried after the launch so far, then from each state.
    ``budget`` (replays left, in a list) bounds the search."""
    commit = [min(a.create for a, _full in allocs)
              for _rec, allocs in fetched]

    def holds(got) -> bool:
        return (max(got[g] for g in GAPS) <= limit
                and not got["infeasible_chosen"])

    def search(left: list, carried):
        if not left:
            return []
        for i in left:
            rec, allocs = fetched[i]
            starts = [(None, carried)] if carried is not None else []
            starts += [(index, None) for index in states
                       if index < commit[i]]
            for index, used in starts:
                if budget[0] <= 0:
                    return None
                budget[0] -= 1
                got = replay_member(
                    plain, node_index, rack_attr, rec, allocs,
                    usage_of(index) if used is None else used)
                if holds(got):
                    rest = search([j for j in left if j != i], got["placed"])
                    if rest is not None:
                        return [(i, index)] + rest
        return None

    return search(list(range(len(fetched))), None)


def one_launch(plain: dict, node_index: dict, rack_attr: str,
               fetched: list, snap: int, usage_of) -> list:
    """What an unexplained wave is reported under: one launch from the
    stamped snapshot, at every place the member whose recorded scores
    lie closest to the reference's, so that what the other gaps then
    read is the fault and not the order."""
    left, used, out = list(range(len(fetched))), usage_of(snap), []
    while left:
        tried = [(replay_member(plain, node_index, rack_attr, *fetched[i],
                                used), i) for i in left]
        got, i = min(tried, key=lambda t: [t[0][g] for g in GAPS])
        left.remove(i)
        used = got["placed"]
        out.append((i, None if out else snap))
    return out


def replay_wave(plain: dict, node_index: dict, rack_attr: str,
                fetched: list, explained: list, usage_of,
                served=None) -> list:
    """The members' numbers under ``explained`` (``explain_wave``)."""
    out, used = [], None
    for i, index in explained:
        rec, allocs = fetched[i]
        got = replay_member(plain, node_index, rack_attr, rec, allocs,
                            used if index is None else usage_of(index),
                            served)
        used = got.pop("placed")
        out.append(got)
    return out


def compare_waves(api, picked: list, waves: dict, plain: dict, config: dict,
                  records: list, by_job: dict, limits: dict,
                  served=None, before=None) -> tuple:
    """The sampled waves against the reference: (numbers, what a second
    pass with ``served`` needs: what was fetched and how each wave was
    explained).

    ``waves_members_launches_ahead`` says for each wave how many
    members it had, how many launches explain it, and how far from the
    stamped snapshot (behind it: negative) the farthest state lay that
    a launch started from (0 in all but one wave seen on the chip:
    PERF.md, finding 1)."""
    node_index = {nid: i for i, nid in enumerate(plain["node_ids"])}
    rack_attr = config["cluster"]["rack_attribute"]
    limit = min(limits[g]["limit"] for g in GAPS)
    out = dict.fromkeys(GAPS, 0.0)
    out.update(readback_mismatch=0, infeasible_chosen=0,
               placements_compared=0, scores_compared=0)
    fetched, explained = before or ({}, {})
    moved = sorted({i for allocs in by_job.values() for a in allocs
                    for i in (a.create, a.stop) if i is not None})
    usages: dict = {}

    def usage_of(index: int) -> dict:
        if index not in usages:
            usages[index] = usage_at(plain, records, by_job, index)
        return usages[index]

    shape, unexplained = [], []
    budget = [SEARCH_REPLAYS]
    for snap in picked:
        usages.clear()
        if snap not in fetched:
            fetched[snap] = fetch_wave(api, waves[snap], by_job)
        if snap not in explained:
            # the stamped snapshot; then every later state, up to the
            # wave's last plan commit, that moved an allocation; then
            # the earlier ones, back to the wave before's stamp
            last = max(a.create for _r, allocs in fetched[snap]
                       for a, _full in allocs)
            before_it = max((s for s in waves if s < snap), default=snap)
            states = ([snap] + [i for i in moved if snap < i < last]
                      + [i for i in reversed(moved)
                         if before_it <= i < snap])
            explained[snap] = explain_wave(
                plain, node_index, rack_attr, fetched[snap], states,
                usage_of, limit, budget)
            if explained[snap] is None:
                unexplained.append([snap, [i - snap for i in states]])
                explained[snap] = one_launch(
                    plain, node_index, rack_attr, fetched[snap], snap,
                    usage_of)
        how = explained[snap]
        for got in replay_wave(plain, node_index, rack_attr, fetched[snap],
                               how, usage_of, served):
            for key, value in got.items():
                out[key] = max(out[key], value) if key in GAPS \
                    else out[key] + value
        started = [index - snap for _i, index in how if index is not None]
        shape.append([len(how), len(started), max(started, key=abs)])
    out["waves_members_launches_ahead"] = shape
    if unexplained:
        # for the reader of a run that is not correct: the wave's stamp
        # and how far ahead the states lay that explained nothing
        out["waves_not_explained"] = unexplained
    return out, (fetched, explained)


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit, kind)]) for the result line."""
    rows = []
    correct = True
    for name, value in numbers.items():
        if name in EXACT:
            limit, kind = 0, "max"
        elif name in limits:
            limit, kind = limits[name]["limit"], limits[name]["kind"]
        else:
            continue
        ok = bool(value <= limit if kind == "max" else value >= limit)
        correct = correct and ok
        rows.append((name, value, limit, kind))
    return correct, rows
