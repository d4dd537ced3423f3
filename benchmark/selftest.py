#!/usr/bin/env python3
"""benchmark/selftest.py: the yardstick checked against hand-counted
cases. Run by hand and before a chip call; it needs no chip:

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py

- the trace reduction on a synthetic ``.xplane`` (text proto): busy
  union, idle share, per-program launch times, gap labelling;
- ``roofline.py`` on hand-counted shapes;
- the percentile arithmetic on a window that holds a stall, the
  window that opens and closes on a completion, and the pairing of
  spans that share no trace id;
- the reference's formulas on hand-worked nodes, the control
  (bfloat16) reading apart from float64, the reference over every node
  at once against the reference node by node, and the replay of a wave
  whose members are handed over in the wrong order.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import reference, roofline, trace_reduce  # noqa: E402
from benchmark.generators import jobs as jobs_mod        # noqa: E402
from benchmark.readers import percentile                 # noqa: E402

MS = 1_000_000_000          # picoseconds in a millisecond


def _events(rows: list) -> str:
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} }}" for m, s, d in rows)


def synthetic_trace() -> str:
    """One TPU plane: two launches of ``jit_joint`` (100-300 ms and
    600-700 ms) and one of ``jit_other`` (300-350 ms), whose ops leave
    the device idle 150-200 ms, 350-600 ms and after 700 ms; and a
    host plane that holds the sync marker at 10 ms."""
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
{_events([(1, 100, 200), (2, 300, 50), (1, 600, 100)])}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{_events([(3, 100, 50), (4, 120, 30), (3, 200, 100), (4, 300, 50),
          (3, 600, 100)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_joint(7)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_other(9)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "while.2" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{_events([(1, 10, 0.001)])}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{trace_reduce.SYNC_NAME}" }} }}
}}
"""


def close(a: float, b: float, tol: float = 1e-9) -> bool:
    return abs(a - b) <= tol


def test_trace_reduce() -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto(synthetic_trace())
    planes = trace_reduce.planes_of(data)
    assert close(planes["sync_trace_s"], 0.010), planes["sync_trace_s"]
    # the harness's monotonic clock read 1000.010 at the marker
    to_mono = 1000.010 - planes["sync_trace_s"]
    spans = [("eval.schedule", 1000.100, 0.500),     # 100-600 ms
             ("plan.commit", 1000.360, 0.200),       # 360-560 ms, inner
             ("broker.wait", 1000.700, 0.300)]       # 700-1000 ms
    out = trace_reduce.reduce_trace(planes, 0.0, 0.8, spans, to_mono)
    # busy: 100-150, 200-350, 600-700 = 300 ms of 800
    assert close(out["busy_s"], 0.300), out["busy_s"]
    assert close(out["window_s"], 0.8)
    assert close(out["idle_share"], 1 - 0.3 / 0.8)
    assert sorted(out["launches"]) == ["jit_joint", "jit_other"]
    assert [round(x, 6) for x in out["launches"]["jit_joint"]] == [0.2, 0.1]
    assert close(out["program_s"]["jit_joint"], 0.3)
    assert close(out["program_s"]["jit_other"], 0.05)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 0-100 no span; 150-200 eval.schedule; 350-600 plan.commit (the
    # innermost at 475 ms); 700-800 broker.wait
    assert close(gaps["no span open"], 0.100), gaps
    assert close(gaps["eval.schedule"], 0.050), gaps
    assert close(gaps["plan.commit"], 0.250), gaps
    assert close(gaps["broker.wait"], 0.100), gaps
    ops = dict(out["breakdown"]["device_ops"])
    # fusion.1 at 100-150 ms contains while.2 at 120-150: not a leaf
    assert close(ops["fusion.1"], 0.200) and close(ops["while.2"], 0.080)
    # a launch cut by the window's end is not a launch of the window
    cut = trace_reduce.reduce_trace(planes, 0.0, 0.65, spans, to_mono)
    assert [round(x, 6) for x in cut["launches"]["jit_joint"]] == [0.2]
    assert close(cut["program_s"]["jit_joint"], 0.25)   # the cut one's part
    assert close(cut["busy_s"], 0.250)


def test_no_whole_launch() -> None:
    """A metric whose programs have no whole launch in the traced
    seconds reads nothing; it is never read from a second trace."""
    from jax.profiler import ProfileData

    from benchmark.readers import trace as trace_reader

    planes = trace_reduce.planes_of(
        ProfileData.from_text_proto(synthetic_trace()))

    def launch(programs, lo, hi):
        metric = {"value": "launch", "programs": programs,
                  "reduce": "median", "scale": 1000}
        ctx = {"trace": trace_reduce.reduce_trace(planes, lo, hi, [], 0.0)}
        return trace_reader.read(metric, ctx)

    assert close(launch(["jit_joint"], 0.0, 0.8), 150.0, 1e-6)
    assert close(launch(["nothing", "jit_other$"], 0.0, 0.8), 50.0, 1e-6)
    assert launch(["jit_nothing"], 0.0, 0.8) is None
    # both launches of jit_joint lie across an edge of 0.15 to 0.65 s
    assert launch(["jit_joint"], 0.15, 0.65) is None
    assert close(launch(["jit_other"], 0.15, 0.65), 50.0, 1e-6)


def test_roofline() -> None:
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    lean = {"count": 10, "gpu": 0, "spread": None}
    # 7 base planes + own count = 8 planes x 4 bytes x 10,000 nodes
    assert roofline.step_cost(lean, 10_000) == (320_000, 220_000)
    both = {"count": 3, "gpu": 1, "spread": "${node.datacenter}"}
    assert roofline.step_cost(both, 10_000) == (400_000, 300_000)
    out = roofline.least_seconds([lean, both], 10_000, peak)
    assert out["steps"] == 13
    assert out["bytes"] == 10 * 320_000 + 3 * 400_000
    assert out["bound"] == "bandwidth"
    assert close(out["seconds"], out["bytes"] / 819e9)
    try:
        roofline.peak_of({"TPU v5 lite": peak}, "TPU v9")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device has to be an error")


def test_percentiles() -> None:
    # 100 jobs at 100..199 ms, of which a stall held six for 5 s
    lat = [100.0 + i for i in range(94)] + [5000.0] * 6
    assert percentile(lat, 0.95) == 5000.0
    assert percentile(lat, 0.50) == 149.0
    assert percentile([7.0], 0.95) == 7.0
    lat = [100.0 + i for i in range(96)] + [5000.0] * 4
    assert percentile(lat, 0.95) == 194.0       # four of 100: beyond p95


def test_window() -> None:
    """A window that opens and closes on a completion counts whole
    waves; one cut by the clock counts one more or less by chance."""
    from benchmark.run import end_to_end
    from benchmark.loops.closed import Loop
    from benchmark.traffic import JobRecord

    loop = Loop(None, [], {})
    records = []
    for wave in range(8):               # a wave of 4 done every 2 s
        for i in range(4):
            r = JobRecord(len(records), {"id": f"j{len(records)}"}, {})
            r.t_send, r.acked = 2.0 * wave - 1.5, True
            r.t_done = 2.0 * wave + 0.01 * i
            records.append(r)
            loop.done_times.append(r.t_done)
    t0 = loop.next_done(3.3, 0.0)       # the wave at 4.0 opens it
    t1 = loop.next_done(t0 + 7.0, 0.0)  # the wave at 12.0 closes it
    assert (t0, t1) == (4.0, 12.0), (t0, t1)
    assert loop.next_done(99.0, 0.0) == 99.0        # none: the deadline
    out = end_to_end(records, t0, t1, 1.0, 20.0)
    # (4.0, 12.0]: three of wave 2, waves 3 to 5, one of wave 6: 16 jobs
    assert close(out["evals_per_s"][0], 16 / 8.0), out
    assert close(out["job_p50_ms"][0], 1515.0, 1e-6), out


def test_span_until() -> None:
    """Applier passes carry no trace id: each ``plan.evaluate`` goes
    with the first ``plan.commit`` that starts once it has ended."""
    from benchmark.readers import span

    rows = [("plan.evaluate", "", 1, 0, 10.0, 0.1, 0, 0, 0, "a"),
            ("plan.commit", "", 2, 0, 10.1, 0.3, 0, 0, 0, "b"),
            ("plan.evaluate", "", 3, 0, 10.2, 0.1, 0, 0, 0, "a"),
            ("plan.commit", "", 4, 0, 10.4, 0.2, 0, 0, 0, "b")]
    metric = {"span": "plan.evaluate", "until": "plan.commit",
              "reduce": "max", "scale": 1000}
    assert close(span.read(metric, {"spans": rows}), 400.0, 1e-6)
    metric["reduce"] = "median"
    assert close(span.read(metric, {"spans": rows}), 400.0, 1e-6)
    assert span.read(metric, {"spans": rows[:1]}) is None


def test_reference() -> None:
    # a node of 3,900 MHz and 7,936 MB, half used after the ask:
    # 20 - 2 * 10**0.5 = 13.675..., / 18
    want = (20 - 2 * 10 ** 0.5) / 18
    got = reference.binpack(3900, 7936, 1950, 3968)
    assert close(got, want, 1e-12), (got, want)
    low = reference.binpack(3900, 7936, 1950, 3968, "bfloat16")
    assert abs(float(low) - want) > 1e-4, "the control has to read apart"
    assert reference.even_spread_boost({}, "a") == 0.0
    assert reference.even_spread_boost({"a": 2, "b": 1}, "a") == -1.0
    assert reference.even_spread_boost({"a": 2, "b": 1}, "b") == 1.0
    assert reference.even_spread_boost({"a": 2, "b": 2}, "a") == -1.0
    assert reference.even_spread_boost({"a": 3, "b": 1}, "c") == 1.0
    plain = {"used_cpu": [0.0, 500.0], "used_mem": [0.0, 256.0],
             "cap_cpu": [3900.0, 3900.0], "cap_mem": [7936.0, 7936.0]}
    job = {"cpu": 500.0, "mem": 256.0, "count": 4, "spread": "x"}
    racks = ["r0", "r1"]
    # second step, first went to node 0: node 0 collides (anti-affinity
    # -2/4) and its rack is the fullest (-1); node 1's rack is empty (+1)
    s0 = reference.expected_score(plain, 0, job, [0], racks)
    s1 = reference.expected_score(plain, 1, job, [0], racks)
    b0 = reference.binpack(3900, 7936, 1000, 512)
    b1 = reference.binpack(3900, 7936, 1000, 512)
    assert close(s0, (b0 - 0.5 - 1.0) / 3), (s0, b0)
    assert close(s1, (b1 + 1.0) / 2), (s1, b1)


def _toy_cluster(n: int) -> dict:
    import numpy as np

    plain = {f"{k}_{d}": np.zeros(n) for k in ("cap", "used")
             for d in reference.DIMS}
    plain["cap_cpu"][:] = 13900.0
    plain["cap_mem"][:] = 31744.0
    plain["cap_disk"][:] = 98304.0
    plain["used_cpu"][1] = plain["used_mem"][1] = 6000.0    # half full
    plain.update(node_ids=[f"n{i}" for i in range(n)], node_class=[""] * n,
                 datacenter=["dc-1", "dc-2"] * (n // 2),
                 rack=[f"r{i % 3}" for i in range(n)])
    return plain


def test_reference_of_all() -> None:
    """The reference over every node at once is the reference node by
    node, and a full node is not feasible."""
    import numpy as np

    plain = _toy_cluster(6)
    job = {"cpu": 6000.0, "mem": 6000.0, "disk": 150.0, "gpu": 0.0,
           "count": 4, "spread": "${meta.rack}", "distinct_hosts": False,
           "node_class": "", "datacenters": ["dc-1", "dc-2"]}
    prior = [1, 1, 4]               # node 1 is full now; rack r1 holds all
    used = {d: plain[f"used_{d}"].copy() for d in reference.DIMS}
    own = np.zeros(6)
    names, codes = np.unique(np.array(plain["rack"]), return_inverse=True)
    counts = np.zeros(len(names))
    for p in prior:
        for d in reference.DIMS:
            used[d][p] += job[d]
        own[p] += 1
        counts[codes[p]] += 1
    got = reference.scores_of_all(plain, used, job, own, codes, counts)
    for node in range(6):
        want = reference.expected_score(plain, node, job, prior, plain["rack"])
        assert close(got[node], want, 1e-12), (node, got[node], want)
    ok = reference.feasible_of_all(
        plain, reference.static_mask(plain, job), used, job, own)
    assert ok.tolist() == [True, False, True, True, True, True]
    job["datacenters"] = ["dc-2"]
    assert reference.static_mask(plain, job).tolist() == [False, True] * 3


def test_wave_replay() -> None:
    """A wave's members are replayed in an order, and split into
    launches, under which each one's answers hold, whatever order they
    are handed over in; an answer that passes over a better node reads
    its gap."""
    import numpy as np

    from benchmark import check
    from benchmark.traffic import JobRecord

    plain = _toy_cluster(8)
    index = {nid: i for i, nid in enumerate(plain["node_ids"])}
    used = {d: plain[f"used_{d}"].copy() for d in reference.DIMS}

    def member(name, count):
        return JobRecord(0, {
            "id": name, "cpu": 6000.0, "mem": 6000.0, "disk": 150.0,
            "gpu": 0.0, "count": count, "spread": None,
            "distinct_hosts": False, "node_class": "",
            "datacenters": ["dc-1", "dc-2"]}, {})

    def place(rec, used, skip=()):
        """The best node of each step, as a sound program would serve
        it (``skip``: nodes it does not look at)."""
        used = {d: used[d].copy() for d in reference.DIMS}
        own = np.zeros(8)
        static = reference.static_mask(plain, rec.plain)
        allocs = []
        for step in range(rec.plain["count"]):
            ok = reference.feasible_of_all(plain, static, used, rec.plain,
                                           own)
            score = reference.scores_of_all(plain, used, rec.plain, own)
            ranked = np.where(ok, score, check.NO_SCORE)
            ranked[list(skip)] = check.NO_SCORE
            node = int(np.argmax(ranked))
            top = np.argsort(-ranked)[:3]
            meta = [[plain["node_ids"][i], {}, float(score[i])] for i in top]
            allocs.append((check.RunAlloc(f"{rec.id}-{step}", rec.id, step,
                                          node, 7, None),
                           {"NodeID": plain["node_ids"][node],
                            "JobID": rec.id, "Name": f"{rec.id}.web[{step}]",
                            "Metrics": {"ScoreMeta": meta}}))
            for d in reference.DIMS:
                used[d][node] += rec.plain[d]
            own[node] += 1
        return allocs, used

    a, b = member("a", 1), member("b", 2)
    # a takes the half-full node 1 and fills it; b, after it, cannot
    allocs_a, after_a = place(a, used)
    allocs_b, _ = place(b, after_a)
    assert allocs_a[0][0].node == 1 and allocs_b[0][0].node != 1
    later = {d: used[d].copy() for d in reference.DIMS}
    later["cpu"][1] = later["mem"][1] = 0.0     # index 9: node 1 was freed

    def usage_of(state):
        return {5: used, 9: later}[state]

    def replay(fetched, states=(5,)):
        how = check.explain_wave(plain, index, "meta.rack", fetched,
                                 list(states), usage_of, 1e-4, [100])
        if how is None:
            how = check.one_launch(plain, index, "meta.rack", fetched, 5,
                                   usage_of)
        return how, check.replay_wave(plain, index, "meta.rack", fetched,
                                      how, usage_of)

    for fetched in ([(a, allocs_a), (b, allocs_b)],
                    [(b, allocs_b), (a, allocs_a)]):
        how, got = replay(fetched)
        assert [state for _i, state in how] == [5, None], how
        assert all(g[k] <= 1e-12 for g in got for k in check.GAPS), got
        assert sum(g["placements_compared"] for g in got) == 3
        assert not any(g["infeasible_chosen"] for g in got)
    # b in a launch of its own saw the snapshot alone: node 1 half full
    alone_b, _ = place(b, used)
    assert alone_b[0][0].node == 1
    how, got = replay([(a, allocs_a), (b, alone_b)])
    assert [state for _i, state in how] == [5, 5], how
    assert all(g[k] <= 1e-12 for g in got for k in check.GAPS), got
    # a placed against a later state, in which node 1 is empty like the
    # rest (its plan was committed at index 12, the allocs' ``create``)
    for a_alloc, _full in allocs_a:
        a_alloc.create = 12
    fresh_a, _ = place(a, later)
    for a_alloc, _full in fresh_a:
        a_alloc.create = 12
    how, got = replay([(a, fresh_a)], states=(5, 9))
    assert how == [(0, 9)], how
    assert all(g[k] <= 1e-12 for g in got for k in check.GAPS), got
    how, got = replay([(a, fresh_a)])       # not offered: not explained
    assert got[0]["score_max_abs_diff"] > 0.1, got
    # a program that does not look at node 1 serves an empty node
    blind, _ = place(a, used, skip=[1])
    how, got = replay([(a, blind)])
    assert got[0]["chosen_short_of_best"] > 0.1, got
    assert got[0]["topk_short_of_best"] > 0.1, got
    assert got[0]["score_max_abs_diff"] <= 1e-12, got


def test_deck() -> None:
    shapes = [{"share": 0.6, "count": [5, 20]}, {"share": 0.4, "count": [1, 4]}]
    cards = jobs_mod.deck(shapes, 10)
    assert [c[0] for c in cards].count(0) == 6
    assert all(5 <= n <= 20 for s, n in cards if s == 0)
    assert sorted(n for s, n in cards if s == 1) == [1, 2, 3, 4]


def main() -> int:
    for test in (test_trace_reduce, test_no_whole_launch, test_roofline,
                 test_percentiles, test_window, test_span_until,
                 test_reference, test_reference_of_all, test_wave_replay,
                 test_deck):
        test()
        print(f"{test.__name__}: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
