"""The device's wait between launches and what held the next launch:
the always-on gap counter (``WaveStats``), the ``fsm.entry`` spans, the
tracer's unread CPU clock, and the benchmark's ``gap`` and ``span_cpu``
readers on synthetic rows.
"""

import json
import os
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.parallel import coalesce
from nomad_tpu.parallel.coalesce import WaveStats
from nomad_tpu.server import fsm as fsm_mod
from nomad_tpu.telemetry.trace import Tracer, tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestGapCounter:
    def test_first_launch_makes_no_gap(self):
        stats = WaveStats()
        stats.launch_dispatched()
        stats.launch_ready()
        assert (stats.gaps, stats.gap_seconds_sum) == (0, 0.0)

    def test_wait_from_ready_to_the_next_call(self):
        stats = WaveStats()
        for wait in (0.05, 0.02):
            stats.launch_dispatched()
            stats.launch_ready()
            time.sleep(wait)
        stats.launch_dispatched()
        stats.launch_ready()
        assert stats.gaps == 2
        assert 0.07 <= stats.gap_seconds_sum < 0.5
        snap = stats.snapshot()
        assert (snap["gaps"], snap["gap_seconds"]) \
            == (2, stats.gap_seconds_sum)

    def test_overlapping_launches_wait_zero(self):
        stats = WaveStats()
        stats.launch_dispatched()
        stats.launch_ready()
        time.sleep(0.02)
        stats.launch_dispatched()
        waited = stats.gap_seconds_sum
        time.sleep(0.05)
        stats.launch_dispatched()             # the second still runs
        stats.launch_ready()
        stats.launch_ready()
        assert stats.gaps == 2
        assert waited >= 0.02
        assert stats.gap_seconds_sum == waited

    def test_reset_forgets_the_last_launch(self):
        stats = WaveStats()
        stats.launch_dispatched()
        stats.launch_ready()
        stats.reset()
        stats.launch_dispatched()
        stats.launch_ready()
        assert (stats.gaps, stats.gap_seconds_sum) == (0, 0.0)

    def test_a_launch_that_raises_is_ready(self, monkeypatch):
        stats = WaveStats()
        monkeypatch.setattr(coalesce, "wave_stats", stats)

        def boom(*_a, **_kw):
            raise RuntimeError("device")

        monkeypatch.setattr(coalesce.profiler, "call", boom)
        with pytest.raises(RuntimeError):
            coalesce.timed_call("joint", None, (), (), ())
        assert stats._in_flight == 0 and stats._last_ready is not None

    def test_exported_with_the_wave_series(self, monkeypatch):
        from nomad_tpu.telemetry.exporter import prometheus_text

        stats = WaveStats()
        stats.gap_seconds_sum, stats.gaps = 1.5, 6
        monkeypatch.setattr(coalesce, "wave_stats", stats)
        text = prometheus_text()
        assert "nomad_tpu_wave_launch_gap_seconds_total 1.500000" in text
        assert "nomad_tpu_wave_launch_gaps_total 6" in text


class TestUnreadCpuClock:
    @pytest.mark.parametrize("every", [1, 2], ids=["every-tree", "1-in-2"])
    def test_read_or_marked(self, every):
        t = Tracer()
        t.enable()
        t.cpu_sample_every = every
        for _ in range(4):
            with t.span("root"):
                with t.span("child"):
                    sum(range(1000))
                t.record("waited", 0.001)
        rows = t.drain_rows()
        live = [r for r in rows if r[0] != "waited"]
        for r in live:
            # a read clock is a number, an unread one None: never the
            # 0 of a span that did no work
            assert (r[7] is None) == (r[8] is None)
            assert r[7] is None or r[7] >= 0.0
        read = [r for r in live if r[7] is not None]
        assert len(read) == len(live) // every
        assert all(r[7] is None and r[8] is None for r in rows
                   if r[0] == "waited")
        by_id = {r[2]: r for r in rows}
        for r in live:
            if r[0] == "child":
                # a tree is read whole or not at all
                assert (r[7] is None) == (by_id[r[3]][7] is None)

    def test_unread_clock_in_api_and_aggregates(self):
        t = Tracer()
        t.enable()
        t.cpu_sample_every = 2
        for _ in range(2):
            with t.span("x"):
                pass
        spans = t.spans()
        unread = next(s for s in spans if s.cpu_s is None)
        assert unread.exclusive_cpu_s is None
        api = unread.to_api()
        assert api["CpuMs"] is None and api["ExclusiveCpuMs"] is None
        json.dumps(api)
        read = next(s for s in spans if s.cpu_s is not None)
        # the aggregate scales the one read span by the sampling rate
        assert t.stage_totals()["x"]["cpu_s"] == pytest.approx(
            2 * read.cpu_s)
        dst = Tracer()
        dst.enable()
        dst.ingest(t.drain_rows())
        assert dst.stage_totals()["x"]["count"] == 2


class TestFsmEntry:
    @pytest.fixture()
    def traced(self):
        tracer.reset()
        tracer.enable()
        yield
        tracer.disable()
        tracer.reset()

    @staticmethod
    def _plan_results(node, job, placed, stopped):
        from nomad_tpu.structs.eval_plan import Plan

        def allocs(n):
            return [mock.alloc(node_id=node.id, job=job, job_id=job.id)
                    for _ in range(n)]

        return {"alloc_index": 0, "plans": [{
            "plan": Plan(job=job),
            "node_allocation": {node.id: allocs(placed)},
            "node_update": {node.id: allocs(stopped)},
            "node_preemptions": {},
            "deployment": None,
            "deployment_updates": [],
        }]}

    def _fsm(self):
        from nomad_tpu.state.store import StateStore

        state = StateStore()
        node, job = mock.node(), mock.job()
        state.upsert_node(node)
        state.upsert_job(job)
        return fsm_mod.NomadFSM(state), node, job

    def test_single_entry_path(self, traced):
        fsm, node, job = self._fsm()
        fsm.apply(fsm_mod.APPLY_PLAN_RESULTS,
                  self._plan_results(node, job, 3, 2))
        fsm.apply(fsm_mod.NODE_REGISTER, {"node": mock.node()})
        applies = {s.span_id for s in tracer.spans(name="fsm.apply")}
        entries = tracer.spans(name="fsm.entry")
        assert [e.attrs for e in entries] == [
            {"type": fsm_mod.APPLY_PLAN_RESULTS, "placed": 3, "stopped": 2},
            {"type": fsm_mod.NODE_REGISTER}]
        assert all(e.parent_id in applies for e in entries)

    def test_batch_path_one_entry_each(self, traced):
        fsm, node, job = self._fsm()
        results = fsm.apply_batch([
            (fsm_mod.NODE_REGISTER, {"node": mock.node()}),
            (fsm_mod.APPLY_PLAN_RESULTS,
             self._plan_results(node, job, 4, 0)),
            ("NoSuchRequestType", {}),
        ])
        assert [err is None for _i, err in results] == [True, True, False]
        (apply,) = tracer.spans(name="fsm.apply")
        entries = tracer.spans(name="fsm.entry")
        assert [e.attrs["type"] for e in entries] == [
            fsm_mod.NODE_REGISTER, fsm_mod.APPLY_PLAN_RESULTS,
            "NoSuchRequestType"]
        assert (entries[1].attrs["placed"], entries[1].attrs["stopped"]) \
            == (4, 0)
        assert all(e.parent_id == apply.span_id for e in entries)
        # the batch's one root swap stays directly under fsm.apply
        txn = [s for s in tracer.spans(name="store.txn")
               if s.parent_id == apply.span_id]
        assert len(txn) == 1

    def test_nothing_built_with_the_tracer_off(self, monkeypatch):
        tracer.disable()
        monkeypatch.setattr(fsm_mod, "entry_attrs", None)
        fsm, node, job = self._fsm()
        fsm.apply(fsm_mod.APPLY_PLAN_RESULTS,
                  self._plan_results(node, job, 1, 1))
        fsm.apply_batch([(fsm_mod.NODE_REGISTER, {"node": mock.node()})])


# -- the benchmark's readers, on synthetic rows ---------------------------

def _row(name, start, dur, trace="", sid=0, parent=0, cpu=None,
         thread="t", attrs=None):
    return (name, trace, sid, parent, start, dur, 0.0, cpu, None, thread,
            attrs)


def _launch(sid, start, call, ready, trace="", attrs=None):
    """A wave.launch span with its call and its wait."""
    return [_row("wave.launch", start, ready + 0.001 - start, trace, sid,
                 attrs=attrs),
            _row("kernel.dispatch", call, 0.001, trace, sid + 1, sid),
            _row("kernel.execute", call + 0.001, ready - call - 0.001,
                 trace, sid + 2, sid)]


def _gap_metric(label):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           f"gap_{label}_ms.json")) as f:
        return json.load(f)


class TestGapReader:
    """One gap, 1.0 s to 2.0 s, before a launch released by ``ev``:
    commit 1.0-1.2 (0.05 of it under gc), another evaluation's run
    1.2-1.25, client 1.25-1.3, api 1.3-1.4, dequeue 1.4-1.5, schedule
    1.5-1.9, launch 1.9-2.0."""

    REL = {"release": "suspend", "released_by": "ev"}

    def rows(self, attrs=REL):
        rows = _launch(10, 0.5, 0.6, 1.0)
        rows += _launch(20, 1.9, 2.0, 2.1, trace="other", attrs=attrs)
        rows += [
            _row("plan.evaluate", 0.95, 0.15),
            _row("fsm.entry", 1.1, 0.1,
                 attrs={"type": "ApplyPlanResultsRequestType"}),
            _row("gc.collect", 1.05, 0.05),
            _row("http.job_register", 1.3, 0.05),
            _row("fsm.entry", 1.34, 0.06,
                 attrs={"type": "JobRegisterRequestType"}),
            # the releaser's enqueue, and its scheduling run, on
            # through the launch it fires
            _row("eval.e2e", 1.4, 0.9, trace="ev"),
            _row("eval.schedule", 1.5, 0.7, trace="ev"),
            # another evaluation's run, before the releaser's enqueue
            _row("eval.schedule", 1.2, 0.05, trace="elsewhere"),
        ]
        return rows

    def test_labels_partition_the_gap(self):
        from benchmark.readers import gap

        gaps, seconds = gap.split(self.rows())
        assert gaps == 1
        want = {"gc": 0.05, "commit": 0.15, "evals": 0.05, "client": 0.05,
                "api": 0.1, "dequeue": 0.1, "schedule": 0.4, "launch": 0.1}
        assert seconds == pytest.approx(want)
        assert sum(seconds.values()) == pytest.approx(1.0)

    @pytest.mark.parametrize("label,ms", [
        ("gc", 50.0), ("launch", 100.0), ("schedule", 400.0),
        ("dequeue", 100.0), ("api", 100.0), ("commit", 150.0),
        ("evals", 50.0), ("client", 50.0)])
    def test_metric_files_read_a_mean(self, label, ms):
        from benchmark.readers import gap

        ctx = {"spans": self.rows()}
        assert gap.read(_gap_metric(label), ctx) == pytest.approx(ms)

    def test_launch_outranks_schedule_and_gc_outranks_all(self):
        from benchmark.readers import gap

        rows = self.rows()
        # a collection over the whole gap takes all of it
        gaps, seconds = gap.split(rows + [_row("gc.collect", 0.9, 1.2)])
        assert seconds["gc"] == pytest.approx(1.0)
        # the releaser already scheduling when the last launch ended:
        # schedule, up to the next launch's own span
        rows = [r for r in rows if r[0] != "eval.schedule" or r[1] != "ev"]
        rows.append(_row("eval.schedule", 0.8, 1.4, trace="ev"))
        gaps, seconds = gap.split(rows)
        assert seconds["schedule"] == pytest.approx(0.9 - 0.05)
        assert seconds["launch"] == pytest.approx(0.1)

    def test_falls_back_to_the_launch_trace_id(self):
        from benchmark.readers import gap

        rows = self.rows({"release": "arrive", "released_by": ""})
        rows = [r for r in rows if r[0] != "wave.launch" or r[2] != 20]
        rows += _launch(20, 1.9, 2.0, 2.1, trace="ev",
                        attrs={"release": "arrive"})[:1]
        assert gap.split(rows)[1]["schedule"] == pytest.approx(0.4)

    def test_overlapping_launches_count_a_gap_of_zero(self):
        from benchmark.readers import gap

        rows = self.rows() + _launch(30, 2.05, 2.06, 2.3,
                                     attrs=self.REL)
        gaps, seconds = gap.split(rows)
        assert gaps == 2
        assert sum(seconds.values()) == pytest.approx(1.0)

    def test_a_parent_without_the_attributes_reads_nothing(self):
        from benchmark.readers import gap

        rows = [r[:10] + (None if r[0] == "wave.launch" else r[10],)
                for r in self.rows()]
        assert gap.split(rows) is None
        ctx = {"spans": rows}
        assert all(gap.read(_gap_metric(label), ctx) is None
                   for label in gap.LABELS)
        assert gap.read(_gap_metric("commit"), {"spans": []}) is None


def _cpu_metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


class TestSpanCpuReader:
    @staticmethod
    def rows():
        place = {"triggered_by": "job-register", "job": "a"}
        purge = {"triggered_by": "job-deregister", "job": "b"}
        return [
            # a placement: 0.2 s of wall, 0.08 s of CPU; parked 0.05 s
            # (0.001 CPU), launched 0.03 s (0.004 CPU) one level down
            _row("eval.schedule", 0.0, 0.2, "p", 1, cpu=0.08, attrs=place),
            # a second placement, nothing under it: 0.05 s, 0.02 of CPU
            _row("eval.schedule", 0.25, 0.05, "p2", 11, cpu=0.02,
                 attrs=place),
            _row("wave.park", 0.01, 0.05, "p", 2, 1, cpu=0.001),
            _row("sched.assembly", 0.07, 0.05, "p", 3, 1, cpu=0.03),
            _row("wave.launch", 0.08, 0.03, "p", 4, 3, cpu=0.004),
            _row("kernel.execute", 0.09, 0.02, "p", 5, 4, cpu=0.001),
            # a purge: 0.1 s, 0.03 s of CPU, 0.04 s on the applier
            _row("eval.schedule", 0.3, 0.1, "q", 6, cpu=0.03, attrs=purge),
            _row("plan.wait", 0.33, 0.04, "q", 7, 6, cpu=0.002),
            # another thread's span under the purge's id: not its own
            _row("plan.wait", 0.3, 0.09, "q", 8, 6, cpu=0.0,
                 thread="other"),
            # unread clock, a kind no metric names: skipped
            _row("eval.schedule", 0.5, 0.1, "r", 9, cpu=None, attrs=place),
            _row("eval.schedule", 0.7, 0.1, "s", 10, cpu=0.05,
                 attrs={"triggered_by": "scheduled", "job": "c"}),
            _row("fsm.entry", 0.8, 0.02, cpu=0.015,
                 attrs={"type": "ApplyPlanResultsRequestType",
                        "placed": 1200, "stopped": 0}),
            _row("fsm.entry", 0.9, 0.01, cpu=0.002,
                 attrs={"type": "JobRegisterRequestType"}),
        ]

    @pytest.mark.parametrize("name,want", [
        # CPU by means: the chip host's CPU clock moves in 10 ms ticks
        ("sched_place_cpu_ms", (80 - 1 - 4 + 20) / 2),
        ("sched_purge_cpu_ms", 30 - 2),
        # wall less CPU of what is left: (200-50-30)-(75), 50-20,
        # (100-40)-(28); a median
        ("sched_offcpu_ms", 32),
        ("fsm_plan_cpu_ms", 15),
    ])
    def test_metric_files(self, name, want):
        from benchmark.readers import span_cpu

        got = span_cpu.read(_cpu_metric(name), {"spans": self.rows()})
        assert got == pytest.approx(want)

    def test_a_parent_without_the_attributes_reads_nothing(self):
        from benchmark.readers import span_cpu

        rows = [r[:10] + (None,) if r[0] == "eval.schedule" else r
                for r in self.rows() if r[0] != "fsm.entry"]
        for name in ("sched_place_cpu_ms", "sched_purge_cpu_ms",
                     "sched_offcpu_ms", "fsm_plan_cpu_ms"):
            assert span_cpu.read(_cpu_metric(name), {"spans": rows}) is None

    def test_an_unread_excluded_subtree_skips_its_row(self):
        from benchmark.readers import span_cpu

        rows = [r[:7] + (None,) + r[8:] if r[0] == "wave.park" else r
                for r in self.rows()]
        # the first placement is skipped; the second alone is read
        assert span_cpu.read(_cpu_metric("sched_place_cpu_ms"),
                             {"spans": rows}) == pytest.approx(20.0)


def test_metric_files_match_benchmark_json():
    """The thirteen files as ``BENCHMARK.json`` lists them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    names = ["launch_gap_ms"] + [f"gap_{x}_ms" for x in (
        "gc", "launch", "schedule", "dequeue", "api", "commit", "evals",
        "client")] \
        + ["sched_place_cpu_ms", "sched_purge_cpu_ms", "sched_offcpu_ms",
           "fsm_plan_cpu_ms"]
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            metric = json.load(f)
        for key in ("name", "unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert metric[key] == listed[name][key], (name, key)
