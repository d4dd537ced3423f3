"""The launch record and what hangs on it (ISSUE 26): span attributes,
one ``wave.launch`` per device launch with its members and children,
the step counters at the same boundary, no tracer-only waits, the plan
applier's pass number, ``store.txn``, ``http.<handler>``, and the
benchmark's metric files that read them.
"""

import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from nomad_tpu import mock, telemetry  # noqa: E402
from nomad_tpu.ops.kernel import (  # noqa: E402
    LaunchOrigin,
    build_kernel_in,
    default_kernel_launch,
    infer_features,
    pad_steps_live,
)
from nomad_tpu.parallel import coalesce  # noqa: E402
from nomad_tpu.parallel.coalesce import LaunchCoalescer, wave_stats  # noqa: E402
from nomad_tpu.telemetry.kernel_profile import profiler  # noqa: E402
from nomad_tpu.telemetry.trace import Span, Tracer, tracer  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def tracer_only():
    """The benchmark's setting: the tracer on, the profiler off."""
    telemetry.disable()
    telemetry.reset()
    tracer.enable()
    yield
    telemetry.disable()
    telemetry.reset()


def _kin(steps: int, nodes: int = 20):
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.stack import XLAGenericStack
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs.eval_plan import Plan
    from nomad_tpu.tensors.schema import ClusterTensors

    h = Harness()
    for _ in range(nodes):
        h.state.upsert_node(mock.node())
    job = mock.simple_job()
    h.state.upsert_job(job)
    snap = h.state.snapshot()
    c = ClusterTensors.build(snap.nodes())
    st = XLAGenericStack(False, EvalContext(snap, Plan()), c)
    st.set_job(job)
    ev = st._build_eval_tensors(job.task_groups[0], np.zeros(c.n_pad, bool))
    return (build_kernel_in(c, ev, steps), pad_steps_live(steps),
            infer_features(ev))


def _two_member_wave(steps: int, origins):
    """Two members through a coalescer, the first parked before the
    second arrives: the placement order is the arrival order."""
    kin, k_pad, feats = _kin(steps)
    co = LaunchCoalescer(2)
    errors = []

    def member(origin):
        try:
            co.launch(kin, k_pad, feats, origin=origin)
        except BaseException as e:              # noqa: BLE001
            errors.append(e)
        finally:
            co.done()

    first = threading.Thread(target=member, args=(origins[0],))
    first.start()
    deadline = time.monotonic() + 30
    while not co._pending and time.monotonic() < deadline:
        time.sleep(0.001)
    second = threading.Thread(target=member, args=(origins[1],))
    second.start()
    first.join(300)
    second.join(300)
    assert not first.is_alive() and not second.is_alive()
    assert not errors, errors
    return k_pad


class TestSpanAttributes:
    def test_attrs_survive_drain_ingest_and_to_api(self):
        src = Tracer()
        src.enable()
        with src.span("a.b", trace_id="t", attrs={"members": 2}) as sp:
            sp.set(program="joint", evals=["e1", "e2"])
        src.record("c.d", 0.25, attrs={"bytes": 512})
        with src.span("plain"):
            pass
        rows = src.drain_rows()
        assert [len(r) for r in rows] == [11, 11, 11]
        dst = Tracer()
        dst.enable()
        dst.ingest(rows)
        by_name = {s.name: s for s in dst.spans()}
        assert by_name["a.b"].attrs == {"members": 2, "program": "joint",
                                        "evals": ["e1", "e2"]}
        assert by_name["c.d"].attrs == {"bytes": 512}
        assert by_name["plain"].attrs is None
        api = by_name["a.b"].to_api()
        assert api["Attrs"]["evals"] == ["e1", "e2"]
        assert "Attrs" not in by_name["plain"].to_api()
        json.dumps(api)

    def test_ten_field_rows_still_rebuild(self):
        sp = Span("old", "t", 1, 0, 0.0, 1.0, 0.0, 0.0, 0.0, "thread")
        assert sp.attrs is None and sp.dur_s == 1.0

    def test_disabled_span_takes_attrs_and_set(self):
        t = Tracer()
        with t.span("x", attrs={"k": 1}) as sp:
            sp.set(more=2)
        t.record("y", 0.1, attrs={"k": 1})
        assert t.spans() == []


class TestLaunchRecord:
    def test_two_member_wave_names_its_members_in_order(self, tracer_only):
        k_pad = _two_member_wave(300, [
            LaunchOrigin("eval-a", 41, 300, False),
            LaunchOrigin("eval-b", 43, 300, True)])
        launches = tracer.spans(name="wave.launch")
        assert len(launches) == 1
        rec = launches[0]
        assert rec.attrs["evals"] == ["eval-a", "eval-b"]
        assert rec.attrs["steps"] == [300, 300]
        assert rec.attrs["state_index"] == [41, 43]
        assert rec.attrs["relaunch"] == [False, True]
        assert rec.attrs["program"] == "joint"
        assert (rec.attrs["members"], rec.attrs["slots"]) == (2, 4)
        assert rec.attrs["padded_steps"] == \
            coalesce.wave_step_pad(2, k_pad) == 2048
        assert rec.attrs["deadline"] is False
        assert rec.attrs["seq"] >= 1 and "with_topk" in rec.attrs["features"]
        kids = [s for s in tracer.spans() if s.parent_id == rec.span_id]
        assert sorted(s.name for s in kids if s.name != "kernel.compile") \
            in (["kernel.d2h", "kernel.execute", "wave.assemble"],
                ["kernel.d2h", "kernel.dispatch", "kernel.execute",
                 "wave.assemble"])
        # no time under the record worth naming beside its children: 5%
        # of a launch that does device work (2 ms where the program was
        # compiled already and the whole launch is a few ms of CPU)
        outside = rec.dur_s - sum(s.dur_s for s in kids)
        assert 0 <= outside <= max(0.05 * rec.dur_s, 0.002), \
            (rec.dur_s, [(s.name, s.dur_s) for s in kids])
        d2h = next(s for s in kids if s.name == "kernel.d2h")
        assert d2h.attrs["bytes"] > 0

    @pytest.mark.parametrize("profiling", [False, True],
                             ids=["profiler-off", "profiler-on"])
    def test_launch_children_once_per_launch(self, tracer_only, profiling):
        if profiling:
            profiler.enable()
        _two_member_wave(2, [None, None])
        kin, k_pad, feats = _kin(2)
        default_kernel_launch(kin, k_pad, feats)
        launches = tracer.spans(name="wave.launch")
        assert len(launches) == 2
        assert launches[0].attrs["evals"] == ["", ""]
        assert launches[1].attrs["program"] in ("single_topk", "single_full")
        for rec in launches:
            kids = [s.name for s in tracer.spans()
                    if s.parent_id == rec.span_id]
            called = [n for n in kids
                      if n in ("kernel.dispatch", "kernel.compile")]
            assert len(called) == 1, kids
            assert kids.count("kernel.execute") == 1, kids
            assert kids.count("kernel.d2h") == 1, kids
            assert kids.count("kernel.h2d") == (1 if profiling else 0), kids
        assert launches[1].attrs["seq"] == launches[0].attrs["seq"] + 1

    def test_deferred_topk_fetch_has_its_own_name(self, tracer_only):
        kin, k_pad, feats = _kin(2)
        out = coalesce.launch_wave([kin], [k_pad], [feats])[0]
        assert tracer.spans(name="kernel.d2h.topk") == []
        np.asarray(out.topk_idx)
        np.asarray(out.topk_scores)
        topk = tracer.spans(name="kernel.d2h.topk")
        assert len(topk) == 1 and topk[0].attrs["bytes"] > 0
        assert len(tracer.spans(name="kernel.d2h")) == 1


class TestReleasePaths:
    """``release`` and ``released_by`` on each of the four ways a
    rendezvous completes. The device call is stubbed under the record
    (``_launch_wave``): the record is what is tested."""

    @pytest.fixture()
    def recorded(self, tracer_only, monkeypatch):
        monkeypatch.setattr(
            coalesce, "_launch_wave",
            lambda kins, *_a, **_kw: [object()] * len(kins))
        monkeypatch.setattr(coalesce, "wave_cohorts", _NoCohorts())
        # a short launch on record, so that a parked member's deadline
        # arms (LaunchCoalescer._window_s); fresh so no other test's
        # launches move it
        ewma = coalesce._LatencyEWMA()
        ewma.update(0.01)
        monkeypatch.setattr(coalesce, "wave_latency_ewma", ewma)
        monkeypatch.setattr(coalesce, "wave_deadline_ewma",
                            coalesce._LatencyEWMA(alpha=0.25))

    @staticmethod
    def _member(co, trace_id, errors):
        def run():
            try:
                with tracer.span("eval.schedule", trace_id=trace_id):
                    co.launch(_NodeAxisOnly(), 8, None,
                              origin=LaunchOrigin(trace_id, 1, 3, False))
            except BaseException as e:          # noqa: BLE001
                errors.append(e)
            finally:
                co.done(trace_id)
        t = threading.Thread(target=run)
        t.start()
        return t

    @staticmethod
    def _parked(co):
        deadline = time.monotonic() + 30
        while not co._pending and time.monotonic() < deadline:
            time.sleep(0.001)
        assert co._pending

    @pytest.mark.parametrize("path", ["arrive", "done", "suspend",
                                      "deadline"])
    def test_release_and_released_by(self, recorded, path):
        errors: list = []
        if path == "arrive":
            co = LaunchCoalescer(2, adaptive=False)
            first = self._member(co, "ev-a", errors)
            self._parked(co)
            self._member(co, "ev-b", errors).join(30)
            first.join(30)
            want = "ev-b"
        elif path == "done":
            co = LaunchCoalescer(2, adaptive=False)
            first = self._member(co, "ev-a", errors)
            self._parked(co)
            # the second participant finishes without launching (a
            # purge evaluation of the batch): its spans have closed
            co.done("ev-b")
            first.join(30)
            want = "ev-b"
        elif path == "suspend":
            co = LaunchCoalescer(2, adaptive=False)
            first = self._member(co, "ev-a", errors)
            self._parked(co)
            with tracer.span("eval.schedule", trace_id="ev-b"):
                co.suspend()            # off to the plan applier
                co.resume()
            co.done("ev-b")
            first.join(30)
            want = "ev-b"
        else:
            co = LaunchCoalescer(2, window_max_s=0.05)
            # the second participant has arrived (and stepped back in):
            # the first member's deadline arms once it parks, and fires
            with tracer.span("eval.schedule", trace_id="ev-b"):
                co.suspend()
                co.resume()
            first = self._member(co, "ev-a", errors)
            first.join(30)
            co.done("ev-b")
            want = "ev-a"
        assert not errors, errors
        (rec,) = tracer.spans(name="wave.launch")
        assert rec.attrs["release"] == path
        assert rec.attrs["released_by"] == want
        assert rec.attrs["deadline"] is (path == "deadline")

    def test_direct_and_lone_launches_carry_no_release(self, tracer_only):
        kin, k_pad, feats = _kin(2)
        coalesce.launch_wave([kin], [k_pad], [feats])
        default_kernel_launch(kin, k_pad, feats)
        assert [("release" in s.attrs, "released_by" in s.attrs)
                for s in tracer.spans(name="wave.launch")] \
            == [(False, False)] * 2

    def test_nothing_built_with_the_tracer_off(self, monkeypatch):
        telemetry.disable()
        monkeypatch.setattr(coalesce, "launch_attrs", None)
        monkeypatch.setattr(coalesce.tracer, "context", None)
        kin, k_pad, feats = _kin(2)
        co = LaunchCoalescer(1)
        co.launch(kin, k_pad, feats)
        co.done("ev")


class _NoCohorts:
    def note_wave(self, _members):
        return None


class TestStepCounters:
    @pytest.fixture()
    def stubbed(self, monkeypatch):
        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            return [object()] * len(kins)

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)
        wave_stats.reset()
        yield
        wave_stats.reset()

    def test_300_step_member_counts_300_of_512(self, stubbed):
        k_pad = pad_steps_live(300)
        co = LaunchCoalescer(1)
        co.launch(_NodeAxisOnly(), k_pad, None,
                  origin=LaunchOrigin("e", 5, 300, False))
        co.done()
        assert (wave_stats.steps_sum, wave_stats.padded_steps_sum) \
            == (300, 512)
        assert wave_stats.relaunched_members_sum == 0

    def test_relaunched_member_counts_once(self, stubbed):
        co = LaunchCoalescer(1)
        co.launch(_NodeAxisOnly(), 8, None,
                  origin=LaunchOrigin("e", 5, 3, False))
        co.launch(_NodeAxisOnly(), 8, None,
                  origin=LaunchOrigin("e", 9, 2, True))
        co.done()
        assert wave_stats.relaunched_members_sum == 1
        assert wave_stats.steps_sum == 5
        assert wave_stats.launches == 2

    def test_lone_launch_counts_its_steps(self, tracer_only):
        wave_stats.reset()
        kin, k_pad, feats = _kin(3)
        default_kernel_launch(kin, k_pad, feats,
                              origin=LaunchOrigin("lone", 2, 3, True))
        assert wave_stats.steps_sum == 3
        assert wave_stats.padded_steps_sum == k_pad
        # a lone launch's programs run every padded step
        assert wave_stats.executed_steps_sum == k_pad
        assert wave_stats.relaunched_members_sum == 1
        # it is no wave: the fill counters do not see it
        assert wave_stats.launches == 0 and wave_stats.slots_sum == 0
        rec = tracer.spans(name="wave.launch")[0]
        assert rec.attrs["evals"] == ["lone"]
        assert rec.attrs["state_index"] == [2]
        assert rec.attrs["executed_steps"] == k_pad

    def test_wave_of_three_runs_its_900_real_steps(self, tracer_only,
                                                  monkeypatch):
        """ISSUE 37: three members of 300 steps in 512-step blocks: the
        record says 900 real steps of 2,048 compiled and 900 run, and
        the coalescer's counter grows by the 900 the program runs."""
        kin, k_pad, feats = _kin(300)
        origins = [LaunchOrigin(f"e{i}", 7, 300, False) for i in range(3)]
        coalesce.launch_wave([kin] * 3, [k_pad] * 3, [feats] * 3,
                             mesh=None, origins=origins)
        rec = tracer.spans(name="wave.launch")[0]
        assert sum(rec.attrs["steps"]) == 900
        assert rec.attrs["padded_steps"] == 2048
        assert rec.attrs["executed_steps"] == 900

        wave_stats.reset()
        co = LaunchCoalescer(3)
        requests = [coalesce._Request(_NodeAxisOnly(), k_pad, None, o)
                    for o in origins]
        launched = []
        monkeypatch.setattr(
            coalesce, "launch_wave", lambda kins, *a, **kw:
            launched.append(len(kins)) or [object()] * len(kins))
        co._fire(requests)
        assert launched == [3]
        assert (wave_stats.steps_sum, wave_stats.padded_steps_sum,
                wave_stats.executed_steps_sum) == (900, 2048, 900)
        assert wave_stats.snapshot()["executed_steps"] == 900
        from nomad_tpu.telemetry.exporter import prometheus_text

        assert "nomad_tpu_wave_executed_steps_total 900" in prometheus_text()
        wave_stats.reset()
        assert wave_stats.executed_steps_sum == 0
        assert wave_stats.snapshot()["executed_steps"] == 0

    def test_executed_steps_by_program(self):
        """The mesh's fused program runs its padded bucket whole; the
        joint programs the real steps alone."""
        assert coalesce.executed_steps("joint", 900, 2048) == 900
        assert coalesce.executed_steps("joint_sharded", 900, 2048) == 900
        assert coalesce.executed_steps("fused_wave_sharded", 900, 2048) \
            == 2048

    def test_scheduler_says_who_and_against_which_state(self):
        """Through the real stack: the launcher is handed the eval id,
        the snapshot's index and the real steps."""
        from nomad_tpu.scheduler.testing import Harness

        seen = []

        def launcher(kin, k_steps, features, origin=None):
            seen.append((k_steps, origin))
            return default_kernel_launch(kin, k_steps, features, origin)

        h = Harness()
        for _ in range(10):
            h.state.upsert_node(mock.node())
        job = mock.simple_job()
        job.task_groups[0].count = 3
        h.state.upsert_job(job)
        ev = mock.eval(job_id=job.id, type=job.type)
        from nomad_tpu.scheduler.generic import GenericScheduler

        state = h.state.snapshot()
        sched = GenericScheduler(state, h, kernel_launch=launcher)
        sched.process(ev)
        assert seen, "the scheduler never launched"
        k_steps, origin = seen[0]
        assert origin.eval_id == ev.id
        assert origin.state_index == state.latest_index()
        assert origin.steps == 3 and k_steps >= 3
        assert origin.relaunch is False


class _NodeAxisOnly:
    """What ``_fire`` reads of a request's tensors: the node axis."""

    class cap_cpu:
        shape = (8,)


class TestNoTracerOnlyWaits:
    def test_state_h2d_makes_the_same_jax_calls_traced_and_not(
            self, monkeypatch):
        from nomad_tpu.tensors import device_state

        calls = {"put": 0, "block": 0}
        real_put = jax.device_put

        def counting_put(*a, **kw):
            calls["put"] += 1
            return real_put(*a, **kw)

        def counting_block(x):
            calls["block"] += 1
            return x

        monkeypatch.setattr(device_state.jax, "device_put", counting_put)
        monkeypatch.setattr(device_state.jax, "block_until_ready",
                            counting_block)
        host = {"used_cpu": np.arange(64, dtype=np.float32),
                "used_mem": np.ones(64, np.float32)}
        seen = {}
        for on in (False, True):
            calls.update(put=0, block=0)
            tracer.enable() if on else tracer.disable()
            try:
                tracer.reset()
                ds = device_state.DeviceClusterState()
                planes = ds._upload(host)
                ds._scatter(planes, host, {3, 5})
                seen[on] = dict(calls)
                spans = tracer.spans(name="state.h2d")
            finally:
                tracer.disable()
                tracer.reset()
        assert seen[False] == seen[True]
        assert seen[True]["block"] == 0 and seen[True]["put"] > 0
        assert [s.attrs["rows"] for s in spans] == [64, 2]
        assert all(s.attrs["bytes"] > 0 for s in spans)

    def test_no_tracer_enabled_branch_under_tensors(self):
        """``git grep -n "tracer.enabled" nomad_tpu/tensors`` finds no
        branch that calls into jax: it finds nothing at all."""
        hits = []
        base = os.path.join(ROOT, "nomad_tpu", "tensors")
        for dirpath, _dirs, files in os.walk(base):
            for name in files:
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, name)) as f:
                    for n, line in enumerate(f, 1):
                        if re.search(r"tracer\.enabled", line):
                            hits.append((name, n, line.strip()))
        assert hits == []


@pytest.fixture()
def live_server(tracer_only):
    from nomad_tpu.server.server import Server, ServerConfig

    server = Server(ServerConfig(num_workers=1, worker_batch_size=4))
    server.start()
    try:
        for _ in range(12):
            server.node_register(mock.node())
        jobs = []
        for _ in range(3):
            job = mock.simple_job()
            job.task_groups[0].count = 2
            jobs.append(job)
            server.job_register(job)
        deadline = time.time() + 120
        while time.time() < deadline:
            snap = server.state.snapshot()
            if sum(len(snap.allocs_by_job(j.namespace, j.id))
                   for j in jobs) >= 6:
                break
            time.sleep(0.05)
        else:
            pytest.fail("the jobs were never placed")
        time.sleep(0.2)          # the commit thread closes its span
        yield server
    finally:
        server.shutdown()


class TestPlanAndStore:
    def test_plan_pass_shares_its_number(self, live_server):
        evaluates = tracer.spans(name="plan.evaluate")
        commits = {s.attrs["pass"]: s for s in
                   tracer.spans(name="plan.commit")}
        groups = {s.attrs["pass"]: s for s in
                  tracer.spans(name="plan.group_commit")}
        assert evaluates and commits
        paired = 0
        for ev in evaluates:
            n = ev.attrs["pass"]
            assert groups[n].attrs is ev.attrs or groups[n].attrs == ev.attrs
            assert ev.trace_id == ev.attrs["evals"][0]
            assert ev.attrs["plans"] == len(ev.attrs["evals"]) >= 1
            commit = commits.get(n)
            if commit is None:
                continue        # nothing of the pass was committed
            paired += 1
            assert commit.trace_id == ev.trace_id
            assert commit.start_s >= ev.start_s
            assert commit.attrs["allocs"] <= ev.attrs["allocs"]
        assert paired >= 1
        assert len({s.attrs["pass"] for s in evaluates}) == len(evaluates)
        assert sum(c.attrs["allocs"] for c in commits.values()) >= 6

    def test_store_txn_lies_inside_fsm_apply(self, live_server):
        """Directly (a batch's one root swap) or under the
        ``fsm.entry`` of the entry that wrote it."""
        applies = {s.span_id: s for s in tracer.spans(name="fsm.apply")}
        entries = {s.span_id: s.parent_id
                   for s in tracer.spans(name="fsm.entry")}
        txns = tracer.spans(name="store.txn")
        inside = [t for t in txns
                  if entries.get(t.parent_id, t.parent_id) in applies]
        assert inside, "no store.txn under an fsm.apply"
        for t in inside:
            parent = applies[entries.get(t.parent_id, t.parent_id)]
            assert parent.start_s <= t.start_s
            assert t.start_s + t.dur_s <= parent.start_s + parent.dur_s + 1e-6
            assert t.thread == parent.thread
        wrote_allocs = [t for t in inside if "allocs" in t.attrs["tables"]]
        assert wrote_allocs and all(t.attrs["rows"] >= 1
                                    for t in wrote_allocs)

    def test_eval_schedule_says_its_kind(self, live_server):
        """Every evaluation's run names its trigger and its job: the
        three registers' placements among them."""
        runs = tracer.spans(name="eval.schedule")
        assert runs and all(set(s.attrs) == {"triggered_by", "job"}
                            for s in runs)
        placed = {s.attrs["job"] for s in runs
                  if s.attrs["triggered_by"] == "job-register"}
        assert len(placed) == 3
        assert all(s.trace_id for s in runs)

    def test_lone_and_wave_launches_sit_under_eval_schedule(
            self, live_server):
        schedules = {s.span_id for s in tracer.spans(name="eval.schedule")}
        parks = {s.span_id for s in tracer.spans(name="wave.park")}
        launches = tracer.spans(name="wave.launch")
        assert launches
        for rec in launches:
            assert rec.parent_id in schedules | parks, rec.to_api()
            assert rec.attrs["members"] == len(rec.attrs["evals"])
            assert all(e for e in rec.attrs["evals"])
            assert all(i >= 0 for i in rec.attrs["state_index"])


class TestHttpSpans:
    @pytest.fixture()
    def agent(self, tracer_only):
        from nomad_tpu.api.agent import Agent, AgentConfig

        a = Agent(AgentConfig.dev())
        a.start()
        try:
            yield a
        finally:
            a.shutdown()

    def test_handler_span_carries_the_status(self, agent):
        with urllib.request.urlopen(agent.http.addr + "/v1/jobs",
                                    timeout=30) as r:
            assert r.status == 200
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(agent.http.addr + "/v1/job/nope",
                                   timeout=30)
        deadline = time.monotonic() + 5
        while len(tracer.spans(name="http.job_get")) < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert [s.attrs["status"] for s in
                tracer.spans(name="http.jobs_list")] == [200]
        assert [s.attrs["status"] for s in
                tracer.spans(name="http.job_get")] == [404]

    def test_no_span_for_held_requests(self, agent):
        import socket

        host, port = agent.http.addr.replace("http://", "").rsplit(":", 1)
        s = socket.create_connection((host, int(port)), timeout=30)
        try:
            s.sendall(f"GET /v1/event/stream HTTP/1.1\r\nHost: {host}"
                      "\r\n\r\n".encode())
            assert b"200" in s.makefile("rb").readline()
        finally:
            s.close()
        # a blocking query that returns at once is still one
        with urllib.request.urlopen(
                agent.http.addr + "/v1/jobs?index=1&wait=10ms",
                timeout=30) as r:
            assert r.status == 200
        with urllib.request.urlopen(agent.http.addr + "/v1/nodes",
                                    timeout=30) as r:
            assert r.status == 200
        deadline = time.monotonic() + 5
        while not tracer.spans(name="http.nodes_list") \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        names = {s.name for s in tracer.spans()
                 if s.name.startswith("http.")}
        assert "http.event_stream" not in names
        assert "http.jobs_list" not in names
        assert names == {"http.nodes_list"}


def _metric_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = ("wave_dispatch_ms", "wave_execute_wait_ms", "wave_d2h_ms",
           "store_txn_ms", "plan_queue_wait_ms", "http_register_ms",
           "traced_job_p50_ms")
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert set(new) <= set(listed)
    return [(name, listed[name]) for name in new]


_NEW_METRICS = _metric_files()


class TestMetricFiles:
    """The metric files this PR adds, read by the benchmark's own
    readers from recorded rows: each returns a number."""

    @pytest.fixture(scope="class")
    def recorded(self):
        t = Tracer()
        t.enable()
        t0 = time.monotonic()
        with t.span("http.job_register") as sp:
            sp.set(status=200)
        with t.span("wave.launch", attrs={"seq": 1}):
            t.record("kernel.dispatch", 0.004)
            with t.span("kernel.execute"):
                time.sleep(0.002)
            with t.span("kernel.d2h") as sp:
                sp.set(bytes=64)
        t.record("plan.queue_wait", 0.25)
        with t.span("fsm.apply"):
            with t.span("store.txn"):
                pass
        rows = t.drain_rows()

        class Job:
            t_send, t_ack, t_done = t0, t0 + 0.01, t0 + 0.9

        return {"spans": rows, "records": [Job], "t0": t0 - 1,
                "t1": time.monotonic() + 1, "drained_at": t0 + 2,
                "counters": {}}

    @pytest.mark.parametrize("name,entry", _NEW_METRICS,
                             ids=[n for n, _ in _NEW_METRICS])
    def test_file_loads_and_reads_a_number(self, recorded, name, entry):
        import importlib

        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            metric = json.load(f)
        for key in ("name", "unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert metric[key] == entry[key], key
        reader = importlib.import_module(
            f"benchmark.readers.{metric['reader']}")
        value = reader.read(metric, recorded)
        assert isinstance(value, float) and value >= 0
        if name == "wave_dispatch_ms":
            assert value == pytest.approx(4.0)
        if name == "traced_job_p50_ms":
            assert value == pytest.approx(900.0)
        # where the program records no such span (the parent commit),
        # the reader finds nothing and says so
        if metric["reader"] == "span":
            assert reader.read(metric, dict(recorded, spans=[])) is None

    @pytest.mark.parametrize("counters,reduce,want", [
        (["nomad_tpu.parallel.coalesce:wave_stats.steps_sum",
          "nomad_tpu.parallel.coalesce:wave_stats.padded_steps_sum"],
         "ratio", 58.59375),
        (["nomad_tpu.parallel.coalesce:wave_stats.relaunched_members_sum"],
         "delta", 1.0),
        (["nomad_tpu.parallel.coalesce:wave_stats.steps_sum",
          "nomad_tpu.parallel.coalesce:wave_stats.executed_steps_sum"],
         "ratio", 100.0),
    ], ids=["step_fill", "relaunched_members", "executed_step_fill"])
    def test_counter_reader_reads_the_step_counters(
            self, counters, reduce, want):
        """``step_fill`` and ``relaunched_members`` as a later PR can
        add them (PERF.md, Open questions): the counter reader over the
        window's growth of ``wave_stats``."""
        from benchmark.readers import counter
        from benchmark.tracing import resolve

        wave_stats.reset()
        before = {p: float(resolve(p, {})) for p in counters}
        wave_stats.observe_wave(4, False, steps=1200, padded_steps=2048,
                                executed_steps=1200, relaunched=1)
        ctx = {"counters": {p: (before[p], float(resolve(p, {})))
                            for p in counters}}
        wave_stats.reset()
        metric = {"counters": counters, "reduce": reduce,
                  "scale": 100 if reduce == "ratio" else 1}
        assert counter.read(metric, ctx) == pytest.approx(want)


def test_executed_step_fill_file_reads_the_counters():
    """``executed_step_fill`` as ``BENCHMARK.json`` lists it: the real
    steps over the steps the programs ran; nothing on a parent that
    lacks the counter (``benchmark/tracing._read_counters``)."""
    from benchmark.readers import counter

    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "executed_step_fill.json")) as f:
        metric = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for key in ("name", "unit", "better", "source", "layer", "moves",
                "workloads"):
        assert metric[key] == listed["executed_step_fill"][key], key
    steps, executed = metric["counters"]
    assert counter.read(metric, {"counters": {
        steps: (100.0, 1000.0), executed: (100.0, 1000.0)}}) == 100.0
    # a lone launch's padded steps ran: 900 real of 1,024 run
    assert counter.read(metric, {"counters": {
        steps: (0.0, 900.0), executed: (0.0, 1024.0)}}) \
        == pytest.approx(87.890625)
    assert counter.read(metric, {"counters": {
        steps: (0.0, 900.0), executed: (None, None)}}) is None
