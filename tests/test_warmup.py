"""ISSUE 2: AOT kernel warmup, adaptive wave coalescing, feature-key
canonicalization, wave telemetry, and the donation-warning fix.

The acceptance surface, CI-gated on the CPU backend:
- a steady-state eval loop after manifest warmup records ZERO jit
  cache misses (the compile share of the live path's wall goes to the
  warmup thread instead);
- the adaptive coalescer fires partial waves at its deadline instead
  of parking forever behind members that never arrive;
- plan submission yields the wave rendezvous (pipelining), so a wave
  can fire while another member blocks on the applier;
- near-identical feature sets canonicalize onto one compiled variant;
- ``make_preemption_apply_loop`` no longer asks XLA to donate buffers
  it cannot alias (the warning is promoted to an error in conftest).
"""

import json
import os
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from nomad_tpu import mock, telemetry
from nomad_tpu.ops import warmup as kernel_warmup
from nomad_tpu.telemetry.kernel_profile import profiler

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))


@pytest.fixture()
def clean_telemetry():
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _register_jobs(server, n_jobs, count=3):
    jobs = []
    for _ in range(n_jobs):
        j = mock.simple_job()
        j.task_groups[0].count = count
        jobs.append(j)
        server.job_register(j)
    return jobs


def _drain_worker(server, batch_size=8):
    """Deterministic eval loop: a manual batching worker drains the
    broker (jobs registered first, so batches are full-size)."""
    from nomad_tpu.server.worker import Worker

    w = Worker(server, 0, batch_size=batch_size)
    while w.run_once(timeout=0.0):
        pass
    return w


def _clear_kernel_caches():
    from nomad_tpu.ops.kernel import (
        place_taskgroup_jit,
        place_taskgroup_topk_jit,
        place_taskgroups_joint_jit,
    )

    place_taskgroups_joint_jit.clear_cache()
    place_taskgroup_topk_jit.clear_cache()
    place_taskgroup_jit.clear_cache()


class TestManifest:
    def test_roundtrip_and_merge(self, tmp_path):
        e1 = {"kernel": "joint", "wave": 16, "steps": 64, "nodes": 64,
              "shared": True, "neutral_shared": False,
              "features": {"n_spreads": 0, "with_topk": True}}
        e2 = {"kernel": "single_topk", "nodes": 64, "steps": 16,
              "features": {"n_spreads": 0}}
        path = str(tmp_path / "warmup.json")
        assert kernel_warmup.save_manifest([e1], path) == 1
        # merge unions and dedupes
        assert kernel_warmup.save_manifest([e1, e2], path) == 2
        got = kernel_warmup.load_manifest(path)
        assert len(got) == 2
        data = json.loads(open(path).read())
        assert data["version"] == kernel_warmup.MANIFEST_VERSION

    def test_expand_lattice_covers_waves_layouts_and_singles(self):
        e = {"kernel": "joint", "wave": 32, "steps": 512, "nodes": 64,
             "shared": True, "neutral_shared": False,
             "features": {"n_spreads": 0}}
        out = kernel_warmup.expand_lattice([e])
        joint = [x for x in out if x["kernel"] == "joint"]
        waves = sorted({x["wave"] for x in joint})
        assert waves == [1, 4, 16, 32]
        # observed per-member step count (512/32 = 16) is preserved at
        # every wave bucket, and the follow-up-eval floor bucket (8)
        # rides along
        steps_at = lambda w: {x["steps"] for x in joint  # noqa: E731
                              if x["wave"] == w}
        assert {256, 128} <= steps_at(16)
        assert {64, 32} <= steps_at(4)
        assert {16, 8} <= steps_at(1)
        # 1-waves force the fully-shared layout (a lone member shares
        # every field with itself); multi-member waves also cover the
        # all-stacked retry layout
        assert all(x["shared"] and x["neutral_shared"]
                   for x in joint if x["wave"] == 1)
        assert any(x["wave"] == 16 and not x["shared"]
                   and not x["neutral_shared"] for x in joint)
        # the rescheduling feature variant (penalties + preferred) is
        # covered alongside the observed one
        assert any(x["features"].get("with_step_penalties")
                   and x["features"].get("with_preferred")
                   for x in joint)
        # direct (1-eval batch) dispatch programs are covered too
        singles = {x["kernel"] for x in out if x["kernel"] != "joint"}
        assert singles == {"single_topk", "single_full"}
        assert {x["steps"] for x in out
                if x["kernel"] == "single_topk"} == {8, 16}

    def test_expand_lattice_up_to_max_wave(self):
        e = {"kernel": "joint", "wave": 4, "steps": 32, "nodes": 64,
             "shared": True, "neutral_shared": False,
             "features": {"n_spreads": 0}}
        out = kernel_warmup.expand_lattice([e], max_wave=32)
        waves = sorted({x["wave"] for x in out
                        if x["kernel"] == "joint"})
        assert waves == [1, 4, 16, 32]

    def test_manifest_from_profiler_skips_sharded(self, clean_telemetry):
        from nomad_tpu.ops.kernel import LEAN_FEATURES

        profiler.call("joint", lambda *a: 0, (), (),
                      (16, 64, 64, True, False, LEAN_FEATURES))
        profiler.call("joint_sharded", lambda *a: 0, (), (),
                      (16, 64, 64, True, False, LEAN_FEATURES, ("d0",)))
        entries = kernel_warmup.manifest_from_profiler(profiler)
        assert [e["kernel"] for e in entries] == ["joint"]


class TestAOTWarmupSteadyState:
    def test_zero_jit_misses_after_manifest_warmup(
            self, tmp_path, clean_telemetry):
        """The tentpole claim: record a burst's bucket keys, clear the
        jit caches (a fresh process), warm from the manifest, and a
        steady-state eval loop compiles NOTHING."""
        from nomad_tpu.server.server import Server, ServerConfig

        # adaptive deadline off for THIS test: wave sizes must be
        # deterministic so the recording run observes exactly the
        # buckets the steady-state run launches (deadline-fired
        # partial waves are covered by TestAdaptiveCoalescer and the
        # lattice expansion)
        server = Server(ServerConfig(num_workers=0, heartbeat_ttl=3600.0,
                                     coalesce_adaptive=False))
        server.start()
        try:
            for _ in range(40):
                server.node_register(mock.node())
            jobs = _register_jobs(server, 8)
            _drain_worker(server)
            snap = server.state.snapshot()
            placed = sum(len(snap.allocs_by_job(j.namespace, j.id))
                         for j in jobs)
            assert placed == 24

            path = str(tmp_path / "warmup.json")
            entries = kernel_warmup.manifest_from_profiler(profiler)
            assert entries, "profiler recorded no bucket keys"
            kernel_warmup.save_manifest(entries, path)

            # fresh-process simulation: drop every compiled program
            _clear_kernel_caches()
            profiler.reset()
            compiled, failed = kernel_warmup.warmup_from_manifest(path)
            assert compiled >= len(entries)
            assert failed == 0

            profiler.reset()
            jobs2 = _register_jobs(server, 8)
            _drain_worker(server)
            snap = server.state.snapshot()
            placed2 = sum(len(snap.allocs_by_job(j.namespace, j.id))
                          for j in jobs2)
            assert placed2 == 24
            s = profiler.summary()
            assert s["Launches"] >= 1
            assert s["JitCacheMisses"] == 0, s["PerKey"]
        finally:
            server.shutdown()

    def test_server_persists_and_warms_manifest(
            self, tmp_path, clean_telemetry):
        """Lifecycle: a server with a manifest path persists observed
        keys on shutdown; the next server start warms them (background
        thread)."""
        from nomad_tpu.server.server import Server, ServerConfig

        path = str(tmp_path / "warmup.json")
        server = Server(ServerConfig(
            num_workers=0, heartbeat_ttl=3600.0,
            warmup_manifest_path=path))
        server.start()
        try:
            for _ in range(20):
                server.node_register(mock.node())
            _register_jobs(server, 4)
            _drain_worker(server, batch_size=4)
        finally:
            server.shutdown()
        assert os.path.exists(path)
        assert kernel_warmup.load_manifest(path)

        server2 = Server(ServerConfig(
            num_workers=0, heartbeat_ttl=3600.0,
            warmup_manifest_path=path))
        server2.start()
        try:
            t = server2._warmup_thread
            assert t is not None
            t.join(timeout=120)
            assert not t.is_alive()
        finally:
            server2.shutdown()


class TestConfigKnobs:
    def test_agent_config_file_parses_warmup_and_window(self, tmp_path):
        from nomad_tpu.api.config_file import load_config_files

        p = tmp_path / "agent.hcl"
        p.write_text('''
server {
  enabled                = true
  kernel_warmup          = true
  warmup_manifest        = "/var/lib/nomad_tpu/warmup.json"
  coalesce_adaptive      = false
  coalesce_window_min_ms = 2
  coalesce_window_max_ms = 80
}
''')
        cfg = load_config_files([str(p)])
        assert cfg.kernel_warmup is True
        assert cfg.warmup_manifest == "/var/lib/nomad_tpu/warmup.json"
        assert cfg.coalesce_adaptive is False
        assert cfg.coalesce_window_min_ms == 2.0
        assert cfg.coalesce_window_max_ms == 80.0

    def test_knobs_thread_through_to_server_config(self, tmp_path):
        from nomad_tpu.api.agent import Agent, AgentConfig

        a = Agent(AgentConfig(
            serf_enabled=False, kernel_warmup=False,
            warmup_manifest=str(tmp_path / "m.json"),
            coalesce_window_min_ms=3.0, coalesce_window_max_ms=77.0))
        a.start()
        try:
            sc = a.server.config
            assert sc.kernel_warmup is False
            assert sc.warmup_manifest_path.endswith("m.json")
            assert sc.coalesce_window_min_ms == 3.0
            assert sc.coalesce_window_max_ms == 77.0
        finally:
            a.shutdown()


class TestAdaptiveCoalescer:
    @staticmethod
    def _stubbed(monkeypatch, latency_s=0.02):
        """(coalesce, fired, KinStub): ``launch_wave`` stubbed to note the
        wave sizes, and a wave-latency sample short enough to arm a
        deadline (a cold process parks for full waves)."""
        from nomad_tpu.parallel import coalesce

        fired = []

        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            fired.append(len(kins))
            return [object() for _ in kins]

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)
        monkeypatch.setattr(coalesce, "wave_latency_ewma",
                            coalesce._LatencyEWMA())
        monkeypatch.setattr(coalesce, "wave_deadline_ewma",
                            coalesce._LatencyEWMA(alpha=0.25))
        coalesce.wave_latency_ewma.update(latency_s)

        class KinStub:
            class _Arr:
                shape = (8,)
            cap_cpu = _Arr()

        return coalesce, fired, KinStub

    @staticmethod
    def _until(cond, seconds=5.0):
        t_end = time.perf_counter() + seconds
        while not cond() and time.perf_counter() < t_end:
            time.sleep(0.005)
        return cond()

    def test_partial_wave_fires_at_deadline(self, monkeypatch):
        """All four participants have arrived once (their first wave);
        two place again and park, the other two linger: the wave fires
        at the window deadline with just those two."""
        coalesce, fired, KinStub = self._stubbed(monkeypatch)
        c = coalesce.LaunchCoalescer(4, window_min_s=0.01,
                                     window_max_s=0.01)
        results = {}
        linger = threading.Event()

        def member(i):
            try:
                c.launch(KinStub(), 1, None)
                if i < 2:
                    results[i] = c.launch(KinStub(), 1, None)
                else:
                    linger.wait(10)
            finally:
                c.done()

        threads = [threading.Thread(target=member, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        try:
            assert self._until(lambda: len(fired) == 2), (
                f"deadline never fired: {fired}")
        finally:
            linger.set()
            for t in threads:
                t.join(timeout=10)
        assert fired == [4, 2]
        assert results[0] is not None and results[1] is not None
        assert c.deadline_launches == 1

    def test_first_wave_waits_for_members_still_on_their_way(
            self, monkeypatch):
        """A 20 ms wave latency arms deadlines, but one participant of
        the batch has not arrived yet (its tensors are being built): the
        two that have park for the full wave, and each such wait is
        counted (ISSUE 30; a deadline here cut every wave of PERF.md
        finding 27-2)."""
        coalesce, fired, KinStub = self._stubbed(monkeypatch)
        c = coalesce.LaunchCoalescer(3, window_min_s=0.001,
                                     window_max_s=0.050)
        assert c._window_s() is not None
        held0 = coalesce.wave_stats.snapshot()["held_for_arrivals"]

        def member():
            try:
                c.launch(KinStub(), 1, None)
            finally:
                c.done()

        threads = [threading.Thread(target=member) for _ in range(3)]
        for t in threads[:2]:
            t.start()
        time.sleep(0.25)
        assert fired == [], "a deadline cut the first wave"
        threads[2].start()
        for t in threads:
            t.join(timeout=10)
        assert fired == [3]
        assert c.deadline_launches == 0
        snap = coalesce.wave_stats.snapshot()
        assert snap["held_for_arrivals"] - held0 == 2

    @pytest.mark.parametrize("how", ["done", "suspend"])
    def test_a_member_that_finishes_or_steps_aside_has_arrived(
            self, monkeypatch, how):
        """Deadlines work as before once every participant has launched
        once, finished without launching (``done``) or stepped aside
        (``suspend``): here the third does one of the latter, the other
        two ride one wave, one of them places again and its deadline
        fires."""
        coalesce, fired, KinStub = self._stubbed(monkeypatch)
        c = coalesce.LaunchCoalescer(3, window_min_s=0.01,
                                     window_max_s=0.01)
        getattr(c, how)()                # this thread is the third member
        out = {}
        linger = threading.Event()

        def member(i):
            try:
                c.launch(KinStub(), 1, None)
                if i == 0:
                    out[i] = c.launch(KinStub(), 1, None)
                else:
                    linger.wait(10)
            finally:
                c.done()

        threads = [threading.Thread(target=member, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        try:
            assert self._until(lambda: len(fired) == 2), (
                f"deadline never fired: {fired}")
        finally:
            linger.set()
            for t in threads:
                t.join(timeout=10)
        assert fired == [2, 1] and out[0] is not None
        assert c.deadline_launches == 1
        if how == "suspend":
            c.resume()
            c.done()

    @pytest.mark.parametrize("latency_s, window_max_s", [
        (None, 0.001),       # cold process: no wave-latency sample yet
        (0.36, 0.050),       # the window it asks for, 180 ms, outgrows the cap
    ])
    def test_parks_for_full_waves(self, monkeypatch, latency_s,
                                  window_max_s):
        """Deadlines stay disarmed without a wave-latency sample (cold
        process, first compiles in flight), and once half the wave
        latency no longer fits under the cap: a deadline at the cap
        would cut a wave whose members are still being prepared, the
        pieces would collide in the applier and each leftover would
        compile a fresh bucket (a 360 ms wave armed a 50 ms deadline
        until PR 27)."""
        from nomad_tpu.parallel import coalesce

        fired = []

        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            fired.append(len(kins))
            return [object() for _ in kins]

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)
        monkeypatch.setattr(coalesce, "wave_latency_ewma",
                            coalesce._LatencyEWMA())
        if latency_s is not None:
            coalesce.wave_latency_ewma.update(latency_s)

        class KinStub:
            class _Arr:
                shape = (8,)
            cap_cpu = _Arr()

        c = coalesce.LaunchCoalescer(3, window_min_s=0.001,
                                     window_max_s=window_max_s)
        out = {}

        def member(i):
            out[i] = c.launch(KinStub(), 1, None)

        threads = [threading.Thread(target=member, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        assert fired == [], "a deadline cut the wave"
        c.done()                       # the third member finishes: the
        for t in threads:              # rendezvous completes the wave
            t.join(timeout=10)
        assert fired == [2]
        assert len(out) == 2
        assert c.deadline_launches == 0
        for _ in range(2):
            c.done()

    def test_full_wave_still_fires_immediately(self, monkeypatch):
        from nomad_tpu.parallel import coalesce

        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            return [object() for _ in kins]

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)

        class KinStub:
            class _Arr:
                shape = (8,)
            cap_cpu = _Arr()

        c = coalesce.LaunchCoalescer(2, window_min_s=30.0,
                                     window_max_s=30.0)
        out = {}

        def member(i):
            out[i] = c.launch(KinStub(), 1, None)
            c.done()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=member, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        # rendezvous completed far below the 30s window
        assert time.perf_counter() - t0 < 5.0
        assert c.deadline_launches == 0
        assert len(out) == 2

    def test_suspended_member_does_not_block_wave(self, monkeypatch):
        """Pipelined plan submit: a participant inside its plan window
        (suspend) must not hold up the remaining members' wave."""
        from nomad_tpu.parallel import coalesce

        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            return [object() for _ in kins]

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)

        class KinStub:
            class _Arr:
                shape = (8,)
            cap_cpu = _Arr()

        c = coalesce.LaunchCoalescer(3, window_min_s=30.0,
                                     window_max_s=30.0, adaptive=False)
        c.suspend()                      # member 2 is off at the applier
        out = {}

        def member(i):
            out[i] = c.launch(KinStub(), 1, None)

        threads = [threading.Thread(target=member, args=(i,))
                   for i in range(2)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert time.perf_counter() - t0 < 5.0
        assert len(out) == 2 and all(v is not None for v in out.values())
        c.resume()
        for _ in range(3):
            c.done()

    def test_wave_stats_and_exporter_gauges(self, monkeypatch):
        from nomad_tpu.parallel import coalesce
        from nomad_tpu.telemetry.exporter import prometheus_text

        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            return [object() for _ in kins]

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)
        coalesce.wave_stats.reset()

        class KinStub:
            class _Arr:
                shape = (8,)
            cap_cpu = _Arr()

        c = coalesce.LaunchCoalescer(2, window_min_s=30.0,
                                     window_max_s=30.0)

        def member():
            c.launch(KinStub(), 1, None)
            c.done()

        threads = [threading.Thread(target=member) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        snap = coalesce.wave_stats.snapshot()
        assert snap["launches"] == 1
        assert snap["full_launches"] == 1
        assert 0.0 < snap["fill_ratio"] <= 1.0
        text = prometheus_text()
        assert "nomad_tpu_wave_fill_ratio" in text
        assert 'nomad_tpu_wave_park_latency_seconds{quantile="0.99"}' \
            in text
        assert 'nomad_tpu_wave_launches_total{fired="deadline"}' in text
        assert ("nomad_tpu_wave_held_for_arrivals_total "
                f"{snap['held_for_arrivals']}") in text


class TestFeatureCanonicalization:
    def test_near_identical_features_share_a_variant(self):
        from nomad_tpu.ops.kernel import KernelFeatures, canonical_features
        from nomad_tpu.parallel.coalesce import union_features
        from nomad_tpu.tensors.schema import MAX_SPREADS

        a = KernelFeatures(n_spreads=1, with_step_penalties=True,
                           with_preferred=False)
        b = KernelFeatures(n_spreads=3, with_step_penalties=False,
                           with_preferred=True)
        ca, cb = canonical_features(a), canonical_features(b)
        assert ca == cb
        assert ca.n_spreads == MAX_SPREADS
        assert ca.with_step_penalties and ca.with_preferred
        # the wave union canonicalizes too
        assert union_features([a]) == union_features([b])

    def test_canonicalization_keeps_lean_lean(self):
        from nomad_tpu.ops.kernel import LEAN_FEATURES, canonical_features

        assert canonical_features(LEAN_FEATURES) == LEAN_FEATURES

    def test_canonical_features_preserve_placements(self):
        """Rounding a feature set UP must not change what the kernel
        chooses (neutral planes are no-ops by definition)."""
        from nomad_tpu.ops.kernel import (
            build_kernel_in,
            canonical_features,
            infer_features,
            pad_steps,
            place_taskgroup_jit,
        )
        from nomad_tpu.scheduler.context import EvalContext
        from nomad_tpu.scheduler.stack import XLAGenericStack
        from nomad_tpu.structs.eval_plan import Plan
        from nomad_tpu.tensors.schema import ClusterTensors
        from nomad_tpu.state.store import StateStore

        s = StateStore()
        for _ in range(6):
            s.upsert_node(mock.node())
        job = mock.job()
        s.upsert_job(job)
        snap = s.snapshot()
        c = ClusterTensors.build(snap.nodes())
        ctx = EvalContext(snap, Plan())
        st = XLAGenericStack(False, ctx, c)
        st.set_job(job)
        tg = job.task_groups[0]
        ev = st._build_eval_tensors(tg, np.zeros(c.n_pad, bool))
        kin = build_kernel_in(c, ev, 3)
        feats = infer_features(ev)
        kp = pad_steps(3)
        lean = place_taskgroup_jit(kin, kp, feats)
        canon = place_taskgroup_jit(kin, kp, canonical_features(feats))
        assert (np.asarray(lean.chosen) == np.asarray(canon.chosen)).all()
        assert np.allclose(np.asarray(lean.scores),
                           np.asarray(canon.scores), atol=1e-6)


class TestDonationAlignment:
    def test_preemption_loop_emits_no_donation_warning(self):
        """The seed's preemption cell warned 'Some donated buffers were
        not usable' (pre_cpu/pre_mem were donated but never returned).
        conftest promotes that warning to an error suite-wide; this
        test exercises the loop so the promotion has teeth."""
        import jax
        import jax.numpy as jnp

        from nomad_tpu.ops.kernel import build_kernel_in
        from nomad_tpu.parallel.batching import (
            device_put_shared,
            make_preemption_apply_loop,
        )
        from nomad_tpu.parallel.synthetic import (
            synthetic_cluster,
            synthetic_eval,
        )

        cluster = synthetic_cluster(100, cpu=3900.0, mem=7936.0,
                                    disk=98304.0, seed=7)
        ev0 = synthetic_eval(cluster, desired_count=4)
        shared = device_put_shared(build_kernel_in(cluster, ev0, 4))
        z = jnp.zeros(cluster.n_pad, jnp.float32)
        rng = np.random.default_rng(0)
        ac = jnp.asarray(rng.choice([250.0, 500.0], (2, 4))
                         .astype(np.float32))
        am = jnp.asarray(rng.choice([128.0, 256.0], (2, 4))
                         .astype(np.float32))
        ns = jnp.asarray(np.full(4, 4, np.int32))
        loop = make_preemption_apply_loop(4)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            out = loop(shared, z + 0, z + 0, z + 1000.0, z + 1000.0,
                       z + 0.5, ac, am, ns)
            jax.block_until_ready(out)


class TestDecomposeDedupe:
    def test_overlapping_wall_intervals_count_once(self):
        """Two pipelined compiles overlapping on the clock must not sum
        past wall (the seed artifact's attributed_share was 1.0267)."""
        import trace_report
        from nomad_tpu.telemetry.trace import Span

        wall = 2.0
        stage_totals = {
            "kernel.compile": {"count": 2, "total_s": 2.4,
                               "exclusive_s": 2.4, "cpu_s": 0.0,
                               "exclusive_cpu_s": 0.0},
            "eval.schedule": {"count": 10, "total_s": 1.5,
                              "exclusive_s": 1.5, "cpu_s": 1.5,
                              "exclusive_cpu_s": 1.5},
        }
        # two compile spans overlapping 1.2s-1.2s => union 1.4s
        spans = [
            Span("kernel.compile", "t", 1, 0, 0.0, 1.2, 0, 0, 0, "a"),
            Span("kernel.compile", "t", 2, 0, 0.2, 1.2, 0, 0, 0, "b"),
        ]
        out = trace_report.decompose(stage_totals, wall, 10, spans=spans)
        assert out["attributed_share"] <= 1.0
        # raw sums stay honest and the overlap is reported
        assert out["attributed_raw_s"] == pytest.approx(3.9)
        assert out["parallel_overlap_s"] > 0
        # compile's share reflects the deduped interval, not the sum
        assert out["stages"]["compile"]["share_of_wall"] \
            == pytest.approx(1.4 / 2.0, abs=0.01)

    def test_no_spans_keeps_raw_attribution(self):
        import trace_report

        stage_totals = {
            "kernel.execute": {"count": 1, "total_s": 0.5,
                               "exclusive_s": 0.5, "cpu_s": 0.0,
                               "exclusive_cpu_s": 0.0},
        }
        out = trace_report.decompose(stage_totals, 1.0, 10)
        assert out["attributed_share"] == pytest.approx(0.5)

    def test_cpu_coverage_is_thread_cpu_over_thread_cpu(self):
        """Overlap cannot raise it: a device stage's wall is not in it,
        a wait counts by the CPU burned inside it and never by its
        wall, a span no table names counts on the spent side only, and
        a thread started since the first reading counts whole."""
        import trace_report

        def agg(total_s, cpu_s):
            return {"count": 1, "total_s": total_s, "exclusive_s": total_s,
                    "cpu_s": cpu_s, "exclusive_cpu_s": cpu_s}

        stage_totals = {
            "eval.schedule": agg(3.0, 0.5),
            "kernel.execute": agg(2.0, 0.01),     # wall stage: its CPU
            "wave.park": agg(9.0, 0.08),          # a wait: its CPU
            "bg.drainer": agg(0.1, 0.01),
            "some.unnamed": agg(0.3, 0.3),
        }
        out = trace_report.cpu_coverage(
            stage_totals, before={1: 10.0, 2: 5.0},
            after={1: 10.4, 2: 5.3, 3: 0.3})
        assert out["named_cpu_s"] == pytest.approx(0.6)
        assert out["python_cpu_s"] == pytest.approx(1.0)
        assert out["cpu_coverage"] == pytest.approx(0.6)

    def test_python_threads_cpu_reads_every_live_thread(self):
        import trace_report

        stop = threading.Event()

        def spin():
            while not stop.is_set():
                pass

        spinner = threading.Thread(target=spin, daemon=True)
        spinner.start()
        try:
            first = trace_report.python_threads_cpu_s()
            mine = time.thread_time()
            while time.thread_time() - mine < 0.02:
                pass
            second = trace_report.python_threads_cpu_s()
        finally:
            stop.set()
            spinner.join()
        me = threading.get_ident()
        assert second[me] - first[me] >= 0.02
        assert second[spinner.ident] > first[spinner.ident]
