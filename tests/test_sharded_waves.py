"""Live waves over the device mesh (VERDICT r2 missing #2).

The coalescer's joint wave kernel runs with its node axis sharded over
the mesh (parallel/sharded.joint_sharded_entry): the SAME program, so
placements must be identical to single-device dispatch — per-step
argmax/top-k lower to per-shard reductions + cross-shard collectives
(SURVEY.md §2.10 node-axis-over-ICI mapping). Tests run on the
8-virtual-CPU mesh (conftest forces the device count).
"""

import numpy as np
import pytest

import jax

from nomad_tpu import mock
from nomad_tpu.parallel import coalesce


@pytest.fixture
def wave_mesh():
    from nomad_tpu.parallel.sharded import wave_mesh as make

    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    return make(8)


class TestShardedWaveParity:
    def test_launch_wave_identical_to_single_device(self, wave_mesh):
        from nomad_tpu.ops.kernel import (
            LEAN_FEATURES,
            build_kernel_in,
            infer_features,
        )
        from nomad_tpu.parallel.synthetic import (
            synthetic_cluster,
            synthetic_eval,
        )

        cluster = synthetic_cluster(200, cpu=2000.0, mem=4096.0,
                                    disk=50000.0, seed=5)
        rng = np.random.default_rng(3)
        kins, steps, feats = [], [], []
        for i in range(5):
            ev = synthetic_eval(cluster, desired_count=4)
            kin = build_kernel_in(cluster, ev, 4)
            kin = kin._replace(
                ask_cpu=np.asarray(float(rng.choice([100, 300, 500])),
                                   np.float32))
            kins.append(kin)
            steps.append(4)
            feats.append(LEAN_FEATURES._replace(with_topk=True))

        coalesce.configure_wave_mesh(None)
        single = coalesce.launch_wave(kins, steps, feats)

        before = coalesce.sharded_wave_launches
        coalesce.configure_wave_mesh(wave_mesh)
        try:
            sharded = coalesce.launch_wave(kins, steps, feats)
        finally:
            coalesce.configure_wave_mesh(None)
        assert coalesce.sharded_wave_launches == before + 1

        for s, m in zip(single, sharded):
            np.testing.assert_array_equal(np.asarray(s.chosen),
                                          np.asarray(m.chosen))
            np.testing.assert_array_equal(np.asarray(s.found),
                                          np.asarray(m.found))
            np.testing.assert_allclose(np.asarray(s.scores),
                                       np.asarray(m.scores),
                                       rtol=1e-6, atol=1e-7)
        assert any(np.asarray(s.found).any() for s in single)

    def test_spread_wave_identical_to_single_device(self, wave_mesh):
        """``joint_sharded`` with spread stanzas: the per-node count
        plane is node-sharded like every [N] carry, and each placement
        reads the chosen node's bucket from a node-sharded plane."""
        from nomad_tpu.ops.kernel import build_kernel_in, infer_features
        from nomad_tpu.parallel.synthetic import (
            synthetic_cluster,
            synthetic_eval,
        )

        cluster = synthetic_cluster(200, cpu=2000.0, mem=4096.0,
                                    disk=50000.0, seed=5)
        kins, steps, feats = [], [], []
        for i in range(3):
            ev = synthetic_eval(cluster, desired_count=12, with_spread=True,
                                used_frac=0.3, seed=i)
            kins.append(build_kernel_in(cluster, ev, 12))
            steps.append(12)
            feats.append(infer_features(ev))
        assert all(f.n_spreads == 1 for f in feats)

        coalesce.configure_wave_mesh(None)
        single = coalesce.launch_wave(kins, steps, feats)
        before = coalesce.sharded_wave_launches
        coalesce.configure_wave_mesh(wave_mesh)
        try:
            sharded = coalesce.launch_wave(kins, steps, feats)
        finally:
            coalesce.configure_wave_mesh(None)
        assert coalesce.sharded_wave_launches == before + 1

        for s, m in zip(single, sharded):
            assert np.asarray(s.found).all()
            np.testing.assert_array_equal(np.asarray(s.chosen),
                                          np.asarray(m.chosen))
            np.testing.assert_allclose(np.asarray(s.scores),
                                       np.asarray(m.scores),
                                       rtol=1e-6, atol=1e-7)


    @pytest.mark.parametrize("program", ["fused_wave_sharded",
                                         "joint_sharded"])
    def test_tied_shuffled_wave_identical_to_single_device(self, wave_mesh,
                                                           program):
        """Identical empty nodes, every member with its own shuffle: a
        tie goes to the node the member's permutation meets first. Both
        mesh programs and the one-chip ``joint`` take the rank plane from
        the same ``ops/kernel._inv``. The launcher picks the mesh program
        from the wave's features (``coalesce.wave_program``): a spread
        slot in the union, its stanzas all inactive, is outside the
        fused envelope and runs ``joint_sharded``."""
        from nomad_tpu.ops.kernel import LEAN_FEATURES, build_kernel_in
        from nomad_tpu.parallel.synthetic import (
            synthetic_cluster,
            synthetic_eval,
        )

        cluster = synthetic_cluster(200, cpu=2000.0, mem=4096.0,
                                    disk=50000.0, seed=5)
        rng = np.random.default_rng(30)
        feats0 = LEAN_FEATURES._replace(
            with_topk=True, with_shuffle=True,
            n_spreads=int(program == "joint_sharded"))
        kins, steps, feats = [], [], []
        for _ in range(4):
            ev = synthetic_eval(cluster, desired_count=6)
            perm = rng.permutation(cluster.n_pad).astype(np.int32)
            kins.append(build_kernel_in(cluster, ev, 6, node_perm=perm))
            steps.append(6)
            feats.append(feats0)
        assert coalesce.wave_program(
            wave_mesh.size, cluster.n_pad,
            coalesce.union_features(feats)) == program

        single = coalesce.launch_wave(kins, steps, feats, mesh=None)
        fused_before = coalesce.fused_wave_stats.snapshot()
        before = coalesce.sharded_wave_launches
        sharded = coalesce.launch_wave(kins, steps, feats, mesh=wave_mesh)
        assert coalesce.sharded_wave_launches == before + 1
        fused_after = coalesce.fused_wave_stats.snapshot()
        assert (fused_after["launches"] - fused_before["launches"],
                fused_after["fallbacks"] - fused_before["fallbacks"]) == (
            (1, 0) if program == "fused_wave_sharded" else (0, 1))

        # the wave's first step meets nothing but ties
        first = kins[0].node_perm[
            np.argmax(np.asarray(kins[0].base_mask)[kins[0].node_perm])]
        assert np.asarray(single[0].chosen)[0] == first != 0
        for s, m in zip(single, sharded):
            assert np.asarray(s.found).all()
            np.testing.assert_array_equal(np.asarray(s.chosen),
                                          np.asarray(m.chosen))
            np.testing.assert_array_equal(np.asarray(s.topk_idx),
                                          np.asarray(m.topk_idx))
            np.testing.assert_allclose(np.asarray(s.scores),
                                       np.asarray(m.scores),
                                       rtol=1e-6, atol=1e-7)


def _shared_layout_wave(n_nodes=200, members=4, k=3, seed=5):
    """B kins whose three sharing groups are ALL identity-shared (the
    live stack.py build's steady shape): wave-shared planes from one
    (cluster, usage) pair, neutral/job groups from frozen singletons."""
    from nomad_tpu.ops.kernel import (
        LEAN_FEATURES,
        build_kernel_in,
        neutral_planes,
    )
    from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

    cluster = synthetic_cluster(n_nodes, seed=seed)
    cluster.avail_mbits = np.zeros(cluster.n_pad, np.int32)
    cluster.avail_mbits[:n_nodes] = 1000

    class _U:
        pass

    u = _U()
    u.uid = "wave-test"
    u.version = 1
    u.structure_version = 0
    u.rows = {nid: i for i, nid in enumerate(cluster.node_ids)}
    u.n = cluster.n_real
    for f, dt in (("used_cpu", np.float32), ("used_mem", np.float32),
                  ("used_disk", np.float32), ("used_cores", np.int32),
                  ("used_mbits", np.int32)):
        setattr(u, f, np.zeros(cluster.n_real, dt))
    u.row_events = ()
    u.row_events_floor = 0
    u.node_events = ()

    shared = cluster.wave_shared_planes(u)
    neutral = neutral_planes(cluster.n_pad)
    base_mask = cluster.ready.copy()
    base_mask.setflags(write=False)
    ev = synthetic_eval(cluster, desired_count=k)
    kins, steps, feats = [], [], []
    for i in range(members):
        kin = build_kernel_in(cluster, ev, k)
        kin = kin._replace(
            ask_cpu=np.asarray(100.0 + 50 * i, np.float32),
            **{f: shared[f] for f in shared},
            port_conflict=neutral.zeros_bool,
            dev_free=neutral.zeros_dev,
            dev_aff_score=neutral.zeros_f32,
            job_tg_count=neutral.zeros_i32,
            job_any_count=neutral.zeros_i32,
            penalty=neutral.zeros_bool,
            aff_score=neutral.zeros_f32,
            base_mask=base_mask,
        )
        kins.append(kin)
        steps.append(k)
        feats.append(LEAN_FEATURES._replace(with_topk=True))
    return cluster, u, kins, steps, feats


class TestShardedSharedLayout:
    def test_shared_layout_parity_and_resident_h2d(self, wave_mesh):
        """The ISSUE 14 steady shape: identity-shared planes resident
        SHARDED via the device state — bit-identical to single-device
        dispatch, zero fallbacks, and the second sharded wave's h2d is
        just node_perm + scalars (the resident planes move nothing)."""
        from nomad_tpu import telemetry
        from nomad_tpu.telemetry.kernel_profile import profiler
        from nomad_tpu.tensors.device_state import default_device_state

        cluster, u, kins, steps, feats = _shared_layout_wave()
        prior = default_device_state.mesh
        telemetry.enable()
        telemetry.reset()
        try:
            default_device_state.configure_mesh(wave_mesh)
            default_device_state.ensure(cluster, u)
            sharded = coalesce.launch_wave(kins, steps, feats,
                                           mesh=wave_mesh)
            h2d_1 = profiler.summary()["TransferBytes"]["h2d"]
            coalesce.launch_wave(kins, steps, feats, mesh=wave_mesh)
            h2d_2 = profiler.summary()["TransferBytes"]["h2d"] - h2d_1
            single = coalesce.launch_wave(kins, steps, feats,
                                          mesh=None)
            for s, m in zip(single, sharded):
                np.testing.assert_array_equal(np.asarray(s.chosen),
                                              np.asarray(m.chosen))
                np.testing.assert_array_equal(np.asarray(s.found),
                                              np.asarray(m.found))
                np.testing.assert_allclose(np.asarray(s.scores),
                                           np.asarray(m.scores),
                                           rtol=1e-6, atol=1e-7)
            assert any(np.asarray(s.found).any() for s in single)
            stats = coalesce.sharded_wave_stats.snapshot()
            assert stats["launches"] == 2
            assert stats["fallbacks"] == 0
            assert stats["mesh_devices"] == 8
            # resident sharded planes upload NOTHING on the repeat
            # wave: node_perm ([B, N] i32) + step planes + scalars
            # only — far under one [N] f32 node plane per member
            assert h2d_2 < 40_000, h2d_2
        finally:
            default_device_state.configure_mesh(prior)
            telemetry.disable()
            telemetry.reset()

    def test_indivisible_mesh_falls_back_unsharded(self):
        """A 3-device mesh over a 256-row pad bucket cannot split the
        node axis: the wave must dispatch single-device, count a
        fallback, and still place identically."""
        from nomad_tpu.parallel.sharded import wave_mesh as make

        mesh3 = make(3)
        _, _, kins, steps, feats = _shared_layout_wave(seed=7)
        before = coalesce.sharded_wave_stats.snapshot()
        sharded_before = coalesce.sharded_wave_launches
        out_m = coalesce.launch_wave(kins, steps, feats, mesh=mesh3)
        out_s = coalesce.launch_wave(kins, steps, feats, mesh=None)
        after = coalesce.sharded_wave_stats.snapshot()
        assert coalesce.sharded_wave_launches == sharded_before
        assert after["fallbacks"] == before["fallbacks"] + 1
        for a, b in zip(out_m, out_s):
            np.testing.assert_array_equal(np.asarray(a.chosen),
                                          np.asarray(b.chosen))


class TestShardedWarmup:
    def test_warmup_populates_sharded_jit_signatures(self, wave_mesh):
        """ops/warmup learns the sharded joint programs: a manifest
        entry warmed with ``mesh`` makes the live sharded launch of
        that bucket shape a cache HIT (0 joint_sharded misses) — the
        steady-state-keeps-0-compiles contract, mesh edition."""
        from nomad_tpu import telemetry
        from nomad_tpu.ops import warmup as kernel_warmup
        from nomad_tpu.ops.kernel import LEAN_FEATURES, pad_steps
        from nomad_tpu.telemetry.kernel_profile import profiler

        _, _, kins, steps, feats = _shared_layout_wave(seed=11)
        n_pad = int(np.asarray(kins[0].cap_cpu).shape[0])
        b_pad = coalesce.pad_wave(len(kins))
        feat_union = coalesce.union_features(feats)
        entry = {
            "kernel": "joint", "wave": b_pad,
            "steps": pad_steps(b_pad * steps[0]), "nodes": n_pad,
            # the all-stacked layout (no residency installed here)
            "shared": False, "neutral_shared": False,
            "job_shared": False,
            "features": dict(feat_union._asdict()),
        }
        compiled, failed = kernel_warmup.warmup_entries(
            [entry], mesh=wave_mesh, mesh_only=True)
        assert compiled == 1 and failed == 0
        telemetry.enable()
        telemetry.reset()
        try:
            coalesce.launch_wave(kins, steps, feats, mesh=wave_mesh)
            assert profiler.misses_for("joint_sharded") == 0, \
                profiler.summary()["PerKey"]
        finally:
            telemetry.disable()
            telemetry.reset()

    def test_sharded_launch_keys_fold_into_manifest(self, wave_mesh):
        """A mesh server's manifest must not go empty just because
        every wave dispatched sharded: joint_sharded profiler keys
        fold into mesh-agnostic joint entries."""
        from nomad_tpu import telemetry
        from nomad_tpu.ops import warmup as kernel_warmup
        from nomad_tpu.telemetry.kernel_profile import profiler

        _, _, kins, steps, feats = _shared_layout_wave(seed=13)
        telemetry.enable()
        telemetry.reset()
        try:
            coalesce.launch_wave(kins, steps, feats, mesh=wave_mesh)
            entries = kernel_warmup.manifest_from_profiler(profiler)
        finally:
            telemetry.disable()
            telemetry.reset()
        joints = [e for e in entries if e["kernel"] == "joint"]
        assert joints, entries
        assert joints[0]["nodes"] == 256


class TestServerOverMesh:
    def test_server_places_through_sharded_waves(self, wave_mesh):
        """A live server with use_device_mesh=True places a batched
        job's allocations through shard_map-style sharded waves."""
        import time

        from nomad_tpu.server.server import Server, ServerConfig

        before = coalesce.sharded_wave_launches
        server = Server(ServerConfig(
            num_workers=1, worker_batch_size=8, heartbeat_ttl=3600.0,
            use_device_mesh=True,
        ))
        server.start()
        try:
            assert server.wave_mesh is not None
            for _ in range(30):
                server.node_register(mock.node())
            jobs = []
            for _ in range(8):
                job = mock.simple_job()
                job.task_groups[0].count = 3
                jobs.append(job)
                server.job_register(job)
            deadline = time.time() + 120
            placed = 0
            while time.time() < deadline:
                snap = server.state.snapshot()
                placed = sum(len(snap.allocs_by_job(j.namespace, j.id))
                             for j in jobs)
                if placed >= 24:
                    break
                time.sleep(0.1)
            assert placed >= 24, placed
            assert coalesce.sharded_wave_launches > before
            # placements are real: every alloc row maps to a node with
            # capacity accounting in the usage planes
            u = server.state.snapshot().usage
            assert float(u.used_cpu.sum()) >= 24 * 500
        finally:
            server.shutdown()


class TestMiniMeshSmoke:
    def test_steady_sharded_bursts_keep_zero_new_compiles(self):
        """Tier-1 mini-mesh smoke (ISSUE 14 satellite): a live mesh
        server places two bursts through sharded waves; the SECOND
        burst re-uses burst 1's compiled sharded programs (0 new
        joint_sharded misses), every wave dispatches sharded
        (fallbacks 0), and the resident cluster state advances by
        dirty-row scatter between waves."""
        import time

        from nomad_tpu import mock, telemetry
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.telemetry.kernel_profile import profiler
        from nomad_tpu.tensors.device_state import default_device_state

        server = Server(ServerConfig(
            num_workers=1, worker_batch_size=8, heartbeat_ttl=3600.0,
            use_device_mesh=True,
        ))
        telemetry.enable()
        telemetry.reset()
        server.start()
        try:
            assert server.wave_mesh is not None
            # the server adopted its mesh into the resident state
            assert default_device_state.mesh is server.wave_mesh
            for _ in range(30):
                server.node_register(mock.node())

            def burst(n_jobs: int) -> None:
                jobs = []
                for _ in range(n_jobs):
                    job = mock.simple_job()
                    job.task_groups[0].count = 3
                    jobs.append(job)
                    server.job_register(job)
                deadline = time.time() + 120
                while time.time() < deadline:
                    snap = server.state.snapshot()
                    placed = sum(
                        len(snap.allocs_by_job(j.namespace, j.id))
                        for j in jobs)
                    if placed >= 3 * n_jobs:
                        return
                    time.sleep(0.05)
                raise AssertionError("burst did not place in time")

            burst(8)
            stats1 = coalesce.sharded_wave_stats.snapshot()
            assert stats1["launches"] >= 1, stats1
            assert stats1["fallbacks"] == 0, stats1
            # the warmup-manifest flow, mesh edition: burst 1's
            # observed keys (sharded keys fold into joint entries)
            # expand over the bucket lattice and AOT-compile the
            # sharded signatures — burst 2 then cannot hit a tail
            # bucket cold (a deadline-fired partial wave lands on a
            # smaller, pre-warmed bucket)
            from nomad_tpu.ops import warmup as kernel_warmup

            entries = kernel_warmup.expand_lattice(
                kernel_warmup.manifest_from_profiler(profiler),
                max_wave=8)
            compiled, failed = kernel_warmup.warmup_entries(
                entries, mesh=server.wave_mesh, mesh_only=True)
            assert compiled >= 1 and failed == 0, (compiled, failed)
            misses1 = profiler.misses_for("joint_sharded")
            burst(8)
            stats2 = coalesce.sharded_wave_stats.snapshot()
            assert stats2["launches"] > stats1["launches"], stats2
            assert stats2["fallbacks"] == 0, stats2
            # steady state: burst 2's sharded waves are all cache hits
            assert profiler.misses_for("joint_sharded") == misses1, \
                profiler.summary()["PerKey"]
            # dirty-row advancement ran (the between-wave scatter)
            assert default_device_state.snapshot()["delta_advances"] \
                >= 1, default_device_state.snapshot()
        finally:
            server.shutdown()
            telemetry.disable()
            telemetry.reset()
            assert default_device_state.mesh is None
