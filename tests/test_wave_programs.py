"""The wave programs as the launcher runs them (ISSUE 31).

A wave has one program per dispatch kind and ``coalesce.wave_program``
names it: ``joint`` on one device, ``fused_wave_sharded`` or
``joint_sharded`` on a mesh. The tests here run on the CPU what a TPU
runs:

- the lattice: randomized waves (used planes, permutations, penalties,
  pins, port conflicts, distinct hosts, spreads) through ``launch_wave``
  on one device, held against test_kernel.py's float64
  ``_pick_reference``, which imports nothing of the program;
- the launcher's stacking, step layout and split against a direct call
  of ``place_taskgroups_joint_jit`` over a fully stacked wave, bit for
  bit, in each of the eight sharing layouts;
- ``wave_program``'s table; the launch record; a steady burst that
  compiles nothing;
- the mesh's fused program (``fused_sharded_entry``) against ``joint``
  on the conftest 8-virtual-device mesh, bit for bit.
"""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_kernel import _pick_reference, spread_tensor

from nomad_tpu import telemetry
from nomad_tpu.ops.kernel import (
    FULL_FEATURES,
    FUSED_METRIC_FIELDS,
    LEAN_FEATURES,
    MAX_PENALTY_NODES,
    TOPK,
    KernelIn,
    build_kernel_in,
    pad_steps,
    place_taskgroups_joint_jit,
    unpack_fused_wave,
)
from nomad_tpu.parallel import coalesce
from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval
from nomad_tpu.telemetry.kernel_profile import profiler
from nomad_tpu.telemetry.trace import tracer

K = 4
B = 4

#: the lattice: each variant pinned to a node count in a DIFFERENT pad
#: bucket so padded shapes ride along (n_real strictly below n_pad
#: everywhere). The first six are inside the mesh program's envelope;
#: the two spread ones are the benchmark cell's own feature set and a
#: wave whose members disagree about spreads.
_VARIANTS = (
    ("lean", 60),
    ("shuffle", 200),
    ("penalty_preferred", 383),
    ("distinct", 60),
    ("ports", 200),
    ("kitchen_sink", 383),
    ("spread_even", 200),
    ("spread_mixed", 383),
)
_SHUFFLED = ("shuffle", "penalty_preferred", "kitchen_sink",
             "spread_even", "spread_mixed")


def _racks(cluster):
    return np.array([int(c.split("-")[1])
                     for c in cluster.computed_classes])


def _member_spreads(variant, m, cluster, rng):
    """The spread stanzas of member ``m``: none outside the spread
    variants; the cell's one even stanza over racks, the counts its job
    already has drawn at random; or, for every other member of
    ``spread_mixed``, targets over datacenters at half weight beside an
    even stanza that some nodes lack the attribute of."""
    racks = _racks(cluster)
    if variant == "spread_even":
        return [spread_tensor(cluster.n_pad, racks,
                              counts=rng.integers(0, 4, 50), even=True)]
    if variant == "spread_mixed" and m % 2 == 0:
        lacking = racks.copy()
        lacking[rng.choice(cluster.n_real, 40, replace=False)] = -1
        return [spread_tensor(cluster.n_pad, racks % 3,
                              counts=rng.integers(0, 3, 3),
                              desired=[2.0, 1.0, 1.0], weight=0.5),
                spread_tensor(cluster.n_pad, lacking,
                              counts=rng.integers(0, 2, 50), even=True)]
    return []


_ORDER = [(m, j) for m in range(B) for j in range(K)]    # the launcher's


def _wave_members(seed, variant, n_nodes, draw=0):
    """B randomized members of one wave: their KernelIn and features as
    the scheduler would hand them to the launcher, and the same members
    as the reference reads them (plain values, no program types but the
    tensors a KernelIn is built from). Each variant is built so that
    what it is named for decides some step: ``lean`` and ``shuffle``
    draw their used planes from three levels, so that nodes tie and the
    tie-break picks; a penalty sits on the node the step would have
    taken without it; the ports variants leave some nodes short of
    dynamic ports; the distinct ones leave a member fewer free hosts
    than it has steps."""
    rng = np.random.default_rng([seed, n_nodes, draw])
    cluster = synthetic_cluster(
        n_nodes, cpu=3900.0, mem=7936.0, disk=98304.0,
        seed=int(rng.integers(0, 99)))
    n_pad = cluster.n_pad
    kp = pad_steps(K)
    ports = variant in ("ports", "kitchen_sink")
    if ports or variant == "spread_mixed":
        cluster.free_dyn[:n_nodes] = rng.integers(0, 6, n_nodes)
    kins, feats, members, perms = [], [], [], []
    for m in range(B):
        ev = synthetic_eval(
            cluster, ask_cpu=float(rng.choice([250, 500, 900])),
            ask_mem=float(rng.choice([128, 256, 700])), desired_count=K)
        ev = dataclasses.replace(
            ev, spreads=_member_spreads(variant, m, cluster, rng))
        perm = rng.permutation(n_pad).astype(np.int32)
        f = LEAN_FEATURES._replace(
            with_topk=True, with_shuffle=variant in _SHUFFLED,
            n_spreads=len(ev.spreads))
        if variant in ("lean", "shuffle"):
            levels = rng.choice([0.1, 0.3, 0.5], (2, n_pad))
        else:
            levels = 0.6 * rng.random((2, n_pad))
        plain = dict(used_cpu=(3900.0 * levels[0]).astype(np.float32),
                     used_mem=(7936.0 * levels[1]).astype(np.float32))
        kin = build_kernel_in(cluster, ev, K, node_perm=perm)._replace(
            used_cpu=plain["used_cpu"], used_mem=plain["used_mem"])
        if ports or (variant == "spread_mixed" and m == 3):
            plain.update(port_conflict=rng.random(n_pad) < 0.3,
                         reserved_ports=True, dyn_ports=2)
            kin = kin._replace(
                port_conflict=plain["port_conflict"],
                ask_has_reserved_ports=np.asarray(True),
                ask_dyn_ports=np.asarray(2, np.int32))
            f = f._replace(with_ports=True)
        if variant in ("distinct", "kitchen_sink"):
            # the group holds every node but three, and a fourth step
            # finds no host; or but ten, three in ten of them held by
            # another group of the job, and ports to fit as well
            sink = variant == "kitchen_sink"
            held = np.ones(n_pad, np.int32)
            held[rng.choice(n_nodes, 10 if sink else K - 1,
                            replace=False)] = 0
            plain.update(
                job_tg_count=held,
                job_any_count=held + (rng.random(n_pad) < 0.3),
                distinct_job=sink, distinct_tg=True)
            kin = kin._replace(
                job_tg_count=plain["job_tg_count"],
                job_any_count=plain["job_any_count"],
                distinct_hosts_job=np.asarray(plain["distinct_job"]),
                distinct_hosts_tg=np.asarray(True))
            f = f._replace(with_distinct=True)
        if variant in ("penalty_preferred", "kitchen_sink") or (
                variant == "spread_mixed" and m % 2 == 1):
            f = f._replace(with_step_penalties=True, with_preferred=True)
        kins.append(kin)
        feats.append(f)
        members.append(dict(
            ev=ev, active=np.asarray(kin.spread_active), n_steps=K,
            penalty=np.full((kp, MAX_PENALTY_NODES), -1, np.int32),
            preferred=np.full(kp, -1, np.int32), **plain))
        # without the shuffle a tie goes to the lowest node
        perms.append(perm if f.with_shuffle else np.arange(n_pad))
    # penalties and pins last: a penalty on the node each of a member's
    # first two steps takes without one, and one step pinned elsewhere
    penalized = [m for m in range(B) if feats[m].with_step_penalties]
    unpenalized = penalized and _pick_reference(
        cluster, members, _ORDER, perms)
    for m in penalized:
        mb = members[m]
        for j in (0, 1):
            mb["penalty"][j, 0] = unpenalized[m * K + j][0]
        mb["preferred"][int(rng.integers(0, K))] = rng.integers(0, n_nodes)
        kins[m] = kins[m]._replace(step_penalty=mb["penalty"],
                                   step_preferred=mb["preferred"])
    return cluster, kins, feats, members, perms


#: the reference's scores are float64 and the program's float32, held
#: to 1e-6 of each other: two nodes closer than twice that, and not
#: tied exactly, are a step float32 cannot be asked to decide
_DECIDABLE = 2e-6


def _sound_wave(seed, variant, n_nodes):
    """The first draw of the case in which every step's best score
    stands clear of the next one or ties with it exactly, and the
    reference's rows for it."""
    for draw in range(4):
        cluster, kins, feats, members, perms = _wave_members(
            seed, variant, n_nodes, draw)
        want = _pick_reference(cluster, members, _ORDER, perms)
        gaps = [top[0] - top[1] for *_, top in want]
        if all(g == 0.0 or g > _DECIDABLE for g in gaps):
            return kins, feats, want
    raise AssertionError(f"seed {seed} ({variant}): no decidable draw")


def _run_lattice_seed(seed):
    variant, n_nodes = _VARIANTS[seed % len(_VARIANTS)]
    kins, feats, want = _sound_wave(seed, variant, n_nodes)
    outs = coalesce.launch_wave(kins, [K] * B, feats, mesh=None)
    for (m, j), (node, score, found, *_) in zip(_ORDER, want):
        ctx = f"seed {seed} ({variant}), member {m}, step {j}"
        assert np.asarray(outs[m].found)[j] == found, ctx
        assert np.asarray(outs[m].chosen)[j] == node, ctx
        assert np.asarray(outs[m].scores)[j] == pytest.approx(
            score, abs=1e-6), ctx
    return outs, want


class TestLatticeAgainstTheReference:
    """``launch_wave`` on one device is ``joint``; its answers are the
    float64 reference's across the lattice. Variant and pad bucket
    cycle with the seed."""

    @pytest.mark.parametrize("seed", range(25))
    def test_launch_wave_matches_the_float64_reference(self, seed):
        _run_lattice_seed(seed)

    def test_the_lattice_places_and_refuses(self):
        """The cases hold what they are named for: every variant
        places, and the distinct ones also meet steps with no host
        left (in ``distinct`` each member has three for its four)."""
        for seed, (variant, _) in enumerate(_VARIANTS):
            _, want = _run_lattice_seed(seed)
            placed = sum(found for _, _, found, *_ in want)
            if variant == "distinct":
                assert placed == B * (K - 1)
            elif variant == "kitchen_sink":
                assert B <= placed < B * K
            else:
                assert placed == B * K, variant


# ---------------------------------------------------------------------------
# The launcher's own work: stacking, step layout, split
# ---------------------------------------------------------------------------

_LAYOUT_STEPS = (4, 2, 8)        # ragged: k_max 8, three members in four slots


def _layout_wave(shared, neutral_shared, job_shared):
    """Three members that share, by identity, exactly the groups of
    planes the flags name (coalesce's three sharing groups); a group
    that is not shared differs between members in value too."""
    rng = np.random.default_rng(31)
    cluster = synthetic_cluster(200, cpu=3900.0, mem=7936.0,
                                disk=98304.0, seed=3)
    n_pad = cluster.n_pad
    ev = synthetic_eval(cluster, desired_count=8, used_frac=0.5, seed=4)
    kins = []
    for k in _LAYOUT_STEPS:
        kin = build_kernel_in(
            cluster, ev, k,
            node_perm=rng.permutation(n_pad).astype(np.int32))
        kin = kin._replace(
            ask_cpu=np.float32(rng.choice([250, 500, 900])),
            ask_mem=np.float32(rng.choice([128, 256, 700])),
            ask_has_reserved_ports=np.asarray(True))
        if not shared:
            kin = kin._replace(
                used_cpu=(2000.0 * rng.random(n_pad)).astype(np.float32),
                used_mem=(4000.0 * rng.random(n_pad)).astype(np.float32))
        if not neutral_shared:
            kin = kin._replace(port_conflict=rng.random(n_pad) < 0.3)
        if not job_shared:
            kin = kin._replace(
                job_tg_count=rng.integers(0, 2, n_pad).astype(np.int32))
        kins.append(kin)
    feats = LEAN_FEATURES._replace(with_topk=True, with_shuffle=True,
                                   with_ports=True)
    return kins, feats


def _stack_wave(kins, k_steps, slots=B):
    """The wave stated without the launcher: a member axis on every
    leaf, ``slots`` wide (the slots past the members hold the first
    member with no steps to place), each member's steps together and
    the members in turn. Returns the stacked KernelIn, ``step_member``,
    ``step_local``, the padded step count and each member's first row."""
    k_max = max(k_steps)

    def padded(kin):
        pen = np.full((k_max, kin.step_penalty.shape[1]), -1, np.int32)
        pen[:kin.step_penalty.shape[0]] = kin.step_penalty
        pref = np.full(k_max, -1, np.int32)
        pref[:kin.step_preferred.shape[0]] = kin.step_preferred
        return kin._replace(step_penalty=pen, step_preferred=pref)

    rows = [padded(k) for k in kins]
    rows += [rows[0]._replace(n_steps=np.asarray(0, np.int32))] * (
        slots - len(rows))
    stacked = KernelIn(*[
        np.stack([np.asarray(getattr(k, f)) for k in rows])
        for f in KernelIn._fields])
    t_pad = pad_steps(slots * k_max)
    member = np.full(t_pad, -1, np.int32)
    local = np.zeros(t_pad, np.int32)
    starts = np.concatenate(([0], np.cumsum(k_steps)))
    for m, k in enumerate(k_steps):
        member[starts[m]:starts[m] + k] = m
        local[starts[m]:starts[m] + k] = np.arange(k)
    return stacked, member, local, t_pad, starts


class TestLauncherAgainstADirectCall:
    @pytest.mark.parametrize(
        "layout", list(itertools.product((True, False), repeat=3)),
        ids=lambda lay: "-".join(
            n for n, on in zip(("shared", "neutral", "job"), lay) if on)
        or "stacked")
    def test_bit_identical_in_every_sharing_layout(self, layout):
        """Whatever the launcher ships once for the wave, and however
        it lays the members' steps out and splits the results, each
        member's answers are those of ``place_taskgroups_joint_jit``
        over the same members with a member axis on every leaf."""
        kins, feats = _layout_wave(*layout)
        telemetry.enable()
        telemetry.reset()
        try:
            outs = coalesce.launch_wave(
                kins, list(_LAYOUT_STEPS), [feats] * len(kins), mesh=None)
            keys = [key for kernel, key in profiler.keys()
                    if kernel == "joint"]
        finally:
            telemetry.disable()
            telemetry.reset()
        # the launcher found the layout the members were built to have
        assert [key[3:6] for key in keys] == [layout]

        stacked, member, local, t_pad, starts = _stack_wave(
            kins, _LAYOUT_STEPS)
        ref = place_taskgroups_joint_jit(
            stacked, jnp.asarray(member), jnp.asarray(local), t_pad,
            coalesce.union_features([feats]))

        assert np.asarray(ref.found).sum() == sum(_LAYOUT_STEPS)
        for m, k in enumerate(_LAYOUT_STEPS):
            rows = slice(starts[m], starts[m] + k)
            for field in ("chosen", "scores", "found", "topk_idx",
                          "topk_scores"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(outs[m], field)),
                    np.asarray(getattr(ref, field))[rows],
                    err_msg=f"{field} of member {m}")
            for field in FUSED_METRIC_FIELDS:
                assert getattr(outs[m], field) == \
                    np.asarray(getattr(ref, field))[m], field


# ---------------------------------------------------------------------------
# Which program, and what a launch of it records
# ---------------------------------------------------------------------------

_LEAN = LEAN_FEATURES._replace(with_topk=True, with_shuffle=True)
_SPREAD = _LEAN._replace(n_spreads=FULL_FEATURES.n_spreads)


class TestWaveProgram:
    @pytest.mark.parametrize("mesh_size, n_nodes, feats, program", [
        (0, 64, _LEAN, "joint"),                    # no mesh
        (0, 16384, _SPREAD, "joint"),               # the benchmark's cell
        (1, 64, _LEAN, "joint"),                    # a mesh of one device
        (8, 64, _LEAN, "fused_wave_sharded"),       # shards 8 wide: TOPK
        (8, 32, _LEAN, "joint_sharded"),            # shards 4 wide
        (8, 64, _SPREAD, "joint_sharded"),          # outside the envelope
        (8, 32, _SPREAD, "joint_sharded"),
        (4, 16384, _LEAN, "fused_wave_sharded"),    # the four-chip host
        (4, 16384, _SPREAD, "joint_sharded"),
        (4, 16384, _LEAN._replace(with_devices=True), "joint_sharded"),
        (6, 64, _LEAN, "joint"),                    # 6 does not divide 64
        (6, 64, _SPREAD, "joint"),
        (6, 96, _LEAN, "fused_wave_sharded"),       # a node axis padded for it
    ])
    def test_table(self, mesh_size, n_nodes, feats, program):
        assert TOPK == 8
        assert coalesce.wave_program(mesh_size, n_nodes, feats) == program


class TestOneDeviceLaunch:
    def test_lean_wave_records_joint(self):
        """On the CPU, as on a TPU, a lean one-device wave is ``joint``
        with its eager fetch: the launch record and the dispatch
        counters say so, and the mesh's fused counters stay still."""
        _, kins, feats, _, _ = _wave_members(7, "shuffle", 200)
        telemetry.enable()
        telemetry.reset()
        try:
            coalesce.launch_wave(kins, [K] * B, feats, mesh=None)
            records = [s.attrs for s in tracer.spans(name="wave.launch")]
            dispatches = dict(profiler.summary()["Dispatches"])
            fused = coalesce.fused_wave_stats.snapshot()
        finally:
            telemetry.disable()
            telemetry.reset()
        assert [r["program"] for r in records] == ["joint"]
        assert records[0]["slots"] == B
        assert dispatches == {"joint": 1, "wave_fetch": 1}
        assert fused == {"launches": 0, "fallbacks": 0}

    def test_steady_joint_burst_adds_no_jit_cache_miss(self):
        """After ONE warm wave, repeated waves of the same bucket shape
        compile nothing: two device interactions each, the program and
        the fetch."""
        _, kins, feats, _, _ = _wave_members(11, "spread_even", 200)
        telemetry.enable()
        try:
            coalesce.launch_wave(kins, [K] * B, feats, mesh=None)   # warm
            telemetry.reset()
            for _ in range(3):
                coalesce.launch_wave(kins, [K] * B, feats, mesh=None)
            prof = profiler.summary()
        finally:
            telemetry.disable()
            telemetry.reset()
        assert prof["JitCacheMisses"] == 0, prof["PerKey"]
        assert dict(prof["Dispatches"]) == {"joint": 3, "wave_fetch": 3}


# ---------------------------------------------------------------------------
# The mesh's fused program against the composite
# ---------------------------------------------------------------------------


def _assert_bitwise(fo, ref, t_pad, b, ctx=""):
    host = unpack_fused_wave(np.asarray(fo.packed), t_pad, b)
    np.testing.assert_array_equal(
        host["chosen"], np.asarray(ref.chosen), err_msg=f"chosen {ctx}")
    np.testing.assert_array_equal(
        host["found"], np.asarray(ref.found), err_msg=f"found {ctx}")
    # scores BITWISE, not allclose: same step math
    np.testing.assert_array_equal(
        host["scores"], np.asarray(ref.scores), err_msg=f"scores {ctx}")
    for name in FUSED_METRIC_FIELDS:
        np.testing.assert_array_equal(
            host[name], np.asarray(getattr(ref, name)),
            err_msg=f"{name} {ctx}")
    np.testing.assert_array_equal(
        np.asarray(fo.topk_idx), np.asarray(ref.topk_idx),
        err_msg=f"topk_idx {ctx}")
    np.testing.assert_array_equal(
        np.asarray(fo.topk_scores), np.asarray(ref.topk_scores),
        err_msg=f"topk_scores {ctx}")
    for nm in ("a_cpu", "a_mem", "a_disk"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fo, nm)), np.asarray(getattr(ref, nm)),
            err_msg=f"{nm} {ctx}")


class TestFusedShardedParity:
    """``fused_sharded_entry``'s shard_map program on the conftest
    8-virtual-device mesh against the single-device composite, bit for
    bit, in each variant of its envelope."""

    @pytest.fixture()
    def mesh(self):
        from nomad_tpu.parallel.sharded import wave_mesh as make

        assert len(jax.devices()) >= 8, \
            "conftest must force 8 CPU devices"
        return make(8)

    @pytest.mark.parametrize("seed", range(6))
    def test_sharded_bit_identity(self, seed, mesh):
        from nomad_tpu.parallel.sharded import fused_sharded_entry

        variant, n_nodes = _VARIANTS[seed]
        _, kins, feats, _, _ = _wave_members(seed + 77, variant, n_nodes)
        feats = coalesce.union_features(feats)
        stacked, sm, sl, t_pad, _ = _stack_wave(kins, [K] * B)
        n_pad = stacked.cap_cpu.shape[-1]
        assert coalesce.wave_program(mesh.size, n_pad, feats) == \
            "fused_wave_sharded"
        ref = place_taskgroups_joint_jit(
            stacked, jnp.asarray(sm), jnp.asarray(sl), t_pad, feats)
        fn, kin_sh, repl = fused_sharded_entry(mesh)
        kin_dev = KernelIn(*[jax.device_put(x, s)
                             for x, s in zip(stacked, kin_sh)])
        fo = fn(kin_dev, jax.device_put(sm, repl),
                jax.device_put(sl, repl), t_pad, feats)
        _assert_bitwise(fo, ref, t_pad, B,
                        ctx=f"sharded variant={variant}")

    def test_launch_wave_sharded_zero_fallbacks(self, mesh):
        """launch_wave over the mesh must take the fused sharded path
        (fused launches counted, zero fused fallbacks, zero unsharded
        fallbacks) and match the one-device ``joint`` exactly."""
        _, kins, feats, _, _ = _wave_members(5, "shuffle", 200)
        steps = [K] * len(kins)

        telemetry.enable()
        telemetry.reset()
        try:
            single = coalesce.launch_wave(kins, steps, feats, mesh=None)
            coalesce.fused_wave_stats.reset()
            coalesce.sharded_wave_stats.reset()
            sharded = coalesce.launch_wave(kins, steps, feats,
                                           mesh=mesh)
            fused = coalesce.fused_wave_stats.snapshot()
            sw = coalesce.sharded_wave_stats.snapshot()
            programs = [s.attrs["program"]
                        for s in tracer.spans(name="wave.launch")]
        finally:
            telemetry.disable()
            telemetry.reset()
        assert programs == ["joint", "fused_wave_sharded"]
        assert fused["launches"] == 1 and fused["fallbacks"] == 0
        assert sw["fallbacks"] == 0
        for s, m in zip(single, sharded):
            np.testing.assert_array_equal(np.asarray(s.chosen),
                                          np.asarray(m.chosen))
            np.testing.assert_array_equal(np.asarray(s.found),
                                          np.asarray(m.found))
            np.testing.assert_array_equal(np.asarray(s.scores),
                                          np.asarray(m.scores))
            np.testing.assert_array_equal(np.asarray(s.topk_idx),
                                          np.asarray(m.topk_idx))
        assert any(np.asarray(s.found).any() for s in single)
