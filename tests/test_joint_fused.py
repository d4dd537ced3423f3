"""``joint_fused``, the Pallas form of a one-device wave, against ``joint``.

- Parity, in interpret mode on the CPU: ``place_taskgroups_joint_fused``
  and ``place_taskgroups_joint`` on the same stacked wave give the same
  ``JointOut``, field by field, bit for bit: one to four members with
  uneven steps, spread off, one active stanza of four and four active,
  top-k on and off, one shared rank and per-member ranks, step
  penalties and pins (one on an infeasible node), cores, ports,
  network, devices and distinct hosts, a node axis with every node
  infeasible, and planes the wave ships once.
- The router (``coalesce.wave_program``): the kernel on a TPU backend
  inside its envelope; ``joint`` on the CPU, for an axis the kernel
  does not tile and past its VMEM; the mesh programs as they were.
- The launcher with the kernel routed in: the launch record, the
  counter, the answers of a ``joint`` launch.
- The kernel compiled for a described v5e at the benchmark cell's
  widths (no chip: the TPU compiler refuses what Mosaic cannot lower).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_kernel import spread_tensor
from test_wave_programs import _stack_wave

from nomad_tpu import telemetry
from nomad_tpu.ops.joint_fused import (
    NODE_TILE,
    fused_joint_supported,
    place_taskgroups_joint_fused,
    place_taskgroups_joint_fused_jit,
)
from nomad_tpu.ops.kernel import (
    FULL_FEATURES,
    LEAN_FEATURES,
    MAX_PENALTY_NODES,
    KernelFeatures,
    KernelIn,
    build_kernel_in,
    default_kernel_launch,
    pad_steps,
    place_taskgroups_joint_jit,
)
from nomad_tpu.parallel import coalesce
from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval
from nomad_tpu.tensors.schema import MAX_DEV_REQS
from nomad_tpu.telemetry.exporter import prometheus_text
from nomad_tpu.telemetry.trace import tracer

_EXTRAS = ("cores", "ports", "network", "distinct", "devices")

#: name -> (node axis, each member's steps, spreads, top-k, shuffle,
#: penalties and pins, extras, every node infeasible, groups shipped
#: once). ``spreads``: "off", "one" (one active stanza of the four the
#: canonical set compiles) or "four"; ``shuffle``: "off", "shared" (one
#: permutation for the wave) or "member".
CASES = {
    "one_member": (1024, [13], "off", True, "off", False, (), False, ()),
    "two_members_topk_off": (1024, [7, 20], "off", False, "shared", False,
                             (), False, ()),
    "three_members_one_stanza": (2048, [9, 3, 16], "one", True, "member",
                                 False, (), False, ()),
    "four_members_four_stanzas": (1024, [11, 5, 16, 2], "four", True,
                                  "member", False, (), False, ()),
    "the_cell_in_small": (2048, [24, 30, 17, 28], "one", True, "member",
                          False, (), False, ()),
    "a_member_with_no_steps": (1024, [6, 0, 9], "one", True, "member",
                               False, (), False, ()),
    "penalties_and_pins": (1024, [10, 14, 6], "off", True, "shared", True,
                           (), False, ()),
    "pins_without_topk": (2048, [12, 9], "four", False, "member", True,
                          (), False, ()),
    "cores_ports_network_distinct": (1024, [14, 11, 8, 16], "off", True,
                                     "member", False,
                                     ("cores", "ports", "network",
                                      "distinct"), False, ()),
    "devices": (1024, [15, 6], "off", True, "off", False, ("devices",),
                False, ()),
    "everything": (2048, [20, 12, 31, 7], "four", True, "member", True,
                   _EXTRAS, False, ()),
    "every_node_infeasible": (1024, [9, 12], "one", True, "member", True,
                              ("ports",), True, ()),
    "planes_shipped_once": (1024, [16, 9, 12, 5], "one", True, "shared",
                            False, ("ports", "distinct"), False,
                            ("shared", "neutral", "job")),
    "job_planes_shipped_once": (2048, [8, 19, 10], "four", True, "member",
                                True, ("cores", "devices"), False,
                                ("job",)),
}


def _member(cluster, rng, m, case):
    """One member's KernelIn with the case's planes drawn at random."""
    n_pad, steps, spreads, _topk, _shuffle, aux, extras, blocked, _ = case
    n = cluster.n_real
    ev = synthetic_eval(
        cluster, ask_cpu=float(rng.choice([250, 500, 900])),
        ask_mem=float(rng.choice([128, 256, 700])),
        ask_disk=float(rng.choice([0, 150])),
        desired_count=int(rng.integers(1, 40)), used_frac=0.6,
        seed=int(rng.integers(0, 1000)))
    racks = np.array([int(c.split("-")[1])
                      for c in cluster.computed_classes])
    if spreads == "one":
        ev.spreads = [spread_tensor(n_pad, racks,
                                    counts=rng.integers(0, 4, 50),
                                    even=True)]
    elif spreads == "four":
        lacking = racks.copy()
        lacking[rng.choice(n, 40, replace=False)] = -1
        ev.spreads = [
            spread_tensor(n_pad, racks, counts=rng.integers(0, 4, 50),
                          even=True),
            spread_tensor(n_pad, racks % 3, counts=rng.integers(0, 3, 3),
                          desired=[2.0 + m, 1.0, 4.0], weight=0.5),
            spread_tensor(n_pad, lacking, counts=rng.integers(0, 2, 50),
                          even=True),
            spread_tensor(n_pad, racks % 7, desired=[3.0] * 7,
                          weight=0.25),
        ]
    k = pad_steps(max(steps[m], 1))
    pen = np.full((k, MAX_PENALTY_NODES), -1, np.int32)
    pref = np.full(k, -1, np.int32)
    if aux:
        for j in range(0, steps[m], 3):
            pen[j, : 1 + j % MAX_PENALTY_NODES] = rng.integers(
                0, n, 1 + j % MAX_PENALTY_NODES)
        pref[rng.integers(0, max(steps[m], 1))] = rng.integers(0, n)
        # a pin on a padding node, which no member may take
        pref[min(1, k - 1)] = n + 3
    kin = build_kernel_in(
        cluster, ev, steps[m], pen, pref,
        node_perm=rng.permutation(n_pad).astype(np.int32))
    kin = kin._replace(
        penalty=rng.random(n_pad) < 0.05,
        aff_score=np.where(rng.random(n_pad) < 0.1,
                           rng.uniform(-1, 1, n_pad), 0.0
                           ).astype(np.float32),
        algorithm_spread=np.asarray(m % 3 == 2))
    if "cores" in extras:
        kin = kin._replace(
            free_cores=rng.integers(0, 5, n_pad).astype(np.int32),
            used_cores=rng.integers(0, 3, n_pad).astype(np.int32),
            ask_cores=np.asarray(int(m % 2), np.int32))
    if "ports" in extras:
        kin = kin._replace(
            free_dyn=rng.integers(0, 6, n_pad).astype(np.int32),
            port_conflict=rng.random(n_pad) < 0.3,
            ask_has_reserved_ports=np.asarray(m % 2 == 0),
            ask_dyn_ports=np.asarray(2, np.int32))
    if "network" in extras:
        kin = kin._replace(
            avail_mbits=rng.integers(100, 1000, n_pad).astype(np.int32),
            used_mbits=rng.integers(0, 900, n_pad).astype(np.int32),
            ask_mbits=np.asarray(100, np.int32))
    if "distinct" in extras:
        held = (rng.random(n_pad) < 0.2).astype(np.int32)
        kin = kin._replace(
            job_tg_count=held,
            job_any_count=held + (rng.random(n_pad) < 0.3),
            distinct_hosts_job=np.asarray(m % 2 == 1),
            distinct_hosts_tg=np.asarray(True))
    if "devices" in extras:
        kin = kin._replace(
            dev_free=rng.integers(0, 4, (n_pad, MAX_DEV_REQS)
                                  ).astype(np.float32),
            ask_dev=np.array([1, 0, 2, 0], np.float32),
            has_dev_affinity=np.asarray(m % 2 == 0),
            dev_aff_score=rng.uniform(0, 1, n_pad).astype(np.float32))
    if blocked:
        kin = kin._replace(base_mask=np.zeros(n_pad, bool))
    return kin


def _features(case) -> KernelFeatures:
    _n, _steps, spreads, topk, shuffle, aux, extras, _b, _g = case
    return LEAN_FEATURES._replace(
        n_spreads=0 if spreads == "off" else FULL_FEATURES.n_spreads,
        with_topk=topk, with_shuffle=shuffle != "off",
        with_step_penalties=aux, with_preferred=aux,
        with_cores="cores" in extras, with_ports="ports" in extras,
        with_network="network" in extras,
        with_distinct="distinct" in extras,
        with_devices="devices" in extras)


def _members(name):
    """The case's members as the scheduler hands them to the launcher:
    each member's KernelIn and step bucket."""
    case = CASES[name]
    n_pad, steps = case[0], case[1]
    rng = np.random.default_rng(sorted(CASES).index(name))
    cluster = synthetic_cluster(
        n_pad - 37, cpu=3900.0, mem=7936.0, disk=98304.0, n_pad=n_pad,
        seed=int(rng.integers(0, 99)))
    kins = [_member(cluster, rng, m, case) for m in range(len(steps))]
    return kins, [pad_steps(max(s, 1)) for s in steps]


def _wave(name):
    """The case's wave as the launcher stacks it: the stacked KernelIn
    (the groups the case names shipped once, as member 0's planes),
    ``step_member``, ``step_local``, the padded step count."""
    kins, k_steps = _members(name)
    shuffle, groups = CASES[name][4], CASES[name][8]
    stacked, member, local, t_pad, _ = _stack_wave(
        kins, k_steps, coalesce.pad_wave(len(kins)))
    once = [f for f in KernelIn._fields if coalesce.wave_field_is_shared(
        f, "shared" in groups, "neutral" in groups, "job" in groups)]
    if shuffle == "shared":
        once.append("node_perm")
    stacked = stacked._replace(**{f: getattr(stacked, f)[0] for f in once})
    return stacked, member, local, t_pad


@pytest.mark.parametrize("name", sorted(CASES))
def test_fused_kernel_matches_joint(name):
    """Every ``JointOut`` field of the kernel, interpreted, is the XLA
    program's: ``chosen``, ``found`` and ``topk_idx`` bit for bit, and
    ``scores``, ``topk_scores``, the metrics and the final capacity
    carry equal too, inert rows included; the case places and refuses
    what it is named for."""
    stacked, member, local, t_pad = _wave(name)
    feats = _features(CASES[name])
    args = (stacked, jnp.asarray(member), jnp.asarray(local), t_pad, feats)
    want = place_taskgroups_joint_jit(*args)
    got = place_taskgroups_joint_fused_jit(*args, True)
    for field in want._fields:
        w, g = np.asarray(getattr(want, field)), np.asarray(getattr(got, field))
        assert g.dtype == w.dtype and g.shape == w.shape, field
        np.testing.assert_array_equal(g, w, err_msg=f"{name}: {field}")
    found = np.asarray(want.found)
    if CASES[name][7]:
        assert not found.any()
    else:
        assert found.sum() > len(CASES[name][1])


_CELL = KernelFeatures(n_spreads=4, with_topk=True, with_devices=False,
                       with_ports=False, with_cores=False,
                       with_network=False, with_distinct=False,
                       with_step_penalties=False, with_preferred=False,
                       with_shuffle=True)


class TestRouter:
    @pytest.mark.parametrize("mesh_size, n_nodes, feats, backend, program", [
        (0, 16384, _CELL, "tpu", "joint_fused"),     # the benchmark's cell
        (0, 1024, FULL_FEATURES, "tpu", "joint_fused"),
        (1, 16384, LEAN_FEATURES, "tpu", "joint_fused"),  # a mesh of one
        (6, 16384, _CELL, "tpu", "joint_fused"),     # 6 does not divide it
        (0, 16384, _CELL, "cpu", "joint"),           # the tier-1 tests' CPU
        (0, 512, _CELL, "tpu", "joint"),             # under one node tile
        (0, 1536, _CELL, "tpu", "joint"),            # not a whole tile count
        (0, 1 << 20, FULL_FEATURES, "tpu", "joint"),  # past the VMEM budget
        (4, 16384, LEAN_FEATURES._replace(with_topk=True), "tpu",
         "fused_wave_sharded"),                      # the mesh's programs
        (4, 16384, _CELL, "tpu", "joint_sharded"),
        (8, 32, LEAN_FEATURES, "tpu", "joint_sharded"),
    ])
    def test_rule(self, mesh_size, n_nodes, feats, backend, program):
        assert coalesce.wave_program(
            mesh_size, n_nodes, feats, backend) == program

    def test_every_feature_is_inside_the_envelope(self):
        """No ``KernelFeatures`` flag sends a tiled node axis back to
        ``joint``: the full set fits at the cell's width."""
        assert NODE_TILE == 1024
        assert fused_joint_supported(16384, FULL_FEATURES._replace(
            with_shuffle=True))

    def test_the_cpu_keeps_joint(self):
        assert jax.default_backend() == "cpu"
        assert coalesce.wave_program(0, 16384, _CELL) == "joint"


def test_launcher_records_and_counts_the_kernel(monkeypatch):
    """A coalescer's wave routed to the kernel (as on a TPU; the kernel
    interpreted) runs it: the launch record says ``joint_fused``,
    ``wave_stats.fused_launches`` counts it beside ``launches`` and so
    does its Prometheus series, and every member's answers are those of
    the same wave launched through ``joint``."""
    kins, k_steps = _members("the_cell_in_small")
    feats = [_features(CASES["the_cell_in_small"])] * len(kins)
    want = coalesce.launch_wave(kins, k_steps, feats, mesh=None)
    route = coalesce.wave_program
    monkeypatch.setattr(
        coalesce, "wave_program",
        lambda mesh_size, n, f, backend=None: route(mesh_size, n, f, "tpu"))
    monkeypatch.setattr(
        coalesce, "place_taskgroups_joint_fused_jit",
        jax.jit(functools.partial(place_taskgroups_joint_fused,
                                  interpret=True), static_argnums=(3, 4)))
    telemetry.enable()
    telemetry.reset()
    try:
        reqs = [coalesce._Request(kin, k, f)
                for kin, k, f in zip(kins, k_steps, feats)]
        coalesce.LaunchCoalescer(participants=len(reqs))._fire(reqs)
        records = [s.attrs for s in tracer.spans(name="wave.launch")]
        stats = coalesce.wave_stats.snapshot()
        text = prometheus_text()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert [r["program"] for r in records] == ["joint_fused"]
    assert stats["launches"] == 1 and stats["fused_launches"] == 1
    assert "nomad_tpu_wave_joint_fused_launches_total 1" in text
    for r, w in zip(reqs, want):
        assert r.error is None
        for field in w._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(r.out, field)),
                np.asarray(getattr(w, field)), err_msg=field)


def test_lone_launches_keep_their_programs(monkeypatch):
    """A lone evaluation runs ``single_full`` (or ``single_topk``)
    whatever the wave router names: the Pallas program is a wave's."""
    route = coalesce.wave_program
    monkeypatch.setattr(
        coalesce, "wave_program",
        lambda mesh_size, n, f, backend=None: route(mesh_size, n, f, "tpu"))
    kins, k_steps = _members("three_members_one_stanza")
    telemetry.enable()
    telemetry.reset()
    try:
        default_kernel_launch(kins[0], k_steps[0],
                              _features(CASES["three_members_one_stanza"]))
        records = [s.attrs for s in tracer.spans(name="wave.launch")]
        stats = coalesce.wave_stats.snapshot()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert [r["program"] for r in records] == ["single_full"]
    assert stats["fused_launches"] == 0


def test_reset_clears_the_counter():
    coalesce.wave_stats.note_fused_launch()
    assert coalesce.wave_stats.snapshot()["fused_launches"] >= 1
    coalesce.wave_stats.reset()
    assert coalesce.wave_stats.snapshot()["fused_launches"] == 0


def test_trace_report_counts_the_kernel_on_the_wave_path():
    """``bench/trace_report.py`` counts a ``joint_fused`` dispatch as
    wave-critical, as it counts ``joint``: a wave is the program and
    its eager fetch, two dispatches on either backend."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "bench"))
    import trace_report

    for program in ("joint", "joint_fused"):
        dispatches = {program: 3, "wave_fetch": 3, "topk_drain": 2}
        assert trace_report._wave_dispatch_quotient(dispatches, 3) == 2.0


# ---------------------------------------------------------------------------
# Compiled for the chip, without it
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _shapes(one, n, k, b, groups):
    """The wave's KernelIn as shapes on the described chip: leaves of the
    named sharing groups unbatched, ``node_perm`` and the scalars per
    member, as the launcher ships them."""
    from nomad_tpu.ops.kernel import KIN_UNBATCHED_RANKS

    stacked, *_ = _wave("one_member")
    out = []
    for field, x, r in zip(KernelIn._fields, stacked, KIN_UNBATCHED_RANKS):
        x = np.asarray(x)
        shape = list(x.shape[1:] if x.ndim > r else x.shape)
        shape = [n if d == 1024 else k if d == 16 else d for d in shape]
        if not coalesce.wave_field_is_shared(field, *groups) or r == 0 \
                or field == "node_perm":
            shape = [b] + shape
        out.append(jax.ShapeDtypeStruct(tuple(shape), x.dtype,
                                        sharding=one))
    return KernelIn(*out)


@pytest.mark.parametrize("feats, groups", [
    (_CELL, (True, True, True)),                 # the cell's waves
    (FULL_FEATURES._replace(with_shuffle=True), (False, False, False)),
], ids=["cell", "every_feature_stacked"])
def test_compiles_for_a_v5e_at_the_cell_widths(one_chip, feats, groups):
    """16,384 nodes, four members of 512 steps: Mosaic lowers the kernel
    and the compiled program holds it as one custom call."""
    kin = _shapes(one_chip, 16384, 512, 4, groups)
    steps = jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip)
    compiled = place_taskgroups_joint_fused_jit.lower(
        kin, steps, steps, 2048, feats).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
