"""A one-device wave's placement steps as one Pallas kernel.

``place_taskgroups_joint`` (ops/kernel.py) runs a wave's real steps as
a ``fori_loop`` of some thirty XLA operations each, over a node axis
that is 16 vregs a plane at 16,384 nodes: the step is bound by the
latency of launching operations, not by bandwidth or compute
(``PERF.md``, section 5). ``joint_fused`` runs the same steps inside one
``pallas_call``: a sequential grid over the wave's members, the shared
capacity carry and the member's job-local planes held in VMEM scratch
for the whole wave, each step's feasibility, score, pick, top-k and
carry update done on the planes where they lie. Pallas fetches member
m+1's stacked planes while member m runs; planes the wave ships once
(unbatched) have a constant block index and are fetched once.

Same arguments, same ``JointOut``, same answers as
``place_taskgroups_joint``, which stays the CPU path and the reference
(tests/test_joint_fused.py holds the two together in interpret mode).
The step layout is the launcher's (``coalesce.launch_wave``): member 0's
steps, then member 1's, each member's own from local index 0 on. The
kernel walks the members in that order and runs each member's real
steps, those of its block inside its ``n_steps``; the wrapper then lays
the rows out at each step's ``t`` and gives every other row what an
inert step of ``joint`` writes. The per-member metrics run once a
launch, in XLA, as in ``joint``.

Node planes are laid out as (rows, 128) tiles; the node axis must be a
multiple of ``NODE_TILE``. ``coalesce.wave_program`` routes a wave here
on a TPU backend when ``fused_joint_supported`` holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nomad_tpu.ops.kernel import (
    _STEP_BUCKETS,
    FULL_FEATURES,
    KIN_UNBATCHED_RANKS,
    MAX_PENALTY_NODES,
    NEG_INF,
    TOPK,
    JointOut,
    KernelFeatures,
    KernelIn,
    _inv,
    _spread_node_planes,
    wave_in_axes,
    wave_member_metrics,
)
from nomad_tpu.tensors.schema import MAX_DEV_REQS, SPREAD_BUCKETS

LANES = 128
#: nodes of one (8, 128) f32 tile: the node axis is a whole number of
#: them, so a plane is a whole number of vregs
NODE_TILE = 8 * LANES
#: the most VMEM a wave's planes may take (a v5e core has 128 MiB), and
#: the most a launch asks the compiler for
VMEM_BUDGET = 96 << 20
VMEM_LIMIT = 112 << 20

# lanes of a step's output row
_CHOSEN, _FOUND = TOPK, TOPK + 1          # i32 row; _CHOSEN is the f32 score
_INT_MIN = -(2 ** 31)


def fused_joint_supported(n_nodes: int, f: KernelFeatures) -> bool:
    """Whether a one-device wave of this padded node axis and canonical
    feature union fits the kernel: a whole number of node tiles, and
    its VMEM at the largest step bucket inside ``VMEM_BUDGET``. Every
    ``KernelFeatures`` flag is inside the envelope."""
    return (n_nodes % NODE_TILE == 0
            and _vmem_bytes(n_nodes, _STEP_BUCKETS[-1], f) <= VMEM_BUDGET)


def _n_planes(f: KernelFeatures) -> tuple:
    """(input node planes, scratch node planes) a member holds."""
    s = f.n_spreads
    planes = 10 + int(f.with_shuffle)          # capacity, used, job, mask
    planes += 3 * int(f.with_cores) + 2 * int(f.with_ports)
    planes += (MAX_DEV_REQS + 1) * int(f.with_devices)
    planes += 2 * int(f.with_network) + int(f.with_distinct) + 3 * s
    scratch = 1 + int(f.with_cores) + int(f.with_network)
    scratch += 2 * int(f.with_ports) + MAX_DEV_REQS * int(f.with_devices)
    scratch += int(f.with_distinct) + s
    return planes, scratch


def _vmem_bytes(n: int, k: int, f: KernelFeatures) -> int:
    """VMEM of a launch at node axis ``n`` and step bucket ``k``, every
    input stacked and double-buffered (the most a wave can ask)."""
    planes, scratch = _n_planes(f)
    kp = -(-k // 8) * 8
    rows_out = 2 * kp * LANES * 4                  # the i32 and f32 rows
    step_aux = kp * LANES * 4                      # penalty ids and pins
    counts = 8 * LANES * 4
    return 2 * ((planes + 3) * n * 4 + rows_out + step_aux + counts) \
        + scratch * n * 4 + counts


def place_taskgroups_joint_fused(
    kin: KernelIn,
    step_member: jnp.ndarray,
    step_local: jnp.ndarray,
    t_steps: int,
    features: KernelFeatures = FULL_FEATURES,
    interpret: bool = False,
) -> JointOut:
    """``place_taskgroups_joint`` as one Pallas kernel (module doc).
    ``interpret`` runs the kernel in the Pallas interpreter, as on a
    backend with no Mosaic compiler."""
    f = features
    n = kin.cap_cpu.shape[-1]
    b = kin.n_steps.shape[0]
    rows = n // LANES
    s_n = f.n_spreads
    k = kin.step_penalty.shape[-2]
    kp = -(-k // 8) * 8
    f32, i32 = jnp.float32, jnp.int32

    def stacked(x, rank) -> bool:
        return jnp.ndim(x) == rank + 1

    ranks = dict(zip(KernelIn._fields, KIN_UNBATCHED_RANKS))

    def bcast(name):
        """A per-member scalar or short vector leaf, [B, ...]."""
        x = getattr(kin, name)
        if stacked(x, ranks[name]):
            return x
        return jnp.broadcast_to(x, (b,) + jnp.shape(x))

    # -- the step layout: each member's real steps, in turn ------------
    member = jnp.clip(step_member, 0, b - 1)
    real = (step_member >= 0) & (step_local < jnp.asarray(kin.n_steps)[member])
    n_real = jnp.zeros(b, i32).at[member].add(real.astype(i32))
    last = jnp.max(jnp.where(n_real > 0, jnp.arange(b, dtype=i32), 0))

    # -- per-member scalars, prefetched to SMEM: name -> column ---------
    def table(cols, dtype):
        parts, at = [], {}
        for name, x in cols:
            at[name] = sum(p.shape[1] for p in parts)
            parts.append(jnp.reshape(x, (b, -1)).astype(dtype))
        return jnp.concatenate(parts, axis=1), at

    iscal, iat = table([
        ("active", bcast("spread_active")[:, :s_n]),
        ("even", bcast("spread_even")[:, :s_n]),
        ("dev_on", bcast("has_dev_affinity")),
        ("dh_job", bcast("distinct_hosts_job")),
        ("dh_tg", bcast("distinct_hosts_tg")),
        ("cores", bcast("ask_cores")),
        ("dyn", bcast("ask_dyn_ports")),
        ("reserved", bcast("ask_has_reserved_ports")),
        ("mbits", bcast("ask_mbits")),
        ("spread", bcast("algorithm_spread")),
    ], i32)
    fscal, fat = table([
        ("cpu", bcast("ask_cpu")),
        ("mem", bcast("ask_mem")),
        ("disk", bcast("ask_disk")),
        # the anti-affinity denominator and the cores' cpu, as
        # ``_score`` and ``_feasible`` compute them
        ("denom", jnp.maximum(bcast("desired_count").astype(f32), 1.0)),
        ("cores", bcast("ask_cores").astype(f32)),
        ("weight", bcast("spread_weight")[:, :s_n]),
        ("dev", bcast("ask_dev")),
    ], f32)

    # -- the node planes, as (rows, 128) tiles --------------------------
    inputs, specs, names = [], [], []

    def member_block(m, n_real_ref, last_ref, *_):
        return jnp.minimum(m, last_ref[0])

    def add(name, x, per_member: bool):
        """An input whose block is one member's (``per_member``: the
        leading axis is the member's) or the whole array."""
        shape = tuple(x.shape[1:] if per_member else x.shape)
        zeros = (0,) * len(shape)
        if per_member:
            spec = pl.BlockSpec(
                (1,) + shape,
                lambda m, *sp: (member_block(m, *sp),) + zeros)
        else:
            spec = pl.BlockSpec(shape, lambda m, *sp: zeros)
        inputs.append(x)
        specs.append(spec)
        names.append((name, per_member))

    def plane(name, x=None, dtype=None):
        x = getattr(kin, name) if x is None else x
        per = stacked(x, 1)
        if dtype is not None:
            x = x.astype(dtype)
        add(name, jnp.reshape(x, x.shape[:-1] + (rows, LANES)), per)

    plane("cap_cpu")
    plane("cap_mem")
    plane("cap_disk")
    plane("base_mask", dtype=i32)
    plane("used_cpu")
    plane("used_mem")
    plane("used_disk")
    plane("job_tg_count")
    plane("penalty", dtype=i32)
    plane("aff_score")
    if f.with_shuffle:
        plane("rank", _inv(kin.node_perm))
    if f.with_cores:
        plane("free_cores")
        plane("shares_per_core")
        plane("used_cores")
    if f.with_ports:
        plane("free_dyn")
        plane("port_conflict", dtype=i32)
    if f.with_devices:
        plane("dev_aff_score")
        dev = jnp.swapaxes(kin.dev_free, -1, -2)       # [.., D, N]
        add("dev_free", jnp.reshape(dev, dev.shape[:-1] + (rows, LANES)),
            stacked(kin.dev_free, 2))
    if f.with_network:
        plane("used_mbits")
        plane("avail_mbits")
    if f.with_distinct:
        plane("job_any_count")
    if s_n > 0:
        sb = kin.spread_bucket[..., :s_n, :]
        spread_stacked = any(stacked(getattr(kin, x), 2) for x in (
            "spread_bucket", "spread_counts", "spread_desired"))
        if spread_stacked:
            cnt_n, des_n = jax.vmap(
                lambda kin_m: _spread_node_planes(kin_m, s_n),
                in_axes=(wave_in_axes(kin),))(kin)
            sb = jnp.broadcast_to(sb, (b,) + sb.shape[-2:])
        else:
            cnt_n, des_n = _spread_node_planes(kin, s_n)
        for name, x in (("spread_bucket", sb), ("cnt_n", cnt_n),
                        ("des_n", des_n)):
            add(name, jnp.reshape(x, x.shape[:-1] + (rows, LANES)),
                spread_stacked)
        counts = kin.spread_counts[..., :s_n, :]
        counts = jnp.concatenate([counts, jnp.zeros(
            counts.shape[:-2] + (8 - s_n, SPREAD_BUCKETS), f32)], axis=-2)
        add("spread_counts", counts, spread_stacked)
    step_aux = f.with_step_penalties or f.with_preferred
    if step_aux:
        pen = kin.step_penalty
        aux = jnp.concatenate([pen, kin.step_preferred[..., None]], axis=-1)
        aux = jnp.pad(aux, [(0, 0)] * (aux.ndim - 2)
                      + [(0, kp - k), (0, LANES - aux.shape[-1])],
                      constant_values=-1)
        add("step_aux", jnp.reshape(aux, aux.shape[:-2] + (kp // 8, 8, LANES)),
            stacked(kin.step_penalty, 2))

    # -- outputs and scratch --------------------------------------------
    node_shape = (rows, LANES)
    out_shape = [
        jax.ShapeDtypeStruct((b, kp // 8, 8, LANES), i32),
        jax.ShapeDtypeStruct((b, kp // 8, 8, LANES), f32),
        jax.ShapeDtypeStruct(node_shape, f32),
        jax.ShapeDtypeStruct(node_shape, f32),
        jax.ShapeDtypeStruct(node_shape, f32),
    ]
    row_spec = pl.BlockSpec(
        (1, kp // 8, 8, LANES),
        lambda m, *sp: (member_block(m, *sp), 0, 0, 0))
    carry_spec = pl.BlockSpec(node_shape, lambda m, *sp: (0, 0))
    out_specs = [row_spec, row_spec, carry_spec, carry_spec, carry_spec]
    scratch_names = ["job_tg_count"]
    if f.with_cores:
        scratch_names.append("a_cores")
    if f.with_network:
        scratch_names.append("a_mbits")
    if f.with_ports:
        scratch_names += ["a_dyn", "port_conflict"]
    if f.with_distinct:
        scratch_names.append("job_any_count")
    scratch = [pltpu.VMEM(node_shape, i32) for _ in scratch_names]
    if f.with_devices:
        scratch_names.append("a_dev")
        scratch.append(pltpu.VMEM((MAX_DEV_REQS,) + node_shape, f32))
    if s_n > 0:
        scratch_names += ["cnt_n", "spread_counts"]
        scratch += [pltpu.VMEM((s_n,) + node_shape, f32),
                    pltpu.VMEM((8, SPREAD_BUCKETS), f32)]

    kernel = _make_kernel(f, n, rows, iscal.shape[1], fscal.shape[1], iat,
                          fat, tuple(names), tuple(scratch_names), step_aux)
    vmem_limit = min(_vmem_bytes(n, kp, f) + (16 << 20), VMEM_LIMIT)
    out_i, out_f, a_cpu, a_mem, a_disk = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="place_taskgroups_joint_fused",
    )(n_real, last[None], iscal.reshape(-1), fscal.reshape(-1), *inputs)

    # -- each step's row at its own t; the inert rows as joint's --------
    at = (member, jnp.clip(step_local, 0, kp - 1))
    gi = out_i.reshape(b, kp, LANES)[at]
    gf = out_f.reshape(b, kp, LANES)[at]
    found = real & (gi[:, _FOUND] != 0)
    if f.with_topk:
        topk_idx = jnp.where(real[:, None], gi[:, :TOPK],
                             jnp.arange(TOPK, dtype=i32))
        topk_scores = jnp.where(real[:, None], gf[:, :TOPK], NEG_INF)
    else:
        topk_idx = jnp.zeros((t_steps, TOPK), i32)
        topk_scores = jnp.full((t_steps, TOPK), NEG_INF, f32)
    metrics = wave_member_metrics(kin, f)
    return JointOut(
        chosen=jnp.where(real, gi[:, _CHOSEN], -1),
        scores=jnp.where(real, gf[:, _CHOSEN], 0.0),
        found=found, topk_idx=topk_idx, topk_scores=topk_scores,
        **metrics, a_cpu=a_cpu.reshape(n), a_mem=a_mem.reshape(n),
        a_disk=a_disk.reshape(n))


def _make_kernel(f: KernelFeatures, n: int, rows: int, ni: int, nf: int,
                 iat: dict, fat: dict, layout: tuple, scratch_names: tuple,
                 step_aux: bool):
    """The kernel body for one feature set and layout (all static):
    ``iat``/``fat`` name the columns of a member's row of prefetched
    scalars, ``layout`` the inputs (name, stacked per member)."""
    n_in = len(layout)
    s_n = f.n_spreads

    def kernel(n_real_ref, _last_ref, isc_ref, fsc_ref, *refs):
        ins = dict(zip((nm for nm, _ in layout), refs[:n_in]))
        per = dict(layout)
        out_i, out_f, a_cpu, a_mem, a_disk = refs[n_in:n_in + 5]
        scr = dict(zip(scratch_names, refs[n_in + 5:]))
        m = pl.program_id(0)

        def get(name, *idx):
            """The member's plane (or a sub-plane of a stacked one)."""
            ref = ins[name]
            return ref[(0,) + idx] if per[name] else ref[idx or ...]

        def iv(name, i=0):
            return isc_ref[m * ni + iat[name] + i]

        def fv(name, i=0):
            return fsc_ref[m * nf + fat[name] + i]

        shape = (rows, LANES)
        node = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)

        def step(j, carry):
            ask_cpu, ask_mem, ask_disk = fv("cpu"), fv("mem"), fv("disk")
            used_cpu = get("used_cpu") + a_cpu[...]
            used_mem = get("used_mem") + a_mem[...]
            used_disk = get("used_disk") + a_disk[...]
            cap_cpu, cap_mem = get("cap_cpu"), get("cap_mem")
            jtc = scr["job_tg_count"][...]

            # -- feasibility (_feasible) --
            ask_cpu_total = ask_cpu
            if f.with_cores:
                ask_cores = iv("cores")
                ask_cpu_total = ask_cpu + fv("cores") * get(
                    "shares_per_core")
            feasible = (get("base_mask") != 0) \
                & ((cap_cpu - used_cpu) >= ask_cpu_total) \
                & ((cap_mem - used_mem) >= ask_mem) \
                & ((get("cap_disk") - used_disk) >= ask_disk)
            if f.with_cores:
                used_cores = get("used_cores") + scr["a_cores"][...]
                feasible = feasible & ((ask_cores <= 0) | (
                    (get("free_cores") - used_cores) >= ask_cores))
            if f.with_ports:
                ask_dyn = iv("dyn")
                free_dyn = get("free_dyn") - scr["a_dyn"][...]
                fit_dyn = (ask_dyn <= 0) | (free_dyn >= ask_dyn)
                conflict = (scr["port_conflict"][...] != 0) & (
                    iv("reserved") != 0)
                feasible = feasible & ~conflict & fit_dyn
            if f.with_devices:
                for d in range(MAX_DEV_REQS):
                    want = fv("dev", d)
                    free = get("dev_free", d) - scr["a_dev"][d]
                    feasible = feasible & ((want <= 0) | (free >= want))
            if f.with_network:
                ask_mbits = iv("mbits")
                used_mbits = get("used_mbits") + scr["a_mbits"][...]
                feasible = feasible & ((ask_mbits <= 0) | (
                    (used_mbits + ask_mbits) <= get("avail_mbits")))
            if f.with_distinct:
                jac = scr["job_any_count"][...]
                feasible = feasible & ~(
                    ((iv("dh_job") != 0) & (jac > 0))
                    | ((iv("dh_tg") != 0) & (jtc > 0)))

            # -- the step's own planes: penalty ids and a pinned node --
            pen = get("penalty") != 0
            if step_aux:
                tile = get("step_aux", j // 8)
                this_row = sub == j % 8

                def aux(p):
                    return jnp.max(jnp.where(this_row & (lane == p), tile,
                                             _INT_MIN))
                if f.with_step_penalties:
                    for p in range(MAX_PENALTY_NODES):
                        pen = pen | (node == aux(p))

            # -- score (_score) --
            util_cpu = used_cpu + ask_cpu_total
            util_mem = used_mem + ask_mem
            fc = jnp.where(cap_cpu > 0, 1.0 - util_cpu / cap_cpu, 0.0)
            fm = jnp.where(cap_mem > 0, 1.0 - util_mem / cap_mem, 0.0)
            total = jnp.power(10.0, fc) + jnp.power(10.0, fm)
            binpack = jnp.clip(20.0 - total, 0.0, 18.0)
            spreadfit = jnp.clip(total - 2.0, 0.0, 18.0)
            fit = jnp.where(iv("spread") != 0, spreadfit,
                            binpack) / 18.0
            score_sum = fit
            nplanes = jnp.ones_like(fit)
            if f.with_devices:
                dev_on = iv("dev_on") != 0
                score_sum = score_sum + jnp.where(
                    dev_on, get("dev_aff_score"), 0.0)
                nplanes = nplanes + jnp.where(dev_on, 1.0, 0.0)
            collisions = jtc.astype(jnp.float32)
            anti = -(collisions + 1.0) / fv("denom")
            anti_on = collisions > 0
            score_sum = score_sum + jnp.where(anti_on, anti, 0.0)
            nplanes = nplanes + anti_on.astype(jnp.float32)
            score_sum = score_sum + jnp.where(pen, -1.0, 0.0)
            nplanes = nplanes + pen.astype(jnp.float32)
            aff = get("aff_score")
            aff_on = aff != 0.0
            score_sum = score_sum + jnp.where(aff_on, aff, 0.0)
            nplanes = nplanes + aff_on.astype(jnp.float32)
            if s_n > 0:
                spread_total = jnp.zeros(shape, jnp.float32)
                for s in range(s_n):
                    spread_total = jax.lax.cond(
                        iv("active", s) != 0,
                        lambda tot, s=s: tot + _stanza(s), lambda tot: tot,
                        spread_total)
                spread_on = spread_total != 0.0
                score_sum = score_sum + jnp.where(spread_on, spread_total,
                                                  0.0)
                nplanes = nplanes + spread_on.astype(jnp.float32)
            final = score_sum / nplanes

            # -- the pick (_pick), the pin, the top-k --
            masked = jnp.where(feasible, final, NEG_INF)
            best_score = jnp.max(masked)
            best = masked == best_score
            # the first best node: the pick without a shuffle, and the
            # top-k's first entry (ties to the lower index) either way
            first = jnp.min(jnp.where(best, node, n))
            if f.with_shuffle:
                rank = get("rank")
                least = jnp.min(jnp.where(best, rank, n))
                idx = jnp.min(jnp.where(rank == least, node, n))
            else:
                idx = first
            picked = best_score
            if f.with_preferred:
                pref = aux(MAX_PENALTY_NODES)
                pref_c = jnp.clip(pref, 0, n - 1)
                pref_ok = (pref >= 0) & (jnp.max(jnp.where(
                    (node == pref_c) & feasible, 1, 0)) > 0)
                idx = jnp.where(pref_ok, pref_c, idx)
                picked = jnp.max(jnp.where(node == idx, masked, -jnp.inf))
            found = picked > NEG_INF / 2

            row_i = jnp.where(lane == _CHOSEN, jnp.where(found, idx, -1),
                              jnp.where(lane == _FOUND,
                                        found.astype(jnp.int32), 0))
            row_f = jnp.where(lane == _CHOSEN, jnp.where(found, picked, 0.0),
                              0.0)
            if f.with_topk:
                rest = masked
                for kk in range(TOPK):
                    if kk == 0:
                        v, i = best_score, first
                    else:
                        v = jnp.max(rest)
                        i = jnp.min(jnp.where(rest == v, node, n))
                    rest = jnp.where(node == i, -jnp.inf, rest)
                    row_i = jnp.where(lane == kk, i, row_i)
                    row_f = jnp.where(lane == kk, v, row_f)
            this_row = sub == j % 8
            out_i[0, j // 8] = jnp.where(this_row, row_i, out_i[0, j // 8])
            out_f[0, j // 8] = jnp.where(this_row, row_f, out_f[0, j // 8])

            # -- the carry (joint's one-hot updates, as selects) --
            one = (node == idx) & found
            a_cpu[...] = jnp.where(one, a_cpu[...] + ask_cpu_total,
                                   a_cpu[...])
            a_mem[...] = jnp.where(one, a_mem[...] + ask_mem, a_mem[...])
            a_disk[...] = jnp.where(one, a_disk[...] + ask_disk, a_disk[...])
            scr["job_tg_count"][...] = jnp.where(one, jtc + 1, jtc)
            if f.with_cores:
                scr["a_cores"][...] = jnp.where(
                    one, scr["a_cores"][...] + ask_cores, scr["a_cores"][...])
            if f.with_network:
                scr["a_mbits"][...] = jnp.where(
                    one, scr["a_mbits"][...] + ask_mbits, scr["a_mbits"][...])
            if f.with_ports:
                scr["a_dyn"][...] = jnp.where(
                    one, scr["a_dyn"][...] + ask_dyn, scr["a_dyn"][...])
                scr["port_conflict"][...] = jnp.where(
                    one & (iv("reserved") != 0), 1,
                    scr["port_conflict"][...])
            if f.with_devices:
                for d in range(MAX_DEV_REQS):
                    scr["a_dev"][d] = jnp.where(
                        one, scr["a_dev"][d] + fv("dev", d),
                        scr["a_dev"][d])
            if f.with_distinct:
                scr["job_any_count"][...] = jnp.where(one, jac + 1, jac)
            for s in range(s_n):
                @pl.when(iv("active", s) != 0)
                def _(s=s):
                    _bump(s, idx, found)
            return carry

        def _stanza(s):
            """One active stanza's boost for every node
            (``_spread_score``)."""
            counts_b = scr["spread_counts"][s:s + 1, :]      # (1, B)
            cnt = scr["cnt_n"][s]
            des = get("des_n", s)
            desired = jnp.where(
                des > 0.0, ((des - (cnt + 1.0)) / des) * fv("weight", s),
                -1.0)
            present = counts_b > 0.0
            any_alloc = jnp.max(jnp.where(present, 1, 0)) > 0
            minc = jnp.min(jnp.where(present, counts_b, jnp.inf))
            maxc = jnp.max(jnp.where(present, counts_b, -jnp.inf))
            delta = jnp.where(
                minc > 0, (minc - cnt) / jnp.maximum(minc, 1.0), -1.0)
            even = jnp.where(
                cnt != minc,
                delta,
                jnp.where(
                    minc == maxc,
                    -1.0,
                    jnp.where(minc == 0, 1.0,
                              (maxc - minc) / jnp.maximum(minc, 1.0)),
                ),
            )
            even = jnp.where(any_alloc, even, 0.0)
            stanza = jnp.where(iv("even", s) != 0, even, desired)
            return jnp.where(get("spread_bucket", s) < 0, -1.0, stanza)

        def _bump(s, idx, found):
            """A placement on node ``idx`` under active stanza ``s``
            (``_bump_spread``)."""
            sb = get("spread_bucket", s)
            b_star = jnp.max(jnp.where(node == idx, sb, -1))
            on = (b_star >= 0) & found
            lane_b = jax.lax.broadcasted_iota(jnp.int32, (1, SPREAD_BUCKETS),
                                              1)
            counts = scr["spread_counts"][s:s + 1, :]
            scr["spread_counts"][s:s + 1, :] = jnp.where(
                (lane_b == b_star) & on, counts + 1.0, counts)
            cnt = scr["cnt_n"][s]
            scr["cnt_n"][s] = jnp.where((sb == b_star) & on, cnt + 1.0, cnt)

        @pl.when(m == 0)
        def _():
            for ref in (a_cpu, a_mem, a_disk):
                ref[...] = jnp.zeros(ref.shape, jnp.float32)
            for name in ("a_cores", "a_mbits", "a_dyn"):
                if name in scr:
                    scr[name][...] = jnp.zeros(scr[name].shape, jnp.int32)
            if "a_dev" in scr:
                scr["a_dev"][...] = jnp.zeros(scr["a_dev"].shape,
                                              jnp.float32)

        n_steps = n_real_ref[m]

        @pl.when(n_steps > 0)
        def _():
            # the member's job-local carry, from its own planes
            for name in ("job_tg_count", "job_any_count", "port_conflict",
                         "spread_counts"):
                if name in scr:
                    scr[name][...] = get(name)
            if s_n > 0:
                for s in range(s_n):
                    scr["cnt_n"][s] = get("cnt_n", s)
            jax.lax.fori_loop(0, n_steps, step, 0)

    return kernel


place_taskgroups_joint_fused_jit = jax.jit(
    place_taskgroups_joint_fused, static_argnums=(3, 4, 5))
