"""Sharded, batched placement: the multi-chip scheduler hot path.

A batch of B independent (evaluation, task group) placement problems —
each a :class:`~nomad_tpu.ops.kernel.KernelIn` over the same padded
node axis — runs as ONE ``jit`` over a 2D device mesh:

- every array gains a leading batch dim, sharded over the ``evals``
  mesh axis (dp: the analog of reference worker parallelism,
  nomad/worker.go:386);
- node-axis planes shard over the ``nodes`` mesh axis (sp: the cluster
  table split across the slice over ICI).

Sharding is GSPMD-style: we annotate in/out shardings on the
*unmodified* single-problem kernel (vmapped), and XLA inserts the
collectives — the global ``argmax``/``top_k`` over the sharded node
axis compiles to an all-gather+reduce riding ICI, which is the tensor
formulation of the reference's MaxScore/Limit iterators
(scheduler/select.go) and of the leader's global plan ordering.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nomad_tpu.ops.kernel import (
    FULL_FEATURES,
    KernelFeatures,
    KernelIn,
    KernelOut,
    place_taskgroup,
)
from nomad_tpu.parallel.mesh import AXIS_EVALS, AXIS_NODES

_B = AXIS_EVALS
_N = AXIS_NODES

# PartitionSpec per KernelIn field for the BATCHED layout (leading B dim).
_IN_SPECS = dict(
    # [B, N] node planes
    cap_cpu=P(_B, _N), cap_mem=P(_B, _N), cap_disk=P(_B, _N),
    free_cores=P(_B, _N), shares_per_core=P(_B, _N), free_dyn=P(_B, _N),
    base_mask=P(_B, _N), used_cpu=P(_B, _N), used_mem=P(_B, _N),
    used_disk=P(_B, _N), used_cores=P(_B, _N), used_mbits=P(_B, _N),
    avail_mbits=P(_B, _N), port_conflict=P(_B, _N),
    dev_aff_score=P(_B, _N), job_tg_count=P(_B, _N), penalty=P(_B, _N),
    aff_score=P(_B, _N), job_any_count=P(_B, _N),
    # [B, N, D]
    dev_free=P(_B, _N, None),
    # [B] scalars
    has_dev_affinity=P(_B), distinct_hosts_job=P(_B), distinct_hosts_tg=P(_B),
    ask_cpu=P(_B), ask_mem=P(_B), ask_disk=P(_B), ask_cores=P(_B),
    ask_dyn_ports=P(_B), ask_has_reserved_ports=P(_B), ask_mbits=P(_B),
    desired_count=P(_B), algorithm_spread=P(_B), n_steps=P(_B),
    # tie-break permutation [B, N] (replicated over nodes: it indexes
    # the global node axis, so it cannot shard with it)
    node_perm=P(_B, None),
    # per-step planes [B, K, ...]
    step_penalty=P(_B, None, None), step_preferred=P(_B, None),
    # spreads
    spread_active=P(_B, None), spread_even=P(_B, None),
    spread_weight=P(_B, None),
    spread_bucket=P(_B, None, _N),
    spread_counts=P(_B, None, None), spread_desired=P(_B, None, None),
    # [B, D]
    ask_dev=P(_B, None),
)

assert set(_IN_SPECS) == set(KernelIn._fields)


def batched_in_shardings(mesh: Mesh) -> KernelIn:
    return KernelIn(**{f: NamedSharding(mesh, s) for f, s in _IN_SPECS.items()})


def batched_out_shardings(mesh: Mesh) -> KernelOut:
    # outputs are small (per-placement rows); shard only the batch axis
    return KernelOut(
        **{f: NamedSharding(mesh, P(_B)) for f in KernelOut._fields}
    )


def stack_kernel_ins(kins: Sequence[KernelIn]) -> KernelIn:
    """Stack B single-problem inputs into one batched KernelIn.

    All problems must share the same padded node axis (the bucketed
    static shapes from tensors/schema.pad_bucket guarantee few distinct
    buckets; the broker batches compatible evals together).
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *kins)


def make_place_batch(
    mesh: Mesh, k_steps: int, features: KernelFeatures = FULL_FEATURES
):
    """Compile the batched, sharded placement step for ``mesh``.

    Returns ``fn(kin_batched) -> KernelOut`` (batched) — the framework's
    "training step": one launch schedules a whole batch of evaluations
    across the slice.
    """
    vmapped = jax.vmap(lambda kin: place_taskgroup(kin, k_steps, features))
    return jax.jit(
        vmapped,
        in_shardings=(batched_in_shardings(mesh),),
        out_shardings=batched_out_shardings(mesh),
    )


# ---------------------------------------------------------------------------
# Sharded JOINT waves: the live coalescer's multi-chip path.
#
# The joint wave kernel (ops/kernel.place_taskgroups_joint) is the live
# server's launch shape: a stacked member axis + one serialized step
# axis with a shared capacity carry. Sharding its NODE axis over the
# mesh runs the same program across the slice — each step's masked
# argmax/top-k lowers to a per-shard reduce + cross-shard all-reduce
# riding ICI (the reference's MaxScore iterator as a collective;
# SURVEY.md section 2.10) — so results are bit-identical to the
# single-device path by construction.
# ---------------------------------------------------------------------------

# PartitionSpec per stacked KernelIn field ([B, ...] member axis
# replicated, node axis sharded).
_JOINT_SPECS = dict(
    cap_cpu=P(None, _N), cap_mem=P(None, _N), cap_disk=P(None, _N),
    free_cores=P(None, _N), shares_per_core=P(None, _N),
    free_dyn=P(None, _N), base_mask=P(None, _N), used_cpu=P(None, _N),
    used_mem=P(None, _N), used_disk=P(None, _N), used_cores=P(None, _N),
    used_mbits=P(None, _N), avail_mbits=P(None, _N),
    port_conflict=P(None, _N), dev_aff_score=P(None, _N),
    job_tg_count=P(None, _N), penalty=P(None, _N), aff_score=P(None, _N),
    job_any_count=P(None, _N),
    dev_free=P(None, _N, None),
    has_dev_affinity=P(None), distinct_hosts_job=P(None),
    distinct_hosts_tg=P(None),
    ask_cpu=P(None), ask_mem=P(None), ask_disk=P(None), ask_cores=P(None),
    ask_dyn_ports=P(None), ask_has_reserved_ports=P(None),
    ask_mbits=P(None), desired_count=P(None), algorithm_spread=P(None),
    n_steps=P(None),
    node_perm=P(None, None),        # indexes the GLOBAL node axis
    step_penalty=P(None, None, None), step_preferred=P(None, None),
    spread_active=P(None, None), spread_even=P(None, None),
    spread_weight=P(None, None),
    spread_bucket=P(None, None, _N),
    spread_counts=P(None, None, None), spread_desired=P(None, None, None),
    ask_dev=P(None, None),
)

assert set(_JOINT_SPECS) == set(KernelIn._fields)


def shared_field_spec(field: str) -> P:
    """PartitionSpec of a WAVE-SHARED (unbatched) KernelIn leaf: the
    stacked layout's spec minus the leading member axis. Single source
    of truth for the sharded launcher, the device-resident state's
    frozen-singleton placement, and the AOT warmup — a drift here
    would make a resident plane's sharding miss the jit's
    ``in_shardings`` and silently reshard every wave."""
    return P(*tuple(_JOINT_SPECS[field])[1:])


def node_axis_sharding(mesh: Mesh) -> NamedSharding:
    """The [n_pad] node-plane sharding: rows split over the mesh's
    nodes axis (tensors/device_state.py places resident generations
    with this)."""
    return NamedSharding(mesh, P(_N))


def joint_in_shardings(mesh: Mesh, shared: bool = False,
                       neutral_shared: bool = False,
                       job_shared: bool = False):
    """(KernelIn-of-NamedSharding, replicated) for a wave layout: a
    field that ships UNBATCHED under the layout flags loses the member
    axis and keeps its node-axis split; stacked fields keep the full
    joint spec. The launcher pre-places host leaves with exactly these
    shardings so the jit's ``in_shardings`` never reshard."""
    from nomad_tpu.parallel.coalesce import wave_field_is_shared

    kin = KernelIn(**{
        f: NamedSharding(
            mesh,
            shared_field_spec(f)
            if wave_field_is_shared(f, shared, neutral_shared,
                                    job_shared)
            else s)
        for f, s in _JOINT_SPECS.items()
    })
    return kin, NamedSharding(mesh, P())


import weakref

# keyed by the live mesh OBJECT (weakly): a freed mesh's entry
# evicts itself, and an unrelated mesh allocated at the same address
# can never collide with a stale jit bound to dead devices. Each
# mesh maps sharing-layout flags -> the compiled wrapper (the sharing
# groups change leaf SHAPES, so every layout is its own in_shardings
# pytree; the (t_steps, features) variants are cached by jit itself).
_joint_sharded_cache: "weakref.WeakKeyDictionary" = \
    weakref.WeakKeyDictionary()


def joint_sharded_entry(mesh: Mesh, shared: bool = False,
                        neutral_shared: bool = False,
                        job_shared: bool = False):
    """(jit fn, KernelIn-of-NamedSharding, replicated) for the joint
    wave program with the node axis sharded over ``mesh``'s nodes axis
    under the given sharing layout. Cached per (mesh, layout) as ONE
    entry — the launcher needs the shardings on every wave (to
    pre-place host leaves), so rebuilding ~40 NamedShardings per
    launch would be repeated dispatch-path work; the (t_steps,
    features) variants are cached by jit itself (static args)."""
    from nomad_tpu.ops.kernel import place_taskgroups_joint

    layouts = _joint_sharded_cache.get(mesh)
    if layouts is None:
        layouts = _joint_sharded_cache[mesh] = {}
    key = (shared, neutral_shared, job_shared)
    hit = layouts.get(key)
    if hit is not None:
        return hit
    kin_shardings, repl = joint_in_shardings(
        mesh, shared, neutral_shared, job_shared)
    fn = jax.jit(
        place_taskgroups_joint,
        static_argnums=(3, 4),
        in_shardings=(kin_shardings, repl, repl),
        out_shardings=repl,      # outputs are small per-step rows
    )
    entry = (fn, kin_shardings, repl)
    layouts[key] = entry
    return entry


def wave_mesh(n_devices: int = 0, devices=None) -> Mesh:
    """A 1D nodes-axis mesh for live waves (the coalescer's multi-chip
    routing; evals parallelism comes from wave batching, so the whole
    slice goes to the node axis)."""
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    if n_devices:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (_N,))


def unstack_kernel_outs(out: KernelOut) -> List[KernelOut]:
    """Split a batched KernelOut back into per-problem results."""
    b = out.chosen.shape[0]
    import numpy as np

    host = KernelOut(*[np.asarray(x) for x in out])
    return [KernelOut(*[f[i] for f in host]) for i in range(b)]


# ---------------------------------------------------------------------------
# Fused sharded waves (ISSUE 19): the wave scan with the node-axis
# split made explicit by ``shard_map`` in place of GSPMD's partitioning
# of the composite, and the launcher's eager read-back packed into one
# buffer (ops/kernel.pack_fused_wave):
# each shard runs the SAME per-step math as the composite
# (ops/kernel._feasible/_score on its local node rows — shared code,
# not a reimplementation) and the per-step argmax / preferred-pin /
# top-k merge across shards is a handful of scalar-or-[TOPK]-wide
# collectives (pmax/pmin/all_gather) riding ICI. The carry planes stay
# local [N/D] the whole scan and the a_* outputs come back node-axis
# sharded — no full gather anywhere, same invariant the mesh cell
# measures for the composite.
#
# Tie-break parity: the composite picks ``argmax(masked)`` (lowest
# index among equal maxima) or, with shuffle on,
# ``perm[argmax(masked[perm])]`` (lowest PERMUTATION RANK among
# maxima). Both reduce to "minimize a per-node i32 rank among the
# global maxima" with rank = global index or inv(perm) — which is
# exactly the pmax-value / pmin-rank / pmin-index cascade below, so
# selection is bit-identical, not just score-identical.
# ---------------------------------------------------------------------------


def _fused_sharded_core(kin: KernelIn, step_member, step_local, *,
                        t_steps: int, features: KernelFeatures,
                        n_shards: int):
    """Per-shard body of the fused sharded wave (runs under
    shard_map; node-axis leaves arrive pre-sliced to [.., N/D])."""
    from nomad_tpu.ops.kernel import (
        KIN_UNBATCHED_RANKS,
        NEG_INF,
        TOPK,
        JointOut,
        _feasible,
        _inv,
        _score,
        pack_fused_wave,
    )

    f = features
    n_loc = kin.cap_cpu.shape[-1]
    n_glob = n_loc * n_shards
    b = kin.n_steps.shape[0]
    g0 = jax.lax.axis_index(_N) * n_loc
    giota = g0.astype(jnp.int32) + jnp.arange(n_loc, dtype=jnp.int32)
    big = jnp.int32(2**31 - 1)

    def _bat(x, rank):
        if jnp.ndim(x) == rank + 1:
            return x
        return jnp.broadcast_to(x, (b,) + jnp.shape(x))

    zf = jnp.zeros(n_loc, jnp.float32)
    zi = jnp.zeros(n_loc, jnp.int32)
    init = dict(
        a_cpu=zf, a_mem=zf, a_disk=zf,
        job_tg_count=_bat(kin.job_tg_count, 1),
    )
    if f.with_ports:
        init["a_dyn"] = zi
        init["port_conflict"] = _bat(kin.port_conflict, 1)
    if f.with_distinct:
        init["job_any_count"] = _bat(kin.job_any_count, 1)

    # tie-break rank rows, local slice: inv(perm) under shuffle
    # (node_perm is REPLICATED — it indexes the global axis — so the
    # inverse is computed in full and sliced to this shard's rows),
    # else the global index itself
    if f.with_shuffle:
        # [B, N/D] or, one permutation for the wave, [N/D]
        rank_rows = jax.lax.dynamic_slice_in_dim(
            _inv(kin.node_perm), g0, n_loc, axis=-1)

    def member_view(st, m):
        kin_m = KernelIn(*[
            x[m] if jnp.ndim(x) == r + 1 else x
            for x, r in zip(kin, KIN_UNBATCHED_RANKS)
        ])
        st_m = dict(
            used_cpu=kin_m.used_cpu + st["a_cpu"],
            used_mem=kin_m.used_mem + st["a_mem"],
            used_disk=kin_m.used_disk + st["a_disk"],
            job_tg_count=st["job_tg_count"][m],
        )
        if f.with_ports:
            st_m["free_dyn"] = kin_m.free_dyn - st["a_dyn"]
            st_m["port_conflict"] = st["port_conflict"][m]
        if f.with_distinct:
            st_m["job_any_count"] = st["job_any_count"][m]
        return kin_m, st_m

    def step(st, t):
        member = step_member[t]
        active_step = member >= 0
        m = jnp.clip(member, 0, b - 1)
        j = step_local[t]
        kin_m, st_m = member_view(st, m)

        feasible, ask_cpu_total, _ = _feasible(kin_m, st_m, f)
        penalty = kin_m.penalty
        if f.with_step_penalties:
            pen_ids = kin_m.step_penalty[j]      # GLOBAL node ids
            step_pen = jnp.any(giota[:, None] == pen_ids[None, :],
                               axis=1)
            penalty = penalty | step_pen
        final = _score(kin_m, st_m, ask_cpu_total, penalty, f, None)
        active = active_step & (j < kin_m.n_steps)
        masked = jnp.where(feasible & active, final, NEG_INF)

        if f.with_shuffle:
            rank = (rank_rows[m] if rank_rows.ndim == 2
                    else rank_rows)
        else:
            rank = giota
        vmax = jax.lax.pmax(jnp.max(masked), _N)
        is_max = masked == vmax
        rwin = jax.lax.pmin(
            jnp.min(jnp.where(is_max, rank, big)), _N)
        best = jax.lax.pmin(
            jnp.min(jnp.where(is_max & (rank == rwin), giota, big)),
            _N)
        if f.with_preferred:
            pref = kin_m.step_preferred[j]
            prefc = jnp.clip(pref, 0, n_glob - 1)
            feas_pref = jax.lax.pmax(
                jnp.max(((giota == prefc) & feasible)
                        .astype(jnp.int32)), _N) > 0
            pref_ok = (pref >= 0) & feas_pref & active
            idx = jnp.where(pref_ok, prefc, best)
        else:
            idx = best
        at_idx = giota == idx
        val = jax.lax.pmax(
            jnp.max(jnp.where(at_idx, masked, -jnp.inf)), _N)
        found = val > NEG_INF / 2

        if f.with_topk:
            # local top-k, then merge: each shard surfaces its TOPK
            # best in value-desc/index-asc order, and the flat
            # [D*TOPK] concatenation preserves global-index order
            # between shards for equal values — so a second top_k
            # reproduces the composite's global tie order exactly
            tv_loc, ti_loc = jax.lax.top_k(masked, TOPK)
            gi_loc = giota[ti_loc]
            tv_all = jax.lax.all_gather(tv_loc, _N)     # [D, TOPK]
            gi_all = jax.lax.all_gather(gi_loc, _N)
            topv, pos = jax.lax.top_k(tv_all.reshape(-1), TOPK)
            topi = gi_all.reshape(-1)[pos]
        else:
            topv = jnp.full(TOPK, NEG_INF)
            topi = jnp.zeros(TOPK, jnp.int32)

        upd = (found & active).astype(jnp.float32)
        updi = (found & active).astype(jnp.int32)
        one = at_idx.astype(jnp.float32) * upd
        onei = at_idx.astype(jnp.int32) * updi
        st2 = dict(
            a_cpu=st["a_cpu"] + one * ask_cpu_total,
            a_mem=st["a_mem"] + one * kin_m.ask_mem,
            a_disk=st["a_disk"] + one * kin_m.ask_disk,
            job_tg_count=st["job_tg_count"].at[m].add(onei),
        )
        if f.with_ports:
            st2["a_dyn"] = st["a_dyn"] + onei * kin_m.ask_dyn_ports
            st2["port_conflict"] = st["port_conflict"].at[m].set(
                st["port_conflict"][m]
                | ((one > 0) & kin_m.ask_has_reserved_ports)
            )
        if f.with_distinct:
            st2["job_any_count"] = st["job_any_count"].at[m].add(onei)
        out = (
            jnp.where(found, idx, -1).astype(jnp.int32),
            jnp.where(found, val, 0.0),
            found & active,
            topi.astype(jnp.int32),
            topv,
        )
        return st2, out

    st_final, (chosen, scores, found, topk_idx, topk_scores) = \
        jax.lax.scan(step, init, jnp.arange(t_steps))

    # per-member metrics: local partial sums + one exact i32 psum
    def member_metrics(kin_m: KernelIn):
        st0 = dict(
            used_cpu=kin_m.used_cpu, used_mem=kin_m.used_mem,
            used_disk=kin_m.used_disk, job_tg_count=kin_m.job_tg_count,
            used_cores=kin_m.used_cores, used_mbits=kin_m.used_mbits,
            free_dyn=kin_m.free_dyn, port_conflict=kin_m.port_conflict,
            dev_free=kin_m.dev_free, job_any_count=kin_m.job_any_count,
            spread_counts=kin_m.spread_counts,
        )
        feas0, _, dims0 = _feasible(kin_m, st0, f)
        base_i = kin_m.base_mask
        ex = lambda fit: jnp.sum(base_i & ~fit).astype(jnp.int32)  # noqa: E731
        return (
            jnp.sum(base_i).astype(jnp.int32),
            jnp.sum(feas0).astype(jnp.int32),
            ex(dims0["fit_cpu"]), ex(dims0["fit_mem"]),
            ex(dims0["fit_disk"]), ex(dims0["fit_ports"]),
            ex(dims0["fit_dev"]), ex(dims0["fit_cores"]),
        )

    in_axes = KernelIn(*[
        0 if jnp.ndim(x) == r + 1 else None
        for x, r in zip(kin, KIN_UNBATCHED_RANKS)
    ])
    locs = jax.vmap(member_metrics, in_axes=(in_axes,))(kin)
    mets = [jax.lax.psum(x, _N) for x in locs]

    out = JointOut(
        chosen=chosen, scores=scores, found=found,
        topk_idx=topk_idx, topk_scores=topk_scores,
        nodes_evaluated=mets[0], nodes_feasible=mets[1],
        exhausted_cpu=mets[2], exhausted_mem=mets[3],
        exhausted_disk=mets[4], exhausted_ports=mets[5],
        exhausted_devices=mets[6], exhausted_cores=mets[7],
        a_cpu=st_final["a_cpu"], a_mem=st_final["a_mem"],
        a_disk=st_final["a_disk"],
    )
    packed = pack_fused_wave(out, t_steps, int(b))
    return (packed, topk_idx, topk_scores,
            st_final["a_cpu"], st_final["a_mem"], st_final["a_disk"])


#: fused sharded entries, cached per live mesh object like
#: _joint_sharded_cache (same WeakKeyDictionary rationale)
_fused_sharded_cache: "weakref.WeakKeyDictionary" = \
    weakref.WeakKeyDictionary()


def fused_sharded_entry(mesh: Mesh, shared: bool = False,
                        neutral_shared: bool = False,
                        job_shared: bool = False):
    """(jit fn, KernelIn-of-NamedSharding, replicated) for the FUSED
    wave program with the node axis split over ``mesh`` via
    shard_map. Same sharding discipline as joint_sharded_entry — the
    in_specs ARE shared_field_spec's layout, so resident mesh-placed
    twins flow in without resharding."""
    import functools

    from nomad_tpu.ops.kernel import FusedWaveOut

    layouts = _fused_sharded_cache.get(mesh)
    if layouts is None:
        layouts = _fused_sharded_cache[mesh] = {}
    key = (shared, neutral_shared, job_shared)
    hit = layouts.get(key)
    if hit is not None:
        return hit
    kin_shardings, repl = joint_in_shardings(
        mesh, shared, neutral_shared, job_shared)
    in_specs = (KernelIn(*[s.spec for s in kin_shardings]), P(), P())
    out_specs = (P(), P(), P(), P(_N), P(_N), P(_N))
    n_shards = int(mesh.shape[_N])

    def run(kin, step_member, step_local, t_steps, features):
        body = functools.partial(
            _fused_sharded_core, t_steps=t_steps, features=features,
            n_shards=n_shards)
        res = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(
            kin, step_member, step_local)
        return FusedWaveOut(*res)

    fn = jax.jit(run, static_argnums=(3, 4),
                 in_shardings=(kin_shardings, repl, repl))
    entry = (fn, kin_shardings, repl)
    layouts[key] = entry
    return entry
