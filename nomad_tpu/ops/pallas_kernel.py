"""Pallas TPU kernel for the batched placement hot path (alternative
backend).

One program per evaluation; all node planes live in VMEM for the whole
placement loop (`fori_loop` over the K steps, masked global argmax and
one-hot deduction as pure VPU work), so HBM sees each shared plane
once per launch.

**Measured status (round 5, REAL TPU v5e chip, 10k nodes, B=512,
best-of-3 materialized timing):** `pallas_topk_place_batch` (full-
width pass + approx_max_k in XLA, the K-step candidate deduction scan
as one VMEM-resident pallas program, 256-row batch tiles) runs at
**98.9k evals/s vs the all-XLA candidate kernel's 119.8k — 82% —
at exact score parity** (same 170,607 score sum / 204,800 placements
on the same ask stream). Two findings from getting it on-chip:
(1) a loop-carried bool vector trips a Mosaic layout-inference bug
(scf.yield on vector<8x128xi1>); the validity flag is carried as f32.
(2) per-program grid overhead dominates small batch tiles — tb=8
measured ~10% slower than tb=256.

The remaining gap is NOT the scan (it is a small fraction of launch
time): it is the full-width scoring sweep + top-k, where XLA's fused
sweep and hardware-tuned approx_max_k are already near the HBM
roofline. Fusing them into this program would mean re-implementing
approx_max_k's bucketed selection in VPU ops to save one [B,N]
intermediate round-trip — measured headroom under 20%, so the
scheduler and bench stay on the XLA path via per-machine calibration
(bench.py `_calibrate_and_size` times both and picks the winner; on
this chip it correctly picks XLA). The kernel remains the pallas-side
evolution seam, now proven on hardware end to end.

Feature coverage is the **lean binpack variant** (the common service/
batch ask: cpu/mem/disk feasibility + binpack/spread fit + job
anti-affinity + penalty + node-affinity planes, no ports/devices/
cores/bandwidth/spread-stanza/distinct/preferred planes). The host
falls back to the XLA kernel for asks outside this envelope — the
same static-specialization seam `infer_features` already provides.

Semantics parity (same pointers as ops/kernel.py):
- feasibility: funcs.go:166 AllocsFit dimensions cpu/mem/disk
- score: funcs.go:259 ScoreFitBinPack / :286 ScoreFitSpread, /18
  (rank.go:547), anti-affinity rank.go:588, penalty rank.go:655,
  affinity rank.go:730, appended-plane normalization rank.go:764
- per-step deduction between placements of one task group
  (generic_sched.go computePlacements sequential accounting)
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
LANES = 128
K_SLOTS = 128          # output columns per eval (one aligned lane row)


class PallasOut(NamedTuple):
    chosen: jnp.ndarray      # i32[B, K]
    scores: jnp.ndarray      # f32[B, K]
    found: jnp.ndarray       # bool[B, K]


def _place_kernel(scal_f, scal_i,
                  cap_cpu, cap_mem, cap_disk,
                  used_cpu, used_mem, used_disk,
                  base, jobtg, penalty, aff,
                  chosen_ref, score_ref, found_ref,
                  *, k_steps: int, r: int):
    b = pl.program_id(0)
    a_cpu = scal_f[b, 0]
    a_mem = scal_f[b, 1]
    a_disk = scal_f[b, 2]
    algo_spread = scal_f[b, 3]
    n_steps = scal_i[b, 0]
    desired = scal_i[b, 1]

    cc = cap_cpu[:]
    cm = cap_mem[:]
    cd = cap_disk[:]
    base_m = base[:] > 0.0
    pen = penalty[:] > 0.0
    affs = aff[:]

    rows = jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 1)
    flat = rows * LANES + cols
    kcol = jax.lax.broadcasted_iota(jnp.int32, (1, K_SLOTS), 1)
    # outputs are one full (B, K) block revisited by every program; each
    # program row-masks its own writes (TPU blocks need >=8 sublanes, so
    # a (1, K) per-program block is not lowerable)
    out_rows = jax.lax.broadcasted_iota(jnp.int32, chosen_ref.shape, 0)
    mine = out_rows == b

    denom = jnp.maximum(desired.astype(jnp.float32), 1.0)
    aff_on = affs != 0.0
    pen_f = jnp.where(pen, -1.0, 0.0)
    extra_planes = pen.astype(jnp.float32) + aff_on.astype(jnp.float32)
    aff_sum = jnp.where(aff_on, affs, 0.0) + pen_f

    def body(i, carry):
        uc, um, ud, utg, ch, sc, fo = carry
        feas = (
            base_m
            & ((cc - uc) >= a_cpu)
            & ((cm - um) >= a_mem)
            & ((cd - ud) >= a_disk)
        )
        # computeFreePercentage with zero-capacity guard (funcs.go:235)
        fc = jnp.where(cc > 0, 1.0 - (uc + a_cpu) / cc, 0.0)
        fm = jnp.where(cm > 0, 1.0 - (um + a_mem) / cm, 0.0)
        total = jnp.power(10.0, fc) + jnp.power(10.0, fm)
        binpack = jnp.clip(20.0 - total, 0.0, 18.0)
        spreadfit = jnp.clip(total - 2.0, 0.0, 18.0)
        fit = jnp.where(algo_spread > 0, spreadfit, binpack) / 18.0

        coll = utg.astype(jnp.float32)
        anti_on = coll > 0
        ssum = fit + jnp.where(anti_on, -(coll + 1.0) / denom, 0.0) + aff_sum
        nplanes = 1.0 + anti_on.astype(jnp.float32) + extra_planes
        final = ssum / nplanes

        active = i < n_steps
        masked = jnp.where(feas & active, final, NEG_INF)
        amax = jnp.max(masked)
        # first-max index (jnp.argmax parity): min flat id at the max
        idx = jnp.min(jnp.where(masked == amax, flat, jnp.int32(2**30)))
        fnd = amax > NEG_INF / 2

        one = (flat == idx) & fnd
        onef = one.astype(jnp.float32)
        uc = uc + onef * a_cpu
        um = um + onef * a_mem
        ud = ud + onef * a_disk
        utg = utg + one.astype(jnp.int32)

        at_i = kcol == i
        ch = jnp.where(at_i, jnp.where(fnd, idx, -1), ch)
        sc = jnp.where(at_i, jnp.where(fnd, amax, 0.0), sc)
        fo = jnp.where(at_i, fnd.astype(jnp.int32), fo)
        return uc, um, ud, utg, ch, sc, fo

    init = (
        used_cpu[:], used_mem[:], used_disk[:],
        jobtg[:].astype(jnp.int32),
        jnp.full((1, K_SLOTS), -1, jnp.int32),
        jnp.zeros((1, K_SLOTS), jnp.float32),
        jnp.zeros((1, K_SLOTS), jnp.int32),
    )
    _, _, _, _, ch, sc, fo = jax.lax.fori_loop(0, k_steps, body, init)
    chosen_ref[:] = jnp.where(mine, ch, chosen_ref[:])
    score_ref[:] = jnp.where(mine, sc, score_ref[:])
    found_ref[:] = jnp.where(mine, fo, found_ref[:])


@functools.partial(
    jax.jit,
    static_argnames=("k_steps", "interpret"),
)
def pallas_place_batch(cap_cpu, cap_mem, cap_disk,
                       used_cpu, used_mem, used_disk,
                       base_mask, job_tg_count, penalty, aff_score,
                       ask_cpu, ask_mem, ask_disk,
                       n_steps, desired_count, algorithm_spread,
                       k_steps: int, interpret: bool = False) -> PallasOut:
    """Place k_steps allocations for each of B evals in one launch.

    Plane args are f32[N] (N % 128 == 0, bool planes pre-cast to 0/1
    f32); ask args are per-eval vectors [B]; desired_count /
    algorithm_spread broadcast scalars or [B].
    """
    n = cap_cpu.shape[0]
    assert n % LANES == 0, f"node axis {n} not lane-aligned"
    assert 0 < k_steps <= K_SLOTS
    r = n // LANES
    real_b = ask_cpu.shape[0]
    # the (B, K_SLOTS) output block needs >=8 sublanes to lower on
    # TPU; pad tail batches up and slice the extras back off
    B = max(8, real_b)
    if real_b < B:
        pad = B - real_b
        zpad = lambda x: jnp.pad(jnp.asarray(x), (0, pad))  # noqa: E731
        ask_cpu, ask_mem = zpad(ask_cpu), zpad(ask_mem)
        n_steps = zpad(n_steps)   # padded evals place 0 steps

    def plane(x):
        return jnp.asarray(x, jnp.float32).reshape(r, LANES)

    bcast = lambda x: jnp.broadcast_to(jnp.asarray(x), (B,))  # noqa: E731
    scal_f = jnp.stack([
        jnp.asarray(ask_cpu, jnp.float32),
        jnp.asarray(ask_mem, jnp.float32),
        bcast(ask_disk).astype(jnp.float32),
        bcast(algorithm_spread).astype(jnp.float32),
    ], axis=1)
    scal_i = jnp.stack([
        jnp.asarray(n_steps, jnp.int32),
        bcast(desired_count).astype(jnp.int32),
    ], axis=1)

    shared_spec = pl.BlockSpec(
        (r, LANES), lambda b, *_: (0, 0), memory_space=pltpu.VMEM,
    )
    out_spec = pl.BlockSpec((B, K_SLOTS), lambda b, *_: (0, 0),
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[shared_spec] * 10,
        out_specs=[out_spec, out_spec, out_spec],
    )
    chosen, scores, found = pl.pallas_call(
        functools.partial(_place_kernel, k_steps=k_steps, r=r),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, K_SLOTS), jnp.int32),
            jax.ShapeDtypeStruct((B, K_SLOTS), jnp.float32),
            jax.ShapeDtypeStruct((B, K_SLOTS), jnp.int32),
        ],
        interpret=interpret,
    )(
        scal_f, scal_i,
        plane(cap_cpu), plane(cap_mem), plane(cap_disk),
        plane(used_cpu), plane(used_mem), plane(used_disk),
        plane(base_mask), plane(job_tg_count), plane(penalty),
        plane(aff_score),
    )
    return PallasOut(
        chosen=chosen[:real_b, :k_steps],
        scores=scores[:real_b, :k_steps],
        found=found[:real_b, :k_steps] > 0,
    )


# ---------------------------------------------------------------------------
# Fused candidate-set scan: the hybrid hot path.
#
# The XLA candidate-set kernel (ops/kernel.place_taskgroup_topk) is one
# full-width scoring pass + approx_max_k + a K-wide deduction scan. The
# scan is tiny compute ([B, ~32] tensors) but unrolls to ~30 XLA ops per
# placement step — per-op overhead dominates it. This kernel keeps the
# full-width pass + approx_max_k in XLA (one fused elementwise pass over
# [B, N] + the TPU-optimized selection) and runs the ENTIRE deduction
# scan as one pallas program: candidate planes live in VMEM registers,
# each step is pure VPU work on a (TB, 128) tile, and the bound check
# (place_taskgroup_topk's `valid`) is tracked in-register. Exactness is
# inherited from the same rest-max bound: when `valid` is False the
# caller re-runs the full-width kernel.
# ---------------------------------------------------------------------------

C_LANES = 128           # candidate axis, one lane row
_SCAL_LANES = 8         # per-eval scalars packed into lanes of one row


def _cand_scan_kernel(scal, cap_cpu, cap_mem, cap_disk,
                      used_cpu, used_mem, used_disk,
                      base, jobtg, penalty, aff, node_id,
                      chosen_ref, score_ref, found_ref, valid_ref,
                      *, k_steps: int, tb: int):
    cols = jax.lax.broadcasted_iota(jnp.int32, (tb, C_LANES), 1)

    def lane(j):
        return jnp.sum(jnp.where(cols == j, scal[:], 0.0), axis=1,
                       keepdims=True)

    a_cpu = lane(0)
    a_mem = lane(1)
    a_disk = lane(2)
    algo_spread = lane(3)
    n_steps = lane(4)
    desired = lane(5)
    rest_max = lane(6)

    cc = cap_cpu[:]
    cm = cap_mem[:]
    cd = cap_disk[:]
    base_m = base[:] > 0.0
    pen = penalty[:] > 0.0
    affs = aff[:]
    nid = node_id[:]

    denom = jnp.maximum(desired, 1.0)
    aff_on = affs != 0.0
    pen_f = jnp.where(pen, -1.0, 0.0)
    extra_planes = pen.astype(jnp.float32) + aff_on.astype(jnp.float32)
    aff_sum = jnp.where(aff_on, affs, 0.0) + pen_f

    def body(i, carry):
        uc, um, ud, utg, ch, sc, fo, ok = carry
        feas = (
            base_m
            & ((cc - uc) >= a_cpu)
            & ((cm - um) >= a_mem)
            & ((cd - ud) >= a_disk)
        )
        fc = jnp.where(cc > 0, 1.0 - (uc + a_cpu) / cc, 0.0)
        fm = jnp.where(cm > 0, 1.0 - (um + a_mem) / cm, 0.0)
        total = jnp.power(10.0, fc) + jnp.power(10.0, fm)
        binpack = jnp.clip(20.0 - total, 0.0, 18.0)
        spreadfit = jnp.clip(total - 2.0, 0.0, 18.0)
        fit = jnp.where(algo_spread > 0, spreadfit, binpack) / 18.0

        coll = utg
        anti_on = coll > 0
        ssum = fit + jnp.where(anti_on, -(coll + 1.0) / denom, 0.0) + aff_sum
        nplanes = 1.0 + anti_on.astype(jnp.float32) + extra_planes
        final = ssum / nplanes

        active = i.astype(jnp.float32) < n_steps            # [TB, 1]
        masked = jnp.where(feas & active, final, NEG_INF)
        rowmax = jnp.max(masked, axis=1, keepdims=True)      # [TB, 1]
        # first-max lane (argmax parity with the XLA candidate order)
        at_max = masked == rowmax
        lane_idx = jnp.min(
            jnp.where(at_max, cols, jnp.int32(2 ** 30)), axis=1,
            keepdims=True)
        fnd = rowmax > NEG_INF / 2
        # chosen NODE id: duplicate candidate rows of one node share
        # deductions (preferred-pin duplicates in the XLA path)
        chosen_id = jnp.sum(
            jnp.where(cols == lane_idx, nid, 0.0), axis=1, keepdims=True)
        share = (nid == chosen_id) & fnd & (active > 0)
        upd = share.astype(jnp.float32)
        uc = uc + upd * a_cpu
        um = um + upd * a_mem
        ud = ud + upd * a_disk
        utg = utg + upd
        # bound check: best candidate must still beat the rest of the
        # cluster (place_taskgroup_topk's ok accumulation). Carried as
        # f32 0/1: a loop-carried bool vector trips a Mosaic layout-
        # inference bug (scf.yield on vector<8x128xi1> with vpad
        # mismatch) on current TPU toolchains
        ok = ok * ((active <= 0) | ~fnd
                   | (rowmax >= rest_max)).astype(jnp.float32)

        at_i = cols == i
        placed = fnd & (active > 0)
        ch = jnp.where(at_i, jnp.where(placed, chosen_id, -1.0), ch)
        sc = jnp.where(at_i, jnp.where(placed, rowmax, 0.0), sc)
        fo = jnp.where(at_i, placed.astype(jnp.float32), fo)
        return uc, um, ud, utg, ch, sc, fo, ok

    init = (
        used_cpu[:], used_mem[:], used_disk[:], jobtg[:],
        jnp.full((tb, C_LANES), -1.0, jnp.float32),
        jnp.zeros((tb, C_LANES), jnp.float32),
        jnp.zeros((tb, C_LANES), jnp.float32),
        jnp.ones((tb, 1), jnp.float32),
    )
    _, _, _, _, ch, sc, fo, ok = jax.lax.fori_loop(0, k_steps, body, init)

    # a missing placement while the rest of the cluster might still fit
    # also invalidates the run (candidates exhausted, full kernel could
    # place) — place_taskgroup_topk's `missing` check
    want = (cols < k_steps) & (cols.astype(jnp.float32) < n_steps)
    missing = jnp.any(want & (fo <= 0.0), axis=1, keepdims=True)
    rest_bad = rest_max <= NEG_INF / 2
    valid = (ok > 0.0) & (~missing | rest_bad)

    chosen_ref[:] = ch.astype(jnp.int32)
    score_ref[:] = sc
    found_ref[:] = (fo > 0.0).astype(jnp.int32)
    valid_ref[:] = jnp.broadcast_to(
        valid.astype(jnp.int32), (tb, C_LANES))


@functools.partial(
    jax.jit,
    static_argnames=("k_steps", "k_cand", "interpret"),
)
def pallas_topk_place_batch(cap_cpu, cap_mem, cap_disk,
                            used_cpu, used_mem, used_disk,
                            base_mask, job_tg_count, penalty, aff_score,
                            ask_cpu, ask_mem, ask_disk,
                            n_steps, desired_count, algorithm_spread,
                            k_steps: int, k_cand: int = 64,
                            interpret: bool = False):
    """Candidate-set placement for a batch of B lean evals, pallas scan.

    Shared planes are f32/bool[N] (the wave's common snapshot); asks are
    per-eval [B]. Returns (chosen i32[B,K] node rows, scores f32[B,K],
    found bool[B,K], valid bool[B]) — `valid=False` members must re-run
    via the full-width kernel, exactly like place_taskgroup_topk.
    """
    n = cap_cpu.shape[0]
    real_b = ask_cpu.shape[0]
    k_cand = min(k_cand, n, C_LANES)
    assert 0 < k_steps <= C_LANES

    f32 = lambda x: jnp.asarray(x, jnp.float32)          # noqa: E731
    bcast = lambda x: jnp.broadcast_to(jnp.asarray(x), (real_b,))  # noqa: E731
    cc, cm, cd = f32(cap_cpu), f32(cap_mem), f32(cap_disk)
    uc, um, ud = f32(used_cpu), f32(used_mem), f32(used_disk)
    base = jnp.asarray(base_mask, bool)
    utg = f32(job_tg_count)
    pen = jnp.asarray(penalty, bool)
    aff = f32(aff_score)
    a_cpu = f32(ask_cpu)[:, None]
    a_mem = f32(ask_mem)[:, None]
    a_disk = f32(bcast(ask_disk))[:, None]
    algo = f32(bcast(algorithm_spread))[:, None]
    desired = f32(bcast(desired_count))[:, None]

    # ---- full-width pass (XLA fuses this into one HBM sweep) ----
    feas = (
        base[None, :]
        & ((cc - uc)[None, :] >= a_cpu)
        & ((cm - um)[None, :] >= a_mem)
        & ((cd - ud)[None, :] >= a_disk)
    )
    fc = jnp.where(cc[None, :] > 0, 1.0 - (uc[None, :] + a_cpu) / cc[None, :], 0.0)
    fm = jnp.where(cm[None, :] > 0, 1.0 - (um[None, :] + a_mem) / cm[None, :], 0.0)
    total = jnp.power(10.0, fc) + jnp.power(10.0, fm)
    binpack = jnp.clip(20.0 - total, 0.0, 18.0)
    spreadfit = jnp.clip(total - 2.0, 0.0, 18.0)
    fit = jnp.where(algo > 0, spreadfit, binpack) / 18.0
    coll = utg[None, :]
    anti_on = coll > 0
    pen_f = jnp.where(pen, -1.0, 0.0)[None, :]
    aff_on = (aff != 0.0)[None, :]
    ssum = (fit + jnp.where(anti_on, -(coll + 1.0) / jnp.maximum(desired, 1.0),
                            0.0)
            + jnp.where(aff_on, aff[None, :], 0.0) + pen_f)
    nplanes = (1.0 + anti_on.astype(jnp.float32) + aff_on.astype(jnp.float32)
               + pen.astype(jnp.float32)[None, :])
    final0 = ssum / nplanes
    masked0 = jnp.where(feas, final0, NEG_INF)           # [B, N]

    _, cand_idx = jax.lax.approx_max_k(masked0, k_cand, recall_target=0.95)
    rows = jnp.arange(real_b)[:, None]
    rest_max = jnp.max(masked0.at[rows, cand_idx].set(NEG_INF), axis=1)

    # ---- gather candidate planes, pad to the lane width ----
    pad_c = C_LANES - k_cand

    def gpad(x, fill):
        g = x[cand_idx].astype(jnp.float32)              # [B, k_cand]
        return jnp.pad(g, ((0, 0), (0, pad_c)), constant_values=fill)

    planes = [
        gpad(cc, 0.0), gpad(cm, 0.0), gpad(cd, 0.0),
        gpad(uc, 0.0), gpad(um, 0.0), gpad(ud, 0.0),
        gpad(base, 0.0),                                  # pad infeasible
        gpad(utg, 0.0), gpad(pen, 0.0), gpad(aff, 0.0),
        jnp.pad(cand_idx.astype(jnp.float32), ((0, 0), (0, pad_c)),
                constant_values=-1.0),                    # node ids
    ]

    scal = jnp.zeros((real_b, _SCAL_LANES), jnp.float32)
    scal = scal.at[:, 0].set(a_cpu[:, 0])
    scal = scal.at[:, 1].set(a_mem[:, 0])
    scal = scal.at[:, 2].set(a_disk[:, 0])
    scal = scal.at[:, 3].set(algo[:, 0])
    scal = scal.at[:, 4].set(jnp.asarray(n_steps, jnp.float32))
    scal = scal.at[:, 5].set(desired[:, 0])
    scal = scal.at[:, 6].set(rest_max)
    scal = jnp.pad(scal, ((0, 0), (0, C_LANES - _SCAL_LANES)))

    # batch-tile: large tiles amortize per-program grid overhead (the
    # whole working set is ~12 x tb x 128 x 4B — ~1.5MiB at tb=256,
    # comfortably VMEM-resident); tiny batches still round to the
    # native 8-sublane tile
    tb = max(8, min(256, 1 << (real_b - 1).bit_length()))
    b_pad = (-real_b) % tb
    if b_pad:
        planes = [jnp.pad(p, ((0, b_pad), (0, 0))) for p in planes]
        scal = jnp.pad(scal, ((0, b_pad), (0, 0)))       # n_steps=0 pad
    B = real_b + b_pad

    blk = pl.BlockSpec((tb, C_LANES), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    chosen, scores, found, valid = pl.pallas_call(
        functools.partial(_cand_scan_kernel, k_steps=k_steps, tb=tb),
        grid=(B // tb,),
        in_specs=[blk] * 12,
        out_specs=[blk] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((B, C_LANES), jnp.int32),
            jax.ShapeDtypeStruct((B, C_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, C_LANES), jnp.int32),
            jax.ShapeDtypeStruct((B, C_LANES), jnp.int32),
        ],
        interpret=interpret,
    )(scal, *planes)
    return (
        chosen[:real_b, :k_steps],
        scores[:real_b, :k_steps],
        found[:real_b, :k_steps] > 0,
        valid[:real_b, 0] > 0,
    )


def make_schedule_apply_step_pallas(k_steps: int, interpret: bool = False):
    """Drop-in replacement for batching.make_schedule_apply_step's lean
    variant: same signature, same optimistic-batch + scatter-commit
    semantics, pallas placement inside."""

    # deferred: batching lazily imports this module for the fused
    # top-k scan, so a module-level import here would be circular
    from nomad_tpu.parallel.batching import _jit_donating

    def step(shared, used_cpu, used_mem, ask_cpu, ask_mem, n_steps):
        out = pallas_place_batch(
            shared.cap_cpu, shared.cap_mem, shared.cap_disk,
            used_cpu, used_mem, shared.used_disk,
            shared.base_mask, shared.job_tg_count, shared.penalty,
            shared.aff_score,
            ask_cpu, ask_mem, shared.ask_disk,
            n_steps, shared.desired_count, shared.algorithm_spread,
            k_steps=k_steps, interpret=interpret,
        )
        rows = out.chosen.reshape(-1)
        ok = out.found.reshape(-1)
        w_cpu = (jnp.broadcast_to(ask_cpu[:, None], out.chosen.shape)
                 .reshape(-1) * ok)
        w_mem = (jnp.broadcast_to(ask_mem[:, None], out.chosen.shape)
                 .reshape(-1) * ok)
        safe = jnp.where(ok, rows, 0)
        used_cpu2 = used_cpu.at[safe].add(jnp.where(ok, w_cpu, 0.0))
        used_mem2 = used_mem.at[safe].add(jnp.where(ok, w_mem, 0.0))
        return out, used_cpu2, used_mem2

    # donation through the owning wrapper (PR 2/10 discipline): a raw
    # donate_argnums jit here is handed caller-owned ``jnp.asarray``
    # planes — the runtime can't always use them ("Some donated
    # buffers were not usable: float32[16384]" leaking into the bench
    # tail), and when it CAN they alias caller memory
    return _jit_donating(step, (1, 2))


# ---------------------------------------------------------------------------
# Fused wave mega-kernel (ISSUE 19): the whole joint wave — feasibility
# masking, binpack/spread scoring, the per-step capacity-carry scan,
# and top-k selection — as ONE pallas program with one packed readback
# (ops/kernel.FusedWaveOut). The body runs the SAME scan core as the
# XLA composite (ops/kernel.place_taskgroups_joint) over values read
# from the kernel refs, so bit-identity with the composite holds by
# construction across the whole supported feature lattice.
#
# Mosaic's verdict (PR 21, jax 0.9.0 / libtpu 0.0.34, TPU v5e): the
# body does NOT lower for TPU. jax's Pallas->Mosaic lowering stops at
# the wave scan (`_scan_lowering_rule`: NotImplementedError, a scan
# with stacked per-step inputs/outputs) before Mosaic sees anything,
# and the step body behind it uses `dynamic_slice`, `scatter`,
# `scatter-add` and `top_k`, none of which has a Pallas-TPU lowering
# rule, plus a full-width 1D permutation gather. That is a rewrite of
# the placement core, not a repair, so the program exists only where
# Pallas interprets it (traced into ordinary XLA ops: the CPU tier-1
# path); on TPU the launcher routes every wave to the composite
# statically (parallel/coalesce.fused_wave_enabled), never by trying
# this one and catching the error. PERF.md "Bring-up on v5e" has the
# evidence; ROADMAP A3/C2 own the delete-or-rewrite decision.
# ---------------------------------------------------------------------------


def pallas_interpret() -> bool:
    """Whether a ``pallas_call`` dispatched to the default device runs
    interpreted: Mosaic compiles for TPU only. The ONE place that
    choice is made; every entry point below takes ``interpret``
    explicitly."""
    return jax.devices()[0].platform != "tpu"


def fused_wave_place(kin, step_member, step_local, t_steps: int,
                     features, interpret: bool):
    """One-dispatch fused wave: (stacked KernelIn, step maps) ->
    ops/kernel.FusedWaveOut. Mirrors place_taskgroups_joint + the
    launcher's eager-fetch packing in a single pallas program."""
    from nomad_tpu.ops.kernel import (
        TOPK,
        FusedWaveOut,
        KernelIn,
        fused_pack_len,
        pack_fused_wave,
        place_taskgroups_joint,
    )

    b = int(kin.n_steps.shape[0])
    n = int(kin.cap_cpu.shape[-1])
    leaves = list(kin)
    # rank-0 leaves (wave-shared scalars) ship as (1,) rows — pallas
    # refs want at least one axis — and are restored inside the body
    scalar = tuple(jnp.ndim(x) == 0 for x in leaves)
    ins = [jnp.reshape(x, (1,)) if s else jnp.asarray(x)
           for x, s in zip(leaves, scalar)]

    def body(sm_ref, sl_ref, *refs):
        kin_refs = refs[:len(leaves)]
        packed_ref, ti_ref, ts_ref, ac_ref, am_ref, ad_ref = \
            refs[len(leaves):]
        vals = [r[...][0] if s else r[...]
                for r, s in zip(kin_refs, scalar)]
        out = place_taskgroups_joint(
            KernelIn(*vals), sm_ref[...], sl_ref[...], t_steps,
            features)
        packed_ref[...] = pack_fused_wave(out, t_steps, b)
        ti_ref[...] = out.topk_idx
        ts_ref[...] = out.topk_scores
        ac_ref[...] = out.a_cpu
        am_ref[...] = out.a_mem
        ad_ref[...] = out.a_disk

    out_shape = (
        jax.ShapeDtypeStruct((fused_pack_len(t_steps, b),), jnp.float32),
        jax.ShapeDtypeStruct((t_steps, TOPK), jnp.int32),
        jax.ShapeDtypeStruct((t_steps, TOPK), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
    )
    res = pl.pallas_call(body, out_shape=out_shape,
                         interpret=interpret)(
        step_member, step_local, *ins)
    return FusedWaveOut(*res)


def _fused_wave_run(kin, step_member, step_local, t_steps: int,
                    features):
    return fused_wave_place(kin, step_member, step_local, t_steps,
                            features, interpret=pallas_interpret())


fused_wave_place_jit = jax.jit(_fused_wave_run, static_argnums=(3, 4))


def make_fused_wave_apply(t_steps: int, features, interpret: bool):
    """Fused wave + carry commit with owned-buffer donation (the
    PR 10/18 discipline): ``fn(kin, used_cpu, used_mem, step_member,
    step_local) -> (FusedWaveOut, used_cpu', used_mem')`` where the
    used planes are donated INTO their post-wave successors. Donation
    routes through batching._jit_donating, which copies the donated
    args into buffers the jit owns — handing it caller-owned
    ``jnp.asarray`` planes neither corrupts them nor trips the
    "donated buffers were not usable" warning conftest promotes to an
    error."""
    from nomad_tpu.parallel.batching import _jit_donating

    def step(kin, used_cpu, used_mem, step_member, step_local):
        kin2 = kin._replace(used_cpu=used_cpu, used_mem=used_mem)
        out = fused_wave_place(kin2, step_member, step_local, t_steps,
                               features, interpret=interpret)
        return out, used_cpu + out.a_cpu, used_mem + out.a_mem

    return _jit_donating(step, (1, 2))
