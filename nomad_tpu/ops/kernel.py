"""The batched placement kernel.

Semantics parity map (Go reference -> tensor formulation):

- FeasibilityWrapper + checkers (feasible.go:1050, :135-1193): host-side
  per-class evaluation folded into ``base_mask``; numeric resource checks
  (cpu/mem/disk/ports/devices/bandwidth/cores) run on device as mask algebra.
- BinPackIterator.Next (rank.go:193-557): utilization = proposed + ask;
  score = ScoreFitBinPack (funcs.go:259) or ScoreFitSpread (funcs.go:286)
  under the cluster scheduler algorithm, normalized by 18 (rank.go:547).
- JobAntiAffinityIterator (rank.go:560): penalty -(collisions+1)/count,
  plane appended only where collisions > 0.
- NodeReschedulingPenaltyIterator (rank.go:630): -1 plane on penalty nodes.
- NodeAffinityIterator (rank.go:674): weighted-sum plane appended where
  nonzero (host precomputes the per-node normalized score).
- SpreadIterator (spread.go:116-245): desired-count boost and
  evenSpreadScoreBoost reproduced on device from bucket counts.
- ScoreNormalizationIterator (rank.go:764): mean over *appended* planes --
  reproduced exactly via per-plane appended masks.
- LimitIterator/MaxScoreIterator (select.go): replaced by global argmax
  over ALL feasible nodes (strictly better placement quality than the
  log2-limited iteration; SURVEY.md section 7.2).
- Sequential resource deduction between placements of one task group
  (generic_sched.go computePlacements loop): ``lax.scan`` steps that
  deduct the chosen node's planes before the next argmax.

Everything is static-shaped; node axis padded (ClusterTensors.n_pad),
placement axis padded to step buckets (``pad_steps``).
"""

from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu.tensors.schema import (
    MAX_DEV_REQS,
    MAX_SPREADS,
    SPREAD_BUCKETS,
    ClusterTensors,
    EvalTensors,
)

#: the checkout this package runs from (nomad_tpu/ops/kernel.py -> repo)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _enable_compile_cache() -> None:
    """Persistent XLA compilation cache, set up when the kernel module
    loads (i.e. only for consumers that touch the device path).

    The scheduler compiles one kernel variant per (wave size, step
    bucket, feature set), and a cold TPU compile is tens of seconds:
    without the cache a fresh server paying full compiles
    mid-scheduling can outlive the eval broker's nack timeout. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
    outside: jax reads the variable itself and no directory is set in
    code. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored) -- the path is part of the
    cache key, so a directory that moves never hits."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))


_enable_compile_cache()

NEG_INF = -1.0e30
TOPK = 8          # top-K score metadata returned per placement (AllocMetric)
MAX_PENALTY_NODES = 4   # previous nodes penalized per rescheduled placement
_STEP_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


def pad_steps(k: int) -> int:
    for b in _STEP_BUCKETS:
        if k <= b:
            return b
    return ((k + 4095) // 4096) * 4096


#: live-path floor for the placement-axis bucket: a follow-up eval
#: placing 1-2 leftover allocs used to compile its own tiny step
#: variant per (wave, k) pair — padding every live launch to at least
#: 8 steps collapses those onto the primary evals' programs. A wave's
#: program runs only its real steps, so the padding costs a wave no
#: device time; a lone launch's programs run every padded step (each
#: scores every node and masks the result afterwards); a cold compile
#: is tens of seconds
MIN_STEP_BUCKET = 8


def pad_steps_live(k: int) -> int:
    return pad_steps(max(k, MIN_STEP_BUCKET))


class NeutralPlanes(NamedTuple):
    """Read-only neutral planes shared BY IDENTITY across evaluations.

    The per-eval tensor build allocates a dozen O(nodes) planes that
    stay all-neutral for the common ask (no devices, no affinities, no
    in-plan ports, fresh job): allocating them per eval was the
    dominant host cost of the live path, and distinct-but-equal arrays
    also defeat the wave coalescer's identity-based sharing (every
    member would ship its own copy of the same zeros). One frozen
    singleton per padded node size serves every eval; writers must
    copy-on-write (the arrays are non-writeable, so a missed copy
    raises instead of corrupting a neighbor eval).
    """

    zeros_f32: np.ndarray       # [N]
    zeros_i32: np.ndarray       # [N]
    zeros_bool: np.ndarray      # [N]
    zeros_dev: np.ndarray       # [N, MAX_DEV_REQS] f32
    neg1_spread_bucket: np.ndarray   # [S, N] i32
    zeros_spread_counts: np.ndarray  # [S, SPREAD_BUCKETS] f32
    neg1_spread_desired: np.ndarray  # [S, SPREAD_BUCKETS] f32
    zeros_spread_flags: np.ndarray   # [S] bool
    zeros_spread_weight: np.ndarray  # [S] f32
    arange_i32: np.ndarray      # [N] identity node_perm


def _frozen(a: np.ndarray) -> np.ndarray:  # graft: frozen
    a.flags.writeable = False
    return a


_NEUTRAL_CACHE: dict = {}


def neutral_planes(n: int) -> NeutralPlanes:  # graft: frozen
    got = _NEUTRAL_CACHE.get(n)
    if got is None:
        got = NeutralPlanes(
            zeros_f32=_frozen(np.zeros(n, np.float32)),
            zeros_i32=_frozen(np.zeros(n, np.int32)),
            zeros_bool=_frozen(np.zeros(n, bool)),
            zeros_dev=_frozen(np.zeros((n, MAX_DEV_REQS), np.float32)),
            neg1_spread_bucket=_frozen(
                np.full((MAX_SPREADS, n), -1, np.int32)),
            zeros_spread_counts=_frozen(
                np.zeros((MAX_SPREADS, SPREAD_BUCKETS), np.float32)),
            neg1_spread_desired=_frozen(
                np.full((MAX_SPREADS, SPREAD_BUCKETS), -1.0, np.float32)),
            zeros_spread_flags=_frozen(np.zeros(MAX_SPREADS, bool)),
            zeros_spread_weight=_frozen(np.zeros(MAX_SPREADS, np.float32)),
            arange_i32=_frozen(np.arange(n, dtype=np.int32)),
        )
        _NEUTRAL_CACHE[n] = got
    return got


_NEUTRAL_WORDS_CACHE: dict = {}


def neutral_port_words(n: int, w: int) -> np.ndarray:  # graft: frozen
    """Frozen all-zero [N, W] u32 port-conflict words."""
    got = _NEUTRAL_WORDS_CACHE.get((n, w))
    if got is None:
        got = _frozen(np.zeros((n, w), np.uint32))
        _NEUTRAL_WORDS_CACHE[(n, w)] = got
    return got


_NEUTRAL_STEP_CACHE: dict = {}


def neutral_step_planes(k_pad: int):  # graft: frozen
    """(step_penalty[k,P]=-1, step_preferred[k]=-1) singletons."""
    got = _NEUTRAL_STEP_CACHE.get(k_pad)
    if got is None:
        got = (
            _frozen(np.full((k_pad, MAX_PENALTY_NODES), -1, np.int32)),
            _frozen(np.full(k_pad, -1, np.int32)),
        )
        _NEUTRAL_STEP_CACHE[k_pad] = got
    return got


class KernelFeatures(NamedTuple):
    """Static specialization flags (hashable; a jit static argument).

    The reference's iterator pipeline only pays for the checkers a job
    actually uses (stack.go wires checkers per ask); the tensor
    formulation gets the same effect by compiling a lean kernel variant
    per feature combination. Disabling a feature removes its planes
    from the compiled program entirely; semantics are unchanged because
    the host only disables features whose inputs are neutral (no ports
    asked, no spreads, ...).
    """

    n_spreads: int = MAX_SPREADS
    with_topk: bool = True        # per-step top-K score metadata (AllocMetric)
    with_devices: bool = True
    with_ports: bool = True
    with_cores: bool = True
    with_network: bool = True     # bandwidth accounting
    with_distinct: bool = True    # distinct_hosts masks in the scan
    with_step_penalties: bool = True  # per-placement penalty node ids
    with_preferred: bool = True   # per-placement preferred-node pins
    # per-eval node-order decorrelation (shuffleNodes util.go:464): the
    # argmax runs over a seeded permutation, so concurrent evals break
    # score TIES on different nodes instead of all piling onto row 0;
    # scores and non-tied choices are unchanged
    with_shuffle: bool = False


FULL_FEATURES = KernelFeatures()


def canonical_features(f: KernelFeatures) -> KernelFeatures:
    """Collapse near-identical feature sets onto one compiled variant.

    Every distinct ``KernelFeatures`` value is a separate XLA compile
    (tens of seconds cold on TPU), and the live path was forking
    variants on axes that don't pay for their slot: a job with 2
    spread stanzas compiled a different program than one with 3, and a
    wave whose single rescheduled member enabled ``with_step_penalties``
    compiled apart from the identical wave that also pinned a
    preferred node. Canonicalization rounds UP onto a coarser lattice:

    - ``n_spreads`` is 0 or MAX_SPREADS (inactive stanzas are no-ops
      by kernel definition; each extra slot costs a few elementwise
      operations over [N] a step, ``_spread_score``);
    - ``with_step_penalties``/``with_preferred`` travel together (both
      read tiny per-step planes whose neutral rows -1 are no-ops).

    Enabling a feature for an ask that ships neutral planes never
    changes placements — that is the coalescer's existing union
    contract — so this only trades a sliver of device time for a
    bounded variant count. Axes that change semantics (``with_shuffle``)
    or materially change program cost (ports/devices/network/cores
    over the wide node axis) are left alone.
    """
    aux = f.with_step_penalties or f.with_preferred
    return f._replace(
        n_spreads=0 if f.n_spreads == 0 else MAX_SPREADS,
        with_step_penalties=aux,
        with_preferred=aux,
    )

#: the lean cpu/mem/disk binpack envelope — what a plain service/batch
#: ask compiles to; bench + parity tests pin it
LEAN_FEATURES = KernelFeatures(
    n_spreads=0, with_topk=False, with_devices=False, with_ports=False,
    with_cores=False, with_network=False, with_distinct=False,
    with_step_penalties=False, with_preferred=False,
)


class KernelIn(NamedTuple):
    """Device-side planes for one (eval, task group). All arrays."""

    # cluster planes (f32/i32/bool over padded node axis)
    cap_cpu: jnp.ndarray
    cap_mem: jnp.ndarray
    cap_disk: jnp.ndarray
    free_cores: jnp.ndarray
    shares_per_core: jnp.ndarray
    free_dyn: jnp.ndarray
    # eval planes
    base_mask: jnp.ndarray
    used_cpu: jnp.ndarray
    used_mem: jnp.ndarray
    used_disk: jnp.ndarray
    used_cores: jnp.ndarray
    used_mbits: jnp.ndarray
    avail_mbits: jnp.ndarray
    port_conflict: jnp.ndarray       # bool[N]: ask reserved port already used
    dev_free: jnp.ndarray            # f32[N, MAX_DEV_REQS]
    dev_aff_score: jnp.ndarray       # f32[N]
    has_dev_affinity: jnp.ndarray    # bool scalar
    job_tg_count: jnp.ndarray        # i32[N]
    penalty: jnp.ndarray             # bool[N]
    aff_score: jnp.ndarray           # f32[N]
    # i32[N]: seeded tie-break permutation. The programs read it once a
    # launch, for each node's rank in it (_inv); a step breaks ties by
    # that rank plane (_pick), never by a permuted copy of the scores
    node_perm: jnp.ndarray
    # per-step planes (placement axis K): rescheduled allocs penalize
    # their previous node(s) (rank.go:630 SetPenaltyNodes is per-Select)
    # and sticky/preferred placements pin a node (stack.go:120-139)
    step_penalty: jnp.ndarray        # i32[K, MAX_PENALTY_NODES], -1 pad
    step_preferred: jnp.ndarray      # i32[K], -1 none
    # distinct_hosts enforcement inside the scan (feasible.go:526):
    # job-level forbids co-location with any of the job's allocs,
    # tg-level with the same task group's
    job_any_count: jnp.ndarray       # i32[N] job allocs on node (any tg)
    distinct_hosts_job: jnp.ndarray  # bool scalar
    distinct_hosts_tg: jnp.ndarray   # bool scalar
    # spreads, stacked [S, ...]
    spread_active: jnp.ndarray       # bool[S]
    spread_even: jnp.ndarray         # bool[S]
    spread_weight: jnp.ndarray       # f32[S]
    spread_bucket: jnp.ndarray       # i32[S, N]
    spread_counts: jnp.ndarray       # f32[S, B]
    spread_desired: jnp.ndarray      # f32[S, B]
    # ask scalars
    ask_cpu: jnp.ndarray
    ask_mem: jnp.ndarray
    ask_disk: jnp.ndarray
    ask_cores: jnp.ndarray
    ask_dyn_ports: jnp.ndarray
    ask_has_reserved_ports: jnp.ndarray  # bool scalar
    ask_dev: jnp.ndarray             # f32[MAX_DEV_REQS]
    ask_mbits: jnp.ndarray
    desired_count: jnp.ndarray       # i32 scalar (anti-affinity denominator)
    algorithm_spread: jnp.ndarray    # bool scalar: ScoreFitSpread mode
    n_steps: jnp.ndarray             # i32 scalar: real placements wanted


#: rank of each KernelIn leaf in the SINGLE-problem (unbatched) layout.
#: The joint wave kernel accepts leaves either unbatched (shared by
#: every member — e.g. the cluster capacity planes and a wave's common
#: snapshot utilization) or stacked with a leading member axis; a leaf
#: whose rank equals the entry here +1 is batched. Shipping shared
#: planes once instead of B times is what keeps wave upload bytes flat
#: in wave size.
KIN_UNBATCHED_RANKS = KernelIn(
    cap_cpu=1, cap_mem=1, cap_disk=1, free_cores=1, shares_per_core=1,
    free_dyn=1, base_mask=1, used_cpu=1, used_mem=1, used_disk=1,
    used_cores=1, used_mbits=1, avail_mbits=1, port_conflict=1,
    dev_free=2, dev_aff_score=1, has_dev_affinity=0, job_tg_count=1,
    penalty=1, aff_score=1, node_perm=1, step_penalty=2,
    step_preferred=1, job_any_count=1, distinct_hosts_job=0,
    distinct_hosts_tg=0, spread_active=1, spread_even=1, spread_weight=1,
    spread_bucket=2, spread_counts=2, spread_desired=2, ask_cpu=0,
    ask_mem=0, ask_disk=0, ask_cores=0, ask_dyn_ports=0,
    ask_has_reserved_ports=0, ask_dev=1, ask_mbits=0, desired_count=0,
    algorithm_spread=0, n_steps=0,
)


class KernelOut(NamedTuple):
    chosen: jnp.ndarray          # i32[K]: node row per placement (-1 none)
    scores: jnp.ndarray          # f32[K]: final normalized score
    found: jnp.ndarray           # bool[K]
    topk_idx: jnp.ndarray        # i32[K, TOPK]
    topk_scores: jnp.ndarray     # f32[K, TOPK]
    # metrics from the first step's masks (AllocMetric inputs)
    nodes_evaluated: jnp.ndarray     # i32: base-eligible nodes
    nodes_feasible: jnp.ndarray      # i32: passed all resource checks
    exhausted_cpu: jnp.ndarray
    exhausted_mem: jnp.ndarray
    exhausted_disk: jnp.ndarray
    exhausted_ports: jnp.ndarray
    exhausted_devices: jnp.ndarray
    exhausted_cores: jnp.ndarray


class LaunchOrigin(NamedTuple):
    """Who asks for a launch: what the scheduler hands the launcher
    beside the tensors, so that the launch record (the ``wave.launch``
    span, ``wave_stats``) can say whom the device placed, against which
    state. Never read by a program."""

    eval_id: str
    state_index: int     # index of the snapshot the tensors were built from
    steps: int           # real placement steps (``k_steps`` is their bucket)
    relaunch: bool       # the scheduler is placing this evaluation again


def features_key(f: KernelFeatures) -> str:
    """``n_spreads=1,with_topk,with_shuffle``: the set features, for a
    launch record."""
    return ",".join(
        name if value is True else f"{name}={value}"
        for name, value in zip(f._fields, f) if value)


def real_steps(k_steps: list, origins: list) -> list:
    """Each member's real placement steps: what its origin says, or its
    step bucket where the caller gave none."""
    return [o.steps if o is not None else k
            for o, k in zip(origins, k_steps)]


def launch_attrs(seq: int, k_steps: list, origins: Optional[list],
                 deadline_fired: bool = False) -> dict:
    """A launch record's attributes that are known before the launch is
    assembled (docs/TELEMETRY.md): the members in the order the device
    program places them. The launcher adds ``program``, ``slots``,
    ``padded_steps``, ``executed_steps`` and ``features`` once it has
    routed the launch."""
    origins = origins or [None] * len(k_steps)
    return {
        "seq": seq,
        "members": len(k_steps),
        "evals": [o.eval_id if o is not None else "" for o in origins],
        "steps": real_steps(k_steps, origins),
        "state_index": [o.state_index if o is not None else -1
                        for o in origins],
        "relaunch": [o is not None and o.relaunch for o in origins],
        "deadline": deadline_fired,
    }


def _feasible(kin: KernelIn, st, f: KernelFeatures) -> tuple:
    """Resource-fit mask planes for the current carry state."""
    true_plane = jnp.ones_like(kin.base_mask)
    free_cpu = kin.cap_cpu - st["used_cpu"]
    free_mem = kin.cap_mem - st["used_mem"]
    free_disk = kin.cap_disk - st["used_disk"]
    # Optional dimensions apply only when the ask requests them — the
    # reference checks bandwidth/ports/devices/cores inside the assign
    # paths it only enters for a non-empty ask (rank.go:270-492), so a
    # node overcommitted on a dimension the ask doesn't use stays
    # feasible. This also makes the lean variants exactly equivalent.
    if f.with_cores:
        ask_cpu_total = (
            kin.ask_cpu + kin.ask_cores.astype(jnp.float32) * kin.shares_per_core
        )
        fit_cores = (kin.ask_cores <= 0) | (
            (kin.free_cores - st["used_cores"]) >= kin.ask_cores
        )
    else:
        ask_cpu_total = kin.ask_cpu
        fit_cores = true_plane
    fit_cpu = free_cpu >= ask_cpu_total
    fit_mem = free_mem >= kin.ask_mem
    fit_disk = free_disk >= kin.ask_disk
    if f.with_ports:
        fit_dyn = (kin.ask_dyn_ports <= 0) | (st["free_dyn"] >= kin.ask_dyn_ports)
        fit_ports = ~(st["port_conflict"] & kin.ask_has_reserved_ports) & fit_dyn
    else:
        fit_ports = true_plane
    if f.with_devices:
        fit_dev = jnp.all(
            (kin.ask_dev[None, :] <= 0) | (st["dev_free"] >= kin.ask_dev[None, :]),
            axis=1,
        )
    else:
        fit_dev = true_plane
    if f.with_network:
        fit_bw = (kin.ask_mbits <= 0) | (
            (st["used_mbits"] + kin.ask_mbits) <= kin.avail_mbits
        )
    else:
        fit_bw = true_plane
    if f.with_distinct:
        distinct_ok = ~(
            (kin.distinct_hosts_job & (st["job_any_count"] > 0))
            | (kin.distinct_hosts_tg & (st["job_tg_count"] > 0))
        )
    else:
        distinct_ok = true_plane
    feasible = (
        kin.base_mask
        & fit_cpu & fit_mem & fit_disk & fit_cores
        & fit_ports & fit_dev & fit_bw & distinct_ok
    )
    return feasible, ask_cpu_total, dict(
        fit_cpu=fit_cpu, fit_mem=fit_mem, fit_disk=fit_disk,
        fit_cores=fit_cores, fit_ports=fit_ports, fit_dev=fit_dev,
    )


def _score(kin: KernelIn, st, ask_cpu_total, penalty,
           f: KernelFeatures, spread_des_n=None) -> tuple:
    """Score planes + appended-mask normalization (rank.go semantics)."""
    util_cpu = st["used_cpu"] + ask_cpu_total
    util_mem = st["used_mem"] + kin.ask_mem

    # computeFreePercentage (funcs.go:235) with zero-capacity guard
    fc = jnp.where(kin.cap_cpu > 0, 1.0 - util_cpu / kin.cap_cpu, 0.0)
    fm = jnp.where(kin.cap_mem > 0, 1.0 - util_mem / kin.cap_mem, 0.0)
    total = jnp.power(10.0, fc) + jnp.power(10.0, fm)
    binpack = jnp.clip(20.0 - total, 0.0, 18.0)        # funcs.go:259
    spreadfit = jnp.clip(total - 2.0, 0.0, 18.0)       # funcs.go:286
    fit = jnp.where(kin.algorithm_spread, spreadfit, binpack) / 18.0

    # plane sum with per-plane appended masks (ScoreNormalizationIterator
    # averages only appended scores, rank.go:764)
    score_sum = fit
    nplanes = jnp.ones_like(fit)

    # device affinity (rank.go:549-554): appended when the ask has device
    # affinities at all
    if f.with_devices:
        dev_on = kin.has_dev_affinity
        score_sum = score_sum + jnp.where(dev_on, kin.dev_aff_score, 0.0)
        nplanes = nplanes + jnp.where(dev_on, 1.0, 0.0)

    # job anti-affinity (rank.go:588-607)
    collisions = st["job_tg_count"].astype(jnp.float32)
    denom = jnp.maximum(kin.desired_count.astype(jnp.float32), 1.0)
    anti = -(collisions + 1.0) / denom
    anti_on = collisions > 0
    score_sum = score_sum + jnp.where(anti_on, anti, 0.0)
    nplanes = nplanes + anti_on.astype(jnp.float32)

    # rescheduling penalty (rank.go:655-663)
    score_sum = score_sum + jnp.where(penalty, -1.0, 0.0)
    nplanes = nplanes + penalty.astype(jnp.float32)

    # node affinity (rank.go:730-745): appended where nonzero
    aff_on = kin.aff_score != 0.0
    score_sum = score_sum + jnp.where(aff_on, kin.aff_score, 0.0)
    nplanes = nplanes + aff_on.astype(jnp.float32)

    # spread (spread.go:116-245)
    if f.n_spreads > 0:
        spread_total = _spread_score(kin, st, spread_des_n, f.n_spreads)
        spread_on = spread_total != 0.0
        score_sum = score_sum + jnp.where(spread_on, spread_total, 0.0)
        nplanes = nplanes + spread_on.astype(jnp.float32)

    return score_sum / nplanes


def _inv(perm: jnp.ndarray) -> jnp.ndarray:
    """Rank of each node in a tie-break permutation: ``inv[perm[r]] =
    r``, for an ``[N]`` permutation or, row by row, a stacked
    ``[B, N]``. One scatter a launch, outside the scan."""
    def inv(p):
        return jnp.zeros_like(p).at[p].set(
            jnp.arange(p.shape[0], dtype=p.dtype))

    return jax.vmap(inv)(perm) if jnp.ndim(perm) == 2 else inv(perm)


def _pick(masked: jnp.ndarray, rank: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The step's choice over the node axis. Without a rank plane the
    first best score, ``argmax(masked)``. With ``rank = _inv(perm)``,
    among the nodes that hold the best score the one the permutation
    meets first (shuffleNodes util.go:464): the node
    ``perm[argmax(masked[perm])]`` names, taken by two reductions and
    no gather (a gather of 16,384 single elements was 116 us of every
    172 us step on the v5e: PERF.md, PR 30). ``rank`` is a permutation,
    so its least value among the best is one node, and with every node
    masked out both forms name ``perm[0]``."""
    if rank is None:
        return jnp.argmax(masked)
    n = masked.shape[0]
    return jnp.argmin(jnp.where(masked == jnp.max(masked), rank, n))


def _spread_node_planes(kin: KernelIn, n_spreads: int) -> tuple:
    """The two bucket tables seen from the node axis, derived once per
    launch: ``cnt_n[s, n] = spread_counts[s, spread_bucket[s, n]]`` and
    ``des_n`` likewise from ``spread_desired`` (both f32[S, N]).

    One elementwise pass over [S, N] per bucket: a wave of B members
    holds B x S x N values at any time, never a bucket axis beside the
    node axis. A bucket-less node (``spread_bucket < 0``) keeps 0 and
    -1, which nothing uses: it scores the missing penalty and no
    placement bumps it."""
    sb = kin.spread_bucket[:n_spreads]
    counts = kin.spread_counts[:n_spreads]
    desired = kin.spread_desired[:n_spreads]

    def bucket_pass(k, planes):
        cnt_n, des_n = planes
        hit = sb == k
        return (jnp.where(hit, counts[:, k, None], cnt_n),
                jnp.where(hit, desired[:, k, None], des_n))

    blank = jnp.zeros(sb.shape, jnp.float32)
    return jax.lax.fori_loop(0, SPREAD_BUCKETS, bucket_pass,
                             (blank, blank - 1.0))


def _spread_score(kin: KernelIn, st, des_n, n_spreads: int) -> jnp.ndarray:
    """Sum of per-stanza spread boosts for every node.

    A node's boost is a function of the count of its OWN bucket and of
    a few per-stanza scalars, so the scan carries that count on the
    node axis (``st["spread_cnt_n"]``, f32[S, N], beside the bucket
    table ``st["spread_counts"]`` that yields the scalars) and the
    score is elementwise over [N]: nothing in a step has both a node
    axis and a bucket axis. ``des_n`` is the desired count of each
    node's bucket (``_spread_node_planes``)."""
    n = kin.cap_cpu.shape[0]
    total = jnp.zeros(n, jnp.float32)
    counts = st["spread_counts"]   # [S, B]
    cnt_n = st["spread_cnt_n"]     # [S, N]
    for s in range(n_spreads):     # static unroll, S is tiny
        counts_b = counts[s]                     # f32[B]
        cnt = cnt_n[s]                           # f32[N]
        # -- desired-count path (spread.go:158-183): usedCount+1 --
        des = des_n[s]                           # f32[N], -1 = even mode
        desired = jnp.where(
            des > 0.0,
            ((des - (cnt + 1.0)) / des) * kin.spread_weight[s],
            -1.0,
        )
        # -- even-spread path (spread.go evenSpreadScoreBoost :193) --
        present = counts_b > 0.0
        any_alloc = jnp.any(present)
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
        stanza = jnp.where(kin.spread_even[s], even, desired)
        # bucket-less nodes score the missing penalty instead
        stanza = jnp.where(kin.spread_bucket[s] < 0, -1.0, stanza)
        total = total + jnp.where(kin.spread_active[s], stanza, 0.0)
    return total


def place_taskgroup(
    kin: KernelIn, k_steps: int, features: KernelFeatures = FULL_FEATURES
) -> KernelOut:
    """Place up to ``k_steps`` allocations of one task group.

    Each scan step: mask -> score -> argmax -> deduct chosen node's
    planes. Steps past ``kin.n_steps`` are inactive (static padding).
    ``features`` statically removes planes the ask does not use.
    """
    n = kin.cap_cpu.shape[0]
    f = features

    init = dict(
        used_cpu=kin.used_cpu,
        used_mem=kin.used_mem,
        used_disk=kin.used_disk,
        job_tg_count=kin.job_tg_count,
    )
    if f.with_cores:
        init["used_cores"] = kin.used_cores
    if f.with_network:
        init["used_mbits"] = kin.used_mbits
    if f.with_ports:
        init["free_dyn"] = kin.free_dyn
        init["port_conflict"] = kin.port_conflict
    if f.with_devices:
        init["dev_free"] = kin.dev_free
    if f.with_distinct:
        init["job_any_count"] = kin.job_any_count
    spread_des_n = None
    if f.n_spreads > 0:
        init["spread_counts"] = kin.spread_counts
        init["spread_cnt_n"], spread_des_n = _spread_node_planes(
            kin, f.n_spreads)

    # metrics from the initial state (one extra mask pass, outside scan)
    feas0, _, dims0 = _feasible(kin, init, f)
    base_i = kin.base_mask
    exhausted = lambda fit: jnp.sum(base_i & ~fit).astype(jnp.int32)  # noqa: E731

    iota = jnp.arange(n, dtype=jnp.int32)
    rank = _inv(kin.node_perm) if f.with_shuffle else None

    def step(st, i):
        feasible, ask_cpu_total, _ = _feasible(kin, st, f)
        # per-step penalty node ids OR'd into the eval-level plane
        penalty = kin.penalty
        if f.with_step_penalties:
            pen_ids = kin.step_penalty[i]                   # i32[P]
            step_pen = jnp.any(iota[:, None] == pen_ids[None, :], axis=1)
            penalty = penalty | step_pen
        final = _score(kin, st, ask_cpu_total, penalty, f, spread_des_n)
        active = i < kin.n_steps
        masked = jnp.where(feasible & active, final, NEG_INF)
        # equal scores resolve in permutation order where the
        # evaluation shuffles, by the rank plane (_pick)
        best = _pick(masked, rank)
        # preferred-node pin: take it when feasible (stack.go preferred-
        # source select), else fall back to the global argmax
        if f.with_preferred:
            pref = kin.step_preferred[i]
            pref_ok = (pref >= 0) & feasible[jnp.clip(pref, 0, n - 1)] & active
            idx = jnp.where(pref_ok, jnp.clip(pref, 0, n - 1), best)
        else:
            idx = best
        found = masked[idx] > NEG_INF / 2

        if f.with_topk:
            topv, topi = jax.lax.top_k(masked, TOPK)
        else:
            topv = jnp.full(TOPK, NEG_INF)
            topi = jnp.zeros(TOPK, jnp.int32)

        # deduct the chosen node's planes (only when found & active)
        upd = (found & active).astype(jnp.float32)
        updi = (found & active).astype(jnp.int32)
        one = jax.nn.one_hot(idx, n, dtype=jnp.float32) * upd
        onei = jax.nn.one_hot(idx, n, dtype=jnp.int32) * updi
        st2 = dict(
            used_cpu=st["used_cpu"] + one * ask_cpu_total,
            used_mem=st["used_mem"] + one * kin.ask_mem,
            used_disk=st["used_disk"] + one * kin.ask_disk,
            job_tg_count=st["job_tg_count"] + onei,
        )
        if f.with_cores:
            st2["used_cores"] = st["used_cores"] + onei * kin.ask_cores
        if f.with_network:
            st2["used_mbits"] = st["used_mbits"] + onei * kin.ask_mbits
        if f.with_ports:
            st2["free_dyn"] = st["free_dyn"] - onei * kin.ask_dyn_ports
            # same reserved ports collide on the chosen node next step
            st2["port_conflict"] = st["port_conflict"] | (
                (one > 0) & kin.ask_has_reserved_ports
            )
        if f.with_devices:
            st2["dev_free"] = st["dev_free"] - one[:, None] * kin.ask_dev[None, :]
        if f.with_distinct:
            st2["job_any_count"] = st["job_any_count"] + onei
        if f.n_spreads > 0:
            st2["spread_counts"], st2["spread_cnt_n"] = _bump_spread(
                kin, st["spread_counts"], st["spread_cnt_n"], idx,
                found & active, f.n_spreads)
        out = (
            jnp.where(found, idx, -1).astype(jnp.int32),
            jnp.where(found, masked[idx], 0.0),
            found & active,
            topi.astype(jnp.int32),
            topv,
        )
        return st2, out

    _, (chosen, scores, found, topk_idx, topk_scores) = jax.lax.scan(
        step, init, jnp.arange(k_steps)
    )

    return KernelOut(
        chosen=chosen,
        scores=scores,
        found=found,
        topk_idx=topk_idx,
        topk_scores=topk_scores,
        nodes_evaluated=jnp.sum(base_i).astype(jnp.int32),
        nodes_feasible=jnp.sum(feas0).astype(jnp.int32),
        exhausted_cpu=exhausted(dims0["fit_cpu"]),
        exhausted_mem=exhausted(dims0["fit_mem"]),
        exhausted_disk=exhausted(dims0["fit_disk"]),
        exhausted_ports=exhausted(dims0["fit_ports"]),
        exhausted_devices=exhausted(dims0["fit_dev"]),
        exhausted_cores=exhausted(dims0["fit_cores"]),
    )


def _bump_spread(kin: KernelIn, counts, cnt_n, idx, placed,
                 n_spreads: int) -> tuple:
    """A placement on node ``idx``: under every active stanza the
    chosen node's bucket ``b*`` gains one, in the bucket table
    (``counts[s, b*]``, a one-hot over the buckets alone) and on the
    node plane (``cnt_n[s, n]`` for every node of bucket ``b*``).
    Nothing moves when nothing was placed (``placed`` false) or the
    chosen node has no bucket value."""
    sb = kin.spread_bucket[:n_spreads]            # i32[S, N]
    # one scalar read per stanza: the column ``sb[:, idx]`` makes the
    # TPU compiler lay every [B, S, N] plane out with S minor-most, 30
    # us a step of copies at 16,384 nodes (PERF.md, PR 27)
    b_star = jnp.stack([sb[s, idx] for s in range(n_spreads)])  # i32[S]
    on = kin.spread_active[:n_spreads] & (b_star >= 0) & placed
    row = jax.nn.one_hot(b_star, SPREAD_BUCKETS, dtype=jnp.float32)
    counts = counts.at[:n_spreads].add(jnp.where(on[:, None], row, 0.0))
    same = (sb == b_star[:, None]) & on[:, None]
    return counts, cnt_n + same.astype(jnp.float32)


place_taskgroup_jit = jax.jit(place_taskgroup, static_argnums=(1, 2))


def place_taskgroup_topk(
    kin: KernelIn, k_steps: int, features: KernelFeatures = FULL_FEATURES,
    n_candidates: int = 0,
) -> tuple:
    """Candidate-set placement: full-width scoring ONCE, sequential
    deduction over a top-K candidate subset.

    The full kernel recomputes feasibility + scores for every node at
    every scan step — O(N * k). But with the binpack fit function
    (funcs.go:259) a placement only changes the CHOSEN node's planes,
    and every score-mutating plane (utilization, job anti-affinity
    counts, penalties) moves non-chosen scores DOWN or not at all, so
    the (K+1)-th initial score upper-bounds everything outside the
    candidate set for the whole scan. One O(N log K) top_k then a
    K-wide scan gives identical placements — the tensor formulation of
    the reference's LimitIterator candidate bound (stack.go:84-91),
    with exact top-K candidates instead of log2(n) random ones.

    Validity: requires no spread stanzas (spread boosts can RAISE
    non-candidate scores) — callers gate on features.n_spreads == 0.
    The returned ``valid`` scalar is False when the bound was ever
    breached mid-scan (candidate max fell below the rest bound, e.g.
    under the cluster-wide spread fit function, or K exhausted); the
    caller must re-run the full kernel then.

    Returns (KernelOut, valid: bool scalar).
    """
    n = kin.cap_cpu.shape[0]
    f = features
    assert f.n_spreads == 0, "top-K path requires no spread stanzas"
    k_cand = n_candidates or min(n, max(2 * k_steps, k_steps + 8, TOPK))

    init = dict(
        used_cpu=kin.used_cpu,
        used_mem=kin.used_mem,
        used_disk=kin.used_disk,
        job_tg_count=kin.job_tg_count,
    )
    if f.with_cores:
        init["used_cores"] = kin.used_cores
    if f.with_network:
        init["used_mbits"] = kin.used_mbits
    if f.with_ports:
        init["free_dyn"] = kin.free_dyn
        init["port_conflict"] = kin.port_conflict
    if f.with_devices:
        init["dev_free"] = kin.dev_free
    if f.with_distinct:
        init["job_any_count"] = kin.job_any_count

    # ---- one full-width pass: metrics + initial scores ----
    feas0, ask_cpu_total0, dims0 = _feasible(kin, init, f)
    final0 = _score(kin, init, ask_cpu_total0, kin.penalty, f, None)
    masked0 = jnp.where(feas0, final0, NEG_INF)
    base_i = kin.base_mask
    exhausted = lambda fit: jnp.sum(base_i & ~fit).astype(jnp.int32)  # noqa: E731

    # approx_max_k is the TPU-fast selection (lax.top_k is orders
    # slower there); exactness is preserved by computing the rest
    # bound EXACTLY below — a recall miss that would have mattered
    # shows up as a bound breach and falls back to the full kernel
    _, cand_idx = jax.lax.approx_max_k(
        masked0, k_cand, recall_target=0.95)
    rest_max = jnp.max(masked0.at[cand_idx].set(NEG_INF))

    # preferred nodes must be selectable even when outside the top-K:
    # union them into the candidate set (duplicates are harmless --
    # duplicate rows share deductions via scatter-by-node below)
    if f.with_preferred:
        prefs = jnp.clip(kin.step_preferred[:k_steps], 0, n - 1)
        pref_valid = kin.step_preferred[:k_steps] >= 0
        cand_idx = jnp.concatenate([cand_idx, prefs])
        k_all = k_cand + k_steps
        cand_is_pref_pad = jnp.concatenate([
            jnp.zeros(k_cand, bool), ~pref_valid])
    else:
        k_all = k_cand
        cand_is_pref_pad = jnp.zeros(k_cand, bool)

    # tie-break decorrelation within the candidate set: the eval's
    # node permutation provides pseudo-random distinct keys per node,
    # so argsort of the gathered keys is a per-eval random candidate
    # order (shuffleNodes util.go:464, restricted to candidates)
    if f.with_shuffle:
        cand_perm = jnp.argsort(kin.node_perm[cand_idx]).astype(jnp.int32)
    else:
        cand_perm = jnp.arange(k_all, dtype=jnp.int32)

    # ---- gather candidate-width planes ----
    def g(x):
        return x[cand_idx]

    kin_c = KernelIn(
        cap_cpu=g(kin.cap_cpu), cap_mem=g(kin.cap_mem),
        cap_disk=g(kin.cap_disk), free_cores=g(kin.free_cores),
        shares_per_core=g(kin.shares_per_core), free_dyn=g(kin.free_dyn),
        base_mask=g(kin.base_mask) & ~cand_is_pref_pad,
        used_cpu=g(kin.used_cpu), used_mem=g(kin.used_mem),
        used_disk=g(kin.used_disk), used_cores=g(kin.used_cores),
        used_mbits=g(kin.used_mbits), avail_mbits=g(kin.avail_mbits),
        port_conflict=g(kin.port_conflict), dev_free=g(kin.dev_free),
        dev_aff_score=g(kin.dev_aff_score),
        has_dev_affinity=kin.has_dev_affinity,
        job_tg_count=g(kin.job_tg_count), penalty=g(kin.penalty),
        aff_score=g(kin.aff_score),
        node_perm=cand_perm,
        step_penalty=kin.step_penalty, step_preferred=kin.step_preferred,
        job_any_count=g(kin.job_any_count),
        distinct_hosts_job=kin.distinct_hosts_job,
        distinct_hosts_tg=kin.distinct_hosts_tg,
        spread_active=kin.spread_active, spread_even=kin.spread_even,
        spread_weight=kin.spread_weight,
        spread_bucket=kin.spread_bucket[:, :1],
        spread_counts=kin.spread_counts,
        spread_desired=kin.spread_desired,
        ask_cpu=kin.ask_cpu, ask_mem=kin.ask_mem, ask_disk=kin.ask_disk,
        ask_cores=kin.ask_cores, ask_dyn_ports=kin.ask_dyn_ports,
        ask_has_reserved_ports=kin.ask_has_reserved_ports,
        ask_dev=kin.ask_dev, ask_mbits=kin.ask_mbits,
        desired_count=kin.desired_count,
        algorithm_spread=kin.algorithm_spread,
        n_steps=kin.n_steps,
    )

    # duplicate candidate rows (a preferred node also in the top-K)
    # must share deductions: scatter per-step deltas by NODE id and
    # re-gather. same_node[i, j] = cand i and cand j are one node.
    same_node = cand_idx[:, None] == cand_idx[None, :]   # bool[K', K']
    share = same_node.astype(jnp.float32)
    sharei = same_node.astype(jnp.int32)

    init_c = dict(
        used_cpu=kin_c.used_cpu, used_mem=kin_c.used_mem,
        used_disk=kin_c.used_disk, job_tg_count=kin_c.job_tg_count,
    )
    if f.with_cores:
        init_c["used_cores"] = kin_c.used_cores
    if f.with_network:
        init_c["used_mbits"] = kin_c.used_mbits
    if f.with_ports:
        init_c["free_dyn"] = kin_c.free_dyn
        init_c["port_conflict"] = kin_c.port_conflict
    if f.with_devices:
        init_c["dev_free"] = kin_c.dev_free
    if f.with_distinct:
        init_c["job_any_count"] = kin_c.job_any_count

    rank_c = _inv(cand_perm) if f.with_shuffle else None

    def step(carry, i):
        st, ok = carry
        feasible, ask_cpu_total, _ = _feasible(kin_c, st, f)
        penalty = kin_c.penalty
        if f.with_step_penalties:
            pen_ids = kin_c.step_penalty[i]
            node_ids = cand_idx
            step_pen = jnp.any(
                node_ids[:, None] == pen_ids[None, :], axis=1)
            penalty = penalty | step_pen
        final = _score(kin_c, st, ask_cpu_total, penalty, f, None)
        active = i < kin_c.n_steps
        masked = jnp.where(feasible & active, final, NEG_INF)
        best = _pick(masked, rank_c)
        if f.with_preferred:
            pref = kin_c.step_preferred[i]
            # the preferred node's candidate row: k_cand + i by layout
            pref_row = k_cand + i
            pref_ok = (pref >= 0) & feasible[pref_row] & active
            idx = jnp.where(pref_ok, pref_row, best)
        else:
            pref_ok = jnp.asarray(False)
            idx = best
        found = masked[idx] > NEG_INF / 2
        # bound check: if the best candidate fell below what the rest
        # of the cluster could offer, the candidate set is invalid.
        # Preferred picks are exempt — they are taken regardless of
        # score in the full kernel too, so the bound is irrelevant
        ok = ok & (~active | ~found | pref_ok | (masked[idx] >= rest_max))

        if f.with_topk:
            topv, topi = jax.lax.top_k(masked, TOPK)
            topi = cand_idx[topi]
        else:
            topv = jnp.full(TOPK, NEG_INF)
            topi = jnp.zeros(TOPK, jnp.int32)

        upd = (found & active).astype(jnp.float32)
        updi = (found & active).astype(jnp.int32)
        one = share[idx] * upd          # all rows of the chosen NODE
        onei = sharei[idx] * updi
        st2 = dict(
            used_cpu=st["used_cpu"] + one * ask_cpu_total,
            used_mem=st["used_mem"] + one * kin_c.ask_mem,
            used_disk=st["used_disk"] + one * kin_c.ask_disk,
            job_tg_count=st["job_tg_count"] + onei,
        )
        if f.with_cores:
            st2["used_cores"] = st["used_cores"] + onei * kin_c.ask_cores
        if f.with_network:
            st2["used_mbits"] = st["used_mbits"] + onei * kin_c.ask_mbits
        if f.with_ports:
            st2["free_dyn"] = st["free_dyn"] - onei * kin_c.ask_dyn_ports
            st2["port_conflict"] = st["port_conflict"] | (
                (one > 0) & kin_c.ask_has_reserved_ports)
        if f.with_devices:
            st2["dev_free"] = st["dev_free"] - one[:, None] * kin_c.ask_dev[None, :]
        if f.with_distinct:
            st2["job_any_count"] = st["job_any_count"] + onei
        out = (
            jnp.where(found, cand_idx[idx], -1).astype(jnp.int32),
            jnp.where(found, masked[idx], 0.0),
            found & active,
            topi.astype(jnp.int32),
            topv,
        )
        return (st2, ok), out

    # candidate-width steps are tiny; full unroll removes the scan's
    # per-step sequencing overhead (the remaining cost driver)
    (_, ok), (chosen, scores, found, topk_idx, topk_scores) = jax.lax.scan(
        step, (init_c, jnp.asarray(True)), jnp.arange(k_steps),
        unroll=True,
    )

    out = KernelOut(
        chosen=chosen, scores=scores, found=found,
        topk_idx=topk_idx, topk_scores=topk_scores,
        nodes_evaluated=jnp.sum(base_i).astype(jnp.int32),
        nodes_feasible=jnp.sum(feas0).astype(jnp.int32),
        exhausted_cpu=exhausted(dims0["fit_cpu"]),
        exhausted_mem=exhausted(dims0["fit_mem"]),
        exhausted_disk=exhausted(dims0["fit_disk"]),
        exhausted_ports=exhausted(dims0["fit_ports"]),
        exhausted_devices=exhausted(dims0["fit_dev"]),
        exhausted_cores=exhausted(dims0["fit_cores"]),
    )
    # a run that failed placements while rest_max was still beatable is
    # also invalid (candidates exhausted but the wider cluster might
    # fit); detect: any inactive-step-before-n_steps with rest feasible
    missing = jnp.any(
        (jnp.arange(k_steps) < kin.n_steps) & ~found)
    ok = ok & (~missing | (rest_max <= NEG_INF / 2))
    return out, ok


place_taskgroup_topk_jit = jax.jit(
    place_taskgroup_topk, static_argnums=(1, 2, 3)
)



def _resident_kin(kin: KernelIn) -> KernelIn:
    """Swap shared-plane leaves for their device-resident twins
    (tensors/device_state.py) so the dispatch uploads only genuinely
    per-eval planes. Substitution is ALL-OR-NOTHING across every
    sharing group: the unprofiled path's jit-cache signature is then
    exactly one of TWO layouts — all-host, or all-shared-resident —
    both populated by the AOT warmup (ops/warmup._call_both_
    placements). A partially-resident eval (say, forked job planes)
    falls back to the all-host signature instead of compiling an
    unwarmed commitment combination on the steady hot path."""
    from nomad_tpu.parallel.coalesce import (
        _JOB_SHAREABLE_FIELDS,
        _NEUTRAL_SHAREABLE_FIELDS,
        _SHAREABLE_FIELDS,
    )
    from nomad_tpu.tensors.device_state import default_device_state

    subs = {}
    for group in (_SHAREABLE_FIELDS, _NEUTRAL_SHAREABLE_FIELDS,
                  _JOB_SHAREABLE_FIELDS):
        for f in group:
            dev = default_device_state.lookup(
                getattr(kin, f),
                frozen_ok=group is not _SHAREABLE_FIELDS)
            if dev is None:
                return kin
            subs[f] = dev
    return kin._replace(**subs)


def default_kernel_launch(kin: KernelIn, k_steps: int,
                          features: KernelFeatures,
                          origin: Optional[LaunchOrigin] = None) -> KernelOut:
    """The stack's direct (non-coalesced) dispatch: candidate-set fast
    path when its preconditions hold, full-width kernel otherwise or on
    a bound breach. Each is a device launch of its own, with its own
    launch record."""
    features = canonical_features(features)
    n_pad = int(np.asarray(kin.cap_cpu).shape[0])
    kin = _resident_kin(kin)
    key = (n_pad, k_steps, features)
    if features.n_spreads == 0 and not bool(kin.algorithm_spread):
        out = _lone_launch("single_topk", place_taskgroup_topk_jit, kin,
                           k_steps, features, key, origin)
        if out is not None:
            return out
    return _lone_launch("single_full", place_taskgroup_jit, kin, k_steps,
                        features, key, origin)


def _lone_launch(program: str, jit_fn, kin: KernelIn, k_steps: int,
                 features: KernelFeatures, key: tuple,
                 origin: Optional[LaunchOrigin]) -> Optional[KernelOut]:
    """One evaluation's launch outside any wave, recorded like a wave's
    (parallel/coalesce.launch_wave): ``wave.launch`` over
    ``kernel.dispatch`` / ``kernel.execute`` (telemetry/kernel_profile.py:
    the single-eval path compiles its own (node-pad, step-bucket,
    features) variants, and recompiles must not hide outside the wave
    accounting) and ``kernel.d2h``. The planes the scheduler walks next
    come back as numpy; the top-k planes stay on the device until the
    plan window's score_meta drain. None where the candidate-set
    program says its bound did not hold."""
    from nomad_tpu.parallel.coalesce import timed_call, wave_stats
    from nomad_tpu.telemetry.kernel_profile import launch_seq
    from nomad_tpu.telemetry.trace import tracer

    steps = origin.steps if origin is not None else int(kin.n_steps)
    wave_stats.observe_lone(
        steps, k_steps, origin is not None and origin.relaunch)
    seq = next(launch_seq)
    attrs = None
    if tracer.enabled:
        attrs = dict(
            launch_attrs(seq, [steps], [origin]), program=program,
            slots=1, padded_steps=k_steps, executed_steps=k_steps,
            features=features_key(features))
    with tracer.span("wave.launch", attrs=attrs):
        out = timed_call(program, jit_fn, (kin,), (k_steps, features),
                         key, jit_fn=jit_fn)
        with tracer.span("kernel.d2h") as sp:
            if program == "single_topk":
                out, ok = out
                if not bool(ok):
                    return None
            host = {f: np.asarray(x) for f, x in zip(out._fields, out)
                    if f not in ("topk_idx", "topk_scores")}
            sp.set(bytes=sum(a.nbytes for a in host.values()))
    return out._replace(**host)


class JointOut(NamedTuple):
    """Outputs of a joint wave: per-step placements + per-member metrics."""

    chosen: jnp.ndarray          # i32[T]
    scores: jnp.ndarray          # f32[T]
    found: jnp.ndarray           # bool[T]
    topk_idx: jnp.ndarray        # i32[T, TOPK]
    topk_scores: jnp.ndarray     # f32[T, TOPK]
    nodes_evaluated: jnp.ndarray     # i32[B]
    nodes_feasible: jnp.ndarray      # i32[B]
    exhausted_cpu: jnp.ndarray       # i32[B]
    exhausted_mem: jnp.ndarray
    exhausted_disk: jnp.ndarray
    exhausted_ports: jnp.ndarray
    exhausted_devices: jnp.ndarray
    exhausted_cores: jnp.ndarray
    # final shared-capacity carry: total resources the wave consumed
    # per node (lets a caller commit the wave as one scatter)
    a_cpu: jnp.ndarray               # f32[N]
    a_mem: jnp.ndarray               # f32[N]
    a_disk: jnp.ndarray              # f32[N]


def place_taskgroups_joint(
    kin: KernelIn,
    step_member: jnp.ndarray,
    step_local: jnp.ndarray,
    t_steps: int,
    features: KernelFeatures = FULL_FEATURES,
) -> JointOut:
    """Place a WAVE of task-group asks with a shared capacity carry.

    ``kin`` is a stacked KernelIn (leading member axis B). The outputs
    have ``t_steps`` placement steps; step t belongs to wave member
    ``step_member[t]`` (-1 = padding) at member-local placement index
    ``step_local[t]``. The loop runs the real steps alone (those of a
    member inside its ``n_steps``), in order; a padded step's row holds
    what an inert step writes, and costs the device nothing.

    This is the on-device form of the leader's serialized plan applier
    (nomad/plan_apply.go:71): every step's feasibility and score see
    the capacity consumed by ALL previous steps — including other
    members' — via shared accumulation planes (cpu/mem/disk, cores,
    bandwidth, dynamic-port counts, device counts). Job-local planes
    (anti-affinity counts, distinct-hosts counts, spread counts, the
    member's own reserved-port conflicts) stay per-member, because
    they only constrain the member's own job. Concurrently scheduled
    evaluations therefore cannot over-subscribe a node within a batch,
    which is what keeps the optimistic plan re-validation
    (plan_apply.go:644) from rejecting lockstep retries.

    Cross-member *identity* conflicts (the same reserved port number
    or the same reserved core id chosen by two members for one node)
    are not modeled on device — exact port/core assignment stays
    host-side and the applier's re-check catches the rare collision,
    exactly as it does between reference scheduler workers.
    """
    n = kin.cap_cpu.shape[-1]
    b = kin.n_steps.shape[0]       # n_steps is always member-stacked
    f = features

    def _bat(x, rank):
        """Ensure a leading member axis (carried leaves need one even
        when the wave shipped the leaf shared/unbatched — the broadcast
        happens ON DEVICE, costing HBM, not host-to-device bytes)."""
        if jnp.ndim(x) == rank + 1:
            return x
        return jnp.broadcast_to(x, (b,) + jnp.shape(x))

    zf = jnp.zeros(n, jnp.float32)
    zi = jnp.zeros(n, jnp.int32)
    init = dict(
        a_cpu=zf, a_mem=zf, a_disk=zf,
        job_tg_count=_bat(kin.job_tg_count, 1),     # [B, N]
    )
    if f.with_cores:
        init["a_cores"] = zi
    if f.with_network:
        init["a_mbits"] = zi
    if f.with_ports:
        init["a_dyn"] = zi
        init["port_conflict"] = _bat(kin.port_conflict, 1)   # [B, N]
    if f.with_devices:
        init["a_dev"] = jnp.zeros((n, kin.dev_free.shape[-1]), jnp.float32)
    if f.with_distinct:
        init["job_any_count"] = _bat(kin.job_any_count, 1)   # [B, N]
    in_axes = wave_in_axes(kin)
    spread_des_n = None
    if f.n_spreads > 0:
        init["spread_counts"] = _bat(kin.spread_counts, 2)   # [B, S, Bk]
        # per member, once per launch                         [B, S, N]
        init["spread_cnt_n"], spread_des_n = jax.vmap(
            lambda kin_m: _spread_node_planes(kin_m, f.n_spreads),
            in_axes=(in_axes,))(kin)

    iota = jnp.arange(n, dtype=jnp.int32)
    # [N] where the wave shares one permutation, else [B, N]
    rank = _inv(kin.node_perm) if f.with_shuffle else None
    rank_per_member = f.with_shuffle and rank.ndim == 2

    def member_view(st, m):
        """The member's single-problem (kin, st) as place_taskgroup
        sees it. Leaves shipped unbatched (shared by every member) are
        used as-is; stacked leaves index the member axis."""
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
        if f.with_cores:
            st_m["used_cores"] = kin_m.used_cores + st["a_cores"]
        if f.with_network:
            st_m["used_mbits"] = kin_m.used_mbits + st["a_mbits"]
        if f.with_ports:
            st_m["free_dyn"] = kin_m.free_dyn - st["a_dyn"]
            st_m["port_conflict"] = st["port_conflict"][m]
        if f.with_devices:
            st_m["dev_free"] = kin_m.dev_free - st["a_dev"]
        if f.with_distinct:
            st_m["job_any_count"] = st["job_any_count"][m]
        if f.n_spreads > 0:
            st_m["spread_counts"] = st["spread_counts"][m]
            st_m["spread_cnt_n"] = st["spread_cnt_n"][m]
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
            pen_ids = kin_m.step_penalty[j]
            step_pen = jnp.any(iota[:, None] == pen_ids[None, :], axis=1)
            penalty = penalty | step_pen
        final = _score(kin_m, st_m, ask_cpu_total, penalty, f,
                       spread_des_n[m] if f.n_spreads > 0 else None)
        active = active_step & (j < kin_m.n_steps)
        masked = jnp.where(feasible & active, final, NEG_INF)
        best = _pick(masked, rank[m] if rank_per_member else rank)
        if f.with_preferred:
            pref = kin_m.step_preferred[j]
            pref_ok = (pref >= 0) & feasible[jnp.clip(pref, 0, n - 1)] & active
            idx = jnp.where(pref_ok, jnp.clip(pref, 0, n - 1), best)
        else:
            idx = best
        found = masked[idx] > NEG_INF / 2

        if f.with_topk:
            topv, topi = jax.lax.top_k(masked, TOPK)
        else:
            topv = jnp.full(TOPK, NEG_INF)
            topi = jnp.zeros(TOPK, jnp.int32)

        upd = (found & active).astype(jnp.float32)
        updi = (found & active).astype(jnp.int32)
        one = jax.nn.one_hot(idx, n, dtype=jnp.float32) * upd
        onei = jax.nn.one_hot(idx, n, dtype=jnp.int32) * updi
        st2 = dict(
            a_cpu=st["a_cpu"] + one * ask_cpu_total,
            a_mem=st["a_mem"] + one * kin_m.ask_mem,
            a_disk=st["a_disk"] + one * kin_m.ask_disk,
            job_tg_count=st["job_tg_count"].at[m].add(onei),
        )
        if f.with_cores:
            st2["a_cores"] = st["a_cores"] + onei * kin_m.ask_cores
        if f.with_network:
            st2["a_mbits"] = st["a_mbits"] + onei * kin_m.ask_mbits
        if f.with_ports:
            st2["a_dyn"] = st["a_dyn"] + onei * kin_m.ask_dyn_ports
            st2["port_conflict"] = st["port_conflict"].at[m].set(
                st["port_conflict"][m]
                | ((one > 0) & kin_m.ask_has_reserved_ports)
            )
        if f.with_devices:
            st2["a_dev"] = st["a_dev"] + one[:, None] * kin_m.ask_dev[None, :]
        if f.with_distinct:
            st2["job_any_count"] = st["job_any_count"].at[m].add(onei)
        if f.n_spreads > 0:
            counts_m, cnt_n_m = _bump_spread(
                kin_m, st_m["spread_counts"], st_m["spread_cnt_n"], idx,
                found & active, f.n_spreads)
            st2["spread_counts"] = st["spread_counts"].at[m].set(counts_m)
            st2["spread_cnt_n"] = st["spread_cnt_n"].at[m].set(cnt_n_m)
        out = (
            jnp.where(found, idx, -1).astype(jnp.int32),
            jnp.where(found, masked[idx], 0.0),
            found & active,
            topi.astype(jnp.int32),
            topv,
        )
        return st2, out

    # Only the steps that place run: a member's own, inside its n_steps
    # (``active`` above), in layout order. Every other row keeps what an
    # inert step writes, so the loop's trip count is the wave's real
    # steps and not the padded bucket, and the outputs keep its shape.
    member_steps = jnp.asarray(kin.n_steps)[jnp.clip(step_member, 0, b - 1)]
    real = (step_member >= 0) & (step_local < member_steps)
    n_real = jnp.sum(real, dtype=jnp.int32)
    real_t = jnp.nonzero(real, size=t_steps, fill_value=0)[0]
    inert = (
        jnp.full(t_steps, -1, jnp.int32),
        jnp.zeros(t_steps, jnp.float32),
        jnp.zeros(t_steps, bool),
        # lax.top_k's ties go to the lower index: an all-NEG_INF row
        jnp.broadcast_to(jnp.arange(TOPK, dtype=jnp.int32) if f.with_topk
                         else jnp.zeros(TOPK, jnp.int32), (t_steps, TOPK)),
        jnp.full((t_steps, TOPK), NEG_INF, jnp.float32),
    )

    def real_step(i, carry):
        st, rows = carry
        t = real_t[i]
        st2, row = step(st, t)
        return st2, tuple(jax.lax.dynamic_update_index_in_dim(r, x, t, 0)
                          for r, x in zip(rows, row))

    st_final, (chosen, scores, found, topk_idx, topk_scores) = \
        jax.lax.fori_loop(0, n_real, real_step, (init, inert))

    return JointOut(
        chosen=chosen, scores=scores, found=found,
        topk_idx=topk_idx, topk_scores=topk_scores,
        **wave_member_metrics(kin, f),
        a_cpu=st_final["a_cpu"], a_mem=st_final["a_mem"],
        a_disk=st_final["a_disk"],
    )


def wave_member_metrics(kin: KernelIn, f: KernelFeatures) -> dict:
    """Each wave member's first-step metrics (AllocMetric inputs), from
    the pre-wave state, identical to the single-problem kernel's: the
    ``JointOut`` fields of ``FUSED_METRIC_FIELDS``, each [B]. Once a
    launch, outside the step loop."""

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
            ex(dims0["fit_cpu"]), ex(dims0["fit_mem"]), ex(dims0["fit_disk"]),
            ex(dims0["fit_ports"]), ex(dims0["fit_dev"]), ex(dims0["fit_cores"]),
        )

    return dict(zip(FUSED_METRIC_FIELDS, jax.vmap(
        member_metrics, in_axes=(wave_in_axes(kin),))(kin)))


def wave_in_axes(kin: KernelIn) -> KernelIn:
    """Which leaves of a stacked wave carry a member axis: 0 for those,
    None for a leaf the wave ships once (``jax.vmap``'s ``in_axes``
    over the members)."""
    return KernelIn(*[
        0 if jnp.ndim(x) == r + 1 else None
        for x, r in zip(kin, KIN_UNBATCHED_RANKS)
    ])


place_taskgroups_joint_jit = jax.jit(
    place_taskgroups_joint, static_argnums=(3, 4)
)


# ---------------------------------------------------------------------------
# The packed wave read-back of the mesh's fused program
# (parallel/sharded.fused_sharded_entry): everything the launcher
# fetches eagerly in ONE flat f32 buffer, so a fused sharded wave is
# one dispatch and one readback that rides the dispatch's own
# synchronization. The top-k planes stay separate device outputs —
# they are lazy (coalesce._WaveTopK) and drain in the plan window, off
# the wave-critical path. One-device waves run ``joint`` above (or its
# Pallas form, ops/joint_fused.py) and fetch its fields one by one.
# ---------------------------------------------------------------------------

#: JointOut metric fields in packed-segment order (8 x [B] after the
#: two [T] rows). Single source of truth for pack (device) and unpack
#: (host) — a drift here would hand members another member's metrics.
FUSED_METRIC_FIELDS = (
    "nodes_evaluated", "nodes_feasible",
    "exhausted_cpu", "exhausted_mem", "exhausted_disk",
    "exhausted_ports", "exhausted_devices", "exhausted_cores",
)


class FusedWaveOut(NamedTuple):
    """One fused wave's device outputs.

    ``packed`` is flat f32[2*T + 8*B]: ``[0:T)`` chosen (exact as f32
    — node ids are far below 2**24; ``found`` is NOT packed because
    it is definitionally ``chosen >= 0``), ``[T:2T)`` scores, then the
    eight B-wide metric segments in FUSED_METRIC_FIELDS order. 8T+32B
    bytes — strictly below the composite's eager fetch (9T+32B), so
    fusing never regresses d2h-per-wave."""

    packed: jnp.ndarray          # f32[2*T + 8*B]
    topk_idx: jnp.ndarray        # i32[T, TOPK]
    topk_scores: jnp.ndarray     # f32[T, TOPK]
    a_cpu: jnp.ndarray           # f32[N] final shared-capacity carry
    a_mem: jnp.ndarray           # f32[N]
    a_disk: jnp.ndarray          # f32[N]


def fused_wave_supported(f: KernelFeatures) -> bool:
    """Whether a wave's (canonical) feature union fits the fused
    sharded program's envelope. Ports, preemption penalties, preferred
    pins, distinct_hosts, shuffle, and top-k are all in (shuffle is
    ALWAYS on for live evals — scheduler/generic.py seeds it per
    eval, so excluding it would turn every live wave into a counted
    fallback). Spread stanzas and the device/core/bandwidth planes
    are out: rare in steady traffic and each would widen the fused
    signature lattice ~2x — on a mesh those waves run
    ``joint_sharded``, counted by ``fused_wave_stats``
    (parallel/coalesce.wave_program is the router)."""
    return (f.n_spreads == 0 and not f.with_devices
            and not f.with_cores and not f.with_network)


def pack_fused_wave(out: JointOut, t_steps: int, b: int) -> jnp.ndarray:
    """Pack a JointOut's eagerly-fetched planes into the flat f32
    buffer (device side; see FusedWaveOut.packed layout)."""
    parts = [out.chosen.astype(jnp.float32), out.scores]
    parts += [getattr(out, name).astype(jnp.float32)
              for name in FUSED_METRIC_FIELDS]
    return jnp.concatenate(parts)


def unpack_fused_wave(packed: np.ndarray, t_steps: int, b: int) -> dict:
    """Host-side inverse of ``pack_fused_wave``: the launcher's eager
    fetch dict (same keys as coalesce._JOINT_FETCH_FIELDS, same
    dtypes as the composite's per-field ``np.asarray`` fetch)."""
    flat = np.asarray(packed)
    chosen = flat[:t_steps].astype(np.int32)
    host = {
        "chosen": chosen,
        "scores": flat[t_steps:2 * t_steps].astype(np.float32),
        "found": chosen >= 0,
    }
    off = 2 * t_steps
    for name in FUSED_METRIC_FIELDS:
        host[name] = flat[off:off + b].astype(np.int32)
        off += b
    return host


def infer_features(ev, any_penalty: bool = True, any_preferred: bool = True,
                   with_topk: bool = True, with_shuffle: bool = False) -> KernelFeatures:
    """Derive the lean static variant for one EvalTensors' ask."""
    ask = ev.ask
    return KernelFeatures(
        n_spreads=len(ev.spreads),
        with_topk=with_topk,
        with_devices=bool(ask.n_dev_reqs > 0 or ev.has_dev_affinity),
        with_ports=bool(ask.n_dyn_ports > 0 or ask.reserved_ports),
        with_cores=bool(ask.cores > 0),
        with_network=bool(ask.total_mbits > 0),
        with_distinct=bool(ev.distinct_hosts_job or ev.distinct_hosts_tg),
        with_step_penalties=bool(any_penalty),
        with_preferred=bool(any_preferred),
        with_shuffle=bool(with_shuffle),
    )


def build_kernel_in(
    cluster: ClusterTensors,
    ev: EvalTensors,
    n_steps: int,
    step_penalty: Optional[np.ndarray] = None,
    step_preferred: Optional[np.ndarray] = None,
    node_perm: Optional[np.ndarray] = None,
) -> KernelIn:
    """Assemble device inputs from the host-side tensor schema.

    ``step_penalty``/``step_preferred`` are per-placement planes sized to
    the padded step count (``pad_steps(n_steps)``); None means no
    penalties/preferences. ``node_perm`` is the seeded tie-break
    permutation (identity when shuffling is off).
    """
    from nomad_tpu.tensors.schema import AskLimitError

    S, N = MAX_SPREADS, cluster.n_pad
    if len(ev.spreads) > S:
        raise AskLimitError(
            f"task group has {len(ev.spreads)} spread stanzas; kernel "
            f"supports {S}"
        )
    neutral = neutral_planes(N)
    if ev.spreads:
        sp_active = np.zeros(S, bool)
        sp_even = np.zeros(S, bool)
        sp_weight = np.zeros(S, np.float32)
        sp_bucket = np.full((S, N), -1, np.int32)
        sp_counts = np.zeros((S, SPREAD_BUCKETS), np.float32)
        sp_desired = np.full((S, SPREAD_BUCKETS), -1.0, np.float32)
        for s, sp in enumerate(ev.spreads[:S]):
            sp_active[s] = True
            sp_even[s] = sp.even
            sp_weight[s] = sp.weight_frac
            sp_bucket[s] = sp.bucket_id
            sp_counts[s] = sp.counts
            sp_desired[s] = sp.desired
    else:
        # frozen singletons: identity-shared across wave members
        sp_active = sp_even = neutral.zeros_spread_flags
        sp_weight = neutral.zeros_spread_weight
        sp_bucket = neutral.neg1_spread_bucket
        sp_counts = neutral.zeros_spread_counts
        sp_desired = neutral.neg1_spread_desired

    # reserved-port conflict: ask bits already set in node planes or the
    # in-plan conflict words
    if ev.ask.reserved_ports:
        words = cluster.port_words | ev.port_conflict_words
        conflict = np.any(words & ev.ask.port_mask[None, :], axis=1)
        if ev.port_live_conflict is not None:
            # live-alloc port occupancy (usage-index bitmaps): the
            # node plane only carries agent-reserved ports
            conflict = conflict | ev.port_live_conflict
        has_res = True
    else:
        conflict = neutral.zeros_bool
        has_res = False

    k_pad = pad_steps(n_steps)
    if step_penalty is None or step_preferred is None:
        np_pen, np_pref = neutral_step_planes(k_pad)
        if step_penalty is None:
            step_penalty = np_pen
        if step_preferred is None:
            step_preferred = np_pref
    if node_perm is None:
        node_perm = neutral.arange_i32

    # leaves stay NUMPY: jit uploads each argument once at call time.
    # Building device arrays here would mean one host->device transfer
    # per field per evaluation (and per wave member when coalescing).
    return KernelIn(
        cap_cpu=np.asarray(cluster.cap_cpu, np.float32),
        cap_mem=np.asarray(cluster.cap_mem, np.float32),
        cap_disk=np.asarray(cluster.cap_disk, np.float32),
        free_cores=np.asarray(cluster.free_cores, np.int32),
        shares_per_core=np.asarray(cluster.shares_per_core, np.float32),
        # identity-preserving when no in-plan dyn ports: wave members
        # then share the cluster's plane (shipped once per wave)
        free_dyn=(np.asarray(cluster.free_dyn, np.int32)
                  if not ev.free_dyn_delta.any()
                  else np.asarray(cluster.free_dyn - ev.free_dyn_delta,
                                  np.int32)),
        base_mask=np.asarray(ev.base_mask, bool),
        used_cpu=np.asarray(ev.used_cpu, np.float32),
        used_mem=np.asarray(ev.used_mem, np.float32),
        used_disk=np.asarray(ev.used_disk, np.float32),
        used_cores=np.asarray(ev.used_cores, np.int32),
        used_mbits=np.asarray(ev.used_mbits, np.int32),
        avail_mbits=np.asarray(ev.avail_mbits, np.int32),
        port_conflict=np.asarray(conflict, bool),
        dev_free=np.asarray(ev.dev_free, np.float32),
        dev_aff_score=np.asarray(ev.dev_aff_score, np.float32),
        has_dev_affinity=np.asarray(ev.has_dev_affinity, bool),
        job_tg_count=np.asarray(ev.job_tg_count, np.int32),
        penalty=np.asarray(ev.penalty, bool),
        aff_score=np.asarray(ev.aff_score, np.float32),
        node_perm=np.asarray(node_perm, np.int32),
        step_penalty=np.asarray(step_penalty, np.int32),
        step_preferred=np.asarray(step_preferred, np.int32),
        job_any_count=np.asarray(ev.job_any_count, np.int32),
        distinct_hosts_job=np.asarray(ev.distinct_hosts_job, bool),
        distinct_hosts_tg=np.asarray(ev.distinct_hosts_tg, bool),
        spread_active=np.asarray(sp_active, bool),
        spread_even=np.asarray(sp_even, bool),
        spread_weight=np.asarray(sp_weight, np.float32),
        spread_bucket=np.asarray(sp_bucket, np.int32),
        spread_counts=np.asarray(sp_counts, np.float32),
        spread_desired=np.asarray(sp_desired, np.float32),
        ask_cpu=np.asarray(ev.ask.cpu, np.float32),
        ask_mem=np.asarray(ev.ask.mem, np.float32),
        ask_disk=np.asarray(ev.ask.disk, np.float32),
        ask_cores=np.asarray(ev.ask.cores, np.int32),
        ask_dyn_ports=np.asarray(ev.ask.n_dyn_ports, np.int32),
        ask_has_reserved_ports=np.asarray(has_res, bool),
        ask_dev=np.asarray(ev.ask.dev_counts, np.float32),
        ask_mbits=np.asarray(ev.ask.total_mbits, np.int32),
        desired_count=np.asarray(ev.desired_count, np.int32),
        algorithm_spread=np.asarray(ev.algorithm == "spread", bool),
        n_steps=np.asarray(n_steps, np.int32),
    )
