"""Eval batching: many evaluations, one kernel launch.

This is the TPU-idiomatic throughput path (SURVEY.md section 7 step 5):
the broker groups compatible evaluations — same cluster snapshot, same
padded node bucket — and launches them as one batched kernel call. The
cluster's node planes stay device-resident between launches; only the
per-eval planes (utilization deltas, eligibility masks, ask scalars)
cross PCIe per batch, which is what amortizes dispatch overhead over
the reference's one-eval-at-a-time worker loop (nomad/worker.go:386).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from nomad_tpu.ops.kernel import (
    FULL_FEATURES,
    KernelFeatures,
    KernelIn,
    place_taskgroup,
    place_taskgroup_topk,
)


def device_put_shared(kin: KernelIn) -> KernelIn:
    """Stage the shared planes on device once."""
    return jax.tree_util.tree_map(jnp.asarray, kin)


def _jit_donating(fn, donate_argnums):
    """``jax.jit`` with donation, taking OWNERSHIP of the donated args.

    ``jnp.asarray(numpy_plane)`` is zero-copy on the CPU backend when
    the allocator happens to hand back an aligned block — the device
    buffer then ALIASES memory the caller still owns. Donating such a
    buffer lets the runtime write the loop's carry in place into the
    caller's numpy array (observed as a 1-in-5 parity flake: the first
    loop call silently rewrote a test's ``used`` planes before the
    second loop ran; tests/test_parallel.py keeps the case). Aliasing
    is undetectable from the array, so
    every donated arg is copied into a buffer this wrapper owns; the
    copy is O(plane) once per loop call, noise against the T-batch scan
    it feeds, and donation still aliases the carry inside the loop.
    """
    if not donate_argnums:
        return jax.jit(fn)
    jitted = jax.jit(fn, donate_argnums=donate_argnums)
    donated = frozenset(donate_argnums)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        args = tuple(
            jnp.array(a, copy=True) if i in donated else a
            for i, a in enumerate(args)
        )
        return jitted(*args, **kwargs)

    return call


def _bound_fallback(valid, primary, full_thunk):
    """Candidate-set bound contract: evals whose bound broke are served
    by the full-width kernel INSIDE the loop. Batch-level ``lax.cond``:
    a batch with no breach pays nothing; a breached batch computes the
    full-width results once and each eval keeps whichever is exact for
    it. ``primary``/``full_thunk()`` are matching pytrees with leading
    batch axis; ``valid`` is bool[B]."""
    def merge(_):
        full = full_thunk()
        return jax.tree_util.tree_map(
            lambda t, f: jnp.where(
                valid.reshape((-1,) + (1,) * (t.ndim - 1)), t, f),
            primary, full)

    return jax.lax.cond(jnp.all(valid), lambda _: primary, merge, None)


@functools.lru_cache(maxsize=32)
def make_schedule_apply_step(k_steps: int, features: KernelFeatures = FULL_FEATURES):
    """Fused batch-schedule + plan-apply with device-resident state.

    The TPU-native steady-state loop: the cluster's utilization planes
    live on device and are the carry; a batch of B evaluations is
    scheduled against that snapshot (optimistic concurrency — evals in
    a batch do not see each other's placements, exactly like reference
    workers scheduling against a shared SnapshotMinIndex snapshot,
    nomad/worker.go:537), then every accepted placement is committed as
    a scatter-add delta (the plan applier's state update,
    nomad/plan_apply.go:209, as on-device algebra). Per-batch host
    traffic is just ask scalars and the result rows.

    Returns fn(shared, used_cpu, used_mem, ask_cpu[B], ask_mem[B],
    n_steps[B]) -> (KernelOut[B], used_cpu', used_mem').
    """

    def step(shared: KernelIn, used_cpu, used_mem, ask_cpu, ask_mem, n_steps):
        def run_one(a_cpu, a_mem, ns):
            kin = shared._replace(
                used_cpu=used_cpu,
                used_mem=used_mem,
                ask_cpu=a_cpu,
                ask_mem=a_mem,
                n_steps=ns,
            )
            return place_taskgroup(kin, k_steps, features)

        out = jax.vmap(run_one)(ask_cpu, ask_mem, n_steps)
        used_cpu2, used_mem2 = commit_placements(
            used_cpu, used_mem, out.chosen, out.found, ask_cpu, ask_mem)
        return out, used_cpu2, used_mem2

    return _jit_donating(step, (1, 2))


@functools.lru_cache(maxsize=32)
def make_schedule_apply_loop(k_steps: int,
                             features: KernelFeatures = FULL_FEATURES,
                             topk: bool = False,
                             reset_every: int = 0):
    """Multi-batch fused loop: T batches of B evals in ONE device call.

    ``lax.scan`` over the batch axis keeps the utilization planes in
    the carry, so a whole measurement burst (or a steady-state window
    of the live system) is a single dispatch: per-dispatch host
    overhead would otherwise dominate and measure the launch path
    instead of the scheduler.

    Runs the vmapped kernels: full-width, or candidate-set when
    ``topk``.

    ``reset_every``: restore the INITIAL utilization planes every that
    many batches (0 = never) — the native baseline's periodic reset
    (bench/baseline_binpack.cc), so a long measurement burst schedules
    against the persisted cluster state instead of saturating it.

    Returns fn(shared, used_cpu, used_mem, ask_cpu[T,B], ask_mem[T,B],
    n_steps[B]) -> (score_sum, placed, fallback, used_cpu', used_mem').
    ``fallback`` counts evals whose candidate-set bound broke and were
    therefore served by the full-width kernel INSIDE the loop (a
    batch-level ``lax.cond``: a batch with no breach pays nothing, a
    batch with one re-runs full-width and merges per eval) — always 0
    without ``topk``, and no eval is ever dropped: committed totals
    are exact for every ask.
    """
    def with_reset(one_batch):
        if not reset_every:
            return lambda carry, asks, uc0, um0: one_batch(
                carry[:2], asks)

        def wrapped(carry, asks, uc0, um0):
            uc, um, t = carry
            hit = (t % reset_every) == 0
            uc = jnp.where(hit, uc0, uc)
            um = jnp.where(hit, um0, um)
            (uc2, um2), stats = one_batch((uc, um), asks)
            return (uc2, um2, t + 1), stats

        return wrapped

    def scan_loop(one_batch, used_cpu, used_mem, ask_cpu, ask_mem):
        body = with_reset(one_batch)
        if reset_every:
            # reset needs the pristine planes as scan constants; the
            # carry planes are donated working copies
            uc0 = used_cpu + 0.0
            um0 = used_mem + 0.0
            init = (used_cpu, used_mem, jnp.asarray(0, jnp.int32))
            (uc, um, _), stats = jax.lax.scan(
                lambda c, a: body(c, a, uc0, um0),
                init, (ask_cpu, ask_mem))
        else:
            (uc, um), stats = jax.lax.scan(
                lambda c, a: body(c, a, None, None),
                (used_cpu, used_mem), (ask_cpu, ask_mem))
        scores, placed, invalid = stats
        return (jnp.sum(scores), jnp.sum(placed), jnp.sum(invalid),
                uc, um)

    # donation is only usable when the donated planes' buffers can
    # alias the returned carry. With ``reset_every`` the body swaps the
    # carry for the pristine copies (``p + 0``) on the very first
    # batch, so the ORIGINAL donated buffers never reach an output and
    # device backends warn "Some donated buffers were not usable"
    # (promoted to an error in tests) — donate nothing then.
    donate = () if reset_every else (1, 2)

    def loop(shared: KernelIn, used_cpu, used_mem, ask_cpu, ask_mem, n_steps):
        def one_batch(carry, asks):
            uc, um = carry
            a_cpu, a_mem = asks

            def run_one(ac, am, ns):
                kin = shared._replace(
                    used_cpu=uc, used_mem=um,
                    ask_cpu=ac, ask_mem=am, n_steps=ns,
                )
                if topk:
                    out, ok = place_taskgroup_topk(kin, k_steps, features)
                    return out, ok
                return place_taskgroup(kin, k_steps, features), jnp.asarray(True)

            out, ok = jax.vmap(run_one)(a_cpu, a_mem, n_steps)
            if topk:
                def run_full(ac, am, ns):
                    kin = shared._replace(
                        used_cpu=uc, used_mem=um,
                        ask_cpu=ac, ask_mem=am, n_steps=ns,
                    )
                    return place_taskgroup(kin, k_steps, features)

                out = _bound_fallback(
                    ok, out,
                    lambda: jax.vmap(run_full)(a_cpu, a_mem, n_steps))
            uc2, um2 = commit_placements(
                uc, um, out.chosen, out.found, a_cpu, a_mem)
            stats = (
                jnp.sum(jnp.where(out.found, out.scores, 0.0)),
                jnp.sum(out.found),
                jnp.sum(~ok),
            )
            return (uc2, um2), stats

        return scan_loop(one_batch, used_cpu, used_mem, ask_cpu, ask_mem)

    return _jit_donating(loop, donate)


def _scan_with_reset(one_batch, planes, asks, reset_every: int):
    """Shared multi-batch scan harness for the timed cell loops:
    ``planes`` is the carried plane tuple, ``asks`` the tuple of
    [T, ...] per-batch inputs. With ``reset_every``, the pristine
    planes re-enter the carry every that many batches (the replay
    benches' baseline-matching reset cadence)."""
    if reset_every:
        init_planes = tuple(p + 0 for p in planes)

        def body(carry, a):
            *ps, t = carry
            hit = (t % reset_every) == 0
            ps = tuple(jnp.where(hit, i, p)
                       for p, i in zip(ps, init_planes))
            ps2, stats = one_batch(tuple(ps), a)
            return (*ps2, t + 1), stats

        (*out, _t), stats = jax.lax.scan(
            body, (*planes, jnp.asarray(0, jnp.int32)), asks)
        return tuple(out), stats
    out, stats = jax.lax.scan(one_batch, planes, asks)
    return tuple(out), stats


@functools.lru_cache(maxsize=8)
def make_device_apply_loop(k_steps: int, reset_every: int = 0):
    """Timed GPU-device cell: BASELINE.md's "GPU device-plugin jobs on
    a heterogeneous pool" config as a fused multi-batch loop.

    Same shape as ``make_schedule_apply_loop`` but the carry includes
    the per-node free-device plane (``dev_free``): the kernel deducts
    device asks between its K steps (rank.go AssignDevice semantics,
    device.go:32) and accepted placements commit their device ask
    across batches with the same scatter algebra as cpu/mem.

    Returns fn(shared, used_cpu, used_mem, dev_free, ask_cpu[T,B],
    ask_mem[T,B], ask_gpu[T,B], n_steps[B]) ->
    (score_sum, placed, used_cpu', used_mem', dev_free').
    """
    from nomad_tpu.ops.kernel import MAX_DEV_REQS

    features = KernelFeatures(
        n_spreads=0, with_topk=False, with_devices=True,
        with_ports=False, with_cores=False, with_network=False,
        with_distinct=False, with_step_penalties=False,
        with_preferred=False,
    )

    def loop(shared: KernelIn, used_cpu, used_mem, dev_free,
             ask_cpu, ask_mem, ask_gpu, n_steps):
        def one_batch(carry, asks):
            uc, um, df = carry
            a_cpu, a_mem, a_gpu = asks

            def run_one(ac, am, ag, ns):
                ad = jnp.zeros((MAX_DEV_REQS,), jnp.float32).at[0].set(ag)
                kin = shared._replace(
                    used_cpu=uc, used_mem=um, dev_free=df,
                    ask_cpu=ac, ask_mem=am, ask_dev=ad, n_steps=ns,
                )
                return place_taskgroup(kin, k_steps, features)

            out = jax.vmap(run_one)(a_cpu, a_mem, a_gpu, n_steps)
            uc2, um2 = commit_placements(
                uc, um, out.chosen, out.found, a_cpu, a_mem)
            rows = out.chosen.reshape(-1)
            ok = out.found.reshape(-1)
            w_gpu = (jnp.broadcast_to(a_gpu[:, None], out.chosen.shape)
                     .reshape(-1) * ok)
            safe = jnp.where(ok, rows, 0)
            df2 = df.at[safe, 0].add(-jnp.where(ok, w_gpu, 0.0))
            stats = (
                jnp.sum(jnp.where(out.found, out.scores, 0.0)),
                jnp.sum(out.found),
            )
            return (uc2, um2, df2), stats

        (uc, um, df), stats = _scan_with_reset(
            one_batch, (used_cpu, used_mem, dev_free),
            (ask_cpu, ask_mem, ask_gpu), reset_every)
        scores, placed = stats
        return jnp.sum(scores), jnp.sum(placed), uc, um, df

    # with reset_every, _scan_with_reset consumes COPIES of the planes
    # (``p + 0``) and the originals never reach an output — donation
    # would be unusable (device backends warn; tests error). Donate
    # only in the no-reset steady loop, where carry in aliases carry
    # out (BENCH_r05's "donated buffers were not usable" tail came
    # from exactly this misalignment).
    return _jit_donating(loop, () if reset_every else (1, 2, 3))


@functools.lru_cache(maxsize=8)
def make_preemption_apply_loop(k_steps: int, reset_every: int = 0):
    """Timed preemption cell: BASELINE.md's "preemption-enabled service
    jobs at 10K nodes" config as a fused multi-batch loop.

    Each placement first tries a normal binpack fit; when NO node has
    free capacity, eligible nodes (those with preemptible lower-
    priority capacity, preemption.go:96 Preemptor eligibility) are
    scored ``(binpack_fit_after_evict + preemption_score) / 2`` — the
    exact device-wide scoring the live path's ``select_preempting``
    computes (scheduler/stack.py, mirroring rank.go:799
    PreemptionScoringIterator) — and the chosen node's preemptible
    capacity is freed (full-eviction upper bound; the live system's
    host-side greedy pass evicts a subset, never more).

    ``pre_cpu/pre_mem`` are per-node planes of capacity held by allocs
    whose priority is more than PRIORITY_DELTA below the placing job's
    (scheduler/preemption.preemptible_planes); ``pre_score`` is the
    net-priority-derived plane (rank.go:858 preemptionScore).

    Returns fn(shared, used_cpu, used_mem, pre_cpu, pre_mem, pre_score,
    ask_cpu[T,B], ask_mem[T,B], n_steps[B]) ->
    (score_sum, placed, preempted, used_cpu', used_mem').
    """
    from nomad_tpu.ops.kernel import NEG_INF

    def loop(shared: KernelIn, used_cpu, used_mem,
             pre_cpu, pre_mem, pre_score,
             ask_cpu, ask_mem, n_steps):
        def one_eval(uc, um, pc, pm, ps, a_cpu, a_mem, ns):
            """K sequential placements with deduction; preemption is
            the per-step fallback (generic_sched.go:800 second pass)."""
            def step(st, i):
                uc, um, pc, pm = st
                free_cpu = shared.cap_cpu - uc
                free_mem = shared.cap_mem - um
                normal = (shared.base_mask
                          & (free_cpu >= a_cpu) & (free_mem >= a_mem))
                # binpack fit (funcs.go:259), normalized like the kernel
                fc = jnp.where(shared.cap_cpu > 0,
                               1.0 - (uc + a_cpu) / shared.cap_cpu, 0.0)
                fm = jnp.where(shared.cap_mem > 0,
                               1.0 - (um + a_mem) / shared.cap_mem, 0.0)
                fit = jnp.clip(
                    20.0 - (jnp.power(10.0, fc) + jnp.power(10.0, fm)),
                    0.0, 18.0) / 18.0
                active = i < ns
                normal_masked = jnp.where(normal & active, fit, NEG_INF)
                best_n = jnp.argmax(normal_masked)
                found_n = normal_masked[best_n] > NEG_INF / 2

                # preemption fallback plane (stack.py select_preempting)
                evictable = (pc > 0) | (pm > 0)
                pre_ok = (shared.base_mask & evictable & ~normal
                          & ((free_cpu + pc) >= a_cpu)
                          & ((free_mem + pm) >= a_mem))
                uce = uc - pc + a_cpu
                ume = um - pm + a_mem
                fce = jnp.where(shared.cap_cpu > 0,
                                1.0 - uce / shared.cap_cpu, 0.0)
                fme = jnp.where(shared.cap_mem > 0,
                                1.0 - ume / shared.cap_mem, 0.0)
                fite = jnp.clip(
                    20.0 - (jnp.power(10.0, fce) + jnp.power(10.0, fme)),
                    0.0, 18.0) / 18.0
                pre_masked = jnp.where(
                    pre_ok & active, (fite + ps) / 2.0, NEG_INF)
                best_p = jnp.argmax(pre_masked)
                found_p = pre_masked[best_p] > NEG_INF / 2

                idx = jnp.where(found_n, best_n, best_p)
                found = found_n | found_p
                preempted = found_p & ~found_n
                score = jnp.where(
                    found_n, normal_masked[best_n],
                    jnp.where(found_p, pre_masked[best_p], 0.0))

                one = jax.nn.one_hot(
                    idx, shared.cap_cpu.shape[0], dtype=jnp.float32
                ) * found.astype(jnp.float32)
                evict = one * preempted.astype(jnp.float32)
                uc2 = uc + one * a_cpu - evict * pc[idx]
                um2 = um + one * a_mem - evict * pm[idx]
                pc2 = pc * (1.0 - evict)
                pm2 = pm * (1.0 - evict)
                return (uc2, um2, pc2, pm2), (score * found, found,
                                              preempted)

            (uc2, um2, pc2, pm2), (scores, found, preempted) = \
                jax.lax.scan(step, (uc, um, pc, pm),
                             jnp.arange(k_steps))
            return (jnp.sum(scores), jnp.sum(found), jnp.sum(preempted),
                    uc2, um2, pc2, pm2)

        def one_batch(carry, asks):
            uc, um, pc, pm = carry
            a_cpu, a_mem = asks
            # batch members schedule against the SAME snapshot
            # (optimistic concurrency, like the lean loop)
            score, placed, preempted, uc2, um2, pc2, pm2 = jax.vmap(
                one_eval, in_axes=(None, None, None, None, None, 0, 0, 0)
            )(uc, um, pc, pm, pre_score, a_cpu, a_mem, n_steps)
            # commit = sum of PLACEMENT adds, but each node's evicted
            # capacity is credited ONCE (two members evicting the same
            # node free it once, not twice). A member's placement adds
            # are its used delta plus whatever it evicted.
            add_uc = jnp.sum(uc2 - uc[None, :] + (pc[None, :] - pc2),
                             axis=0)
            add_um = jnp.sum(um2 - um[None, :] + (pm[None, :] - pm2),
                             axis=0)
            pc3 = jnp.min(pc2, axis=0)
            pm3 = jnp.min(pm2, axis=0)
            stats = (jnp.sum(score), jnp.sum(placed), jnp.sum(preempted))
            return (uc + add_uc - (pc - pc3),
                    um + add_um - (pm - pm3), pc3, pm3), stats

        (uc, um, _pc, _pm), stats = _scan_with_reset(
            one_batch, (used_cpu, used_mem, pre_cpu, pre_mem),
            (ask_cpu, ask_mem), reset_every)
        scores, placed, preempted = stats
        return (jnp.sum(scores), jnp.sum(placed), jnp.sum(preempted),
                uc, um)

    # donate ONLY used_cpu/used_mem: they alias the uc/um outputs.
    # pre_cpu/pre_mem never leave the loop, so donating them has no
    # output to alias — XLA warns "Some donated buffers were not
    # usable" and the donation buys nothing (the warning is promoted
    # to an error in tests so this cannot regress). With reset_every
    # even uc/um are unusable: _scan_with_reset hands the scan COPIES
    # (``p + 0``) and the donated originals never reach an output
    # (the BENCH_r05 device/preemption-path warning) — donate nothing.
    return _jit_donating(loop, () if reset_every else (1, 2))


def commit_placements(used_cpu, used_mem, chosen, found, ask_cpu, ask_mem):
    """The plan applier's state update as on-device algebra
    (nomad/plan_apply.go:209): scatter every accepted placement's ask
    into the cluster utilization planes. Shared by the step and loop
    builders. ``chosen`` i32[B,K] node rows, ``found`` bool[B,K]."""
    rows = chosen.reshape(-1)                           # i32[B*K]
    ok = found.reshape(-1)
    w_cpu = (jnp.broadcast_to(ask_cpu[:, None], chosen.shape)
             .reshape(-1) * ok)
    w_mem = (jnp.broadcast_to(ask_mem[:, None], chosen.shape)
             .reshape(-1) * ok)
    safe = jnp.where(ok, rows, 0)
    used_cpu2 = used_cpu.at[safe].add(jnp.where(ok, w_cpu, 0.0))
    used_mem2 = used_mem.at[safe].add(jnp.where(ok, w_mem, 0.0))
    return used_cpu2, used_mem2
