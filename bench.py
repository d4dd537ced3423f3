#!/usr/bin/env python3
"""Headline benchmark: scheduler evals/sec on a 10K-node C2M-style cluster.

Measures the TPU batched placement path (eval batching: device-resident
cluster planes, one vmapped kernel launch per batch of evaluations —
nomad_tpu/parallel/batching.py) against a native sequential baseline
(bench/baseline_binpack.cc) that mirrors the reference's per-eval hot
loop: shuffleNodes -> feasibility chain -> log2(n)-limited binpack
scoring -> max-score select -> sequential deduction
(reference scheduler/stack.go:84-187, util.go:464, funcs.go:259).

Each "eval" places 10 allocations of a 500 MHz / 256 MB task group
(mock.Job defaults) against 10,000 nodes preloaded to a partially
packed state (the C2M replay shape: ~100K live allocs worth of
utilization).

Beyond the headline kernel number, the JSON line carries what
BASELINE.md's metric definition asks for:
- placement-score parity: the joint sequential kernel
  (ops/kernel.place_taskgroups_joint — exactly the Go loop's
  deduct-between-placements semantics) re-runs the BASELINE'S OWN
  WORKLOAD (same xorshift-seeded utilization, same asks, same reset
  cadence) and reports both mean scores. Global argmax vs the
  reference's log2(n)-limited shuffled scan means parity here reads
  "equal or better".
- end-to-end system throughput + p50/p99 plan latency: a live server
  (broker -> batched worker -> joint kernel waves -> plan applier ->
  state) schedules a burst of jobs; evals/s and plan latency
  percentiles come from that run.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "evals/s", "vs_baseline": N, ...}
"""

import atexit
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_NODES = 10_000
PLACEMENTS_PER_EVAL = 10
BATCH = 512
N_BATCHES = 400
BASELINE_EVALS = 2_000


def _bench_batch(backend: str):
    """(batch, n_batches) for the timed kernel cells.

    Evals in a batch are vmapped-independent (same snapshot, optimistic
    concurrency), so batch width is a pure throughput knob — per-eval
    inputs and placement quality are identical at any width. On an
    accelerator, wide batches amortize dispatch/scan fixed costs
    (measured on the round-5 chip: 512 -> 8192 gained ~2.4x); the CPU
    fallback keeps the narrow batch, whose [B, nodes] intermediates
    fit host caches and the harness window."""
    if backend == "cpu":
        return BATCH, N_BATCHES
    wide = 8192
    total = BATCH * N_BATCHES
    return wide, total // wide

# matched-workload score-parity run (mirrors baseline_binpack.cc)
PARITY_EVALS = 1_000
PARITY_BATCH = 50           # joint-kernel members per launch
PARITY_RESET = 200          # baseline resets utilization every 200 evals

# end-to-end live-server burst
E2E_NODES = 2_000
E2E_JOBS = 200
E2E_ALLOCS_PER_JOB = 10
# one worker: every eval rides a shared-capacity wave, so plans never
# conflict (cross-worker optimism cost ~40% throughput in retries);
# batch 32 keeps the last-plan-in-wave latency under the p99 target
E2E_WORKERS = 1
E2E_BATCH_SIZE = 32
# warmup must exercise the SAME wave bucket as the timed burst (a
# 32-eval wave pads to the 64 bucket); 8 warm jobs only compiled the
# 16 bucket and the burst then paid a cold compile inside the window
E2E_WARMUP_JOBS = 40

# box-relative steady-throughput floor (replaces the absolute 200
# evals/s literal, which was calibrated on a box ~2x faster than the
# next one and therefore meaningless there — CHANGES PR 6). The floor
# scales with trace_report.host_speed_score(), a single-thread Python
# proxy for the GIL-bound scheduler residue that dominates the steady
# burst: floor = EVALS_PER_SEC * (this box's score / REF_HOST_SCORE).
# Reference pair measured together on the PR 8 container, where PR 6
# ran a 106 evals/s median (floor at ~0.8x of it leaves noise margin).
STEADY_FLOOR_REF_HOST_SCORE = 8.7e6
STEADY_FLOOR_EVALS_PER_SEC = 85.0

# box-relative fleet-cell ceilings (ISSUE 11). Both scale INVERSELY
# with host speed (slower box -> higher allowed latency):
# ceiling = REF_MS * (STEADY_FLOOR_REF_HOST_SCORE / this box's score).
# References measured on the PR 11 container (host score ~7.6e6):
# stream deliver p99 ~1.1s under the 10k-client sparse-polling
# rotation (the drain cadence over 10k cursors, not the ring,
# dominates), e2e p99 ~0.7s under full fleet load vs 404ms for the
# lighter contention cell post-PR10 — ceilings leave ~2-4x noise
# margin.
FLEET_DELIVER_P99_REF_MS = 2500.0
FLEET_E2E_P99_REF_MS = 3000.0

# ISSUE 20: the fleet cell's flagship shape — 100k clients spread
# across a REAL 3-server cluster, a reader storm mixing
# stale/default/linearizable against every server. The follower-share
# floor is scale-free (2 of 3 servers are followers; clearing 0.66
# means the read plane actually put them to work); the staleness p99
# ceiling is box-relative like the other fleet gates (a follower's
# serving lag is replication cadence + scheduler residue, both of
# which stretch on slow boxes).
FLEET_CLIENTS = 100_000
FLEET_SERVERS = 3
FLEET_READ_FOLLOWER_SHARE_FLOOR = 0.66
FLEET_READ_STALENESS_P99_REF_MS = 750.0

# box-relative mesh-cell floor (ISSUE 14): sharded 100k-node waves at
# batch 32 on the 8-virtual-device host mesh. Reference measured on
# the PR 14 container (host score ~8.0e6, 1 core: virtual devices
# serialize, so the floor is deliberately ~0.5x the measured 40
# evals/s — a multi-core or real-TPU box clears it by an order of
# magnitude). Scales like the steady floor: floor = EVALS_PER_SEC *
# (this box's score / REF_HOST_SCORE).
MESH_FLOOR_REF_HOST_SCORE = 8.0e6
MESH_FLOOR_EVALS_PER_SEC = 18.0


def _tail_top(segments: dict, n: int = 3) -> dict:
    """Top-N tail segments by p99 share — the 'what makes the tail
    slow' headline emitted for both the steady burst and the
    contention cell."""
    return {seg: row["p99_share"]
            for seg, row in sorted(segments.items(),
                                   key=lambda kv: -kv[1]["p99_share"])[:n]}

_M64 = (1 << 64) - 1


class Budget:
    """Global wall-clock budget (VERDICT r4 #1). The harness window is
    ~25-28 min and `timeout` loses everything unprinted, so the bench
    imposes its OWN deadline safely inside it (default 21 min,
    env-overridable via NOMAD_TPU_BENCH_BUDGET) and burns it
    progressively: each phase gets a share of what remains and sizes
    itself to fit (fewer reps -> smaller bursts -> shorter deadlines ->
    skipped cells)."""

    def __init__(self, total: float = None) -> None:
        if total is None:
            total = float(os.environ.get("NOMAD_TPU_BENCH_BUDGET", "1260"))
        self.total = total
        self.t0 = time.monotonic()

    def spent(self) -> float:
        return time.monotonic() - self.t0

    def remaining(self) -> float:
        return max(self.total - self.spent(), 0.0)

    def share(self, frac: float, floor: float = 10.0) -> float:
        """A phase's slice of the remaining budget."""
        return max(self.remaining() * frac, floor)


class Emitter:
    """Incrementally-flushed JSON line: after every phase the CURRENT
    cumulative dict is printed to stdout as one complete JSON line
    (marked "partial": true), so an external kill at any point leaves
    the last finished phase's numbers on stdout — consumers take the
    last parseable line. The final line drops the partial flag. A
    SIGTERM/SIGALRM handler and atexit re-print the latest state so
    even an abnormal death emits what exists."""

    def __init__(self) -> None:
        self.line = {
            "metric": ("scheduler evals/sec (10k nodes, 10 placements/"
                       "eval, binpack)"),
            "value": None,
            "unit": "evals/s",
            "vs_baseline": None,
            "partial": True,
        }
        self._printed_final = False
        atexit.register(self._atexit)
        for sig in (signal.SIGTERM, signal.SIGALRM):
            try:
                signal.signal(sig, self._on_signal)
            except (ValueError, OSError):  # non-main thread / platform
                pass

    def update(self, **kw) -> None:
        self.line.update(kw)
        self.flush()

    def flush(self, final: bool = False) -> None:
        if final:
            self.line.pop("partial", None)
            self._printed_final = True
        print(json.dumps(self.line), flush=True)

    def _atexit(self) -> None:
        if not self._printed_final:
            self.flush()

    def _on_signal(self, signum, _frame) -> None:
        # async-signal-safe-ish emission: the signal can land MID-print
        # of a normal flush on the same stdout, so write one
        # pre-serialized buffer with a LEADING newline via os.write —
        # a half-written line becomes a discarded fragment and the
        # handler's line stays parseable for `tail -1`
        self.line["killed_by_signal"] = signum
        buf = ("\n" + json.dumps(self.line) + "\n").encode()
        try:
            os.write(1, buf)
        except OSError:
            pass
        # restore default disposition and re-raise so exit status is
        # honest about the interruption
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)


def _xorshift_fill(n: int, seed: int = 42):
    """Replicate baseline_binpack.cc's xorshift utilization init so the
    parity run schedules against byte-identical starting state."""
    import numpy as np

    s = seed & _M64
    used_cpu = np.zeros(n, np.float32)
    used_mem = np.zeros(n, np.float32)
    for i in range(n):
        s = (s ^ (s << 13)) & _M64
        s ^= s >> 7
        s = (s ^ (s << 17)) & _M64
        r1 = (s % 1000) / 1000.0
        s = (s ^ (s << 13)) & _M64
        s ^= s >> 7
        s = (s ^ (s << 17)) & _M64
        r2 = (s % 1000) / 1000.0
        used_cpu[i] = 3900.0 * 0.6 * r1
        used_mem[i] = 7936.0 * 0.6 * r2
    return used_cpu, used_mem


def _baseline_bin() -> str:
    src = os.path.join(REPO, "bench", "baseline_binpack.cc")
    out = os.path.join(REPO, "bench", "baseline_binpack")
    if not os.path.exists(out) or os.path.getmtime(out) < os.path.getmtime(src):
        subprocess.run(
            ["g++", "-O2", "-o", out, src], check=True, capture_output=True
        )
    return out


def _run_baseline_best(argv: list, reps: int = 3) -> dict:
    """Run the native baseline ``reps`` times and keep the FASTEST.

    The denominator must be the baseline at its best: host noise (a
    shared VM's steal time, a stray background process) that lands in
    a single-shot baseline run inflates vs_baseline — round-5 captures
    showed the same replay baseline varying 2.3x between runs while
    the device-side number held steady. Best-of-N mirrors the
    best-of-N the TPU side already gets and biases the comparison
    AGAINST this framework."""
    best = None
    for _ in range(reps):
        proc = subprocess.run(argv, check=True, capture_output=True,
                              text=True)
        out = json.loads(proc.stdout)
        if best is None or out["evals_per_sec"] > best["evals_per_sec"]:
            best = out
    return best


def run_baseline() -> dict:
    """Compile (once) and run the native sequential baseline."""
    return _run_baseline_best(
        [_baseline_bin(), str(N_NODES), str(PLACEMENTS_PER_EVAL),
         str(BASELINE_EVALS)])


def time_batches(loop, shared, used_cpu, used_mem, asks_cpu, asks_mem,
                 n_steps, reps: int = 2):
    """Shared timing harness (also used by bench/grid.py): best-of-N
    reps of ONE fused multi-batch launch (the whole burst is a single
    dispatch, so per-dispatch host overhead does not enter the
    timing). Fresh staging each rep because the loop donates the
    utilization planes.

    Timing MATERIALIZES a result scalar (``float(...)``), which waits
    for the device to finish the launch.

    Returns (best_dt_seconds, (score_sum, placed, fallback)) --
    ``fallback`` = evals served by the in-loop full-width re-run
    after a candidate-bound breach (no eval is dropped; see
    parallel/batching.make_schedule_apply_loop).
    """
    import jax.numpy as jnp

    best_dt = float("inf")
    result = None
    for _rep in range(reps):
        uc, um = jnp.asarray(used_cpu), jnp.asarray(used_mem)
        warm = loop(shared, uc, um, asks_cpu, asks_mem, n_steps)
        float(warm[0])
        uc2, um2 = jnp.asarray(used_cpu), jnp.asarray(used_mem)
        t0 = time.perf_counter()
        scores, placed, fallback, uc2, um2 = loop(
            shared, uc2, um2, asks_cpu, asks_mem, n_steps)
        stats = (float(scores), int(placed), int(fallback))
        dt = time.perf_counter() - t0
        if dt < best_dt:
            best_dt = dt
            result = stats
    return best_dt, result


def _calibrate_and_size(candidates, shared, used_cpu, used_mem,
                        asks_cpu, asks_mem, n_steps, budget_s,
                        n_batches_max):
    """Time a short burst per candidate loop, keep the fastest, then
    size the measured burst to the phase budget: cost model is
    reps x (warmup + timed) full bursts plus one compile of the
    full-size variant (approximated by a 1.4x safety factor on the
    steady-state estimate). Returns (name, loop, n_batches, reps)."""
    # calibration must stay a small FRACTION of the real burst: with
    # wide accelerator batches n_batches_max is small (25), and a
    # 20-batch calibration would be 80% of the measurement (and the
    # n_b floor below would defeat budget shrinking entirely)
    cal_steps = min(max(2, n_batches_max // 10), 20, n_batches_max)
    picked, best_cal, pick_err = None, float("inf"), None
    for name, loop in candidates:
        try:
            dt, _ = time_batches(loop, shared, used_cpu, used_mem,
                                 asks_cpu[:cal_steps], asks_mem[:cal_steps],
                                 n_steps, reps=1)
        except Exception as e:                   # noqa: BLE001
            pick_err = e
            print(f"warning: {name} loop failed calibration: {e}",
                  file=sys.stderr)
            continue
        if dt < best_cal:
            picked, best_cal = (name, loop), dt
    if picked is None:
        raise RuntimeError(f"no usable kernel backend: {pick_err}")
    name, loop = picked
    per_batch = best_cal / cal_steps
    if budget_s is None:
        return name, loop, n_batches_max, 2
    reps = 2
    n_b = int(budget_s / (reps * 2 * per_batch * 1.4))
    if n_b < n_batches_max // 2:
        reps = 1
        n_b = int(budget_s / (reps * 2 * per_batch * 1.4))
    n_b = max(min(n_b, n_batches_max), cal_steps)
    if n_b < n_batches_max:
        print(f"bench budget: shrinking burst to {n_b}/{n_batches_max} "
              f"batches, reps={reps} (est {per_batch * 1e3:.1f} ms/batch, "
              f"budget {budget_s:.0f}s)", file=sys.stderr)
    return name, loop, n_b, reps


def run_tpu(budget_s: float = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops.kernel import LEAN_FEATURES, build_kernel_in
    from nomad_tpu.parallel.batching import (
        device_put_shared,
        make_schedule_apply_loop,
    )
    from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

    rng = np.random.default_rng(7)
    cluster = synthetic_cluster(N_NODES, cpu=3900.0, mem=7936.0,
                                disk=98304.0, seed=7)
    ev0 = synthetic_eval(cluster, desired_count=PLACEMENTS_PER_EVAL)
    shared = device_put_shared(
        build_kernel_in(cluster, ev0, PLACEMENTS_PER_EVAL)
    )
    # lean variant: the baseline's asks are cpu/mem/disk binpack only,
    # so compile without port/device/core/spread/top-k planes (the same
    # static specialization the real stack infers per ask); topk=True
    # engages the candidate-set kernel (exact, bound-checked).
    backend = jax.default_backend()
    candidates = [("xla_topk", make_schedule_apply_loop(
        PLACEMENTS_PER_EVAL, LEAN_FEATURES, topk=True))]

    npad = cluster.n_pad
    batch, n_batches = _bench_batch(backend)
    n_steps = jnp.asarray(np.full(batch, PLACEMENTS_PER_EVAL, np.int32))

    # device-resident cluster utilization (C2M-style partially packed;
    # in the live system the plan applier maintains these planes with
    # the same scatter deltas the fused step applies)
    used_cpu = np.zeros(npad, np.float32)
    used_mem = np.zeros(npad, np.float32)
    used_cpu[:N_NODES] = 3900.0 * 0.6 * rng.random(N_NODES, dtype=np.float32)
    used_mem[:N_NODES] = 7936.0 * 0.6 * rng.random(N_NODES, dtype=np.float32)

    # per-batch ask scalars vary per eval (the only per-eval upload)
    asks_cpu = jnp.asarray(
        rng.choice([250.0, 500.0, 750.0], (n_batches, batch))
        .astype(np.float32))
    asks_mem = jnp.asarray(
        rng.choice([128.0, 256.0, 512.0], (n_batches, batch))
        .astype(np.float32))

    kernel_name, loop, n_b, reps = _calibrate_and_size(
        candidates, shared, used_cpu, used_mem, asks_cpu, asks_mem,
        n_steps, budget_s, n_batches)

    best_dt, (score_sum, placed, fallback) = time_batches(
        loop, shared, used_cpu, used_mem, asks_cpu[:n_b], asks_mem[:n_b],
        n_steps, reps=reps)

    evals = batch * n_b
    return {
        "evals_per_sec": evals / best_dt,
        "mean_score": score_sum / max(placed, 1),
        "invalid": 0,          # no eval is dropped: breaches fall back
        "fallback": fallback,  # ...to the full-width kernel in-loop
        "backend": backend,
        "kernel": kernel_name,
    }


def run_score_parity(baseline_seed: int = 42,
                     budget_s: float = None) -> dict:
    """Mean placement score on the baseline's exact workload, scheduled
    by the joint sequential kernel (deduction between every placement,
    like the Go loop — no batching optimism)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops.kernel import (
        LEAN_FEATURES,
        build_kernel_in,
        place_taskgroups_joint_jit,
    )
    from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

    cluster = synthetic_cluster(N_NODES, cpu=3900.0, mem=7936.0,
                                disk=98304.0, seed=7)
    ev0 = synthetic_eval(cluster, desired_count=PLACEMENTS_PER_EVAL)
    base_kin = build_kernel_in(cluster, ev0, PLACEMENTS_PER_EVAL)
    base_kin = base_kin._replace(
        ask_cpu=jnp.asarray(500.0, jnp.float32),
        ask_mem=jnp.asarray(256.0, jnp.float32),
        ask_disk=jnp.asarray(150.0, jnp.float32),
    )
    npad = cluster.n_pad
    init_cpu = np.zeros(npad, np.float32)
    init_mem = np.zeros(npad, np.float32)
    init_cpu[:N_NODES], init_mem[:N_NODES] = _xorshift_fill(
        N_NODES, baseline_seed)
    init_disk = np.zeros(npad, np.float32)
    init_disk[:N_NODES] = 150.0

    # member layout: PARITY_BATCH members x k steps each, in order
    k = PLACEMENTS_PER_EVAL
    t = PARITY_BATCH * k
    step_member = np.repeat(np.arange(PARITY_BATCH, dtype=np.int32), k)
    step_local = np.tile(np.arange(k, dtype=np.int32), PARITY_BATCH)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * PARITY_BATCH), base_kin)

    score_sum, placed = 0.0, 0
    used_cpu = init_cpu.copy()
    used_mem = init_mem.copy()
    used_disk = init_disk.copy()
    done = 0
    t_start = time.monotonic()
    while done < PARITY_EVALS:
        # budget early-stop only at reset-cadence boundaries so the
        # mean stays comparable to the baseline's 200-eval cycles;
        # always finish at least one full cycle
        if (budget_s is not None and done >= PARITY_RESET
                and done % PARITY_RESET == 0
                and time.monotonic() - t_start > budget_s):
            print(f"bench budget: parity stopped at {done}/{PARITY_EVALS} "
                  "evals (full reset cycles only)", file=sys.stderr)
            break
        if done % PARITY_RESET == 0:
            used_cpu = init_cpu.copy()
            used_mem = init_mem.copy()
            used_disk = init_disk.copy()
        kin = stacked._replace(
            used_cpu=jnp.stack([jnp.asarray(used_cpu)] * PARITY_BATCH),
            used_mem=jnp.stack([jnp.asarray(used_mem)] * PARITY_BATCH),
            used_disk=jnp.stack([jnp.asarray(used_disk)] * PARITY_BATCH),
        )
        out = place_taskgroups_joint_jit(
            kin, jnp.asarray(step_member), jnp.asarray(step_local),
            t, LEAN_FEATURES,
        )
        found = np.asarray(out.found)
        scores = np.asarray(out.scores)
        score_sum += float(scores[found].sum())
        placed += int(found.sum())
        used_cpu = used_cpu + np.asarray(out.a_cpu)
        used_mem = used_mem + np.asarray(out.a_mem)
        used_disk = used_disk + np.asarray(out.a_disk)
        done += PARITY_BATCH
    return {"mean_score": score_sum / max(placed, 1), "placed": placed}


def run_e2e(budget_s: float = None) -> dict:
    """Live-system burst: jobs -> broker -> batched worker (joint
    kernel waves) -> plan applier -> state. Returns evals/s and plan
    latency percentiles. budget_s caps the warmup + burst deadlines and
    drops the second burst when time is short (a first-burst number
    with residual compile noise beats no number)."""
    import numpy as np

    from nomad_tpu import mock
    from nomad_tpu.server.server import Server, ServerConfig

    t_start = time.monotonic()

    def left() -> float:
        if budget_s is None:
            return float("inf")
        return budget_s - (time.monotonic() - t_start)

    server = Server(ServerConfig(
        num_workers=E2E_WORKERS,
        worker_batch_size=E2E_BATCH_SIZE,
        heartbeat_ttl=3600.0,
    ))
    server.start()
    try:
        for _ in range(E2E_NODES):
            server.node_register(mock.node())
        # warmup: a mini burst of the same job shape compiles the wave
        # kernels (one XLA variant per wave/step bucket; tens of
        # seconds each cold on TPU) before the timed window — the
        # steady state is what the metric is defined on, and a real
        # server warms these at startup from the persistent cache
        warm = []
        for _ in range(E2E_WARMUP_JOBS):
            job = mock.simple_job()
            job.task_groups[0].count = E2E_ALLOCS_PER_JOB
            warm.append(job)
            server.job_register(job)
        warm_want = E2E_WARMUP_JOBS * E2E_ALLOCS_PER_JOB
        warm_deadline = time.time() + min(300.0, max(left() * 0.5, 30.0))
        while time.time() < warm_deadline:
            snap = server.state.snapshot()
            if sum(len(snap.allocs_by_job(j.namespace, j.id))
                   for j in warm) >= warm_want:
                break
            time.sleep(0.1)
        # best of two bursts (the same best-of-N the kernel timing
        # uses): the first burst still pays residual compile/caching
        # effects even after warmup; the steady state is what the
        # metric is defined on
        best = None
        for _burst in range(2):
            if best is not None and left() < 60.0:
                print("bench budget: skipping second e2e burst",
                      file=sys.stderr)
                break
            server.plan_latencies.clear()
            # waves/requests are lifetime counters: report this
            # burst's DELTA, not warmup+earlier bursts
            waves0 = sum(w.batch_launches for w in server.workers)
            reqs0 = sum(w.batch_requests for w in server.workers)
            jobs = []
            # poll cheap worker counters, NOT state.snapshot(): a
            # whole-state copy every tick is O(allocs) of GIL the
            # system under test doesn't owe the monitor
            done0 = sum(w.processed for w in server.workers)
            t0 = time.perf_counter()
            for _ in range(E2E_JOBS):
                job = mock.simple_job()
                job.task_groups[0].count = E2E_ALLOCS_PER_JOB
                jobs.append(job)
                server.job_register(job)
            want = E2E_JOBS * E2E_ALLOCS_PER_JOB
            deadline = time.time() + min(600.0, max(left(), 30.0))
            placed = 0
            # background evals (core GC) also bump `processed`, so the
            # counter is a trigger for the exact placement check, not
            # the verdict; dt is stamped before the O(state) check
            target = E2E_JOBS
            dt = None
            while time.time() < deadline:
                if sum(w.processed for w in server.workers) - done0 \
                        >= target:
                    t_done = time.perf_counter()
                    snap = server.state.snapshot()
                    placed = sum(
                        len(snap.allocs_by_job(j.namespace, j.id))
                        for j in jobs
                    )
                    if placed >= want:
                        dt = t_done - t0
                        break
                    target += max(
                        1, (want - placed) // E2E_ALLOCS_PER_JOB)
                time.sleep(0.02)
            if dt is None:
                # deadline exit: the counter trigger can misfire (it is
                # a hint, not the verdict) — take the authoritative
                # placement count before reporting
                dt = time.perf_counter() - t0
                snap = server.state.snapshot()
                placed = sum(
                    len(snap.allocs_by_job(j.namespace, j.id))
                    for j in jobs
                )
            # shared nearest-rank helper (telemetry/histogram.py): the
            # old int(len*0.99) indexing reported the MAX as "p99"
            from nomad_tpu.telemetry.histogram import percentile

            lat = list(server.plan_latencies)
            p50 = percentile(lat, 0.5)
            p99 = percentile(lat, 0.99)
            waves = sum(w.batch_launches for w in server.workers) - waves0
            reqs = sum(w.batch_requests for w in server.workers) - reqs0
            out = {
                "e2e_evals_per_sec": E2E_JOBS / dt,
                "e2e_allocs_placed": placed,
                "e2e_allocs_wanted": want,
                "plan_latency_p50_ms": p50 * 1e3,
                "plan_latency_p99_ms": p99 * 1e3,
                "kernel_waves": waves,
                "kernel_requests": reqs,
            }
            if best is None or out["e2e_evals_per_sec"] > \
                    best["e2e_evals_per_sec"]:
                best = out
        return best
    finally:
        server.shutdown()


def _replay_planes(path: str):
    """Load the C2M replay through the real state store and flatten it
    to kernel planes + an ask stream drawn from the replay's job mix."""
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "bench"))
    import c2m
    from nomad_tpu.tensors.schema import ClusterTensors

    store = c2m.load(path)
    snap = store.snapshot()
    cluster = ClusterTensors.build(snap.nodes())
    u = snap.usage
    perm, valid = cluster.usage_perm(u)
    used_cpu = np.where(valid, u.used_cpu[perm], 0.0).astype(np.float32)
    used_mem = np.where(valid, u.used_mem[perm], 0.0).astype(np.float32)
    used_disk = np.where(valid, u.used_disk[perm], 0.0).astype(np.float32)

    # lean ask stream: the replay's service/batch shapes (device asks
    # go through the full kernel in the live system, not this loop)
    lean = [
        (float(tg.tasks[0].resources.cpu),
         float(tg.tasks[0].resources.memory_mb))
        for j in snap.jobs() for tg in j.task_groups
        if not any(t.resources.devices for t in tg.tasks)
    ]
    rng = np.random.default_rng(11)
    arr = np.asarray(lean, np.float32)[
        rng.integers(0, len(lean), N_BATCHES * BATCH)]
    stats = {
        "replay_nodes": cluster.n_real,
        "replay_allocs": sum(
            1 for a in snap.allocs_iter() if not a.terminal_status()),
        "replay_jobs": len(snap.jobs()),
    }
    return cluster, snap, used_cpu, used_mem, used_disk, arr, stats


# the non-headline timed cells (BASELINE.md:22-25 config list)
CELL_BATCHES = 100
PREEMPTION_PRIORITY = 90    # placing priority for the preemption cell


def _cell_batches() -> int:
    """Cells run full-size on an accelerator; the CPU FALLBACK keeps
    them to a documentation-grade burst (a fallback capture must not
    blow the round's bench budget — the full-size cells alone cost
    ~half an hour of CPU)."""
    import jax

    if jax.default_backend() != "cpu":
        return CELL_BATCHES
    # never EXCEED an explicitly shrunk CELL_BATCHES (tests set it to
    # 2); the floor only bounds the default's divided-down size
    return min(CELL_BATCHES, max(10, CELL_BATCHES // 10))


def _phase(msg: str) -> None:
    print(f"bench phase [{time.strftime('%H:%M:%S')}]: {msg}",
          file=sys.stderr, flush=True)


def _gpu_free_plane(cluster, snap):
    """f32[n_pad]: free nvidia/gpu instances per node at the replay
    snapshot (capacity from NodeDeviceResource minus instances held by
    live allocs' AllocatedDeviceResource rows)."""
    import numpy as np

    free = np.zeros(cluster.n_pad, np.float32)
    for i in range(cluster.n_real):
        node = snap.node_by_id(cluster.node_ids[i])
        if node is None or not node.node_resources.devices:
            continue
        free[i] = sum(len(d.instance_ids)
                      for d in node.node_resources.devices
                      if d.type == "gpu")
    for a in snap.allocs_iter():
        if a.terminal_status() or a.allocated_resources is None:
            continue
        row = cluster.index.get(a.node_id)
        if row is None:
            continue
        for tr in a.allocated_resources.tasks.values():
            for d in tr.devices:
                if d.type == "gpu":
                    free[row] -= len(d.device_ids)
    return np.maximum(free, 0.0)


def run_replay_device(cluster, snap, used_cpu, used_mem, used_disk) -> dict:
    """GPU device-ask cell: the replay's gpu job shape (1 nvidia/gpu +
    cpu/mem) scheduled against the replay's actual free device capacity
    through the device-carrying fused loop."""
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops.kernel import build_kernel_in
    from nomad_tpu.parallel.batching import (
        device_put_shared,
        make_device_apply_loop,
    )
    from nomad_tpu.parallel.synthetic import synthetic_eval

    gpu_free = _gpu_free_plane(cluster, snap)
    ev0 = synthetic_eval(cluster, desired_count=PLACEMENTS_PER_EVAL)
    shared = device_put_shared(
        build_kernel_in(cluster, ev0, PLACEMENTS_PER_EVAL)._replace(
            used_disk=used_disk, ask_disk=np.asarray(150.0, np.float32)))
    loop = make_device_apply_loop(PLACEMENTS_PER_EVAL, reset_every=1)

    # the replay's gpu shape (bench/c2m.py JOB_SHAPES "gpu")
    shape = (4000.0, 8192.0, 1.0)
    T, B = _cell_batches(), BATCH
    a_cpu = jnp.full((T, B), shape[0], jnp.float32)
    a_mem = jnp.full((T, B), shape[1], jnp.float32)
    a_gpu = jnp.full((T, B), shape[2], jnp.float32)
    n_steps = jnp.asarray(np.full(B, PLACEMENTS_PER_EVAL, np.int32))
    df0 = np.zeros((cluster.n_pad, shared.dev_free.shape[1]), np.float32)
    df0[:, 0] = gpu_free

    best_dt, placed = float("inf"), 0
    for _rep in range(2):
        args = (jnp.asarray(used_cpu), jnp.asarray(used_mem),
                jnp.asarray(df0))
        warm = loop(shared, *args, a_cpu, a_mem, a_gpu, n_steps)
        float(warm[0])
        args = (jnp.asarray(used_cpu), jnp.asarray(used_mem),
                jnp.asarray(df0))
        t0 = time.perf_counter()
        out = loop(shared, *args, a_cpu, a_mem, a_gpu, n_steps)
        placed = int(out[1])
        dt = time.perf_counter() - t0
        best_dt = min(best_dt, dt)
    return {
        "device_evals_per_sec": T * B / best_dt,
        "device_placed": placed,
        "device_free_gpus": float(gpu_free.sum()),
    }


def run_replay_preemption(cluster, snap, used_cpu, used_mem, asks) -> dict:
    """Preemption-enabled cell: a priority-90 eval stream over the
    replay state; placements that do not fit free capacity preempt
    lower-priority work (vectorized select_preempting scoring)."""
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops.kernel import build_kernel_in
    from nomad_tpu.parallel.batching import (
        device_put_shared,
        make_preemption_apply_loop,
    )
    from nomad_tpu.parallel.synthetic import synthetic_eval
    from nomad_tpu.scheduler.preemption import preemptible_planes

    pre_cpu, pre_mem, _pre_disk, pre_score = preemptible_planes(
        cluster, snap, None, PREEMPTION_PRIORITY,
        "default", "bench-preemption-job")

    # preemption is definitionally a SATURATED-cluster path, but the
    # replay generator stops at its alloc target leaving ~10% of nodes
    # (an empty compute class) with huge headroom — against which any
    # ask places normally and the eviction path never runs. The cell
    # consumes 90% of each node's remaining free capacity with
    # non-evictable filler, so the mega asks below can land ONLY by
    # evicting the replay's real lower-priority allocations.
    free_cpu = np.maximum(np.asarray(cluster.cap_cpu) - used_cpu, 0)
    free_mem = np.maximum(np.asarray(cluster.cap_mem) - used_mem, 0)
    used_cpu = (used_cpu + 0.9 * free_cpu).astype(np.float32)
    used_mem = (used_mem + 0.9 * free_mem).astype(np.float32)

    ev0 = synthetic_eval(cluster, desired_count=PLACEMENTS_PER_EVAL)
    shared = device_put_shared(
        build_kernel_in(cluster, ev0, PLACEMENTS_PER_EVAL))
    loop = make_preemption_apply_loop(PLACEMENTS_PER_EVAL, reset_every=1)

    T, B = _cell_batches(), BATCH
    # a slice of the replay's LARGEST service shape (bench/c2m.py
    # "service-distinct", 4000/8192): on the saturated planes above it
    # fits NO node's free capacity (0 normal-fit nodes; ~1.8k
    # eviction-eligible ones), so those placements land only through
    # the eviction path; the rest of the stream is the replay's lean
    # mix placing normally
    rng = np.random.default_rng(17)
    mega = rng.random((T, B)) < 0.25
    a_cpu = jnp.asarray(np.where(
        mega, 4000.0, asks[:T * B, 0].reshape(T, B)).astype(np.float32))
    a_mem = jnp.asarray(np.where(
        mega, 8192.0, asks[:T * B, 1].reshape(T, B)).astype(np.float32))
    n_steps = jnp.asarray(np.full(B, PLACEMENTS_PER_EVAL, np.int32))

    best_dt, placed, preempted = float("inf"), 0, 0
    for _rep in range(2):
        args = (jnp.asarray(used_cpu), jnp.asarray(used_mem),
                jnp.asarray(pre_cpu), jnp.asarray(pre_mem))
        warm = loop(shared, *args, jnp.asarray(pre_score),
                    a_cpu, a_mem, n_steps)
        float(warm[0])
        args = (jnp.asarray(used_cpu), jnp.asarray(used_mem),
                jnp.asarray(pre_cpu), jnp.asarray(pre_mem))
        t0 = time.perf_counter()
        out = loop(shared, *args, jnp.asarray(pre_score),
                   a_cpu, a_mem, n_steps)
        placed, preempted = int(out[1]), int(out[2])
        dt = time.perf_counter() - t0
        best_dt = min(best_dt, dt)
    return {
        "preemption_evals_per_sec": T * B / best_dt,
        "preemption_placed": placed,
        "preemption_preempted": preempted,
    }


def _write_planes_file(cluster, used_cpu, used_mem, used_disk,
                       asks, evals: int, k: int) -> str:
    """Export the replay planes for the native baseline (--planes)."""
    import struct as pystruct
    import tempfile

    import numpy as np

    n = cluster.n_real
    fd, path = tempfile.mkstemp(suffix=".c2mp")
    with os.fdopen(fd, "wb") as f:
        f.write(b"C2MP")
        f.write(pystruct.pack("<iii", n, evals, k))
        for plane in (cluster.cap_cpu, cluster.cap_mem, cluster.cap_disk,
                      used_cpu, used_mem, used_disk):
            f.write(np.asarray(plane[:n], "<f4").tobytes())
        f.write(np.asarray(asks[:evals, 0], "<f4").tobytes())
        f.write(np.asarray(asks[:evals, 1], "<f4").tobytes())
        f.write(np.full(evals, 150.0, "<f4").tobytes())
    return path


def run_replay(planes, budget_s: float = None) -> dict:
    """The C2M replay headline: fused loop vs native baseline on the
    SAME persisted cluster planes and the SAME ask stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nomad_tpu.ops.kernel import LEAN_FEATURES, build_kernel_in
    from nomad_tpu.parallel.batching import (
        device_put_shared,
        make_schedule_apply_loop,
    )
    from nomad_tpu.parallel.synthetic import synthetic_eval

    cluster, _snap, used_cpu, used_mem, used_disk, asks, stats = planes

    # native baseline on the identical planes + ask prefix
    planes_file = _write_planes_file(
        cluster, used_cpu, used_mem, used_disk, asks,
        BASELINE_EVALS, PLACEMENTS_PER_EVAL)
    try:
        baseline = _run_baseline_best(
            [_baseline_bin(), "--planes", planes_file])
    finally:
        os.unlink(planes_file)

    ev0 = synthetic_eval(cluster, desired_count=PLACEMENTS_PER_EVAL)
    shared = build_kernel_in(cluster, ev0, PLACEMENTS_PER_EVAL)
    shared = device_put_shared(shared._replace(
        used_disk=used_disk,
        ask_disk=np.asarray(150.0, np.float32),
    ))

    # reset_every=1: every batch schedules against the PERSISTED replay
    # utilization (the baseline's own 200-eval reset cadence), so the
    # burst measures eval throughput on the replay state rather than a
    # saturating cluster, and mean scores are comparable
    backend = jax.default_backend()
    candidates = [("xla_topk", make_schedule_apply_loop(
        PLACEMENTS_PER_EVAL, LEAN_FEATURES, topk=True, reset_every=1))]

    batch, n_batches = _bench_batch(backend)
    n_steps = jnp.asarray(
        np.full(batch, PLACEMENTS_PER_EVAL, np.int32))
    asks_cpu = jnp.asarray(asks[:, 0].reshape(n_batches, batch))
    asks_mem = jnp.asarray(asks[:, 1].reshape(n_batches, batch))

    kernel_name, loop, n_b, reps = _calibrate_and_size(
        candidates, shared, used_cpu, used_mem, asks_cpu, asks_mem,
        n_steps, budget_s, n_batches)

    best_dt, (score_sum, placed, fallback) = time_batches(
        loop, shared, used_cpu, used_mem, asks_cpu[:n_b], asks_mem[:n_b],
        n_steps, reps=reps)
    evals = batch * n_b
    return {
        "evals_per_sec": evals / best_dt,
        "vs_baseline": evals / best_dt / baseline["evals_per_sec"],
        "baseline_evals_per_sec": baseline["evals_per_sec"],
        "baseline_mean_score": baseline["mean_score"],
        "mean_score": score_sum / max(placed, 1),
        "invalid": 0,
        "fallback": fallback,
        "backend": backend,
        "kernel": kernel_name,
        **stats,
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--replay", nargs="?", const="", default=None,
                    help="C2M replay snapshot path (default: generate/"
                         "cache bench/c2m_replay.snap)")
    ap.add_argument("--synthetic", action="store_true",
                    help="skip the replay; bench the synthetic cluster only")
    args = ap.parse_args()

    budget = Budget()
    em = Emitter()
    em.update(budget_s=budget.total)

    _phase("native baseline")
    baseline = run_baseline()
    em.update(score_baseline=round(baseline["mean_score"], 6),
              baseline_evals_per_sec=round(baseline["evals_per_sec"], 2))

    planes = None
    if not args.synthetic and budget.remaining() > 240:
        sys.path.insert(0, os.path.join(REPO, "bench"))
        import c2m

        replay_path = args.replay or c2m.DEFAULT_PATH
        try:
            _phase("replay planes")
            planes = _replay_planes(replay_path)
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: replay planes failed ({e}); "
                  "reporting synthetic only", file=sys.stderr)
    elif not args.synthetic:
        print("bench budget: skipping replay planes build "
              f"({budget.remaining():.0f}s left < 240s)", file=sys.stderr)

    # the backend is whatever jax gives this process; the compile
    # cache is set up by nomad_tpu.ops.kernel when it loads
    import jax

    em.update(backend=jax.default_backend())

    _phase("synthetic kernel burst")
    tpu = run_tpu(budget_s=budget.share(0.18))
    em.update(
        value=round(tpu["evals_per_sec"], 2),
        kernel=tpu["kernel"],
        vs_baseline=round(
            tpu["evals_per_sec"] / baseline["evals_per_sec"], 2),
        synthetic_evals_per_sec=round(tpu["evals_per_sec"], 2),
        synthetic_vs_baseline=round(
            tpu["evals_per_sec"] / baseline["evals_per_sec"], 2),
    )

    _phase("score parity")
    parity = run_score_parity(budget_s=budget.share(0.18))
    em.update(
        score_tpu_sequential=round(parity["mean_score"], 6),
        score_parity=round(
            parity["mean_score"] / max(baseline["mean_score"], 1e-9), 4),
    )

    _phase("live-server e2e")
    e2e = run_e2e(budget_s=budget.share(0.45))
    em.update(
        e2e_evals_per_sec=round(e2e["e2e_evals_per_sec"], 2),
        e2e_allocs=(f"{e2e['e2e_allocs_placed']}/"
                    f"{e2e['e2e_allocs_wanted']}"),
        plan_latency_p50_ms=round(e2e["plan_latency_p50_ms"], 3),
        plan_latency_p99_ms=round(e2e["plan_latency_p99_ms"], 3),
        e2e_kernel_waves=e2e["kernel_waves"],
        e2e_kernel_requests=e2e["kernel_requests"],
    )

    # stage decomposition of the live path (the ISSUE-1 telemetry
    # subsystem): where the per-eval milliseconds actually go. This is
    # the artifact that decides whether the TPU live-path gap is
    # transfer, dispatch, recompilation, or plan-apply serialization.
    if budget.remaining() > 90:
        try:
            _phase("trace decomposition")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            decomp = trace_report.run_traced_burst(
                deadline_s=min(budget.share(0.2), 240.0), bursts=2)
            out_path = os.path.join(REPO, "TRACE_DECOMP.json")
            with open(out_path, "w") as f:
                json.dump(decomp, f, indent=2)
                f.write("\n")
            top = list(decomp["stages"].items())[:3]
            steady = decomp.get("steady_state", {})
            em.update(
                trace_attributed_share=decomp["attributed_share"],
                trace_per_eval_ms=decomp["per_eval_ms"],
                trace_top_stages={k: v["per_eval_ms"] for k, v in top},
                trace_jit_cache_misses=decomp["kernel"]["JitCacheMisses"],
                # the second (steady-state) burst is the compile-share
                # regression artifact: with AOT warmup these must hold
                # at 0 misses / <10% compile share
                trace_steady_jit_cache_misses=steady.get(
                    "jit_cache_misses"),
                trace_steady_compile_share=steady.get("compile_share"),
                # ISSUE 3 steady gates: h2d share of wall with the
                # device-resident cluster state, and the dirty-row
                # upload ratio (delta bytes / full-re-upload bytes)
                trace_steady_h2d_share=steady.get("h2d_share"),
                trace_dirty_row_ratio=steady.get(
                    "dirty_row_upload_ratio"),
                trace_wave_fill_ratio=decomp.get("wave", {}).get(
                    "fill_ratio"),
                trace_park_latency_p99_ms=decomp.get("wave", {}).get(
                    "park_latency_p99_ms"),
                # ISSUE 5 steady gates: total Python-scheduling share
                # (sched-host + its sub-decomposed slices) and the
                # feasibility mask-program cache hit ratio
                trace_steady_sched_host_share=steady.get(
                    "sched_host_share"),
                # ISSUE 10: the reconcile slice's own trajectory line
                # (the fused single-pass classifier's share of steady
                # wall)
                trace_steady_reconcile_share=steady.get(
                    "reconcile_share"),
                trace_feasibility_hit_ratio=steady.get(
                    "feasibility_hit_ratio"),
                # ISSUE 6 steady gates: plan-path share of steady wall
                # (applier + deferred post + fsm), the average plans
                # per batched raft entry, the group-commit fallback
                # count (must be 0 on the lean burst), and the steady
                # burst throughput vs the ISSUE 6 floor (>= 200
                # evals/s on the CPU backend, ~1.5x the PR5 range) —
                # the floor gates only where it is defined
                trace_steady_plan_share=steady.get("plan_share"),
                trace_plan_group_size=steady.get("plan_group_size"),
                trace_plan_group_fallbacks=steady.get(
                    "plan_group_fallbacks"),
                trace_steady_evals_per_sec=decomp.get("evals_per_sec"),
                # ISSUE 14 steady keys: sharded-dispatch coverage of
                # the steady burst (launches > 0 whenever a mesh
                # exists, single-device fallbacks gated 0 — a CPU
                # bench box without use_device_mesh emits 0/0)
                trace_steady_sharded_launches=steady.get(
                    "sharded_wave_launches"),
                trace_steady_sharded_fallbacks=steady.get(
                    "sharded_wave_fallbacks"),
                # wave-critical device interactions per steady wave:
                # ``joint`` and its eager result fetch
                trace_steady_dispatches_per_wave=steady.get(
                    "dispatches_per_wave"),
            )
            # ISSUE 8: the steady burst's e2e latency distribution +
            # tail attribution (TRACE_DECOMP gains the "tail" section;
            # these are its headline lines), and the BOX-RELATIVE
            # steady floor — the absolute 200 evals/s literal gated on
            # host speed, not on the system (see STEADY_FLOOR_* above)
            host_score = trace_report.host_speed_score()
            floor = STEADY_FLOOR_EVALS_PER_SEC * (
                host_score / STEADY_FLOOR_REF_HOST_SCORE)
            tail = decomp.get("tail", {})
            tail_segments = tail.get("segments", {})
            em.update(
                trace_host_speed_score=round(host_score),
                trace_steady_floor=round(floor, 1),
                trace_steady_floor_ok=(
                    decomp.get("evals_per_sec", 0.0) >= floor
                    if decomp.get("backend") == "cpu" else None),
                trace_steady_e2e_p50_ms=steady.get("e2e_p50_ms"),
                trace_steady_e2e_p99_ms=steady.get("e2e_p99_ms"),
                trace_tail_p50_coverage=tail.get("p50_coverage"),
                trace_tail_p99_coverage=tail.get("p99_coverage"),
                trace_tail_p99_top=_tail_top(tail_segments),
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: trace decomposition failed ({e})",
                  file=sys.stderr)
    else:
        print("bench budget: skipping trace decomposition "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 8 / ROADMAP open item 4: the standing contention cell —
    # sustained eval ingest under a heartbeat storm, judged by the e2e
    # latency distribution. trace_e2e_p99_ms is the number the
    # scheduler-worker horizontal-scale work gates on; the flight
    # recorder must capture >= 1 slow-eval tree (the tail is being
    # recorded, not just counted).
    if budget.remaining() > 120:
        try:
            _phase("tail contention cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            cell = trace_report.run_contention_burst(
                deadline_s=min(budget.share(0.25), 150.0))
            tail = cell.get("tail", {})
            em.update(
                contention_evals_per_sec=cell["evals_per_sec"],
                contention_allocs=(f"{cell['allocs_placed']}/"
                                   f"{cell['allocs_wanted']}"),
                contention_heartbeats_per_sec=cell[
                    "heartbeats_per_sec"],
                trace_e2e_p50_ms=cell["e2e_p50_ms"],
                trace_e2e_p99_ms=cell["e2e_p99_ms"],
                trace_tail_slow_captures=cell["slow_trees_captured"],
                trace_tail_contention_p99_top=_tail_top(
                    tail.get("segments", {})),
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: contention cell failed ({e})",
                  file=sys.stderr)
    else:
        print("bench budget: skipping contention cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 11 / ROADMAP open item 4: the standing FLEET cell, grown
    # to the ISSUE 20 flagship shape — 100k simulated clients (ring
    # cursors + heartbeat storm + held blocking queries) spread across
    # a REAL 3-server cluster while the steady eval burst runs, with a
    # reader storm mixing stale/default/linearizable across every
    # server. The trajectory lines are fleet_heartbeats_per_sec /
    # fleet_watch_wakeups_per_sec / fleet_stream_deliver_p99_ms /
    # fleet_e2e_p99_ms plus the read plane's fleet_read_* split; the
    # held-flags gate box-relative (emitted, like
    # trace_steady_floor_ok, so fast and slow bench hosts stay
    # comparable) except fleet_read_follower_share_ok, whose 0.66
    # floor is scale-free. The flagship shape is documented in
    # docs/PERF.md "The serving plane" / "Follower reads".
    if budget.remaining() > 120:
        try:
            _phase("fleet cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            fleet = trace_report.run_fleet_burst(
                n_clients=FLEET_CLIENTS, n_servers=FLEET_SERVERS,
                deadline_s=min(budget.share(0.25), 180.0))
            host_score = trace_report.host_speed_score()
            scale = STEADY_FLOOR_REF_HOST_SCORE / max(host_score, 1.0)
            deliver_ceiling = FLEET_DELIVER_P99_REF_MS * scale
            e2e_ceiling = FLEET_E2E_P99_REF_MS * scale
            staleness_ceiling = FLEET_READ_STALENESS_P99_REF_MS * scale
            serving = fleet.get("serving", {})
            em.update(
                fleet_clients=fleet["clients"],
                fleet_servers=fleet["servers"],
                fleet_heartbeats_per_sec=fleet["heartbeats_per_sec"],
                fleet_watch_wakeups_per_sec=fleet[
                    "watch_wakeups_per_sec"],
                fleet_stream_deliver_p99_ms=fleet[
                    "stream_deliver_p99_ms"],
                fleet_stream_deliver_ok=(
                    fleet["stream_deliver_p99_ms"] <= deliver_ceiling),
                fleet_e2e_p99_ms=fleet["e2e_p99_ms"],
                fleet_e2e_p99_held=(
                    fleet["e2e_p99_ms"] <= e2e_ceiling),
                fleet_evals_per_sec=fleet["evals_per_sec"],
                fleet_allocs=(f"{fleet['allocs_placed']}/"
                              f"{fleet['allocs_wanted']}"),
                fleet_lost_events=serving.get("stream", {}).get(
                    "lost_events", 0),
                fleet_heartbeat_coalesce_ratio=serving.get(
                    "heartbeat", {}).get("coalesce_ratio", 0.0),
                fleet_reads=fleet["reads"],
                fleet_read_follower_share=fleet["read_follower_share"],
                fleet_read_follower_share_ok=(
                    fleet["read_follower_share"]
                    >= FLEET_READ_FOLLOWER_SHARE_FLOOR),
                fleet_read_served_leader=fleet["read_served"]["leader"],
                fleet_read_served_follower=fleet[
                    "read_served"]["follower"],
                fleet_read_forwards=fleet["read_forwards"],
                fleet_read_demotions=fleet["read_demotions"],
                fleet_read_lease_fast=fleet["read_lease_fast"],
                fleet_read_stale_rejects=fleet["read_stale_rejects"],
                fleet_read_unavailable_503s=fleet[
                    "read_unavailable_503s"],
                fleet_read_staleness_p99_ms=fleet[
                    "read_staleness_p99_ms"],
                fleet_read_staleness_ok=(
                    fleet["read_staleness_p99_ms"]
                    <= staleness_ceiling),
                fleet_stale_violations=fleet["stale_violations"],
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: fleet cell failed ({e})",
                  file=sys.stderr)
    else:
        print("bench budget: skipping fleet cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 20: the read-plane mini smoke — a durable 3-server
    # cluster; a stale read lands on a follower with bounded
    # last-contact attribution, a default read forwards its fence
    # across an injected leader step-down, and a linearizable read
    # demotes to the quorum barrier under a forced lease lapse. The
    # verdict rides BENCH_*.json so a routing regression reads as
    # readplane_ok=false, not as silent follower-share drift.
    # Reproduce with trace_report.run_readplane_smoke().
    if budget.remaining() > 30:
        try:
            _phase("readplane smoke")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            rp = trace_report.run_readplane_smoke()
            em.update(
                readplane_ok=rp["ok"],
                readplane_stale_ok=rp["stale_ok"],
                readplane_default_ok=rp["default_ok"],
                readplane_demote_ok=rp["demote_ok"],
                readplane_stale_last_contact_ms=rp[
                    "stale_last_contact_ms"],
                readplane_forwards=rp["default_forwards"],
                readplane_demotions=rp["demotions"],
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: readplane smoke failed ({e})",
                  file=sys.stderr)
    else:
        print("bench budget: skipping readplane smoke "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 14 / ROADMAP open item 1: the MESH cell — the C2M replay
    # shape grown to 100k heterogeneous nodes / 1M resident allocs,
    # scheduled through the live wave launcher with the node axis
    # sharded over the device mesh, dirty-row advancement staying
    # sharded between waves. mesh_parity_ok + mesh_no_full_gather_ok
    # + mesh_unsharded_fallbacks==0 are the acceptance lines;
    # mesh_evals_per_sec is the scale trajectory (box-relative floor,
    # like the steady burst's).
    if budget.remaining() > 90:
        try:
            _phase("mesh cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            cell = trace_report.run_mesh_burst(
                deadline_s=min(budget.share(0.3), 60.0))
            host_score = trace_report.host_speed_score()
            floor = MESH_FLOOR_EVALS_PER_SEC * (
                host_score / MESH_FLOOR_REF_HOST_SCORE)
            em.update(
                mesh_devices=cell["devices"],
                mesh_nodes=cell["nodes"],
                mesh_allocs=cell["allocs_resident"],
                mesh_evals_per_sec=cell["evals_per_sec"],
                mesh_evals_floor=round(floor, 1),
                mesh_evals_floor_ok=(
                    cell["evals_per_sec"] >= floor
                    if cell["backend"] == "cpu" else None),
                mesh_wave_ms_p50=cell["wave_ms_p50"],
                mesh_collective_share=cell["collective_share"],
                mesh_dirty_row_ratio=cell["dirty_row_upload_ratio"],
                mesh_d2h_bytes_per_wave=cell["d2h_bytes_per_wave"],
                mesh_no_full_gather_ok=cell["no_full_gather_ok"],
                mesh_sharded_launches=cell["sharded_launches"],
                mesh_unsharded_fallbacks=cell["sharded_fallbacks"],
                mesh_parity_ok=cell["parity_ok"],
                mesh_jit_cache_misses=cell["jit_cache_misses"],
                mesh_fused_launches=cell["fused_launches"],
                mesh_fused_fallbacks=cell["fused_fallbacks"],
                mesh_dispatches_per_wave=cell["dispatches_per_wave"],
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: mesh cell failed ({e})",
                  file=sys.stderr)
    else:
        print("bench budget: skipping mesh cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 16: the store cell — the MVCC StateStore alone at the mesh
    # cell's population (100k node rows), a snapshot storm under full
    # write load. store_snapshot_p99_us <= 50µs is the acceptance line
    # (snapshot() is one root-pointer read, O(1) at any table size);
    # store_read_lock_share ~0 is the lock-free-reads proof, measured
    # via the lock witness's hold histograms during a pure read storm.
    if budget.remaining() > 90:
        try:
            _phase("store cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            cell = trace_report.run_store_burst(
                deadline_s=min(budget.share(0.15), 30.0))
            em.update(
                store_nodes=cell["nodes"],
                store_allocs=cell["allocs_resident"],
                store_snapshot_p99_us=cell["snapshot_p99_us"],
                store_write_txn_p99_us=cell["write_txn_p99_us"],
                store_read_lock_share=cell["read_lock_share"],
            )
            if not cell["isolation_ok"]:
                print("warning: store cell isolation check FAILED "
                      "(pinned snapshot moved under writes)",
                      file=sys.stderr)
            if cell["snapshot_p99_us"] > 50.0:
                print("warning: store_snapshot_p99_us "
                      f"{cell['snapshot_p99_us']} exceeds the 50µs "
                      "gate", file=sys.stderr)
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: store cell failed ({e})", file=sys.stderr)
    else:
        print("bench budget: skipping store cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 17: the worker cell — A/B the multi-process scheduler
    # plane (scheduler_workers=4, snapshot frames + eval leases over
    # IPC) against the in-process 4-thread baseline on the same steady
    # burst. worker_speedup is the headline (gate: >= 1.5x on a
    # >= 4-core host); parity + the 0-jit-miss / 0-fallback steady
    # gates make a speedup that costs placement correctness a FAILURE,
    # not a win. Reproduce with trace_report.run_worker_burst().
    if budget.remaining() > 180:
        try:
            _phase("worker cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            cell = trace_report.run_worker_burst(
                deadline_s=min(budget.share(0.3), 150.0))
            em.update(
                worker_procs=cell["procs"],
                worker_evals_per_sec=cell["evals_per_sec"],
                worker_evals_per_sec_baseline=cell[
                    "evals_per_sec_baseline"],
                worker_speedup=cell["speedup"],
                worker_lease_reissues=cell["lease_reissues"],
                worker_ipc_p99_ms=cell["ipc_p99_ms"],
                worker_parity_ok=1 if cell["parity_ok"] else 0,
            )
            if not cell["parity_ok"]:
                print("warning: worker cell placement parity FAILED "
                      "(speedup is void without it)", file=sys.stderr)
            if cell["jit_cache_misses"]:
                print("warning: worker cell steady burst had "
                      f"{cell['jit_cache_misses']} jit cache misses",
                      file=sys.stderr)
            if cell["plan_group_fallbacks"]:
                print("warning: worker cell steady burst had "
                      f"{cell['plan_group_fallbacks']} plan-group "
                      "fallbacks", file=sys.stderr)
            if cell["leases_leaked"]:
                print("warning: worker cell leaked "
                      f"{cell['leases_leaked']} generation leases "
                      "after shutdown", file=sys.stderr)
            if cell["speedup"] < 1.5 and os.cpu_count() >= 4:
                print("warning: worker_speedup "
                      f"{cell['speedup']} below the 1.5x gate on a "
                      f"{os.cpu_count()}-core host", file=sys.stderr)
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: worker cell failed ({e})", file=sys.stderr)
    else:
        print("bench budget: skipping worker cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 18: the raft cell — pipelined AppendEntries
    # (max_in_flight=8) A/B'd against the synchronous send->ack->send
    # replicator on the same burst under injected 5ms per-peer send
    # latency. raft_speedup and raft_lag_improvement are the headline
    # (gate: both >= 2x); raft_logs_identical makes a throughput win
    # that diverges a replica a FAILURE. Reproduce with
    # trace_report.run_raft_burst() (docs/PERF.md).
    if budget.remaining() > 120:
        try:
            _phase("raft cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            cell = trace_report.run_raft_burst()
            em.update(
                raft_seed=cell["seed"],
                raft_applies_per_sec=cell["applies_per_sec"],
                raft_applies_per_sec_sync=cell["applies_per_sec_sync"],
                raft_speedup=cell["speedup"],
                raft_lag_improvement=cell["lag_improvement"],
                raft_speedup_ok=1 if cell["speedup_ok"] else 0,
                raft_quorum_p99_ms=cell["pipelined"]["quorum_p99_ms"],
                raft_quorum_p99_ms_sync=cell["sync"]["quorum_p99_ms"],
                raft_pipeline_drains=cell["pipelined"][
                    "pipeline_drains"],
                raft_logs_identical=(
                    1 if cell["logs_identical"] else 0),
            )
            if not cell["logs_identical"]:
                print("warning: raft cell replica logs DIVERGED "
                      "(speedup is void without log equivalence)",
                      file=sys.stderr)
            if not cell["speedup_ok"]:
                print("warning: raft cell speedup "
                      f"{cell['speedup']}x / lag improvement "
                      f"{cell['lag_improvement']}x below the 2x gate",
                      file=sys.stderr)
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: raft cell failed ({e})", file=sys.stderr)
    else:
        print("bench budget: skipping raft cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 12: the chaos cell — every standing fault schedule
    # (leader-kill-mid-wave, plan-commit raft failure, crash-and-drop)
    # against a live 3-node raft cluster, pinned seed, convergence
    # invariants asserted after quiesce. chaos_evals_converged_ok is
    # the acceptance line: 1 means every schedule converged with zero
    # invariant violations. Reproduce any failure with
    # trace_report.run_chaos_burst(schedule=<name>, seed=chaos_seed)
    # (docs/ROBUSTNESS.md).
    if budget.remaining() > 300:
        try:
            _phase("chaos cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            # the schedules run sequentially, each paying warmup
            # (~deadline/2) + burst deadline + settle — size ALL of
            # those from the remaining budget (leaving headroom for
            # the replay headline), not just the burst phase
            n_schedules = len(trace_report.CHAOS_SCHEDULES)
            per_schedule = max(
                (budget.remaining() - 90.0) / n_schedules, 60.0)
            suite = trace_report.run_chaos_suite(
                deadline_s=min(max(per_schedule * 0.4, 30.0), 90.0),
                settle_s=min(max(per_schedule * 0.25, 20.0), 60.0),
                timeline_path=os.path.join(REPO, "CHAOS_TIMELINE.json"))
            tl = suite["timeline"]
            em.update(
                chaos_seed=suite["seed"],
                chaos_evals_converged_ok=(
                    1 if suite["converged_ok"] else 0),
                chaos_faults_fired=suite["faults_fired"],
                chaos_violations=suite["violations"][:8],
                chaos_schedule_stats={
                    name: {
                        "converged": r["converged_ok"],
                        "evals_per_sec": r["evals_per_sec"],
                        "faults_fired": r["faults_fired"],
                        "failover_resumes": r["failover_resumes"],
                        "nodes_down": r["nodes_down"],
                        "stream_lost_markers": r["stream_lost_markers"],
                        "plan_rejections": r["plan_rejections"],
                    }
                    for name, r in suite["schedules"].items()},
                # ISSUE 15: the failover timeline's attribution lines —
                # CHAOS_TIMELINE.json carries the full causally-ordered
                # artifact; these are its CI-gated trend keys
                timeline_failovers=tl["failovers"],
                timeline_events=tl["events"],
                timeline_attributed_share=tl["attributed_share"],
                timeline_attributed_ok=(
                    1 if tl["attributed_share"] >= 0.9 else 0),
                timeline_phase_ms=tl["phase_ms_max"],
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: chaos cell failed ({e})", file=sys.stderr)
    else:
        print("bench budget: skipping chaos cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    # ISSUE 13: the restart cell — kill→restart recovery through the
    # durability plane (torn-write kill + clean leader kill against a
    # data_dir-backed 3-node cluster) plus the seeded torn-tail fuzz.
    # restart_converged_ok is the acceptance line: 1 means every
    # recovery invariant held (no acked write lost, usage bit-identity
    # on restarted replicas, no double-vote, explicit stream resume)
    # AND no fuzz seed ever silently diverged. Reproduce with
    # trace_report.run_restart_chaos(seed=restart_seed)
    # (docs/ROBUSTNESS.md "Durability").
    if budget.remaining() > 180:
        try:
            _phase("restart cell")
            sys.path.insert(0, os.path.join(REPO, "bench"))
            import trace_report

            cell = trace_report.run_restart_chaos(
                deadline_s=min(budget.share(0.3), 120.0),
                settle_s=min(budget.share(0.15), 60.0),
                timeline_path=os.path.join(REPO, "CHAOS_TIMELINE.json"))
            fuzz = trace_report.run_torn_tail_fuzz(seeds=200)
            em.update(
                restart_seed=cell["seed"],
                restart_converged_ok=(
                    1 if cell["converged_ok"]
                    and fuzz["silent_divergences"] == 0 else 0),
                restart_recovery_ms=cell["recovery_ms_max"],
                restart_replayed_entries=cell["replayed_entries"],
                restart_fsync_p99_ms=cell["fsync_p99_ms"],
                restart_violations=cell["violations"][:8],
                restart_torn_fuzz_seeds=fuzz["seeds"],
                restart_torn_fuzz_silent_divergences=fuzz[
                    "silent_divergences"],
                # the restart leg's failover timeline attribution
                # (merged into the same CHAOS_TIMELINE.json artifact)
                timeline_restart_attributed_share=cell[
                    "timeline"]["attribution"]["share"],
            )
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: restart cell failed ({e})",
                  file=sys.stderr)
    else:
        print("bench budget: skipping restart cell "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)

    replay = None
    if planes is not None and budget.remaining() <= 60:
        print("bench budget: skipping C2M replay headline "
              f"({budget.remaining():.0f}s left)", file=sys.stderr)
    if planes is not None and budget.remaining() > 60:
        try:
            _phase("C2M replay headline")
            replay = run_replay(planes, budget_s=budget.share(0.6))
        except Exception as e:                   # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"warning: replay bench failed ({e}); "
                  "reporting synthetic only", file=sys.stderr)
        if replay is not None:
            # headline becomes the C2M replay (BASELINE.md's metric
            # definition — heterogeneous persisted cluster through the
            # real state store)
            em.update(
                metric=("scheduler evals/sec (C2M replay: 10k "
                        "heterogeneous nodes / 100k allocs, "
                        "10 placements/eval, binpack)"),
                value=round(replay["evals_per_sec"], 2),
                kernel=replay["kernel"],
                vs_baseline=round(replay["vs_baseline"], 2),
                replay_nodes=replay["replay_nodes"],
                replay_allocs=replay["replay_allocs"],
                replay_jobs=replay["replay_jobs"],
                replay_invalid=replay["invalid"],
                replay_fallback=replay["fallback"],
            )
        # the remaining BASELINE.md timed configs: device + preemption
        cluster, snap, used_cpu, used_mem, used_disk, asks, _ = planes
        if replay is not None and budget.remaining() <= 90:
            print("bench budget: skipping device/preemption cells "
                  f"({budget.remaining():.0f}s left)", file=sys.stderr)
        if replay is not None and budget.remaining() > 90:
            try:
                _phase("device cell")
                cells = run_replay_device(
                    cluster, snap, used_cpu, used_mem, used_disk)
                em.update(**{
                    k: round(v, 2) if isinstance(v, float) else v
                    for k, v in cells.items()})
            except Exception as e:               # noqa: BLE001
                print(f"warning: device cell failed: {e}", file=sys.stderr)
        if replay is not None and budget.remaining() <= 60:
            print("bench budget: skipping preemption cell "
                  f"({budget.remaining():.0f}s left)", file=sys.stderr)
        if replay is not None and budget.remaining() > 60:
            try:
                _phase("preemption cell")
                cells = run_replay_preemption(
                    cluster, snap, used_cpu, used_mem, asks)
                em.update(**{
                    k: round(v, 2) if isinstance(v, float) else v
                    for k, v in cells.items()})
            except Exception as e:               # noqa: BLE001
                print(f"warning: preemption cell failed: {e}",
                      file=sys.stderr)

    em.line["budget_spent_s"] = round(budget.spent(), 1)
    em.flush(final=True)


if __name__ == "__main__":
    main()
