"""Cross-eval kernel-launch coalescing: one device launch per wave.

The live half of the eval-batching design (SURVEY.md section 7 step 5).
The broker hands a worker B compatible evaluations (`dequeue_batch`);
the worker runs each eval's scheduler on its own thread against one
shared snapshot (the reference's concurrency axis, nomad/worker.go:386,
collapsed into one process). Every scheduler still thinks it owns the
device: when it reaches a placement launch, the request parks here
instead of dispatching. Once every still-running eval of the batch is
parked (or finished), the wave fires as ONE ``jax.vmap``'d kernel call
and each thread resumes with its slice of the output.

Why this is exact: ``KernelIn`` always carries every plane —
``KernelFeatures`` only selects which planes the *compiled program
reads* (ops/kernel.py). A wave compiles the union of its members'
feature sets; members that didn't ask for a feature provide neutral
planes (zero asks, -1 ids, inactive stanzas), which the kernel defines
to be no-ops. So batching changes arithmetic batching only, never
placement semantics.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from nomad_tpu.ops.kernel import (
    TOPK,
    KernelFeatures,
    KernelIn,
    KernelOut,
    LaunchOrigin,
    canonical_features,
    features_key,
    fused_wave_supported,
    launch_attrs,
    pad_steps,
    place_taskgroups_joint_jit,
    real_steps,
    unpack_fused_wave,
)
from nomad_tpu.telemetry.histogram import histograms, percentile
from nomad_tpu.telemetry.kernel_profile import launch_seq, profiler
from nomad_tpu.telemetry.trace import tracer
from nomad_tpu.tensors.device_state import default_device_state
from nomad_tpu.utils.faultpoints import fault
from nomad_tpu.utils.wavecohort import wave_cohorts
from nomad_tpu.utils.witness import witness_lock

#: B is bucketed to limit recompiles. Coarse on purpose: every
#: (wave bucket, step bucket, features) combination is a separate XLA
#: compile, and a cold TPU compile is tens of seconds — paying a few
#: inert filler members per wave is far cheaper than another variant.
#: 32 earns its slot: it is the default worker batch size, and the
#: joint kernel's step scan is O(wave x steps) — padding 32 to 64
#: doubled the live path's per-wave device time for nothing.
_WAVE_BUCKETS = (1, 4, 16, 32, 64, 256)

#: When set (configure_wave_mesh), DIRECT launch_wave calls run the
#: joint program with the node axis sharded over this mesh's devices —
#: per-step argmax/top-k become ICI collectives (SURVEY.md section
#: 2.10). None = single-device dispatch. Results are identical either
#: way. Live servers do NOT use this global: each threads its OWN
#: ``Server.wave_mesh`` through its workers' coalescers, so
#: co-resident servers (with different meshes, or one opted out)
#: cannot affect each other.
_WAVE_MESH = None
#: sentinel: "caller did not choose" — fall back to the global; a
#: coalescer always chooses (its server's mesh, possibly None=unsharded)
_USE_GLOBAL = object()
#: waves dispatched through the sharded path (asserted by tests;
#: the richer accounting lives in ``sharded_wave_stats`` below)
sharded_wave_launches = 0


class _ShardedWaveStats:
    """Sharded-dispatch accounting (exported as the
    ``nomad_tpu_wave_sharded_*`` Prometheus series by
    telemetry/exporter.py; reset with telemetry.reset()).

    ``launches`` counts waves that ran the joint program with the node
    axis sharded over a mesh; ``fallbacks`` counts waves that HAD a
    mesh but dispatched single-device anyway (a node axis the device
    count does not divide) — on a healthy mesh server this must sit at
    ZERO, and the steady-burst gate holds it there. ``mesh_devices``
    is the device count of the newest sharded launch (0 = never
    sharded)."""

    def __init__(self) -> None:
        self._lock = witness_lock("ShardedWaveStats._lock")
        self.launches = 0
        self.fallbacks = 0
        self.mesh_devices = 0

    def note_launch(self, devices: int) -> None:
        with self._lock:
            self.launches += 1
            self.mesh_devices = devices

    def note_fallback(self, devices: int) -> None:
        with self._lock:
            self.fallbacks += 1
            self.mesh_devices = devices

    def reset(self) -> None:
        with self._lock:
            self.launches = 0
            self.fallbacks = 0

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "launches": self.launches,
                "fallbacks": self.fallbacks,
                "mesh_devices": self.mesh_devices,
            }


#: process-wide sharded-wave stats (coalescers are per-chunk and too
#: short-lived to carry their own history, like wave_stats)
sharded_wave_stats = _ShardedWaveStats()

class _FusedWaveStats:
    """Fused-dispatch accounting of MESH waves (exported as the
    ``nomad_tpu_wave_fused_*`` Prometheus series; reset with
    telemetry.reset()).

    ``launches`` counts sharded waves that ran ``fused_wave_sharded``;
    ``fallbacks`` counts sharded waves that ran ``joint_sharded`` — an
    unsupported feature union (spreads/devices/cores/network) or a
    node shard too narrow for the local top-k merge. Both are routing
    decisions made from the wave itself BEFORE dispatch
    (``wave_program``): a program the router chose that then raises
    is an error, never a fallback. Steady lean traffic fits the
    envelope, so the mesh steady-burst gate holds fallbacks at ZERO.
    A one-device wave has one program and counts under neither."""

    def __init__(self) -> None:
        self._lock = witness_lock("FusedWaveStats._lock")
        self.launches = 0
        self.fallbacks = 0

    def note_launch(self) -> None:
        with self._lock:
            self.launches += 1

    def note_fallback(self) -> None:
        with self._lock:
            self.fallbacks += 1

    def reset(self) -> None:
        with self._lock:
            self.launches = 0
            self.fallbacks = 0

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "launches": self.launches,
                "fallbacks": self.fallbacks,
            }


#: process-wide fused-wave stats (same lifetime rationale as above)
fused_wave_stats = _FusedWaveStats()

#: JointOut fields the launcher fetches to host EAGERLY per wave (the
#: wave-critical d2h payload): the per-step placements the scheduler
#: walks immediately plus the per-member metric scalars. The top-k
#: score planes — the bulk of the old payload, [T, TOPK] x 2 — stay ON
#: DEVICE as lazy slices (``_WaveTopK``): they feed only AllocMetric
#: score_meta, whose materialization is deferred onto the plan window
#: (scheduler/stack.py), so their d2h overlaps the next wave's execute
#: instead of riding the wave-critical path.
_JOINT_FETCH_FIELDS = (
    "chosen", "scores", "found",
    "nodes_evaluated", "nodes_feasible",
    "exhausted_cpu", "exhausted_mem", "exhausted_disk",
    "exhausted_ports", "exhausted_devices", "exhausted_cores",
)


class _WaveTopK:
    """One wave's top-k planes, resident on device until first use.

    All members share the holder; the first score_meta materialization
    (inside the batching worker's plan window) fetches BOTH planes with
    one transfer each and caches the host copy for every other member.
    Bytes are metered at fetch time like any other d2h.
    """

    __slots__ = ("_idx", "_scores", "_host", "_lock", "_fetching",
                 "_done")

    def __init__(self, idx_dev, scores_dev) -> None:
        self._idx = idx_dev
        self._scores = scores_dev
        self._host = None
        self._lock = witness_lock("WaveTopK._lock")
        self._fetching = False
        self._done = threading.Event()

    def host(self):
        # claim-then-fetch: the lock only arbitrates WHO fetches; the
        # d2h transfer itself runs unlocked (graftcheck R2 — a device
        # fetch under a lock stalls every other member's deferred
        # score_meta drain behind the PCIe transfer instead of letting
        # them park on the event). Losers wait on the claim's event
        # and read the cached host copy. Each claim gets a FRESH
        # event (captured under the lock): a failed fetch's set() then
        # cannot leave a stale-set event that would busy-spin waiters
        # through the retry claim's whole transfer.
        while True:
            with self._lock:
                if self._host is not None:
                    return self._host
                if not self._fetching:
                    self._fetching = True
                    done = self._done = threading.Event()
                    break
                done = self._done
            done.wait()
        try:
            # deferred-drain seam (chaos plane): the shared top-k fetch
            # runs in the plan window; a failure here hits whichever
            # member claimed the fetch — losers retry the claim (the
            # while-loop above) so one injected error never wedges the
            # whole wave's score_meta drain
            fault("wave.d2h.drain")
            # its own name: this copy runs later, in the plan window,
            # and is far larger than the wave-critical kernel.d2h
            with tracer.span("kernel.d2h.topk") as sp:
                idx = np.asarray(self._idx)
                scores = np.asarray(self._scores)
                sp.set(bytes=idx.nbytes + scores.nbytes)
            profiler.add_bytes("d2h", idx.nbytes + scores.nbytes)
            # counted in the dispatch series but EXCLUDED from the
            # steady dispatches_per_wave key: the drain runs in the
            # plan window, overlapping the next wave's execute — it
            # is not on the wave-critical path the key measures
            profiler.count_dispatch("topk_drain")
            self._host = (idx, scores)
            # release the device buffers
            self._idx = self._scores = None
        finally:
            with self._lock:
                self._fetching = False
            done.set()
        return self._host


class _TopKSlice:
    """A member's lazy [k, TOPK] view of the wave's top-k plane.

    Quacks enough like an array for the scheduler's deferred
    score_meta fill: ``np.asarray`` (via ``__array__``) and row
    indexing both resolve through the shared wave fetch.
    """

    __slots__ = ("_wave", "_field", "_start", "_stop")

    def __init__(self, wave: _WaveTopK, field: int, start: int,
                 stop: int) -> None:
        self._wave = wave
        self._field = field          # 0 = idx, 1 = scores
        self._start = start
        self._stop = stop

    def _resolve(self):
        return self._wave.host()[self._field][self._start:self._stop]

    def __array__(self, dtype=None, copy=None):
        a = self._resolve()
        return a if dtype is None else a.astype(dtype)

    def __getitem__(self, item):
        return self._resolve()[item]

    def __len__(self) -> int:
        return self._stop - self._start

#: node planes shipped once per wave (unbatched) when every member
#: shares them by identity: the cluster-static planes plus the wave
#: snapshot's gathered utilization (stack.py wave-shared build)
_SHAREABLE_FIELDS = (
    "cap_cpu", "cap_mem", "cap_disk", "free_cores", "shares_per_core",
    "avail_mbits", "free_dyn",
    "used_cpu", "used_mem", "used_disk", "used_cores", "used_mbits",
)

#: second sharing group: the WIDE ask planes (devices, spreads,
#: reserved-port conflicts, per-step penalty/preference pins) that
#: stay NEUTRAL for the common ask are frozen singletons
#: (ops/kernel.neutral_planes), so members share them by identity too.
#: They fork only when a member actually asks for devices/spreads/
#: rescheduling — rare in steady traffic, and they are the BULKIEST
#: per-member planes ([N, MAX_DEV_REQS], [S, N]).
#:
#: ``node_perm`` is deliberately NOT here: the shuffle permutation is
#: seeded per eval, so with shuffling on it is never identity-shared —
#: keeping it in this group forced EVERY live multi-member wave onto
#: the all-stacked layout, shipping B copies of dev_free/spread/count
#: planes that were in fact neutral singletons (the bulk of PR 2's
#: 30% h2d share). It ships always-stacked instead (one [B, N] i32
#: plane), which keeps the layout-variant count bounded.
_NEUTRAL_SHAREABLE_FIELDS = (
    "port_conflict", "dev_free", "dev_aff_score",
    "step_penalty", "step_preferred",
    "spread_active", "spread_even", "spread_weight",
    "spread_bucket", "spread_counts", "spread_desired",
)

#: third sharing group: the JOB-LOCAL [N] planes. A follow-up eval of
#: a job with live allocations forks job_tg_count/job_any_count (and a
#: rescheduled one the penalty plane) — common in steady traffic — and
#: used to drag the whole neutral group onto the stacked layout,
#: uploading B copies of the wide device/spread planes for a handful
#: of dirty members. Splitting the job planes into their own group
#: bounds that wave's extra upload to 4 x [B, N] instead of ~1MB.
#: Three all-or-nothing groups -> at most EIGHT layout variants per
#: (bucket, step, features) triple, all enumerable by the AOT warmup
#: lattice.
#:
#: ``base_mask`` joined the group with the feasibility compiler
#: (nomad_tpu/feasibility/): evals with no dynamic feasibility state
#: carry the mask-program cache's FROZEN array — members of equal job
#: specs (and, via content dedup, of any specs whose masks come out
#: equal) share it by identity, so the wave ships ONE base-mask plane
#: and the device broadcasts it to every member: the whole wave's base
#: masks from one dispatch. The frozen array rides the device-resident
#: frozen registry (frozen_ok lookup below), uploading once per
#: (node structure, constraint tree) ever.
_JOB_SHAREABLE_FIELDS = (
    "job_tg_count", "job_any_count", "penalty", "aff_score",
    "base_mask",
)


def wave_field_is_shared(field: str, shared: bool,
                         neutral_shared: bool,
                         job_shared: bool = True) -> bool:
    """Whether a KernelIn field ships UNBATCHED under the given wave
    layout flags. The single source of truth for the three sharing
    groups — the live launcher (``launch_wave``) and the AOT warmup's
    dummy-wave builder (ops/warmup.py) must agree EXACTLY, or warmup
    compiles programs the live path never hits."""
    return (shared and field in _SHAREABLE_FIELDS) or (
        neutral_shared and field in _NEUTRAL_SHAREABLE_FIELDS) or (
        job_shared and field in _JOB_SHAREABLE_FIELDS)


def configure_wave_mesh(mesh) -> None:
    """Route DIRECT launch_wave calls over ``mesh`` (None restores
    single-device dispatch). Live servers ignore this: they pass their
    own ``Server.wave_mesh`` through their coalescers."""
    global _WAVE_MESH
    _WAVE_MESH = mesh


def pad_wave(b: int) -> int:
    for w in _WAVE_BUCKETS:
        if b <= w:
            return w
    return ((b + 255) // 256) * 256


def union_features(features: List[KernelFeatures]) -> KernelFeatures:
    """Smallest feature set that serves every member (see module doc),
    canonicalized (ops/kernel.canonical_features) so near-identical
    waves land on one compiled variant instead of forking the jit
    cache per incidental feature combination."""
    return canonical_features(KernelFeatures(
        n_spreads=max(f.n_spreads for f in features),
        with_topk=any(f.with_topk for f in features),
        with_devices=any(f.with_devices for f in features),
        with_ports=any(f.with_ports for f in features),
        with_cores=any(f.with_cores for f in features),
        with_network=any(f.with_network for f in features),
        with_distinct=any(f.with_distinct for f in features),
        with_step_penalties=any(f.with_step_penalties for f in features),
        with_preferred=any(f.with_preferred for f in features),
        with_shuffle=any(f.with_shuffle for f in features),
    ))


def wave_program(mesh_size: int, n_nodes: int,
                 feats: KernelFeatures) -> str:
    """The one device program a wave runs, from what the launcher can
    observe of it: how many devices its mesh has (0 or 1: none), its
    padded node axis and its canonical feature union. The launcher,
    the AOT warmup (ops/warmup.py) and chip_smoke.py all ask this
    function; nothing else holds the rule.

    One device, or a node axis the mesh does not divide: ``joint``.
    A mesh: ``fused_wave_sharded`` for a feature union inside its
    envelope (ops/kernel.fused_wave_supported) on shards wide enough
    for the local top-k merge, ``joint_sharded`` otherwise."""
    if mesh_size < 2 or n_nodes % mesh_size:
        return "joint"
    if fused_wave_supported(feats) and n_nodes // mesh_size >= TOPK:
        return "fused_wave_sharded"
    return "joint_sharded"


def _pad_kin_steps(kin: KernelIn, k_max: int) -> KernelIn:
    """Pad the per-step planes to the wave's step count (neutral rows)."""
    from nomad_tpu.ops.kernel import neutral_step_planes

    k = int(kin.step_penalty.shape[0])
    if k == k_max:
        return kin
    n_pen, n_pref = neutral_step_planes(k)
    if kin.step_penalty is n_pen and kin.step_preferred is n_pref:
        # neutral singletons pad to the neutral singleton of the wave's
        # step count — identity (and so wave sharing) survives padding
        pen, pref = neutral_step_planes(k_max)
        return kin._replace(step_penalty=pen, step_preferred=pref)
    pen = np.full((k_max, kin.step_penalty.shape[1]), -1, np.int32)
    pen[:k] = np.asarray(kin.step_penalty)
    pref = np.full(k_max, -1, np.int32)
    pref[:k] = np.asarray(kin.step_preferred)
    return kin._replace(step_penalty=pen, step_preferred=pref)


class WaveStats:
    """Process-wide wave-shape observability (exported as Prometheus
    gauges by telemetry/exporter.py; reset with telemetry.reset()).

    ``fill_ratio`` = real members / padded wave slots — low fill means
    the coalescer fires before waves fill (deadline pressure) or the
    broker hands out ragged batches. ``park_latency`` percentiles are
    the rendezvous cost an eval thread pays waiting for its wave; the
    adaptive deadline exists to bound exactly this number."""

    def __init__(self) -> None:
        self._lock = witness_lock("WaveStats._lock")
        self.requests = 0
        self.launches = 0
        self.full_launches = 0
        self.deadline_launches = 0
        # park waits whose deadline would have armed but for a
        # participant of the batch that had not arrived yet
        # (LaunchCoalescer._expected)
        self.held_for_arrivals = 0
        self.members_sum = 0
        self.slots_sum = 0
        # placement steps handed to the device, real and padded, of
        # EVERY launch (a lone one too), the steps its program ran
        # (``executed_steps``), and the members the scheduler was
        # placing again (a retry against a refreshed state)
        self.steps_sum = 0
        self.padded_steps_sum = 0
        self.executed_steps_sum = 0
        self.relaunched_members_sum = 0
        # the device's wait between launches: at each launch's call,
        # the seconds since the last launch's outputs were ready (0
        # where another launch was still in flight), and how many
        # launches followed another (``launch_dispatched``)
        self.gap_seconds_sum = 0.0
        self.gaps = 0
        self._in_flight = 0
        self._last_ready: Optional[float] = None
        self._park_s: deque = deque(maxlen=4096)

    def launch_dispatched(self) -> None:
        """A launch is about to call its program (every launch, a lone
        one too): one clock read."""
        now = time.monotonic()
        with self._lock:
            if self._last_ready is not None:
                self.gaps += 1
                if not self._in_flight:
                    self.gap_seconds_sum += max(now - self._last_ready, 0.0)
            self._in_flight += 1

    def launch_ready(self) -> None:
        """The launch's outputs are ready (``kernel.execute`` ended) or
        its call raised: one clock read."""
        now = time.monotonic()
        with self._lock:
            self._in_flight -= 1
            self._last_ready = now

    def observe_wave(self, members: int, deadline_fired: bool,
                     steps: int = 0, padded_steps: int = 0,
                     executed_steps: int = 0, relaunched: int = 0) -> None:
        with self._lock:
            self.launches += 1
            self.members_sum += members
            self.slots_sum += pad_wave(members)
            if deadline_fired:
                self.deadline_launches += 1
            else:
                self.full_launches += 1
            self.steps_sum += steps
            self.padded_steps_sum += padded_steps
            self.executed_steps_sum += executed_steps
            self.relaunched_members_sum += relaunched

    def observe_lone(self, steps: int, padded_steps: int,
                     relaunched: bool) -> None:
        """A launch outside any wave (ops/kernel.default_kernel_launch):
        its steps count, it is no wave and fills no slots. Its program
        runs every padded step."""
        with self._lock:
            self.steps_sum += steps
            self.padded_steps_sum += padded_steps
            self.executed_steps_sum += padded_steps
            self.relaunched_members_sum += int(relaunched)

    def observe_park(self, seconds: float, held: bool = False) -> None:
        with self._lock:
            self.requests += 1
            self.held_for_arrivals += int(held)
            self._park_s.append(seconds)
        # the streaming histogram keeps the FULL distribution (the
        # deque above is a bounded recent window for the gauges)
        histograms.get("wave_park").record(seconds)

    def reset(self) -> None:
        with self._lock:
            self.requests = 0
            self.launches = 0
            self.full_launches = 0
            self.deadline_launches = 0
            self.held_for_arrivals = 0
            self.members_sum = 0
            self.slots_sum = 0
            self.steps_sum = 0
            self.padded_steps_sum = 0
            self.executed_steps_sum = 0
            self.relaunched_members_sum = 0
            self.gap_seconds_sum = 0.0
            self.gaps = 0
            self._last_ready = None
            self._park_s.clear()

    def snapshot(self) -> dict:
        with self._lock:
            # shared nearest-rank helper (telemetry/histogram.py): the
            # old int(len*0.99) indexing returned the MAX of a
            # 100-sample window as "p99"
            p50 = percentile(self._park_s, 0.5)
            p99 = percentile(self._park_s, 0.99)
            return {
                "requests": self.requests,
                "launches": self.launches,
                "full_launches": self.full_launches,
                "deadline_launches": self.deadline_launches,
                "held_for_arrivals": self.held_for_arrivals,
                "executed_steps": self.executed_steps_sum,
                "gap_seconds": self.gap_seconds_sum,
                "gaps": self.gaps,
                "fill_ratio": (self.members_sum / self.slots_sum
                               if self.slots_sum else 0.0),
                "park_latency_p50_ms": p50 * 1e3,
                "park_latency_p99_ms": p99 * 1e3,
            }


#: process-wide wave stats (all coalescers feed it; they are per-chunk
#: and too short-lived to carry their own history)
wave_stats = WaveStats()


class _LatencyEWMA:
    """Exponentially-weighted wave latency: the adaptive coalescer's
    deadline is a fraction of what a launch actually costs, so parking
    never dominates the device time it tries to amortize."""

    def __init__(self, alpha: float = 0.2) -> None:
        self._lock = witness_lock("LatencyEWMA._lock")
        self._alpha = alpha
        self._value: Optional[float] = None

    def update(self, seconds: float) -> None:
        with self._lock:
            if self._value is None:
                self._value = seconds
            else:
                self._value += self._alpha * (seconds - self._value)

    @property
    def value(self) -> Optional[float]:
        with self._lock:
            return self._value


#: EWMA of launch_wave wall seconds (compile transients included on
#: purpose: while variants still compile, waiting longer for fuller
#: waves is the right call)
wave_latency_ewma = _LatencyEWMA()

#: EWMA of "this launch was deadline-fired" (0/1 per launch). The
#: adaptive window is a fraction of the (device) wave latency — but
#: the device-resident cluster state made launches several times
#: cheaper, and a window that keeps shrinking with launch cost drops
#: below the members' host-prep spread and FRAGMENTS waves: partial
#: fire -> more launches -> lower fill -> more per-launch overhead
#: than the parking it saved. When deadline fires dominate, this
#: signal widens the window back toward the cap, so the coalescer
#: self-corrects instead of feeding back.
wave_deadline_ewma = _LatencyEWMA(alpha=0.25)

#: launches currently executing, token -> perf_counter start. A
#: long-running in-flight launch (a cold XLA compile) disarms the
#: adaptive deadline process-wide: the EWMA only learns about a slow
#: variant AFTER it finishes, but parked members must stop firing
#: partial waves INTO the transient (each would cold-compile its own
#: wave bucket).
_INFLIGHT_LOCK = witness_lock("coalesce._INFLIGHT_LOCK")
_INFLIGHT_STARTS: dict = {}


def _fused_fetch(fout, t_pad: int, b_pad: int):
    """Turn a fused wave's outputs into the launcher's eager host
    dict + lazy top-k holder. ONE packed-buffer readback — and no
    "wave_fetch" dispatch count: profiler.call already blocked on the
    fused program's outputs, so the copy rides the dispatch's own
    synchronization instead of being another device interaction."""
    with tracer.span("kernel.d2h") as sp:
        packed = np.asarray(fout.packed)
        sp.set(bytes=packed.nbytes)
    profiler.add_bytes("d2h", packed.nbytes)
    host = unpack_fused_wave(packed, t_pad, b_pad)
    return host, _WaveTopK(fout.topk_idx, fout.topk_scores)


def _oldest_inflight_age_s() -> float:
    with _INFLIGHT_LOCK:
        if not _INFLIGHT_STARTS:
            return 0.0
        oldest = min(_INFLIGHT_STARTS.values())
    return time.perf_counter() - oldest


def timed_call(*args, **kwargs):
    """``profiler.call`` for every device launch, wave or lone, with
    the wait between launches counted (``WaveStats.launch_dispatched``)."""
    wave_stats.launch_dispatched()
    try:
        return profiler.call(*args, **kwargs)
    finally:
        wave_stats.launch_ready()


def wave_step_pad(members: int, k_max: int) -> int:
    """Padded step count of a wave's device program: sized from the
    PADDED wave so the compiled shape depends only on (wave bucket,
    step bucket, features)."""
    return pad_steps(pad_wave(members) * k_max)


def executed_steps(program: str, steps: int, t_pad: int) -> int:
    """Placement steps a wave's program runs on the device: ``joint``
    and ``joint_sharded`` the real ones alone
    (ops/kernel.place_taskgroups_joint), ``fused_wave_sharded`` every
    padded one."""
    return t_pad if program == "fused_wave_sharded" else steps


def launch_wave(kins: List[KernelIn], k_steps: List[int],
                features: List[KernelFeatures],
                mesh=_USE_GLOBAL,
                origins: Optional[List[Optional[LaunchOrigin]]] = None,
                deadline_fired: bool = False,
                release: Optional[str] = None,
                released_by: str = "") -> List[KernelOut]:
    """Fire B launch requests as ONE joint device call; split results.
    One ``wave.launch`` span, the launch record, covers it: assembly,
    the call, the wait and the copies are its children.

    The wave runs the joint kernel (ops/kernel.place_taskgroups_joint):
    members' placement steps execute in arrival order over a shared
    capacity carry, so members see each other's placements — the
    serialized plan applier's semantics, on device.

    ``mesh``: shard the node axis over this mesh. A coalescer always
    passes its server's choice explicitly — including None for "this
    server opted out" — so co-resident servers never fight over the
    module global; only DIRECT calls (no mesh argument) fall back to
    ``configure_wave_mesh``'s global. ``origins`` (who asked, per
    member), ``deadline_fired`` and, from a coalescer, ``release`` (how
    the rendezvous completed: ``LaunchCoalescer._fire``) and
    ``released_by`` (the evaluation that completed it; the calling
    thread's trace id where empty) only feed the launch record.
    """
    seq = next(launch_seq)
    attrs = None
    if tracer.enabled:
        attrs = launch_attrs(seq, k_steps, origins, deadline_fired)
        if release is not None:
            ctx = tracer.context()
            attrs["release"] = release
            attrs["released_by"] = released_by or (ctx[0] if ctx else "")
    with tracer.span("wave.launch", attrs=attrs) as record:
        return _launch_wave(kins, k_steps, features, mesh, record)


def _launch_wave(kins: List[KernelIn], k_steps: List[int],
                 features: List[KernelFeatures], mesh,
                 record) -> List[KernelOut]:
    if mesh is _USE_GLOBAL:
        mesh = _WAVE_MESH
    # wave-launch seam (chaos plane): an injected failure lands on
    # EVERY member of the wave (the coalescer's _fire propagates it to
    # each parked request) — a crashed wave, mid-cohort; the armed
    # wavecohort window must expire and the broker must redeliver
    fault("wave.launch")
    with tracer.span("wave.assemble"):
        k_max = max(k_steps)
        feats = union_features(features)
        padded = [_pad_kin_steps(kin, k_max) for kin in kins]
        b_pad = pad_wave(len(padded))
        if b_pad > len(padded):
            # inert filler rows: first member with zero active steps
            filler = padded[0]._replace(n_steps=np.asarray(0, np.int32))
            padded = padded + [filler] * (b_pad - len(padded))
        # a mesh the node axis does not split evenly over (pad_bucket's
        # power-of-two floor, 64, covers every power-of-two slice, so
        # an exotic device count) dispatches on one device: counted,
        # and gated to zero on the steady burst
        n_nodes = int(np.asarray(padded[0].cap_cpu).shape[-1])
        mesh_size = int(mesh.size) if mesh is not None else 0
        program = wave_program(mesh_size, n_nodes, feats)
        wave_sharded = program != "joint"
        fused = program == "fused_wave_sharded"
        # stack on HOST (numpy): the jit call below uploads each stacked
        # leaf once; stacking device arrays would dispatch per leaf per
        # member. The big node planes (cluster capacity + the wave
        # snapshot's utilization) are usually IDENTICAL across members;
        # when every one of _SHAREABLE_FIELDS is identity-shared, they
        # ship UNBATCHED (the joint kernel broadcasts on device) so wave
        # upload bytes stay flat in wave size instead of B-fold —
        # sharded waves included: a resident sharded twin costs ZERO
        # upload, exactly like the single-device path. Three
        # all-or-nothing groups -> at most eight layouts per
        # (bucket, features) pair, enumerable by warmup either way.
        def _group_shared(fields) -> bool:
            return all(
                all(getattr(k, f) is getattr(padded[0], f)
                    for k in padded[1:])
                for f in fields
            )

        shareable = _group_shared(_SHAREABLE_FIELDS)
        neutral_shareable = _group_shared(_NEUTRAL_SHAREABLE_FIELDS)
        job_shareable = _group_shared(_JOB_SHAREABLE_FIELDS)

        if wave_sharded:
            from nomad_tpu.parallel.sharded import shared_field_spec

        def _stack_field(f, xs):
            if wave_field_is_shared(f, shareable, neutral_shareable,
                                    job_shareable):
                # device-resident twin when one exists (the cluster
                # state advanced at snapshot time, frozen neutral
                # singletons uploaded once): jit's device_put then
                # moves ZERO bytes for this leaf. The lookup carries
                # the wave's placement — a sharded wave is only served
                # mesh-placed twins (tensors/device_state.py), so the
                # jit's in_shardings never reshard. The snapshot group
                # is registry-only (frozen_ok=False): a STALE
                # snapshot's read-only gathered planes must ship as
                # host numpy, not masquerade as singletons.
                dev = default_device_state.lookup(
                    xs[0], frozen_ok=f not in _SHAREABLE_FIELDS,
                    spec=(shared_field_spec(f) if wave_sharded
                          else None),
                    mesh=mesh if wave_sharded else None)
                if dev is not None:
                    return dev
                return np.asarray(xs[0])
            return np.stack([np.asarray(x) for x in xs])

        stacked = KernelIn(*[
            _stack_field(f, [getattr(k, f) for k in padded])
            for f in KernelIn._fields
        ])

        # step layout: member 0's steps, then member 1's, ... (the
        # applier's serialization order = plan arrival order). The step
        # axis is sized from the PADDED wave (b_pad * k_max) so the
        # compiled shape depends only on (wave bucket, step bucket,
        # features) — retry waves of any real size reuse it. The joint
        # programs run only the real steps (a member's first n_steps of
        # its block), so an inert step costs the device nothing. Built
        # vectorized: the per-member python loop showed up at bench
        # wave sizes.
        t_pad = wave_step_pad(len(kins), k_max)
        ks = np.asarray(k_steps, np.int64)
        starts = np.concatenate(([0], np.cumsum(ks)[:-1]))
        offsets = starts.tolist()
        total = int(ks.sum())
        step_member = np.full(t_pad, -1, np.int32)
        step_local = np.zeros(t_pad, np.int32)
        member_of_step = np.repeat(np.arange(len(ks)), ks)
        step_member[:total] = member_of_step
        step_local[:total] = (np.arange(total)
                              - np.repeat(starts, ks))

    # the jit-cache identity the bucketing scheme promises: a repeat of
    # this key must NOT recompile (the profiler counts violations)
    wave_key = (b_pad, t_pad, n_nodes, shareable, neutral_shareable,
                job_shareable, feats)
    n_real = sum(min(int(np.asarray(kin.n_steps)), k)
                 for kin, k in zip(kins, k_steps))
    record.set(program=program, slots=b_pad, padded_steps=t_pad,
               executed_steps=executed_steps(program, n_real, t_pad),
               features=features_key(feats))
    t_launch = time.perf_counter()
    token = object()
    with _INFLIGHT_LOCK:
        _INFLIGHT_STARTS[token] = t_launch
    try:
        # an exception from the program the router chose PROPAGATES
        # (to every member of the wave, through the coalescer): a
        # compile or device error must read as an error, not as a
        # slower wave
        if wave_sharded:
            from nomad_tpu.parallel.sharded import (
                fused_sharded_entry,
                joint_sharded_entry,
            )

            global sharded_wave_launches
            sharded_wave_launches += 1
            sharded_wave_stats.note_launch(mesh_size)
            # host leaves pre-place with the jit's exact in_shardings
            # (the profiler's explicit upload would otherwise commit
            # them to one device and the call would pay a reshard);
            # step planes ship replicated, raw numpy on purpose
            entry = fused_sharded_entry if fused else joint_sharded_entry
            fn, kin_shardings, repl = entry(
                mesh, shareable, neutral_shareable, job_shareable)
            out = timed_call(
                program, fn,
                (stacked, step_member, step_local),
                (t_pad, feats),
                wave_key + (tuple(mesh.devices.flat),), jit_fn=fn,
                shardings=(kin_shardings, repl, repl),
            )
        else:
            if mesh is not None:
                sharded_wave_stats.note_fallback(mesh_size)
            out = timed_call(
                program, place_taskgroups_joint_jit,
                (stacked, jnp.asarray(step_member),
                 jnp.asarray(step_local)),
                (t_pad, feats),
                wave_key, jit_fn=place_taskgroups_joint_jit,
            )
        if fused:
            host, wave_topk = _fused_fetch(out, t_pad, b_pad)
            fused_wave_stats.note_launch()
        else:
            if wave_sharded:
                # a mesh wave outside the fused envelope, or on
                # shards too narrow for it
                fused_wave_stats.note_fallback()
            with tracer.span("kernel.d2h") as sp:
                # fetch ONLY the planes members consume immediately:
                # the per-step placements and the per-member metric
                # scalars. The joint kernel's final capacity carry
                # (a_cpu/a_mem/a_disk — full node planes) stays on
                # device (the live path commits through plans, never
                # through it), and the top-k planes stay on device
                # too — handed back as lazy slices whose one shared
                # fetch runs in the plan window.
                host = {
                    f: np.asarray(getattr(out, f))
                    for f in _JOINT_FETCH_FIELDS
                }
                d2h_bytes = sum(a.nbytes for a in host.values())
                sp.set(bytes=d2h_bytes)
            # the composite's wave-critical result drain is its own
            # device interaction on top of the program dispatch
            profiler.count_dispatch("wave_fetch")
            profiler.add_bytes("d2h", d2h_bytes)
            wave_topk = _WaveTopK(out.topk_idx, out.topk_scores)
    finally:
        with _INFLIGHT_LOCK:
            _INFLIGHT_STARTS.pop(token, None)
    wave_latency_ewma.update(time.perf_counter() - t_launch)
    results = []
    for i, k in enumerate(k_steps):
        o = offsets[i]
        results.append(KernelOut(
            chosen=host["chosen"][o:o + k],
            scores=host["scores"][o:o + k],
            found=host["found"][o:o + k],
            topk_idx=_TopKSlice(wave_topk, 0, o, o + k),
            topk_scores=_TopKSlice(wave_topk, 1, o, o + k),
            nodes_evaluated=host["nodes_evaluated"][i],
            nodes_feasible=host["nodes_feasible"][i],
            exhausted_cpu=host["exhausted_cpu"][i],
            exhausted_mem=host["exhausted_mem"][i],
            exhausted_disk=host["exhausted_disk"][i],
            exhausted_ports=host["exhausted_ports"][i],
            exhausted_devices=host["exhausted_devices"][i],
            exhausted_cores=host["exhausted_cores"][i],
        ))
    return results


class _Request:
    __slots__ = ("kin", "k_steps", "features", "origin", "out", "error",
                 "event")

    def __init__(self, kin, k_steps, features, origin=None):
        self.kin = kin
        self.k_steps = k_steps
        self.features = features
        #: who asked (LaunchOrigin), for the launch record only
        self.origin = origin
        self.out: Optional[KernelOut] = None
        self.error: Optional[BaseException] = None
        self.event = threading.Event()


class _PlanWindow:
    """Context manager a batching worker wraps around plan submission:
    the participant yields its rendezvous slot while it blocks on the
    serialized applier, so the NEXT wave fires without waiting for it
    (plan submission pipelines behind wave N instead of serializing
    wave N+1)."""

    __slots__ = ("_coalescer",)

    def __init__(self, coalescer: "LaunchCoalescer") -> None:
        self._coalescer = coalescer

    def __enter__(self) -> "_PlanWindow":
        self._coalescer.suspend()
        return self

    def __exit__(self, *exc) -> None:
        self._coalescer.resume()


class LaunchCoalescer:
    """Rendezvous point for one batch of concurrently-scheduled evals.

    Every participant must end with ``done()`` (use try/finally). A
    wave fires when every not-yet-done (and not suspended) participant
    is parked in ``launch`` — OR when a parked request's adaptive
    deadline expires, in which case whatever is pending fires as a
    partial wave and later arrivals form the next one. The deadline is
    a fraction of the EWMA wave latency, at least ``window_min_s``,
    and armed only while that fraction fits under ``window_max_s``
    (``_window_s``): parking is only worth cutting short while the
    device call it amortizes is itself short. And it is armed only once
    every participant has ARRIVED: called ``launch`` once, finished
    (``done``) or stepped aside (``suspend``). Until then the batch's
    members are still being prepared, one after the other under the
    interpreter lock, and will come: a deadline cuts the first wave
    before them whatever the launch costs (PERF.md finding 27-2). A
    participant is one thread (server/worker.py runs each evaluation
    of a batch as one pool task), which is how an arrival is told from
    a member that launches again. The
    observer that completes the rendezvous (a parking launcher, a
    finishing participant, or the deadline owner itself) executes the
    device call — there is no dispatcher thread.
    """

    #: deadline = EWMA wave latency x this fraction (clamped)
    WINDOW_FRACTION = 0.5

    def __init__(self, participants: int, mesh=None,
                 window_min_s: float = 0.001,
                 window_max_s: float = 0.050,
                 adaptive: bool = True) -> None:
        self._cv = threading.Condition(
            witness_lock("LaunchCoalescer._lock"))
        self._active = participants
        # participants not yet arrived, and the threads that have
        self._expected = participants
        self._arrived: set = set()
        # the owning server's device mesh (None = module default)
        self.mesh = mesh
        self._pending: List[_Request] = []
        self.window_min_s = window_min_s
        self.window_max_s = window_max_s
        self.adaptive = adaptive
        # stats (asserted by tests, reported by the worker)
        self.launches = 0
        self.requests = 0
        self.max_wave = 0
        self.deadline_launches = 0

    def _window_s(self) -> Optional[float]:
        """Deadline for a parked request, or None to park until the
        rendezvous completes: no latency sample yet, or a device call
        worth more than the cap can buy.

        Deadlines arm only while the window the wave latency asks for
        (EWMA x fraction) fits under ``window_max_s``. Past that a
        deadline AT the cap cuts waves whose members are still being
        prepared (100 ms apiece on the host at 10,000 nodes, against
        a 50 ms cap): the pieces launch apart against one snapshot
        with no shared capacity carry, the applier refuses what
        collides, the leftovers place again alone and each leftover
        count compiles its own step bucket (PERF.md finding 27-2).
        Cold compiles in flight fall under the same rule."""
        ewma = wave_latency_ewma.value
        if ewma is None:
            return None
        target = ewma * self.WINDOW_FRACTION
        if target > self.window_max_s:
            return None
        # a launch in flight past the cap keeps the device busy (or is
        # a cold compile the EWMA hasn't learned about yet): a partial
        # wave fired now would only queue behind it
        if _oldest_inflight_age_s() > self.window_max_s:
            return None
        # fragmentation feedback: widen (up to 4x, still capped) while
        # recent launches keep firing by deadline instead of by full
        # rendezvous
        frag = wave_deadline_ewma.value or 0.0
        target *= 1.0 + 3.0 * frag
        return min(max(target, self.window_min_s), self.window_max_s)

    def _arrive(self) -> None:
        """The calling participant is here (``_cv`` held)."""
        me = threading.get_ident()
        if me not in self._arrived:
            self._arrived.add(me)
            self._expected -= 1

    def _all_arrived(self) -> bool:
        with self._cv:
            return self._expected <= 0

    def launch(self, kin: KernelIn, k_steps: int,
               features: KernelFeatures,
               origin: Optional[LaunchOrigin] = None) -> KernelOut:
        req = _Request(kin, k_steps, features, origin)
        wave: Optional[List[_Request]] = None
        with self._cv:
            self._arrive()
            self.requests += 1
            self._pending.append(req)
            if len(self._pending) >= self._active:
                wave = self._pending
                self._pending = []
        if wave is not None:
            self._fire(wave, release="arrive")
        else:
            # parked: another member completes the rendezvous and runs
            # the device call, or this member's deadline expires and it
            # fires the partial wave itself. Park time OVERLAPS the
            # firing member's wave stages — the decomposition reports
            # it separately and must not sum it with them. The park
            # span and the park-latency stat cover ONLY the waiting:
            # a deadline owner's own launch work is attributed under
            # wave.launch, never double-reported as parking.
            t0 = time.perf_counter()
            held = False
            with tracer.span("wave.park"):
                if self.adaptive:
                    fired = claimed = False
                    while not (fired or claimed):
                        window = self._window_s()
                        if window is not None and not self._all_arrived():
                            held = True
                            window = None
                        if window is None:
                            # disarmed (no latency sample yet, a compile
                            # transient in flight, or members still on
                            # their way): park, and poll at a coarse
                            # cadence so the deadline arms once the
                            # transient clears and the batch is here
                            fired = req.event.wait(0.05)
                            continue
                        fired = req.event.wait(window)
                        if fired:
                            break
                        if self._window_s() is None:
                            # a transient STARTED during the window
                            # (e.g. another wave hit a cold compile):
                            # do not fire a partial wave into it
                            continue
                        with self._cv:
                            if req in self._pending:
                                wave = self._pending
                                self._pending = []
                                self.deadline_launches += 1
                        claimed = True
                    if wave is None and not fired:
                        # claimed by another member mid-timeout: wait
                        # for its launch like any parked member
                        req.event.wait()
                else:
                    req.event.wait()
            wave_stats.observe_park(time.perf_counter() - t0, held)
            if wave is not None:
                self._fire(wave, deadline_fired=True, release="deadline")
        if req.error is not None:
            raise req.error
        return req.out

    def done(self, trace_id: str = "") -> None:
        """The calling participant has finished. ``trace_id`` names its
        evaluation for the launch record, should it complete the
        rendezvous: its spans have closed by now."""
        wave: Optional[List[_Request]] = None
        with self._cv:
            self._arrive()
            # the thread is free to carry another participant
            self._arrived.discard(threading.get_ident())
            self._active -= 1
            if self._pending and len(self._pending) >= self._active:
                wave = self._pending
                self._pending = []
        if wave is not None:
            self._fire(wave, release="done", released_by=trace_id)

    def suspend(self) -> None:
        """Temporarily yield this participant's rendezvous slot (it is
        about to block outside the scheduling hot path, e.g. on the
        plan applier). Pending requests stop waiting for it."""
        wave: Optional[List[_Request]] = None
        with self._cv:
            self._arrive()
            self._active -= 1
            if self._pending and len(self._pending) >= self._active:
                wave = self._pending
                self._pending = []
        if wave is not None:
            self._fire(wave, release="suspend")

    def resume(self) -> None:
        """Re-take the slot released by ``suspend``."""
        with self._cv:
            self._active += 1

    def plan_window(self) -> _PlanWindow:
        return _PlanWindow(self)

    def _fire(self, wave: List[_Request], deadline_fired: bool = False,
              release: str = "arrive", released_by: str = "") -> None:
        """Launch ``wave`` on the calling thread, the one that completed
        the rendezvous. ``release`` says how, for the launch record: a
        member that arrived (``arrive``), a participant that finished
        (``done``) or stepped aside for the applier (``suspend``), or
        a parked member's deadline (``deadline``)."""
        # members that retried after a partial-commit snapshot refresh
        # may have crossed a node-axis pad bucket; a joint launch needs
        # one node axis, so split by shape (each group still coalesces)
        groups: dict = {}
        for r in wave:
            groups.setdefault(int(r.kin.cap_cpu.shape[0]), []).append(r)
        wave_deadline_ewma.update(1.0 if deadline_fired else 0.0)
        for n_nodes, grp in groups.items():
            self.launches += 1
            self.max_wave = max(self.max_wave, len(grp))
            k_steps = [r.k_steps for r in grp]
            origins = [r.origin for r in grp]
            steps = sum(real_steps(k_steps, origins))
            t_pad = wave_step_pad(len(grp), max(k_steps))
            program = "joint" if self.mesh is None else wave_program(
                int(self.mesh.size), n_nodes,
                union_features([r.features for r in grp]))
            wave_stats.observe_wave(
                len(grp), deadline_fired, steps=steps, padded_steps=t_pad,
                executed_steps=executed_steps(program, steps, t_pad),
                relaunched=sum(1 for o in origins
                               if o is not None and o.relaunch))
            try:
                outs = launch_wave(
                    [r.kin for r in grp], k_steps,
                    [r.features for r in grp],
                    mesh=self.mesh, origins=origins,
                    deadline_fired=deadline_fired, release=release,
                    released_by=released_by,
                )
                for r, out in zip(grp, outs):
                    r.out = out
                # wave-boundary plan batching: the members are about
                # to resume and submit ~len(grp) plans — arm the plan
                # queue's drain window BEFORE releasing them, so the
                # whole wave commits as one raft entry
                # (utils/wavecohort + PlanQueue.dequeue_batch)
                wave_cohorts.note_wave(len(grp))
            except BaseException as e:              # noqa: BLE001
                for r in grp:
                    r.error = e
            for r in grp:
                r.event.set()


_CLUSTER_LRU_MAX = 8


class ClusterCache:
    """ClusterTensors memo shared by a batch's evals.

    When the store publishes usage planes, the process-wide
    incremental cache serves the build: unchanged ``structure_version``
    is an identity hit, a bumped one applies dirty-node deltas from
    the store's change log instead of the full O(nodes) Python rebuild
    every batch used to pay (tensors/schema.IncrementalClusterCache).
    Snapshot-identity keying is the fallback for states without usage
    planes (bare test harnesses)."""

    def __init__(self) -> None:
        self._lock = witness_lock("ClusterCache._lock")
        self._cache = {}

    def get(self, state):
        from nomad_tpu.tensors.schema import (
            ClusterTensors,
            default_incremental_cluster_cache,
        )

        u = getattr(state, "usage", None)
        if u is not None and u.uid:
            built = default_incremental_cluster_cache.get(state)
            # advance the device-resident wave planes HERE, on an eval
            # thread at snapshot time: the dirty-row h2d of the next
            # wave runs while the previous wave's execute holds the
            # device (the functional scatter double-buffers — in-
            # flight waves keep their own generation's arrays). The
            # wave launcher then finds every shared leaf resident and
            # uploads nothing for it.
            default_device_state.ensure(built, u)
            return built
        key = id(state)
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None and hit[0] is state:
                return hit[1]
        built = ClusterTensors.build(state.nodes())
        with self._lock:
            self._cache[key] = (state, built)
            while len(self._cache) > _CLUSTER_LRU_MAX:
                self._cache.pop(next(iter(self._cache)))
        return built


#: process-wide cache used by schedulers outside batch mode too
default_cluster_cache = ClusterCache()
