"""Eval-lifecycle tracing + TPU kernel profiling.

Spans across the full eval hot path (HTTP handler -> broker dequeue ->
worker batch -> snapshot -> wave assembly -> device launch -> plan
submit -> plan apply -> FSM -> store), each with optional attributes;
one launch record (``wave.launch``) per device launch; exposition
through ``/v1/metrics`` + ``/v1/operator/traces``.

Two switches. The *tracer* records and does nothing else: no wait, no
upload, no branch of the program hangs on it, so a traced run is the
same program. It alone gives the launch its ``kernel.compile`` /
``kernel.dispatch`` / ``kernel.execute`` / ``kernel.d2h`` spans. The
kernel *profiler* adds what costs something: an explicit upload of the
host leaves before each launch (``kernel.h2d``) and launch / jit
cache-miss counts per bucket shape, cross-checked against the jit
cache's growth. ``telemetry.enable()``, ``NOMAD_TPU_TRACE=1`` and
``PUT /v1/operator/traces`` turn both on; the benchmark turns on the
tracer alone (``tracer.enable()``) and keeps the profiler off.
Disabled-mode cost on the hot path is one attribute check per span
site.
"""

from __future__ import annotations

import os

from nomad_tpu.telemetry.histogram import (  # noqa: F401
    HistogramRegistry,
    LatencyHistogram,
    histograms,
    percentile,
)
from nomad_tpu.telemetry.kernel_profile import (  # noqa: F401
    KernelProfiler,
    profiled_call,
    profiler,
)
from nomad_tpu.telemetry.trace import (  # noqa: F401
    ConsensusRecorder,
    FlightRecorder,
    Span,
    Tracer,
    consensus_recorder,
    flight_recorder,
    tracer,
)

__all__ = [
    "Span", "Tracer", "tracer",
    "KernelProfiler", "profiler", "profiled_call",
    "LatencyHistogram", "HistogramRegistry", "histograms", "percentile",
    "FlightRecorder", "flight_recorder",
    "ConsensusRecorder", "consensus_recorder",
    "enable", "disable", "enabled", "reset",
]


def enable() -> None:
    tracer.enable()
    profiler.enable()


def disable() -> None:
    tracer.disable()
    profiler.disable()


def enabled() -> bool:
    return tracer.enabled


def reset() -> None:
    tracer.reset()
    profiler.reset()
    # latency histograms + the slow-eval flight recorder cover the
    # same burst window as the tracer aggregates
    histograms.reset()
    flight_recorder.reset()
    # the consensus-plane recorder + per-server raft observer counters
    # follow the same burst window (live-node registrations survive)
    consensus_recorder.reset()
    try:
        from nomad_tpu.raft.observe import raft_observer

        raft_observer.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # wave-shape stats (fill ratio, park latency) live with the
        # coalescer; reset them with the rest so burst decompositions
        # cover exactly their window. Import is lazy/guarded: telemetry
        # must stay importable without jax.
        from nomad_tpu.parallel.coalesce import (
            fused_wave_stats,
            sharded_wave_stats,
            wave_stats,
        )

        wave_stats.reset()
        sharded_wave_stats.reset()
        fused_wave_stats.reset()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # device-residency counters (dirty-row upload ratio etc.)
        # follow the same window; the resident arrays themselves stay
        from nomad_tpu.tensors.device_state import default_device_state

        default_device_state.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # spread-code lookups (hits, builds) follow the same window;
        # the codes themselves stay on their cluster builds
        from nomad_tpu.tensors.schema import spread_code_stats

        spread_code_stats.reset()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # the reconciler's stops (allocations, seconds) follow the
        # same window
        from nomad_tpu.scheduler.generic import stop_stats

        stop_stats.reset()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # feasibility mask-cache counters follow the same window; the
        # cached programs/masks themselves stay resident
        from nomad_tpu.feasibility import default_mask_cache

        default_mask_cache.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # plan group-commit counters (vector vs fallback re-validation,
        # batched raft entries) cover the same burst window
        from nomad_tpu.server.plan_apply import plan_group_stats

        plan_group_stats.reset()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # wave-cohort drain counters (plan-queue wave-boundary
        # batching) follow the burst window; the learned drain EWMA
        # survives like any other timing calibration
        from nomad_tpu.utils.wavecohort import wave_cohorts

        wave_cohorts.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # blocking-query wakeup counters (state/store.py watch_stats)
        # cover the same burst window; the held-watcher gauge tracks
        # live waiters and is never reset
        from nomad_tpu.state.store import watch_stats

        watch_stats.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # MVCC store rate counters (state/store.py store_stats) cover
        # the same burst window; the generation and live-root gauges
        # track durable store state and are never reset
        from nomad_tpu.state.store import store_stats

        store_stats.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # heartbeat fan-in counters (server/server.py) follow the
        # burst window; event-broker stats are per-broker and are
        # windowed by the bench cells via broker.reset_stats()
        from nomad_tpu.server.server import client_update_stats

        client_update_stats.reset_stats()
    except Exception:                           # noqa: BLE001
        pass
    try:
        # read-plane routing counters (server/readplane.py) follow the
        # burst window; the staleness histogram rides the shared
        # registry reset above
        from nomad_tpu.server.readplane import read_stats

        read_stats.reset_stats()
    except Exception:                           # noqa: BLE001
        pass


if os.environ.get("NOMAD_TPU_TRACE", "") not in ("", "0"):
    enable()
