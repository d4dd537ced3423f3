"""JAX-level instrumentation of placement kernel launches.

Every device launch (``joint``, ``joint_sharded`` / ``fused_wave_sharded``,
``single_full``, ``single_topk``) goes through
``KernelProfiler.call``. With the *tracer* on, profiler on or off, the
call records under the caller's ``wave.launch``:

- ``kernel.compile`` jit trace + XLA compile: the call into the jitted
                     function when its jit cache grew (a cold TPU
                     compile is tens of seconds and MUST be visible,
                     not smeared)
- ``kernel.dispatch``the same call when the program was compiled
                     already: the async dispatch
- ``kernel.execute`` ``block_until_ready`` on the outputs. Not the
                     tracer's doing: the call always waits there,
                     because its callers read the outputs next, and
                     the program must do the same thing traced and not

(``kernel.d2h``, the copies of the results to the host, is recorded by
the caller around its fetch.)

The *profiler* (off in the benchmark: it changes the launch path) adds
two things over the tracer:

- ``kernel.h2d``: it uploads the host leaves itself and waits for them
  before the call, so "is it transfer?" is answerable; without it jit
  uploads them inside the dispatch.
- jit cache misses per (kernel, key): the live path is bucketed
  precisely so that repeated waves REUSE compiled programs, and a miss
  counter per bucket shape is the direct test of that claim. A miss is
  classified first by the profiler's own seen set and cross-checked
  against the jit function's cache size when the runtime exposes it
  (``_cache_size``), so bucket-key bugs (two keys mapping to one
  program, or one key recompiling) show up as
  ``misses != cache_growth``.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from nomad_tpu.telemetry.trace import tracer

__all__ = ["KernelProfiler", "profiler", "profiled_call", "launch_seq"]

#: process-wide sequence number of device launches, taken by whoever
#: opens a ``wave.launch`` (the wave launcher, the lone dispatch): the
#: order in which this process handed programs to the device, which is
#: the order its launches appear in on a device trace
launch_seq = itertools.count(1)


class KernelProfiler:
    def __init__(self) -> None:
        self._enabled = False
        self._lock = threading.Lock()
        #: (kernel, key) ever launched -> launch count
        self._launches: Dict[Tuple[str, tuple], int] = {}
        #: (kernel, key) -> compile (cache-miss) count
        self._misses: Dict[Tuple[str, tuple], int] = {}
        #: per-stage cumulative seconds
        self.stage_s: Dict[str, float] = {
            "h2d": 0.0, "compile": 0.0, "dispatch": 0.0, "execute": 0.0,
        }
        #: cumulative transfer BYTES per direction — seconds say how
        #: long the PCIe stages took, bytes say whether the payload
        #: shrank (the device-resident cluster state's whole point).
        #: h2d counts host numpy leaves actually uploaded (resident
        #: device arrays cost nothing and are not counted) plus the
        #: dirty-row uploads device_state performs; d2h counts the
        #: result planes the wave launcher fetches.
        self.transfer_bytes: Dict[str, int] = {"h2d": 0, "d2h": 0}
        #: per-wave device-dispatch accounting (ISSUE 19): device
        #: interactions on the wave path, keyed by program. Every
        #: ``call`` counts one under its kernel name; the wave
        #: launcher adds "wave_fetch" for the composite's eager
        #: per-field result fetch and "topk_drain" for the deferred
        #: top-k materialization. The mesh's fused program's single
        #: packed readback rides its own dispatch's synchronization,
        #: so a fused sharded wave counts exactly ONE.
        self.dispatches: Dict[str, int] = {}
        #: cross-check: observed jit cache growth (when introspectable)
        self.cache_growth = 0
        #: kernel -> devices its outputs were found on after execute:
        #: what says a program really ran on the chip, and across how
        #: many of them (chip_smoke.py asserts on it)
        self.output_devices: Dict[str, set] = {}

    # --- control --------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        with self._lock:
            self._launches.clear()
            self._misses.clear()
            for k in self.stage_s:
                self.stage_s[k] = 0.0
            for k in self.transfer_bytes:
                self.transfer_bytes[k] = 0
            self.dispatches.clear()
            self.cache_growth = 0
            self.output_devices.clear()

    # --- accounting -----------------------------------------------------

    def summary(self) -> Dict:
        with self._lock:
            per_key = [
                {
                    "Kernel": kernel,
                    "Key": "/".join(str(p) for p in key),
                    "Launches": n,
                    "Misses": self._misses.get((kernel, key), 0),
                }
                for (kernel, key), n in sorted(self._launches.items())
            ]
            return {
                "Launches": sum(self._launches.values()),
                "JitCacheMisses": sum(self._misses.values()),
                "JitCacheGrowth": self.cache_growth,
                "StageSeconds": {k: round(v, 6)
                                 for k, v in self.stage_s.items()},
                "TransferBytes": dict(self.transfer_bytes),
                "Dispatches": dict(self.dispatches),
                "OutputDevices": {
                    k: sorted(str(d) for d in devs)
                    for k, devs in self.output_devices.items()},
                "PerKey": per_key,
            }

    def misses_for(self, kernel: str) -> int:
        with self._lock:
            return sum(n for (k, _), n in self._misses.items()
                       if k == kernel)

    def add_bytes(self, direction: str, n: int) -> None:
        """Account ``n`` transfer bytes under ``direction`` ("h2d" or
        "d2h"). No-op when disabled — callers outside ``call`` (the
        wave launcher's d2h fetch, device_state's dirty-row uploads)
        report through this."""
        if not self._enabled or n <= 0:
            return
        with self._lock:
            self.transfer_bytes[direction] = \
                self.transfer_bytes.get(direction, 0) + int(n)

    def count_dispatch(self, program: str, n: int = 1) -> None:
        """Account ``n`` wave-path device dispatches under
        ``program`` (exported as
        ``nomad_tpu_kernel_dispatches_total{program=...}``). No-op
        when disabled, like ``add_bytes`` — callers outside ``call``
        (the composite eager fetch, the deferred top-k drain) report
        through this."""
        if not self._enabled or n <= 0:
            return
        with self._lock:
            self.dispatches[program] = \
                self.dispatches.get(program, 0) + int(n)

    def keys(self) -> list:
        """Every (kernel, bucket-key) ever launched since reset — the
        raw material of the AOT warmup manifest (ops/warmup.py)."""
        with self._lock:
            return list(self._launches)

    # --- the profiled launch -------------------------------------------

    def call(self, kernel: str, fn: Callable, dev_args: tuple,
             static_args: tuple, key: tuple, jit_fn=None,
             shardings=None):
        """Run ``fn(*dev_args, *static_args)`` and wait for its outputs:
        the one seam every device launch shares. ``dev_args`` is the
        array pytree the program reads; ``static_args`` (jit static
        argnums: step bucket, feature set) pass through untouched.

        The tracer alone times the call (``kernel.compile`` when the
        jit cache grew, else ``kernel.dispatch``) and the wait
        (``kernel.execute``); it changes nothing about either. The
        profiler adds the explicit upload (``kernel.h2d``: host leaves
        put on the device and waited for BEFORE the call) and the
        per-key launch and miss counts; the benchmark keeps it off.

        ``key`` is the bucket-shape identity the compile cache SHOULD
        be keyed by; ``jit_fn`` (when it differs from ``fn``, e.g. a
        sharded wrapper) is the object whose ``_cache_size`` is
        consulted. ``shardings`` (a pytree matching ``dev_args``)
        places host leaves at upload time: a sharded wave's explicit
        h2d must land each leaf with the jit's in_shardings, or the
        call would pay a hidden reshard."""
        import jax

        profiling = self._enabled
        if not profiling and not tracer.enabled:
            out = fn(*dev_args, *static_args)
        else:
            if profiling:
                dev_args = self._upload(dev_args, shardings)
            # a jit function's own cache size; a plain callable (tests
            # profile fakes) has none and is classified by the
            # profiler's seen set
            probe = jit_fn if jit_fn is not None else fn
            size_fn = getattr(probe, "_cache_size", None)
            size0 = size_fn() if size_fn is not None else None
            full_key = (kernel, key)
            seen = True
            if profiling:
                with self._lock:
                    seen = full_key in self._launches
                    self._launches[full_key] = \
                        self._launches.get(full_key, 0) + 1
                    self.dispatches[kernel] = \
                        self.dispatches.get(kernel, 0) + 1
            t0 = time.perf_counter()
            out = fn(*dev_args, *static_args)
            call_s = time.perf_counter() - t0
            # a miss is OBSERVED growth of the jit function's cache
            # (survives profiler resets against a warm jit cache). A
            # key we bucketed as seen that grows the cache anyway is
            # the exact bug class this counter exists to expose (two
            # shapes under one bucket key).
            grew = 0 if size0 is None else max(size_fn() - size0, 0)
            miss = bool(grew) if size0 is not None else not seen
            stage = "compile" if miss else "dispatch"
            tracer.record(f"kernel.{stage}", call_s)
            if profiling:
                self._bump_stage(stage, call_s)
                with self._lock:
                    if miss:
                        self._misses[full_key] = \
                            self._misses.get(full_key, 0) + 1
                    self.cache_growth += grew
        # the wait, traced or not: every caller reads the outputs next
        # (np.asarray, bool), which would block here anyway
        with tracer.span("kernel.execute"):
            t0 = time.perf_counter()
            jax.block_until_ready(out)
            wait_s = time.perf_counter() - t0
        if profiling:
            self._bump_stage("execute", wait_s)
            devs = {d for x in jax.tree_util.tree_leaves(out)
                    if isinstance(x, jax.Array) for d in x.devices()}
            with self._lock:
                self.output_devices.setdefault(kernel, set()).update(devs)
        return out

    def _upload(self, dev_args: tuple, shardings):
        """The profiler's explicit upload: jit would upload the host
        numpy leaves transparently inside the call; splitting it out
        is what makes "is it transfer?" answerable. Leaves that are
        already device arrays (the resident cluster state) skip
        device_put entirely: only host leaves pay PCIe, so only they
        are uploaded, blocked on, and byte-metered. One flatten + ONE
        batched device_put: on a firing thread racing B eval threads
        for the GIL, every extra per-leaf python round trip is a
        potential 5ms switch-interval stall inside this span."""
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(dev_args)
        host_idx = [i for i, x in enumerate(leaves)
                    if not isinstance(x, jax.Array)]
        host_leaves = [leaves[i] for i in host_idx]
        shard_leaves = None
        if shardings is not None and host_leaves:
            flat_shards = jax.tree_util.tree_flatten(
                shardings, is_leaf=lambda x: x is None)[0]
            if len(flat_shards) == len(leaves):
                shard_leaves = [flat_shards[i] for i in host_idx]
        up_bytes = sum(getattr(x, "nbytes", 0) for x in host_leaves)
        with tracer.span("kernel.h2d"):
            t0 = time.perf_counter()
            if host_leaves:
                # ONE batched device_put + ONE block: handing the jit
                # call arrays with in-flight transfers makes the
                # dispatch itself stall holding the GIL, which
                # serializes every eval thread behind this launch
                put = jax.device_put(host_leaves, shard_leaves)
                jax.block_until_ready(put)
                for i, v in zip(host_idx, put):
                    leaves[i] = v
            self._bump_stage("h2d", time.perf_counter() - t0)
        self.add_bytes("h2d", up_bytes)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _bump_stage(self, stage: str, dur_s: float) -> None:
        with self._lock:
            self.stage_s[stage] += dur_s


#: process-wide profiler; enabled together with the tracer by
#: telemetry.enable()
profiler = KernelProfiler()


def profiled_call(kernel: str, fn: Callable, dev_args: tuple,
                  static_args: tuple, key: tuple, jit_fn=None):
    return profiler.call(kernel, fn, dev_args, static_args, key,
                         jit_fn=jit_fn)
