"""Device-resident cluster state: kill the per-wave h2d tax.

PR 2's steady-state TRACE_DECOMP made h2d the dominant cost (30.4% of
wall, 4.48 ms/eval): every coalesced wave re-uploaded the full
node x resource shared planes even though the host side already knew
exactly which rows changed (the incremental ClusterTensors cache and
the usage index's change logs). This module is the device half of that
design: the wave-shared planes — the cluster-static capacity planes
plus the snapshot's gathered utilization (``ClusterTensors.
wave_shared_planes``) — live ON the accelerator as committed arrays,
keyed by ``(uid, structure_version)`` generations, and advance between
waves by uploading only the dirty rows and applying them with a jit'd
scatter (``plane.at[rows].set(vals)``).

Advancement is **functional**: a scatter produces new device arrays
while the previous generation's buffers stay untouched, so a wave
still executing against version N never races version N+1's upload —
the double-buffering that lets the (tiny) h2d of the next wave overlap
the current wave's execute. Resident generations are LRU-bounded;
every miss (unprovable log, permuted rows, pad-bucket change, evicted
base) falls back to a full plane upload, which is bit-identical by
construction and property-tested against a fresh
``ClusterTensors.build`` + upload (tests/test_device_state.py, the
device mirror of tests/test_cluster_delta.py).

Mesh sharding (ISSUE 14): when a device mesh is configured
(``configure_mesh``; the server adopts its wave mesh here), resident
generations are placed with a ``NamedSharding`` that splits the node
axis over the mesh's ``nodes`` axis — each device holds its shard of
every wave-shared plane, and the dirty-row scatter advances THOSE
sharded buffers in place-of-layout (a per-mesh jit with sharded
in/out shardings, so wave-to-wave advancement never gathers a plane
to one device and never reshards). Frozen singletons are placed per
KernelIn-field partition spec (parallel/sharded.shared_field_spec) and
keyed by (array identity, spec), so the same neutral plane can be
resident both unsharded and sharded. Lookups carry the caller's mesh:
a single-device launch never receives a sharded buffer (it would
reshard inside the jit), and vice versa — mismatches just miss and
ship host planes, which is always correct.

Dirty-row provenance:

- utilization planes: ``UsagePlanes.row_events`` (state/usage.py), the
  per-version log of nodes whose rows an alloc transition moved,
  complete above ``row_events_floor``;
- cluster-static planes across a ``structure_version`` fork:
  ``UsagePlanes.node_events``, the same log the host-side
  ``IncrementalClusterCache`` replays — usable on device only when the
  surviving rows kept their positions (additions/updates); a
  compaction that permutes rows falls back to a full upload.

The registry maps *host array identity* -> committed device array, the
same identity contract the wave coalescer's sharing layout is built
on: ``launch_wave`` (and ``default_kernel_launch``) swap a shared host
leaf for its resident device twin, making ``jax.device_put`` a no-op
for every plane that didn't change. Frozen neutral singletons
(ops/kernel.neutral_planes etc.) ride the same registry via a bounded
resident cache — they upload once per process, ever.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from nomad_tpu.tensors.schema import (
    ClusterTensors,
    IncrementalClusterCache,
)

__all__ = ["DeviceClusterState", "default_device_state"]

#: dirty-row scatter batches are bucketed so the jit cache holds a
#: handful of (n_pad, rows-bucket, dtype) programs, not one per count
_MIN_ROW_BUCKET = 8


def _row_bucket(r: int) -> int:
    b = _MIN_ROW_BUCKET
    while b < r:
        b *= 2
    return b


def _scatter_rows_impl(plane, rows, vals):
    """``plane.at[rows].set(vals)``; padding rows are out of bounds on
    purpose — scatter drops OOB updates, so a bucketed row batch never
    touches rows it wasn't given."""
    return plane.at[rows].set(vals)


_scatter_rows = jax.jit(_scatter_rows_impl)

#: per-mesh sharded scatter jits (weak: a freed mesh drops its entry).
#: The plane stays split over the nodes axis IN and OUT — advancement
#: of a sharded generation never gathers the plane to one device; row
#: indices address the GLOBAL node axis and ship replicated, each
#: shard applies the updates that land in its slice.
import weakref

_sharded_scatter_cache: "weakref.WeakKeyDictionary" = \
    weakref.WeakKeyDictionary()


def _sharded_scatter(mesh):
    fn = _sharded_scatter_cache.get(mesh)
    if fn is None:
        from nomad_tpu.parallel.sharded import node_axis_sharding
        from jax.sharding import NamedSharding, PartitionSpec

        plane_s = node_axis_sharding(mesh)
        repl = NamedSharding(mesh, PartitionSpec())
        fn = jax.jit(_scatter_rows_impl,
                     in_shardings=(plane_s, repl, repl),
                     out_shardings=plane_s)
        _sharded_scatter_cache[mesh] = fn
    return fn


def _mesh_match(a, b) -> bool:
    """Two mesh handles name the same placement (None = single device;
    jax Mesh compares by devices + axis names)."""
    if a is None or b is None:
        return a is None and b is None
    return a is b or a == b


class _Generation:
    """One resident (uid, structure_version) generation."""

    __slots__ = ("key", "cluster", "version", "planes", "host_ids",
                 "mesh")

    def __init__(self, key, cluster, version, planes, mesh=None):
        self.key = key
        self.cluster = cluster          # host build (identity anchor)
        self.version = version          # usage version of the planes
        self.planes: Dict[str, object] = planes   # field -> device array
        self.host_ids: Tuple[int, ...] = ()
        self.mesh = mesh                # placement (None = one device)


class DeviceClusterState:
    """LRU of device-resident wave-shared plane generations."""

    def __init__(self, max_generations: int = 4,
                 max_frozen: int = 256, mesh=None) -> None:
        self._lock = threading.Lock()
        self._gens: "OrderedDict[tuple, _Generation]" = OrderedDict()
        #: uid -> newest resident structure_version (the fork base)
        self._latest: Dict[str, int] = {}
        #: id(host array) -> (host array, device array, mesh). Strong
        #: host refs pin ids against reuse; entries leave with their
        #: generation. Generations only — frozen singletons live in
        #: the spec-keyed LRU below.
        self._registry: Dict[int, tuple] = {}
        #: (id(host array), spec key) -> (host array, device array).
        #: The spec key is None for single-device placement or the
        #: field's PartitionSpec tuple under the configured mesh — the
        #: same neutral singleton can be resident under both.
        self._frozen: "OrderedDict[tuple, tuple]" = OrderedDict()
        #: frozen-cache key -> Event for uploads in flight: the upload
        #: itself runs OUTSIDE self._lock (graftcheck R2 — a first-
        #: sight frozen upload under the registry lock stalled every
        #: concurrent snapshot-time advance behind one h2d transfer)
        self._frozen_inflight: Dict[tuple, threading.Event] = {}
        self.max_generations = max_generations
        self.max_frozen = max_frozen
        #: device mesh future generations shard their node axis over
        #: (None = single-device placement, the default)
        self._mesh = mesh
        self.reset_stats()

    # --- mesh -----------------------------------------------------------

    @property
    def mesh(self):
        return self._mesh

    def configure_mesh(self, mesh) -> None:
        """Shard future resident generations' node axis over ``mesh``
        (None restores single-device placement). A CHANGE of placement
        evicts everything resident: a plane placed for the old mesh
        can only mis-serve the new dispatch path. The server adopts
        its wave mesh here when it comes up; tests and the bench mesh
        cell configure/restore around their bursts."""
        with self._lock:
            if _mesh_match(mesh, self._mesh):
                return
            self._mesh = mesh
            for gen in list(self._gens.values()):
                self._evict(gen)
            self._gens.clear()
            self._latest.clear()
            self._registry.clear()
            self._frozen.clear()

    def _node_sharding(self, n_pad: int):
        """NamedSharding for [n_pad] node planes under the configured
        mesh, or None for single-device placement (no mesh, or a node
        axis the mesh's device count does not divide — the launcher
        makes the same divisibility call and falls back unsharded)."""
        mesh = self._mesh
        if mesh is None or mesh.size < 2 or n_pad % mesh.size != 0:
            return None
        from nomad_tpu.parallel.sharded import node_axis_sharding

        return node_axis_sharding(mesh)

    # --- stats ----------------------------------------------------------

    def reset_stats(self) -> None:
        with self._lock:
            self.hits = 0
            self.full_uploads = 0        # generations built by full upload
            self.delta_advances = 0      # usage advances by row scatter
            self.fork_deltas = 0         # structure forks by row scatter
            self.usage_full_uploads = 0  # unprovable row log fallbacks
            self.rows_uploaded = 0
            self.bytes_uploaded = 0      # actual h2d bytes (delta + full)
            self.bytes_full_equiv = 0    # what full re-uploads would cost

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "hits": self.hits,
                "full_uploads": self.full_uploads,
                "delta_advances": self.delta_advances,
                "fork_deltas": self.fork_deltas,
                "usage_full_uploads": self.usage_full_uploads,
                "rows_uploaded": self.rows_uploaded,
                "bytes_uploaded": self.bytes_uploaded,
                "bytes_full_equiv": self.bytes_full_equiv,
                "dirty_row_upload_ratio": (
                    round(self.bytes_uploaded / self.bytes_full_equiv, 4)
                    if self.bytes_full_equiv else 0.0),
                "resident_generations": len(self._gens),
                "mesh_devices": (int(self._mesh.size)
                                 if self._mesh is not None else 0),
            }

    def newest_planes(self) -> Dict[str, object]:
        """field -> device array of the most recently used resident
        generation ({} when nothing is resident): what a diagnostic
        reads to see WHERE the wave-shared planes live, and how they
        are split (chip_smoke.py)."""
        with self._lock:
            if not self._gens:
                return {}
            return dict(next(reversed(self._gens.values())).planes)

    # --- registry -------------------------------------------------------

    def lookup(self, arr, frozen_ok: bool = True, spec=None,
               mesh=None) -> Optional[object]:
        """Committed device twin of ``arr`` placed for ``mesh``, or
        None. With ``frozen_ok``, frozen host arrays (read-only
        singletons) are made resident on first sight; mutable arrays
        are served only when a generation registered them.

        ``mesh``/``spec`` are the caller's dispatch placement: a
        single-device launch (mesh None) never receives a sharded
        buffer, a sharded wave never receives a single-device one —
        either would reshard inside the jit and fork its cache.
        ``spec`` (a PartitionSpec, sharded callers only) is the
        KernelIn field's partition for frozen-singleton placement.

        Callers pass ``frozen_ok=False`` for the snapshot-plane group:
        gathered utilization planes are ALSO read-only, and a stale
        snapshot's planes (deregistered by a newer advance) must miss
        — not get full-uploaded on the firing thread and pinned into
        the frozen LRU as if they were process-lifetime singletons."""
        if not isinstance(arr, np.ndarray):
            return None
        ent = self._registry.get(id(arr))
        if ent is not None and ent[0] is arr \
                and _mesh_match(ent[2], mesh):
            return ent[1]
        if frozen_ok and not arr.flags.writeable:
            # lock-free fast path (like the registry read above): a
            # resident frozen singleton is served without touching the
            # lock the advance path holds — only a MISS pays the
            # claim-and-upload dance. Sharded entries are placed for
            # THIS state's mesh, so a caller on a foreign mesh must
            # fall through (and be rejected by the slow path) — the
            # spec key alone would collide across meshes.
            spec_key = None if (spec is None or mesh is None) \
                else tuple(spec)
            if spec_key is None or _mesh_match(mesh, self._mesh):
                ent = self._frozen.get((id(arr), spec_key))
                if ent is not None and ent[0] is arr:
                    return ent[1]
            return self._frozen_resident(arr, spec, mesh)
        return None

    def _frozen_resident(self, arr: np.ndarray, spec=None, mesh=None):
        # claim under the lock, upload outside it: the device_put of a
        # first-sight frozen singleton must not hold the registry lock
        # (it is shared with the dirty-row advance path every eval
        # thread runs at snapshot time — graftcheck R2). Concurrent
        # callers for the same array wait on the claim's event; a
        # caller who finds the upload failed just misses (residency is
        # an optimization, the host array still works).
        sharding = None
        if mesh is not None:
            # sharded placement only under THIS state's configured
            # mesh: uploading under a foreign mesh would pin arrays no
            # dispatch path of this state ever serves
            if not _mesh_match(mesh, self._mesh) or spec is None:
                return None
            from jax.sharding import NamedSharding

            sharding = NamedSharding(self._mesh, spec)
        spec_key = None if spec is None or mesh is None \
            else tuple(spec)
        key = (id(arr), spec_key)
        while True:
            with self._lock:
                ent = self._frozen.get(key)
                if ent is not None and ent[0] is arr:
                    self._frozen.move_to_end(key)
                    return ent[1]
                ev = self._frozen_inflight.get(key)
                if ev is None:
                    ev = self._frozen_inflight[key] = threading.Event()
                    break       # this thread owns the upload
            if not ev.wait(timeout=30.0):
                return None     # uploader wedged: serve the host array
        dev = None
        try:
            dev = self._upload({"_frozen": arr},
                               sharding=sharding)["_frozen"]
            with self._lock:
                # re-validate placement before inserting: the upload
                # ran off-lock, and a racing configure_mesh may have
                # cleared the cache for a NEW mesh — a sharded buffer
                # placed for the old one must not be re-inserted under
                # a spec key the new mesh's lookups would hit (the key
                # encodes the spec, not the mesh). Unsharded entries
                # stay valid under any mesh.
                if spec_key is None or _mesh_match(mesh, self._mesh):
                    self._frozen[key] = (arr, dev)
                    while len(self._frozen) > self.max_frozen:
                        self._frozen.popitem(last=False)
                else:
                    dev = None      # stale placement: callers miss
        finally:
            with self._lock:
                self._frozen_inflight.pop(key, None)
            ev.set()
        return dev

    def _register(self, gen: _Generation,
                  host_planes: Dict[str, np.ndarray]) -> None:
        for hid in gen.host_ids:
            self._registry.pop(hid, None)
        ids = []
        for f, host in host_planes.items():
            self._registry[id(host)] = (host, gen.planes[f], gen.mesh)
            ids.append(id(host))
        gen.host_ids = tuple(ids)

    def _evict(self, gen: _Generation) -> None:
        for hid in gen.host_ids:
            self._registry.pop(hid, None)
        uid, sv = gen.key
        if self._latest.get(uid) == sv:
            self._latest.pop(uid, None)

    # --- uploads --------------------------------------------------------

    def _upload(self, host_planes: Dict[str, np.ndarray],
                sharding=None) -> Dict:
        """Full upload of ``host_planes`` (placed with ``sharding``
        when given — the mesh path's node-axis split); spans +
        byte-counts the real h2d it performs (the kernel profiler's
        transfer accounting)."""
        from nomad_tpu.telemetry.kernel_profile import profiler
        from nomad_tpu.telemetry.trace import tracer

        n_bytes = sum(a.nbytes for a in host_planes.values())
        # own span name: this upload runs on an EVAL thread at
        # snapshot time, overlapping the in-flight wave — the trace
        # decomposition must not sum it into the wave-critical-path
        # kernel.h2d wall stage. It times the ENQUEUE: the tracer never
        # waits for the device (a wait here made traced runs another
        # program); the transfer itself is on the device trace
        with tracer.span("state.h2d") as sp:
            if sharding is None:
                dev = {f: jax.device_put(a)
                       for f, a in host_planes.items()}
            else:
                dev = {f: jax.device_put(a, sharding)
                       for f, a in host_planes.items()}
            sp.set(bytes=n_bytes, rows=max(
                (a.shape[0] for a in host_planes.values()), default=0))
        profiler.add_bytes("h2d", n_bytes)
        self.bytes_uploaded += n_bytes
        return dev

    def _scatter(self, planes: Dict, host_planes: Dict[str, np.ndarray],
                 rows, mesh=None) -> Dict:
        """Advance ``planes`` to match ``host_planes`` given that only
        ``rows`` differ: upload rows + per-plane values, scatter on
        device. Row indices are bucketed with out-of-bounds padding
        (dropped by the scatter). Sharded generations advance through
        the per-mesh sharded scatter: the plane stays split over the
        nodes axis end to end, only the dirty rows and their GLOBAL
        indices ship (replicated — they are a few KB)."""
        from nomad_tpu.telemetry.kernel_profile import profiler
        from nomad_tpu.telemetry.trace import tracer

        scatter = _scatter_rows if mesh is None else _sharded_scatter(mesh)
        repl = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            repl = NamedSharding(mesh, PartitionSpec())
        rows = np.asarray(sorted(rows), np.int32)
        any_plane = next(iter(host_planes.values()))
        n_pad = any_plane.shape[0]
        rb = _row_bucket(len(rows))
        rows_p = np.full(rb, n_pad, np.int32)
        rows_p[:len(rows)] = rows
        n_bytes = rows_p.nbytes
        with tracer.span("state.h2d") as sp:
            rows_dev = jax.device_put(rows_p) if repl is None \
                else jax.device_put(rows_p, repl)
            out = dict(planes)
            for f, host in host_planes.items():
                vals = np.zeros(rb, host.dtype)
                vals[:len(rows)] = host[rows]
                n_bytes += vals.nbytes
                vals_dev = jax.device_put(vals) if repl is None \
                    else jax.device_put(vals, repl)
                out[f] = scatter(planes[f], rows_dev, vals_dev)
            sp.set(bytes=n_bytes, rows=int(len(rows)))
        profiler.add_bytes("h2d", n_bytes)
        self.bytes_uploaded += n_bytes
        self.rows_uploaded += int(len(rows)) * len(host_planes)
        return out

    def warm_scatter(self, n_pad: int) -> int:
        """AOT-compile the dirty-row scatter for every row bucket and
        plane dtype of a node size (ops/warmup.py calls this with the
        manifest's node shapes), including the sharded variant when a
        mesh is configured. The scatter is raw ``jax.jit`` — its
        compiles never show in the profiler's miss accounting, but a
        steady burst whose dirty-row count crosses into a fresh bucket
        used to pay a cold compile INSIDE an eval's snapshot phase.
        Returns the number of (bucket, dtype) programs touched."""
        done = 0
        sharding = self._node_sharding(n_pad)
        variants = [(_scatter_rows, None)]
        if sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            variants.append((_sharded_scatter(self._mesh),
                             NamedSharding(self._mesh, PartitionSpec())))
        b = _MIN_ROW_BUCKET
        while b <= max(n_pad, _MIN_ROW_BUCKET):
            for scatter, repl in variants:
                rows_h = np.full(b, n_pad, np.int32)
                rows = jax.device_put(rows_h) if repl is None \
                    else jax.device_put(rows_h, repl)
                for dtype in (np.float32, np.int32):
                    if repl is None:
                        plane = jax.device_put(np.zeros(n_pad, dtype))
                        vals = jax.device_put(np.zeros(b, dtype))
                    else:
                        plane = jax.device_put(np.zeros(n_pad, dtype),
                                               sharding)
                        vals = jax.device_put(np.zeros(b, dtype), repl)
                    jax.block_until_ready(scatter(plane, rows, vals))
                    done += 1
            if b >= n_pad:
                break
            b *= 2
        return done

    # --- the ensure entry point ----------------------------------------

    def ensure(self, cluster: ClusterTensors, usage) -> Optional[_Generation]:
        """Make the wave-shared planes of (cluster, usage) resident and
        registered; called once per eval at snapshot time (cheap
        version-compare on the hot path), so the next wave's h2d —
        now just the dirty rows — runs on an eval thread while the
        previous wave executes."""
        if usage is None or not getattr(usage, "uid", ""):
            return None
        key = (usage.uid, usage.structure_version)
        # lock-free fast path: dict reads are atomic in CPython and a
        # generation's (cluster, version) pair only moves forward, so
        # a racing advance at worst sends us to the locked path. The
        # hits += 1 is a tolerated read-modify-write race (a stats
        # counter, like worker.processed).
        gen = self._gens.get(key)
        if gen is not None and gen.version == usage.version \
                and gen.cluster is cluster:
            self.hits += 1
            return gen
        if gen is not None and gen.cluster is cluster \
                and gen.version > usage.version:
            # an eval still scheduling against an OLDER usage snapshot
            # (pipelined batches, a neighbor's refreshed retry): its
            # wave simply ships host planes. Demoting the generation
            # here would full-upload per interleave and ping-pong the
            # registry between versions.
            return None
        # BLOCKING acquire on purpose: a batch's eval threads all
        # reach here with the same snapshot; the first advances, the
        # rest wait and then hit the double-checked fast path. Waiting
        # is cheaper than it looks — these threads would otherwise
        # park at the wave rendezvous, and a follower that skipped
        # ahead without residency would make its wave ship FULL host
        # planes (measured: h2d share exploded 17x with a try-lock
        # here on the CPU backend).
        with self._lock:
            gen = self._gens.get(key)
            if gen is not None and gen.version == usage.version \
                    and gen.cluster is cluster:
                self._gens.move_to_end(key)
                self.hits += 1
                return gen
            if gen is not None and gen.cluster is cluster \
                    and gen.version > usage.version:
                return None
            host = cluster.wave_shared_planes(usage)
            full_bytes = sum(a.nbytes for a in host.values())
            self.bytes_full_equiv += full_bytes
            if gen is not None and gen.cluster is cluster \
                    and gen.version < usage.version:
                self._advance_usage(gen, host, usage)
            else:
                if gen is not None:
                    # the key is being re-built from a different host
                    # cluster object: retire the old registrations
                    self._evict(gen)
                gen = self._fork_or_build(key, cluster, host, usage)
            self._register(gen, host)
            gen.version = usage.version
            self._gens[key] = gen
            self._gens.move_to_end(key)
            if usage.structure_version >= self._latest.get(usage.uid, -1):
                self._latest[usage.uid] = usage.structure_version
            while len(self._gens) > self.max_generations:
                _, old = self._gens.popitem(last=False)
                self._evict(old)
            return gen

    # --- advance paths --------------------------------------------------

    @staticmethod
    def _usage_rows_changed(usage, since_version: int):
        """Node ids whose utilization rows changed after
        ``since_version``, or None when the row log cannot prove
        completeness (trimmed past the gap, or poisoned by rebuild)."""
        if since_version < getattr(usage, "row_events_floor", 0):
            return None
        return {nid for v, nid in getattr(usage, "row_events", ())
                if v > since_version}

    def _gen_sharding(self, gen: _Generation):
        if gen.mesh is None:
            return None
        from nomad_tpu.parallel.sharded import node_axis_sharding

        return node_axis_sharding(gen.mesh)

    def _advance_usage(self, gen: _Generation,
                       host: Dict[str, np.ndarray], usage) -> None:
        """Same (uid, structure_version), newer usage version: only
        utilization rows can have moved. A sharded generation advances
        sharded — the scatter and the unprovable-log full-upload
        fallback both keep the generation's placement."""
        changed = self._usage_rows_changed(usage, gen.version)
        usage_host = {f: host[f]
                      for f in ClusterTensors.WAVE_USAGE_FIELDS}
        if changed is None:
            self.usage_full_uploads += 1
            gen.planes.update(self._upload(
                usage_host, sharding=self._gen_sharding(gen)))
            return
        rows = {gen.cluster.index[nid] for nid in changed
                if nid in gen.cluster.index}
        if rows:
            gen.planes = self._scatter(gen.planes, usage_host, rows,
                                       mesh=gen.mesh)
        self.delta_advances += 1

    def _fork_or_build(self, key, cluster: ClusterTensors,
                       host: Dict[str, np.ndarray], usage) -> _Generation:
        """A structure_version this state has no generation for: fork
        from the newest resident generation of the same store by
        dirty-row scatter when the node-change log proves the dirty
        set AND surviving rows kept their positions; otherwise a full
        upload. Placement follows the configured mesh (the fork path
        requires the base's placement to match — the same n_pad under
        the same mesh always does)."""
        sharding = self._node_sharding(cluster.n_pad)
        gen_mesh = self._mesh if sharding is not None else None
        uid, sv = key
        base_sv = self._latest.get(uid)
        base = (self._gens.get((uid, base_sv))
                if base_sv is not None else None)
        if base is not None and base_sv < sv \
                and base.cluster.n_pad == cluster.n_pad \
                and _mesh_match(base.mesh, gen_mesh):
            forked = self._try_fork(base, cluster, host, usage)
            if forked is not None:
                self.fork_deltas += 1
                return _Generation(key, cluster, usage.version, forked,
                                   mesh=gen_mesh)
        self.full_uploads += 1
        return _Generation(key, cluster, usage.version,
                           self._upload(host, sharding=sharding),
                           mesh=gen_mesh)

    def _try_fork(self, base: _Generation, cluster: ClusterTensors,
                  host: Dict[str, np.ndarray], usage) -> Optional[Dict]:
        changed = IncrementalClusterCache._changed_since(
            getattr(usage, "node_events", ()), base.key[1])
        if changed is None:
            return None
        n = cluster.n_real
        stale = []
        for j, nid in enumerate(cluster.node_ids):
            if nid in changed or nid not in base.cluster.index:
                stale.append(j)
            elif base.cluster.index[nid] != j:
                # compaction permuted surviving rows: the device-side
                # scatter cannot express a gather; full upload
                return None
        if len(stale) > max(n // 2, 8):
            return None
        # rows the new build leaves as padding but the base had real
        # nodes in: their new host values are zeros by construction
        rows = set(stale) | set(range(n, base.cluster.n_real))
        dirty_usage = self._usage_rows_changed(usage, base.version)
        if dirty_usage is None:
            static_host = {f: host[f]
                           for f in ClusterTensors.WAVE_STATIC_FIELDS}
            usage_host = {f: host[f]
                          for f in ClusterTensors.WAVE_USAGE_FIELDS}
            planes = dict(base.planes)
            if rows:
                planes = self._scatter(planes, static_host, rows,
                                       mesh=base.mesh)
            self.usage_full_uploads += 1
            planes.update(self._upload(
                usage_host, sharding=self._gen_sharding(base)))
            return planes
        rows_usage = rows | {cluster.index[nid] for nid in dirty_usage
                             if nid in cluster.index}
        planes = dict(base.planes)
        static_host = {f: host[f]
                       for f in ClusterTensors.WAVE_STATIC_FIELDS}
        usage_host = {f: host[f]
                      for f in ClusterTensors.WAVE_USAGE_FIELDS}
        if rows:
            planes = self._scatter(planes, static_host, rows,
                                   mesh=base.mesh)
        if rows_usage:
            planes = self._scatter(planes, usage_host, rows_usage,
                                   mesh=base.mesh)
        return planes


#: process-wide resident state (the batching worker's snapshot path
#: and the wave launcher both consult it)
default_device_state = DeviceClusterState()
