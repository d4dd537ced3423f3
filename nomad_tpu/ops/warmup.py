"""AOT kernel warmup: precompile the live path's placement kernels.

PR 1's TRACE_DECOMP made the live-path gap a measurement: jit
``compile`` was 50% of per-eval wall, one miss per
(wave, nodes, steps, features) bucket key. The buckets exist precisely
so the variant set is small and enumerable — which means a server can
compile all of them BEFORE the first evaluation ever needs one,
instead of paying each cold compile inside a scheduling deadline.

The enumeration is driven by a **warmup manifest**: the bucket keys a
production server actually launched, persisted from the kernel
profiler's per-key stats (telemetry/kernel_profile.py). At startup
(Server.start, background thread) the manifest replays as ahead-of-time
compilations of the ``joint`` wave kernel and the ``single_topk`` /
``single_full`` direct kernels against neutral dummy planes of the
recorded shapes — populating the exact jit caches the live launches
hit (and, transitively, the persistent XLA compilation cache, so the
cost is once per machine, not once per process).

``expand_lattice`` widens a manifest downward over the wave-bucket
axis: tail waves (a partial batch, a deadline-fired wave) use smaller
buckets than the steady state, and those are exactly the variants a
steady-state-derived manifest would otherwise miss.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

LOG = logging.getLogger(__name__)

MANIFEST_VERSION = 1

#: default manifest location (overridable per server via
#: ServerConfig.warmup_manifest_path / agent config `warmup_manifest`)
DEFAULT_MANIFEST_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "nomad_tpu_warmup.json")


def _features_to_dict(f) -> Dict:
    return dict(f._asdict())


def _features_from_dict(d: Dict):
    from nomad_tpu.ops.kernel import KernelFeatures

    return KernelFeatures(**{k: v for k, v in d.items()
                             if k in KernelFeatures._fields})


def manifest_from_profiler(profiler=None) -> List[Dict]:
    """Flatten the kernel profiler's observed (kernel, bucket-key)
    launches into JSON-able manifest entries. Sharded-wave keys fold
    into the SAME mesh-agnostic joint entries (their trailing devices
    tuple dropped): the compiled program is mesh-specific, but the
    bucket lattice a mesh server observes is exactly what the next
    start must precompile — unsharded always, sharded again once its
    own mesh probe lands (warmup_entries' ``mesh``)."""
    if profiler is None:
        from nomad_tpu.telemetry.kernel_profile import profiler as _p

        profiler = _p
    entries: List[Dict] = []
    for kernel, key in profiler.keys():
        try:
            if kernel == "joint_sharded" and len(key) == 8:
                # (joint 7-key, devices-tuple): mesh-agnostic manifest
                kernel, key = "joint", key[:7]
            # the mesh's fused program folds into the SAME joint
            # entries: it reuses the wave bucket key verbatim, and
            # warmup_entries asks coalesce.wave_program which program
            # an entry's wave runs — so one manifest line covers
            # one-device, sharded and fused-sharded
            if kernel == "fused_wave_sharded" and len(key) == 8:
                kernel, key = "joint", key[:7]
            # and so does the one-device Pallas program, under the
            # same 7-key
            if kernel == "joint_fused":
                kernel = "joint"
            if kernel == "joint" and len(key) in (6, 7):
                # len 6: pre-job-group keys from persisted manifests
                # (job_shared defaults True, the common layout)
                b_pad, t_pad, n_nodes, shared, neutral_shared = key[:5]
                job_shared = key[5] if len(key) == 7 else True
                feats = key[-1]
                entries.append({
                    "kernel": "joint",
                    "wave": int(b_pad), "steps": int(t_pad),
                    "nodes": int(n_nodes),
                    "shared": bool(shared),
                    "neutral_shared": bool(neutral_shared),
                    "job_shared": bool(job_shared),
                    "features": _features_to_dict(feats),
                })
            elif kernel in ("single_topk", "single_full") and len(key) == 3:
                n_pad, k_steps, feats = key
                entries.append({
                    "kernel": kernel,
                    "nodes": int(n_pad), "steps": int(k_steps),
                    "features": _features_to_dict(feats),
                })
        except Exception:                       # noqa: BLE001
            continue
    return _dedupe(entries)


def _entry_key(e: Dict) -> Tuple:
    return (e.get("kernel"), e.get("wave"), e.get("steps"),
            e.get("nodes"), e.get("shared"), e.get("neutral_shared"),
            e.get("job_shared", True),
            tuple(sorted((e.get("features") or {}).items())))


def _dedupe(entries: List[Dict]) -> List[Dict]:
    seen = set()
    out = []
    for e in entries:
        k = _entry_key(e)
        if k not in seen:
            seen.add(k)
            out.append(e)
    return out


def load_manifest(path: str) -> List[Dict]:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        return list(data.get("entries", []))
    return list(data)


def save_manifest(entries: List[Dict], path: str,
                  merge: bool = True) -> int:
    """Persist ``entries`` (unioned with any existing manifest when
    ``merge``): the bucket lattice a deployment accumulates over
    restarts is the set worth precompiling. Returns the entry count
    written. Best-effort atomic (write + rename)."""
    if merge and os.path.exists(path):
        try:
            entries = list(load_manifest(path)) + list(entries)
        except Exception:                       # noqa: BLE001
            pass
    entries = _dedupe(entries)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": MANIFEST_VERSION, "entries": entries},
                  f, indent=2)
        f.write("\n")
    os.replace(tmp, path)
    return len(entries)


def expand_lattice(entries: List[Dict],
                   max_wave: Optional[int] = None) -> List[Dict]:
    """Widen joint-wave entries across the bucket lattice a steady
    state reaches from an observed variant:

    - wave axis: every smaller wave bucket (tail / deadline-fired
      partial waves), and — given ``max_wave``, e.g. the worker's
      padded batch size — larger buckets up to the full wave;
    - step axis: every step bucket from the live floor
      (MIN_STEP_BUCKET) up to the observed one — follow-up evals
      placing a job's leftovers launch with fewer steps;
    - layout axis: ALL four sharing layouts for multi-member waves
      (shared x neutral-shared; a retry member forks either group
      independently — a refreshed snapshot stacks the cluster planes,
      a follow-up eval's live-alloc counts stack the neutral group)
      and the fully-shared layout for 1-waves;
    - feature axis: the rescheduling variant (step penalties +
      preferred pins travel together post-canonicalization) —
      follow-up evals for failed allocs carry penalty nodes;
    - plus the direct-dispatch ``single_topk``/``single_full``
      programs a 1-eval batch launches."""
    from nomad_tpu.ops.kernel import MIN_STEP_BUCKET, pad_steps
    from nomad_tpu.parallel.coalesce import _WAVE_BUCKETS, pad_wave

    out = list(entries)
    for e in entries:
        if e.get("kernel") != "joint":
            continue
        b_pad = int(e["wave"])
        ceiling = max(b_pad, pad_wave(max_wave) if max_wave else 0)
        k_max = max(int(e["steps"]) // max(b_pad, 1), 1)
        k_buckets = sorted({pad_steps(k_max),
                            *(b for b in range(1, k_max + 1)
                              if b == pad_steps(b)
                              and b >= MIN_STEP_BUCKET)})
        feats_variants = [dict(e["features"])]
        aux = dict(e["features"])
        if not (aux.get("with_step_penalties")
                and aux.get("with_preferred")):
            aux["with_step_penalties"] = True
            aux["with_preferred"] = True
            feats_variants.append(aux)
        for feats in feats_variants:
            for k in k_buckets:
                for w in _WAVE_BUCKETS:
                    if w > ceiling:
                        continue
                    base = {**e, "features": feats, "wave": w,
                            "steps": pad_steps(w * k)}
                    if w == 1:
                        # a lone member shares every field with
                        # itself: 1-waves ALWAYS take the fully-shared
                        # layout
                        out.append({**base, "shared": True,
                                    "neutral_shared": True,
                                    "job_shared": True})
                    else:
                        # every sharing layout: a member with a
                        # refreshed snapshot stacks the cluster group,
                        # a follow-up eval's live-alloc counts stack
                        # the job group, a device/spread ask stacks
                        # the wide neutral group, a partial-commit
                        # retry stacks them all — each combination is
                        # its own compiled variant the steady state
                        # can hit
                        for sh in (True, False):
                            for ns in (True, False):
                                for js in (True, False):
                                    out.append({
                                        **base, "shared": sh,
                                        "neutral_shared": ns,
                                        "job_shared": js})
                # an eval in a 1-eval batch dispatches DIRECTLY
                # (ops/kernel.default_kernel_launch) with the same
                # shapes and features a wave member would ship
                out.append({"kernel": "single_topk",
                            "nodes": int(e["nodes"]), "steps": k,
                            "features": feats})
                out.append({"kernel": "single_full",
                            "nodes": int(e["nodes"]), "steps": k,
                            "features": feats})
    return _dedupe(out)


# --- dummy-plane construction ----------------------------------------


def _dummy_kin(n: int, k_pad: int):
    """A neutral KernelIn with build_kernel_in's exact dtypes/shapes —
    the jit cache keys on (shape, dtype), so fidelity here is what
    makes the warmup compile THE program the live launch reuses."""
    from nomad_tpu.ops.kernel import (
        KernelIn,
        neutral_planes,
        neutral_step_planes,
    )
    from nomad_tpu.tensors.schema import (
        MAX_DEV_REQS,
        MAX_SPREADS,
        SPREAD_BUCKETS,
    )

    neutral = neutral_planes(n)
    pen, pref = neutral_step_planes(k_pad)
    return KernelIn(
        cap_cpu=neutral.zeros_f32, cap_mem=neutral.zeros_f32,
        cap_disk=neutral.zeros_f32,
        free_cores=neutral.zeros_i32,
        shares_per_core=neutral.zeros_f32,
        free_dyn=neutral.zeros_i32,
        base_mask=neutral.zeros_bool,
        used_cpu=neutral.zeros_f32, used_mem=neutral.zeros_f32,
        used_disk=neutral.zeros_f32,
        used_cores=neutral.zeros_i32, used_mbits=neutral.zeros_i32,
        avail_mbits=neutral.zeros_i32,
        port_conflict=neutral.zeros_bool,
        dev_free=neutral.zeros_dev,
        dev_aff_score=neutral.zeros_f32,
        has_dev_affinity=np.asarray(False, bool),
        job_tg_count=neutral.zeros_i32,
        penalty=neutral.zeros_bool,
        aff_score=neutral.zeros_f32,
        node_perm=neutral.arange_i32,
        step_penalty=pen, step_preferred=pref,
        job_any_count=neutral.zeros_i32,
        distinct_hosts_job=np.asarray(False, bool),
        distinct_hosts_tg=np.asarray(False, bool),
        spread_active=neutral.zeros_spread_flags,
        spread_even=neutral.zeros_spread_flags,
        spread_weight=neutral.zeros_spread_weight,
        spread_bucket=neutral.neg1_spread_bucket,
        spread_counts=neutral.zeros_spread_counts,
        spread_desired=neutral.neg1_spread_desired,
        ask_cpu=np.asarray(0.0, np.float32),
        ask_mem=np.asarray(0.0, np.float32),
        ask_disk=np.asarray(0.0, np.float32),
        ask_cores=np.asarray(0, np.int32),
        ask_dyn_ports=np.asarray(0, np.int32),
        ask_has_reserved_ports=np.asarray(False, bool),
        ask_dev=np.zeros(MAX_DEV_REQS, np.float32),
        ask_mbits=np.asarray(0, np.int32),
        desired_count=np.asarray(1, np.int32),
        algorithm_spread=np.asarray(False, bool),
        n_steps=np.asarray(0, np.int32),
    )


def _call_both_placements(fn, arrays: tuple, statics: tuple,
                          mixed=None) -> None:
    """Populate EVERY jit-cache entry a live launch can hit: the
    kernel profiler device_puts its arguments (committed arrays) while
    the unprofiled path passes host numpy (uncommitted) — jax keys its
    jit cache on commitment, so these are distinct entries over one
    XLA program (the second trace re-hits the compilation cache).

    ``mixed`` (a KernelIn of bools, or None) warms a THIRD signature:
    the unprofiled path with the device-resident cluster state active
    (tensors/device_state.py) passes committed device arrays for the
    shared leaves and host numpy for the rest — commitment follows the
    wave layout flags exactly, so one extra variant per entry covers
    it."""
    import jax

    out = fn(*jax.device_put(arrays), *statics)
    jax.block_until_ready(out)
    out = fn(*arrays, *statics)
    jax.block_until_ready(out)
    if mixed is not None and any(mixed):
        kin = arrays[0]
        kin = kin._replace(**{
            f: jax.device_put(getattr(kin, f))
            for f, m in zip(kin._fields, mixed) if m
        })
        out = fn(kin, *arrays[1:], *statics)
        jax.block_until_ready(out)


def _entry_wave(e: Dict):
    """Build one manifest entry's dummy wave exactly as launch_wave
    stacks it: the layout predicate is SHARED with the launcher
    (wave_field_is_shared), because the jit cache keys on shapes.
    Returns the stacked KernelIn, the step planes, the statics and,
    field by field, whether the layout ships the leaf shared — which
    are the leaves the live launcher swaps for device-resident
    twins."""
    from nomad_tpu.ops.kernel import KernelIn
    from nomad_tpu.parallel.coalesce import wave_field_is_shared

    b_pad = int(e["wave"])
    t_pad = int(e["steps"])
    layout = (bool(e.get("shared", True)),
              bool(e.get("neutral_shared", True)),
              bool(e.get("job_shared", True)))
    feats = _features_from_dict(e["features"])
    k_max = max(t_pad // max(b_pad, 1), 1)
    kin = _dummy_kin(int(e["nodes"]), k_max)
    shared = [wave_field_is_shared(f, *layout) for f in KernelIn._fields]
    stacked = KernelIn(*[
        np.asarray(x) if sh else np.stack([np.asarray(x)] * b_pad)
        for x, sh in zip(kin, shared)
    ])
    step_member = np.full(t_pad, -1, np.int32)
    step_local = np.zeros(t_pad, np.int32)
    step_member[:b_pad * k_max] = np.repeat(np.arange(b_pad), k_max)
    step_local[:b_pad * k_max] = np.tile(np.arange(k_max), b_pad)
    return stacked, step_member, step_local, (t_pad, feats), layout, shared


def _warm_joint(e: Dict, program: str) -> bool:
    """Populate the jit cache of the one-device program ``program``
    (``joint`` or ``joint_fused``, as coalesce.wave_program chose) for
    a manifest entry."""
    import jax.numpy as jnp

    from nomad_tpu.ops.joint_fused import place_taskgroups_joint_fused_jit
    from nomad_tpu.ops.kernel import place_taskgroups_joint_jit

    stacked, step_member, step_local, statics, _layout, shared = \
        _entry_wave(e)
    _call_both_placements(
        place_taskgroups_joint_fused_jit if program == "joint_fused"
        else place_taskgroups_joint_jit,
        (stacked, jnp.asarray(step_member), jnp.asarray(step_local)),
        statics, mixed=shared)
    return True


def _warm_sharded(e: Dict, mesh, entry) -> bool:
    """Populate a mesh program's jit cache for a manifest entry;
    ``entry`` is the program's own (parallel/sharded.joint_sharded_entry
    or fused_sharded_entry, as coalesce.wave_program chose). The live
    signatures a mesh server's waves hit:

    1. every leaf host numpy (telemetry off, nothing resident — the
       jit itself uploads per its in_shardings);
    2. every leaf committed WITH the jit's shardings (the profiled
       path pre-places host leaves, and resident leaves arrive
       mesh-placed);
    3. mixed: the layout's shared leaves committed sharded (the
       resident cluster state + frozen singletons), the rest host.

    All three trace onto ONE XLA program; the extra traces are cache
    hits on the compilation cache."""
    import jax

    from nomad_tpu.ops.kernel import KernelIn

    stacked, step_member, step_local, statics, layout, shared = \
        _entry_wave(e)
    fn, kin_shardings, repl = entry(mesh, *layout)
    arrays = (stacked, step_member, step_local)
    # all-host signature (jit uploads per in_shardings)
    jax.block_until_ready(fn(*arrays, *statics))
    # all-committed signature (the profiled path)
    placed = jax.device_put(arrays, (kin_shardings, repl, repl))
    jax.block_until_ready(fn(*placed, *statics))
    # mixed signature: shared leaves resident (mesh-placed), rest host
    # — only meaningful when the layout shares something (all-stacked
    # waves have no resident leaves, and the mixed call would just
    # repeat the all-host trace)
    subs = {
        f: jax.device_put(getattr(stacked, f), getattr(kin_shardings, f))
        for f, sh in zip(KernelIn._fields, shared) if sh
    }
    if subs:
        jax.block_until_ready(
            fn(stacked._replace(**subs), step_member, step_local,
               *statics))
    return True


def _warm_single(e: Dict) -> bool:
    from nomad_tpu.ops.kernel import (
        KernelIn,
        place_taskgroup_jit,
        place_taskgroup_topk_jit,
    )
    from nomad_tpu.parallel.coalesce import wave_field_is_shared

    n = int(e["nodes"])
    k_steps = int(e["steps"])
    feats = _features_from_dict(e["features"])
    kin = _dummy_kin(n, k_steps)
    # the direct dispatch substitutes BOTH sharing groups when the
    # cluster state is resident (ops/kernel._resident_kin)
    mixed = [wave_field_is_shared(f, True, True, True)
             for f in KernelIn._fields]
    if e["kernel"] == "single_topk":
        if feats.n_spreads != 0:
            return False                # topk path never compiles these
        _call_both_placements(place_taskgroup_topk_jit, (kin,),
                              (k_steps, feats), mixed=mixed)
    else:
        _call_both_placements(place_taskgroup_jit, (kin,),
                              (k_steps, feats), mixed=mixed)
    return True


def warmup_entries(entries: List[Dict], mesh=None,
                   mesh_only: bool = False) -> Tuple[int, int]:
    """Compile every manifest entry; returns (compiled, failed). An
    entry that fails to compile is an ERROR, logged with its traceback
    and counted — the same program would fail the same way under a
    live wave — but the pass goes on to the entries that remain.

    ``mesh``: ALSO warm the mesh program of each wave entry for this
    mesh (the default dispatch on a >=2-device server). ``mesh_only``
    skips the single-device programs."""
    from nomad_tpu.parallel.coalesce import wave_program
    from nomad_tpu.tensors.device_state import default_device_state

    compiled = failed = 0
    node_sizes = set()
    for e in _dedupe(entries):
        try:
            did = False
            if e.get("kernel") == "joint":
                # warm the program the launcher routes this entry's
                # wave to on each dispatch kind the server has, and no
                # other: a second one would double the compile time
                # on a program that never dispatches
                feats = _features_from_dict(e["features"])
                if not mesh_only:
                    did = _warm_joint(e, wave_program(
                        0, int(e["nodes"]), feats))
                program = wave_program(
                    int(mesh.size) if mesh is not None else 0,
                    int(e["nodes"]), feats)
                if program in ("joint_sharded", "fused_wave_sharded"):
                    # else: no mesh, or one the node axis does not
                    # split over, whose waves run on one device
                    from nomad_tpu.parallel.sharded import (
                        fused_sharded_entry,
                        joint_sharded_entry,
                    )

                    did = _warm_sharded(
                        e, mesh,
                        fused_sharded_entry
                        if program == "fused_wave_sharded"
                        else joint_sharded_entry) or did
            elif e.get("kernel") in ("single_topk", "single_full"):
                if not mesh_only:
                    did = _warm_single(e)
            else:
                continue
            if did:
                compiled += 1
                node_sizes.add(int(e["nodes"]))
        except Exception:       # noqa: BLE001 - counted and reported
            failed += 1
            LOG.exception("kernel warmup entry failed to compile: %s", e)
    # the device-resident state's dirty-row scatter rides the same
    # node shapes: precompile its (row-bucket, dtype) programs so the
    # first burst whose dirty set crosses a fresh bucket doesn't pay a
    # cold compile inside an eval's snapshot phase
    for n in sorted(node_sizes):
        default_device_state.warm_scatter(n)
    return compiled, failed


def warmup_from_manifest(path: str, expand: bool = True,
                         max_wave: Optional[int] = None,
                         mesh=None) -> Tuple[int, int]:
    """Load ``path`` and precompile its lattice (expanded across the
    wave-bucket axis unless ``expand=False``; see ``expand_lattice``
    for ``max_wave``, ``warmup_entries`` for ``mesh``). A missing
    manifest is a no-op (a first start has none yet); an unreadable
    one raises."""
    try:
        entries = load_manifest(path)
    except FileNotFoundError:
        return (0, 0)
    if expand:
        entries = expand_lattice(entries, max_wave=max_wave)
    return warmup_entries(entries, mesh=mesh)


def start_background_warmup(path: str, expand: bool = True,
                            max_wave: Optional[int] = None,
                            mesh=None,
                            on_done=None) -> threading.Thread:
    """Server-start entry point: warm the manifest on a daemon thread
    (compiles hold the XLA compile lock, not the GIL, so the server
    keeps serving; waves that race warmup simply compile first and the
    warmup call becomes a cache hit). Entries that failed to compile
    are reported as an error; anything else that goes wrong ends the
    thread with its traceback."""
    def run() -> None:
        compiled, failed = warmup_from_manifest(
            path, expand=expand, max_wave=max_wave, mesh=mesh)
        if failed:
            LOG.error("kernel warmup: %d of %d entries of %s failed "
                      "to compile", failed, compiled + failed, path)
        elif compiled:
            LOG.info("kernel warmup: %d compiled (%s)", compiled, path)
        if on_done is not None:
            on_done(compiled, failed)

    t = threading.Thread(target=run, daemon=True, name="kernel-warmup")
    t.start()
    return t
