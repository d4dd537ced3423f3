#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that nomad-tpu's served scheduling
path starts, and is right, on the chip.

One process does what a user of the system does, at a size a user would
call real. It builds the C2M replay from its seed (bench/c2m.py: 10,000
nodes, 100,000 resident allocs), starts a real server agent with its
HTTP listener (the object ``python -m nomad_tpu agent -server``
builds), restores the replay into the agent's store, and submits jobs
over HTTP with the SDK in two bursts: the lean shapes of C2M's job mix
(plain service and batch), then the full mix (spread, distinct_hosts,
GPU asks). It waits until every alloc is committed, reads them back
over HTTP, and checks the placements with host code that never touches
the kernel, and the device with what jax and the profiler report.

It exits non-zero at the first failed check, with the traceback or the
worker's own error, and never after a caught exception. Any backend but
TPU is a failure, with one exception: a caller who sets
``JAX_PLATFORMS=cpu`` AND a ``--nodes``/``--allocs`` size of their own
gets the sandbox rehearsal of the same path, which says
``platform: cpu``. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, with the device as jax reports it.

On the chip, through the builder's tool:

    chiprun -- python3 chip_smoke.py
    chiprun --chips 4 -- python3 chip_smoke.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "bench"))

#: C2M's lean job shapes: what the lean wave program serves
LEAN_KINDS = ("service", "batch")
#: how far a recorded score may sit from the host's recomputation: the
#: device works in f32 and the chip's pow is not libm's
SCORE_TOL = 1e-3


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def draw_jobs(c2m, rng, tag: str, n: int, kinds=None) -> list:
    """``n`` jobs in the proportions of C2M's job mix (restricted to
    ``kinds``), by largest remainder with at least one of every kind, so
    that a burst of any size exercises every shape it claims to."""
    shapes = [s for s in c2m.JOB_SHAPES
              if kinds is None or s[4] in kinds]
    total = sum(s[0] for s in shapes)
    exact = [n * s[0] / total for s in shapes]
    counts = [max(int(x), 1) for x in exact]
    by_remainder = sorted(range(len(shapes)),
                          key=lambda i: exact[i] - int(exact[i]),
                          reverse=True)
    i = 0
    while sum(counts) < n:
        counts[by_remainder[i % len(shapes)]] += 1
        i += 1
    while sum(counts) > n:
        counts[max(range(len(shapes)), key=counts.__getitem__)] -= 1
    jobs = []
    for shape, count in zip(shapes, counts):
        for _ in range(count):
            job = c2m._make_job(rng, len(jobs), shape)
            job.id = job.name = f"smoke-{tag}-{shape[4]}-{len(jobs)}"
            jobs.append((shape[4], job))
    order = rng.permutation(len(jobs))
    return [jobs[i] for i in order]


def check_workers(server) -> None:
    for w in server.workers:
        if w.last_error is not None:
            raise SmokeFailure(
                f"scheduler worker {w.id} failed an eval:\n{w.last_error}")


def run_burst(api, server, jobs, timeout_s: float) -> float:
    """Register ``jobs`` over HTTP and wait until every alloc they ask
    for is committed and the broker is idle. Returns the seconds from
    the moment the scheduler was let at the backlog.

    The workers are held while the burst registers (the pause a leader
    applies to its workers across a leadership change), so the burst is
    scheduled as ONE backlog, a full-width wave first: the wave shapes,
    and with them the programs compiled, are then the same in every
    run, which is what makes set-up seconds comparable between runs."""
    from nomad_tpu.api.codec import encode

    wanted = {job.id: job.task_groups[0].count for _, job in jobs}
    for w in server.workers:
        w.set_pause(True)
    # a worker already blocked in a dequeue (0.2 s) finishes it first
    time.sleep(0.5)
    with ThreadPoolExecutor(max_workers=8) as pool:
        for res in pool.map(
                lambda kj: api.jobs.register(encode(kj[1])), jobs):
            if not res.get("EvalID"):
                raise SmokeFailure(f"job register returned {res}")
    t0 = time.monotonic()
    for w in server.workers:
        w.set_pause(False)
    deadline = t0 + timeout_s
    while True:
        check_workers(server)
        snap = server.state.snapshot()
        placed = {
            jid: sum(1 for a in snap.allocs_by_job("default", jid)
                     if not a.terminal_status())
            for jid in wanted}
        broker = server.eval_broker.stats()
        busy = (broker["total_ready"] + broker["total_unacked"]
                + broker["total_pending"] + broker["total_waiting"])
        if placed == wanted and not busy:
            return time.monotonic() - t0
        if time.monotonic() > deadline:
            short = {j: (placed[j], wanted[j]) for j in wanted
                     if placed[j] != wanted[j]}
            raise SmokeFailure(
                f"burst not placed after {timeout_s:.0f}s: "
                f"{len(short)} jobs short (placed, wanted) "
                f"{dict(list(short.items())[:8])}; broker {broker}; "
                f"blocked {server.blocked_evals.stats()}")
        time.sleep(0.1)


def wave_programs(profiler) -> list:
    """(kernel, padded nodes, features) of every wave program
    dispatched since the profiler's last reset."""
    from nomad_tpu.ops.kernel import KernelFeatures

    return [(kernel, key[2],
             next(p for p in key if isinstance(p, KernelFeatures)))
            for kernel, key in profiler.keys()
            if not kernel.startswith("single_")]


def dispatched(profiler) -> str:
    """One line on what the device was asked to do since the profiler's
    last reset: each program with the shape part of its bucket key
    (wave/steps/nodes for a wave, nodes/steps for a lone eval)."""
    summary = profiler.summary()
    programs = ", ".join(
        f"{row['Kernel']}[{row['Key'].split('/KernelFeatures(')[0]}]"
        f" x{row['Launches']}" for row in summary["PerKey"])
    others = ", ".join(
        f"{k} x{n}" for k, n in sorted(summary["Dispatches"].items())
        if k in ("wave_fetch", "topk_drain"))
    return f"{programs}; {others}" if others else programs


def check_burst_programs(profiler, label: str, n_devices: int,
                         program: str, mixed: bool) -> None:
    """Every wave of the burst ran the program the launcher's router
    (coalesce.wave_program) names for its nodes and features. A lean
    burst's waves all ran ``program``; a mixed burst put its spread
    and GPU asks into a wave that ran ``program``."""
    from nomad_tpu.ops.kernel import features_key
    from nomad_tpu.parallel.coalesce import wave_program

    waves = wave_programs(profiler)
    for kernel, n_nodes, feats in waves:
        want = wave_program(n_devices, n_nodes, feats)
        if kernel != want:
            raise SmokeFailure(
                f"{label}: a wave over {n_nodes} nodes with "
                f"{features_key(feats)} ran {kernel!r}, the router "
                f"names {want!r}; dispatched: {dispatched(profiler)}")
    if mixed:
        if not any(k == program and (f.n_spreads or f.with_devices)
                   for k, _n, f in waves):
            raise SmokeFailure(
                f"{label}: no dispatch of wave program {program!r} "
                f"with a spread or a device ask in its feature union; "
                f"dispatched: {dispatched(profiler)}")
    elif not waves or any(k != program for k, _n, _f in waves):
        raise SmokeFailure(
            f"{label}: lean waves left the lean program {program!r}: "
            f"{dispatched(profiler)}")


def check_output_devices(profiler, programs, platform: str,
                         n_devices: int) -> None:
    seen = profiler.output_devices
    for program in programs:
        devs = seen.get(program, set())
        if not devs or {d.platform for d in devs} != {platform}:
            raise SmokeFailure(
                f"outputs of {program!r} were found on {sorted(map(str, devs))}, "
                f"expected platform {platform!r}")
        if program.endswith("_sharded") and len(devs) != n_devices:
            raise SmokeFailure(
                f"outputs of {program!r} span {len(devs)} devices, "
                f"expected {n_devices}")


def check_scores(server, jobs, new_job_ids) -> int:
    """Recorded scores against the host's own fit function.

    An alloc's metrics carry the top scores of its placement step. For
    a lean job (no spread, affinity or penalty plane) the final score of
    a node that holds no alloc of this run other than the alloc being
    placed is the fit plane alone, so it must equal
    ``score_fit_binpack(node, resident + ask) / 18`` recomputed here
    from the store. Returns the number of scores compared."""
    from nomad_tpu.structs.resources import allocs_fit, score_fit_binpack

    snap = server.state.snapshot()
    compared = 0
    worst = 0.0
    for kind, job in jobs:
        for alloc in snap.allocs_by_job("default", job.id):
            if not alloc.metrics.score_meta:
                raise SmokeFailure(
                    f"alloc {alloc.id} of {job.id} has no recorded scores")
            for node_id, _planes, score in alloc.metrics.score_meta:
                here = snap.allocs_by_node(node_id)
                if any(a.job_id in new_job_ids and a.id != alloc.id
                       for a in here):
                    continue
                node = snap.node_by_id(node_id)
                resident = [a for a in here if a.id != alloc.id]
                _fit, _dim, util = allocs_fit(node, resident + [alloc])
                want = score_fit_binpack(node, util) / 18.0
                worst = max(worst, abs(score - want))
                if abs(score - want) > SCORE_TOL:
                    raise SmokeFailure(
                        f"score of node {node_id} for alloc {alloc.id} "
                        f"({job.id}): recorded {score!r}, host "
                        f"score_fit_binpack/18 = {want!r}")
                compared += 1
    if not compared:
        raise SmokeFailure("no recorded score could be compared")
    say("score_max_abs_diff", f"{worst:.3g}")
    return compared


def check_placements(server, bursts, n_nodes: int) -> None:
    """The committed state is one a correct scheduler could have left,
    by code that does not touch the kernel."""
    from nomad_tpu.state.usage import usage_rebuild_diff
    from nomad_tpu.structs.resources import allocs_fit

    diff = usage_rebuild_diff(server.state)
    if diff:
        raise SmokeFailure(f"usage planes drifted from a rebuild: {diff[:5]}")
    snap = server.state.snapshot()
    ready = sum(1 for n in snap.nodes() if n.ready())
    if ready != n_nodes:
        raise SmokeFailure(f"{ready} of {n_nodes} nodes are still ready")
    touched = set()
    for jobs in bursts:
        for kind, job in jobs:
            allocs = snap.allocs_by_job("default", job.id)
            nodes = [a.node_id for a in allocs]
            touched.update(nodes)
            if kind == "service-distinct" and len(set(nodes)) != len(nodes):
                raise SmokeFailure(
                    f"distinct_hosts job {job.id} shares a node: {nodes}")
            if kind == "gpu":
                for a in allocs:
                    node = snap.node_by_id(a.node_id)
                    if node.node_class != "gpu":
                        raise SmokeFailure(
                            f"GPU job {job.id} sits on a "
                            f"{node.node_class!r} node {node.id}")
    for node_id in touched:
        node = snap.node_by_id(node_id)
        fit, dim, _used = allocs_fit(
            node, snap.allocs_by_node(node_id), check_devices=True)
        if not fit:
            raise SmokeFailure(f"node {node_id} no longer fits: {dim}")
    failed = [e.id for e in snap.evals_iter()
              if e.status in ("failed", "canceled")
              or e.triggered_by == "failed-follow-up"]
    if failed:
        raise SmokeFailure(f"failed evals: {failed[:5]}")
    say("touched_nodes", len(touched))


def check_readback(api, server, bursts) -> int:
    """What the API serves is what the store committed."""
    snap = server.state.snapshot()
    total = 0
    for jobs in bursts:
        for _kind, job in jobs:
            served = {(a["ID"], a["NodeID"])
                      for a in api.jobs.allocations(job.id)}
            stored = {(a.id, a.node_id)
                      for a in snap.allocs_by_job("default", job.id)}
            if served != stored or len(served) != job.task_groups[0].count:
                raise SmokeFailure(
                    f"job {job.id}: HTTP serves {len(served)} allocs, "
                    f"store holds {len(stored)}, job asks "
                    f"{job.task_groups[0].count}")
            total += len(served)
    return total


def check_resident_planes(server, platform: str, n_devices: int) -> None:
    """The wave-shared planes live on the device; on a mesh, each
    device holds its own quarter (or 1/n) of the rows."""
    from nomad_tpu.parallel.coalesce import sharded_wave_stats
    from nomad_tpu.tensors.device_state import default_device_state

    planes = default_device_state.newest_planes()
    if not planes:
        raise SmokeFailure("no resident cluster planes after two bursts")
    for field, arr in planes.items():
        platforms = {d.platform for d in arr.devices()}
        if platforms != {platform}:
            raise SmokeFailure(
                f"resident plane {field} lives on {platforms}")
    say("resident_planes", f"{len(planes)} on {platform}")
    stats = sharded_wave_stats.snapshot()
    if n_devices == 1:
        if server.wave_mesh is not None or stats["launches"]:
            raise SmokeFailure(
                f"one device, yet a mesh was adopted: {stats}")
        say("mesh", "none adopted (one device)")
        return
    if server.wave_mesh is None or server.wave_mesh.size != n_devices:
        raise SmokeFailure(f"{n_devices} devices but mesh is "
                           f"{server.wave_mesh}")
    if not (stats["launches"] > 0 and stats["fallbacks"] == 0
            and stats["mesh_devices"] == n_devices):
        raise SmokeFailure(f"sharded wave stats: {stats}")
    arr = planes["cap_cpu"]
    shards = arr.addressable_shards
    rows = arr.shape[0] // n_devices
    if (len(shards) != n_devices
            or {s.data.shape for s in shards} != {(rows,)}
            or len({s.device for s in shards}) != n_devices):
        raise SmokeFailure(
            f"resident plane cap_cpu{arr.shape} is split as "
            f"{[(str(s.device), s.data.shape) for s in shards]}")
    say("mesh", f"{n_devices} devices, {stats['launches']} sharded waves, "
                f"0 fallbacks, {rows} rows of cap_cpu on each device")


def main() -> int:
    import c2m

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nodes", type=int, default=None,
                    help=f"replay nodes (default {c2m.N_NODES})")
    ap.add_argument("--allocs", type=int, default=None,
                    help=f"replay resident allocs (default {c2m.N_ALLOCS})")
    ap.add_argument("--jobs", type=int, default=64,
                    help="jobs in each burst")
    ap.add_argument("--seed", type=int, default=c2m.SEED)
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="directory for everything the run writes")
    args = ap.parse_args()
    rehearsal = (os.environ.get("JAX_PLATFORMS") == "cpu"
                 and (args.nodes is not None or args.allocs is not None))
    n_nodes = args.nodes if args.nodes is not None else c2m.N_NODES
    n_allocs = args.allocs if args.allocs is not None else c2m.N_ALLOCS

    import jax
    import jaxlib
    import numpy as np

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU: jax.devices() returned {devices}. "
              "(A rehearsal on the CPU needs JAX_PLATFORMS=cpu and a "
              "--nodes/--allocs size.)", file=sys.stderr)
        return 2

    from nomad_tpu.api.agent import Agent, AgentConfig
    from nomad_tpu.api.client import APIClient
    from nomad_tpu.ops.kernel import FULL_FEATURES, LEAN_FEATURES
    from nomad_tpu.parallel import coalesce
    from nomad_tpu.telemetry.kernel_profile import profiler
    from nomad_tpu.tensors.schema import pad_bucket

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("platform", platform)
    say("device_kind", devices[0].device_kind)
    say("device_count", len(devices))
    say("jax", f"{jax.__version__} jaxlib {jaxlib.__version__} "
               f"libtpu {libtpu}")
    say("compile_cache_dir", jax.config.jax_compilation_cache_dir)
    cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0,
                    "cache_misses": 0}

    def on_event(event: str, **_kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") \
                and name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(on_event)
    say("replay", f"{n_nodes} nodes / {n_allocs} allocs, seed {args.seed}")

    os.makedirs(args.out, exist_ok=True)
    t0 = time.monotonic()
    snap_path = c2m.generate(os.path.join(args.out, "c2m_replay.snap"),
                             n_nodes=n_nodes, n_allocs=n_allocs,
                             seed=args.seed, verbose=False)
    say("generate_s", f"{time.monotonic() - t0:.1f}")

    agent = Agent(AgentConfig(
        name="chip-smoke", num_schedulers=1, serf_enabled=False,
        kernel_warmup=False))
    agent.start()
    try:
        server = agent.server
        t0 = time.monotonic()
        with open(snap_path, "rb") as f:
            server.state.restore_from_bytes(f.read())
        # tens of megabytes nothing reads again: not worth carrying
        # back from the chip in the output directory
        os.remove(snap_path)
        say("restore_s", f"{time.monotonic() - t0:.1f}")
        resident = sum(1 for _ in server.state.snapshot().allocs_iter())
        say("resident_allocs", resident)

        api = APIClient(agent.http_addr)
        rng = np.random.default_rng(args.seed + 1)
        first_job = draw_jobs(c2m, rng, "first", 1, ("service",))
        lean_jobs = draw_jobs(c2m, rng, "lean", args.jobs, LEAN_KINDS)
        mixed_jobs = draw_jobs(c2m, rng, "mixed", args.jobs)
        n_pad = pad_bucket(n_nodes)
        lean_program = coalesce.wave_program(
            len(devices), n_pad, LEAN_FEATURES)
        mixed_program = coalesce.wave_program(
            len(devices), n_pad, FULL_FEATURES)

        # One job alone first, as on any server that has scheduled
        # before its first backlog arrives: it takes the single-eval
        # path, and it builds the host's cluster tensors ONCE. (Every
        # member of a cold first wave would build them for itself:
        # PERF.md, "Bring-up on v5e".)
        profiler.enable()
        new_job_ids = set()
        for label, jobs, program, mixed in (
                ("first_job", first_job, None, False),
                ("lean_burst", lean_jobs, lean_program, False),
                ("mixed_burst", mixed_jobs, mixed_program, True)):
            profiler.reset()
            new_job_ids.update(job.id for _, job in jobs)
            wall = run_burst(api, server, jobs, timeout_s=300.0)
            stages = profiler.summary()["StageSeconds"]
            if program is None:
                ran = [k for k, _key in profiler.keys()
                       if k.startswith("single_")]
                if not ran:
                    raise SmokeFailure(
                        f"{label}: no single-eval dispatch; dispatched: "
                        f"{dispatched(profiler)}")
                check_output_devices(profiler, ran, platform, len(devices))
                served_by = "/".join(sorted(set(ran)))
            else:
                check_burst_programs(profiler, label, len(devices),
                                     program, mixed)
                check_output_devices(profiler, [program], platform,
                                     len(devices))
                served_by = program
            say(label,
                f"{len(jobs)} jobs / "
                f"{sum(j.task_groups[0].count for _, j in jobs)} allocs, "
                f"served by {served_by}; dispatched: {dispatched(profiler)}")
            say(f"{label}_setup_s", f"{stages['compile']:.1f}")
            say(f"{label}_serving_s", f"{wall - stages['compile']:.1f}")
            say(f"{label}_stage_s", stages)
            if label == "lean_burst":
                say("scores_compared",
                    check_scores(server, jobs, new_job_ids))
                fused = coalesce.fused_wave_stats.snapshot()
                if fused["fallbacks"]:
                    raise SmokeFailure(
                        f"lean burst counted fused fallbacks: {fused}")
        check_workers(server)
        bursts = (first_job, lean_jobs, mixed_jobs)
        check_placements(server, bursts, n_nodes)
        say("allocs_read_back", check_readback(api, server, bursts))
        check_resident_planes(server, platform, len(devices))
        say("fused_wave_stats", coalesce.fused_wave_stats.snapshot())
        # programs under a second to compile are neither kept nor
        # counted as written (jax's own threshold)
        say("compile_cache",
            f"{cache_events['compile_requests_use_cache']} requests, "
            f"{cache_events['cache_hits']} hits, "
            f"{cache_events['cache_misses']} written")
    finally:
        agent.shutdown()

    result = {"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
