#!/usr/bin/env python3
"""benchmark/run.py: one cell of nomad-tpu's benchmark, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip: build (or load) the cell's cluster
from the seed, start a real server ``Agent`` with its HTTP listener,
restore the cluster, send ONE job alone, run the cell's traffic
unmeasured for its ``warmup_s`` (and until ``warmup_jobs`` of it are
done: in a checkout's first run its programs compile there), go on
without a pause into the
measured window, stop the clients, let what is in flight finish, check
the answers against the plain reference, print one JSON line, exit.

The cell, its configuration and its per-layer metrics are data, found
by the names in ``BENCHMARK.json``: ``workloads/<cell>.json``, the
configuration's ``file``, the workload's ``loop`` in ``loops/``,
``metrics/<metric>.json`` read by ``readers/<reader>.py``. No name of
any of them is in this file.

Any backend but TPU is an error, except the rehearsal: ``JAX_PLATFORMS=cpu``
together with ``--nodes`` (and ``--allocs``) runs the same path at a
size of the caller's, says ``platform: cpu``, and is never a device
number.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse          # noqa: E402
import hashlib           # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import statistics        # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.readers import percentile    # noqa: E402

#: how long what is in flight at the close may take to finish
DRAIN_S = 120.0
#: how long the window waits for the completion that opens or closes it
EDGE_S = 5.0
#: how long the warm-up may stretch for its ``warmup_jobs`` (a cold
#: checkout compiles the traffic's programs there)
WARMUP_MAX_S = 600.0


def say(key: str, value) -> None:
    print(f"{key}: {value}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: BENCHMARK.json names no {what} {name!r}")


def end_to_end(records: list, t0: float, t1: float, setup_s: float,
               drained_at: float) -> dict:
    """The end-to-end metrics, by name, from the client's side: all the
    jobs done in the window (t0, t1] over all its seconds, and the
    latency of every job registered in it."""
    window = [r for r in records if r.t_send is not None
              and t0 <= r.t_send < t1]
    done_in = [r for r in records if r.t_done is not None
               and t0 < r.t_done <= t1]
    # a job that never came counts with the wait it was given
    lat_ms = [((r.t_done if r.t_done is not None else drained_at)
               - r.t_send) * 1e3 for r in window if r.acked]
    out = {"setup_s": (setup_s, "s"),
           "evals_per_s": (len(done_in) / (t1 - t0), "evals/s")}
    if lat_ms:
        out["job_p50_ms"] = (statistics.median(lat_ms), "ms")
        out["job_p95_ms"] = (percentile(lat_ms, 0.95), "ms")
    return out


def build_cluster(config: dict, seed: int, cache_dir: str):
    """(snapshot bytes, plain record), from the cache in the checkout
    when this configuration and seed were generated there before."""
    from benchmark.generators.plain import load_plain, save_plain

    c = config["cluster"]
    # the file's name holds what the cluster is made from, so that a
    # changed configuration never loads a cluster generated before it
    made_from = hashlib.sha256(json.dumps(
        [config["generator"], c, config["job_shapes"]],
        sort_keys=True).encode()).hexdigest()[:12]
    stem = os.path.join(cache_dir, f"{config['name']}-{made_from}-{seed}")
    if os.path.exists(stem + ".snap") and os.path.exists(stem + ".npz"):
        with open(stem + ".snap", "rb") as f:
            return f.read(), load_plain(stem + ".npz"), True
    gen = importlib.import_module(
        f"benchmark.generators.{config['generator']}")
    data, plain = gen.build(c, config["job_shapes"], seed)
    os.makedirs(cache_dir, exist_ok=True)
    with open(stem + ".snap.tmp", "wb") as f:
        f.write(data)
    os.replace(stem + ".snap.tmp", stem + ".snap")
    save_plain(stem + ".npz", plain)
    return data, plain, False


class CompileCounter:
    """jax.monitoring's compile-cache events, as the smoke listens."""

    NAMES = ("compile_requests_use_cache", "cache_hits", "cache_misses")

    def __init__(self):
        self.counts = dict.fromkeys(self.NAMES, 0)
        self.times: list = []           # (monotonic, name), for the log

    def __call__(self, event: str, **_kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") \
                and name in self.counts:
            self.counts[name] += 1
            self.times.append((time.monotonic(), name))

    def snapshot(self) -> dict:
        return dict(self.counts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--nodes", type=int, default=None,
                    help="rehearsal only: cluster size override")
    ap.add_argument("--allocs", type=int, default=None,
                    help="rehearsal only: resident allocations override")
    ap.add_argument("--control", default=None,
                    help="builder only: after the check, put the reference "
                         "computed in this precision (bfloat16) in the "
                         "program's place and print what the check then "
                         "reads; never part of a benchmark run")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "configuration")
    config = load_json(ROOT, cfg_entry["file"])
    workload = load_json(HERE, "workloads", cell["name"] + ".json")
    rehearsal = (os.environ.get("JAX_PLATFORMS") == "cpu"
                 and args.nodes is not None)
    if rehearsal:
        config["cluster"]["nodes"] = args.nodes
        config["cluster"]["resident_allocs"] = min(
            config["cluster"]["resident_allocs"],
            args.allocs if args.allocs is not None else 10 * args.nodes)

    try:
        import jax
        import nomad_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the system under test is not here: {e}",
              file=sys.stderr)
        return 3
    devices = jax.devices()
    platform = devices[0].platform
    if (platform != "tpu" and not rehearsal) or len(devices) < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); jax.devices() returned {devices}. (A rehearsal on "
              "the CPU needs JAX_PLATFORMS=cpu and --nodes.)",
              file=sys.stderr)
        return 2

    from benchmark import check, traffic
    from nomad_tpu.api.agent import Agent, AgentConfig
    from nomad_tpu.api.client import APIClient

    say("platform", platform)
    say("device_kind", devices[0].device_kind)
    say("device_count", len(devices))
    say("compile_cache_dir", jax.config.jax_compilation_cache_dir)
    compiles = CompileCounter()
    jax.monitoring.register_event_listener(compiles)

    phases = {"imports_s": time.monotonic() - T_PROCESS}
    t = time.monotonic()
    snap, plain, cached = build_cluster(
        config, args.seed, os.path.join(HERE, ".cache"))
    phases["cluster_s" + ("_cached" if cached else "")] = \
        time.monotonic() - t
    t = time.monotonic()
    deck = traffic.build_deck(config, workload, args.seed,
                              f"b{args.seed % 100000}")
    phases["deck_s"] = time.monotonic() - t

    agent = Agent(AgentConfig(name="benchmark", **config["agent"]))
    agent.start()
    result = None
    try:
        server = agent.server
        t = time.monotonic()
        server.state.restore_from_bytes(snap)
        del snap
        phases["restore_s"] = time.monotonic() - t
        api = APIClient(agent.http_addr)
        loop = importlib.import_module(
            f"benchmark.loops.{workload['loop']}").Loop(api, deck, workload)
        loop.start_listener()
        t = time.monotonic()
        lone = loop.send_one_and_wait(600.0)
        phases["first_job_s"] = time.monotonic() - t

        loop.start_clients()
        t_warm = time.monotonic()
        tracing = None
        if args.trace:
            from benchmark import tracing as tracing_mod

            metric_files = [
                load_json(HERE, "metrics", spec["name"] + ".json")
                for spec in bench["per_layer"]
                if "workloads" not in spec
                or cell["name"] in spec["workloads"]]
            tracing = tracing_mod.Tracing(
                os.path.join(HERE, ".trace"), args.seconds, metric_files,
                {"compiles": compiles, "loop": loop}, rehearsal,
                workload["trace_s"])
        # the cell's own traffic, unmeasured, for ``warmup_s`` and until
        # ``warmup_jobs`` of it are done (in a checkout's first run the
        # traffic's programs compile here); the window follows without a
        # pause
        time.sleep(max(t_warm + workload["warmup_s"] - time.monotonic(), 0.0))
        while len(loop.done_times) < 1 + workload["warmup_jobs"] \
                and not loop.errors \
                and time.monotonic() < t_warm + WARMUP_MAX_S:
            time.sleep(0.05)
        # The window opens and closes on a completion: at the first job
        # done once the warm-up is over, and at the first done once
        # ``--seconds`` more have passed (EDGE_S at the most). Where a
        # whole wave's jobs are done together, a window cut at any other
        # instant counts a wave more or less by chance.
        t0 = loop.next_done(time.monotonic(), EDGE_S)
        if tracing is not None:
            tracing.begin()
        counters0 = compiles.snapshot()
        setup_s = t0 - T_PROCESS
        phases["warmup_s"] = t0 - t_warm
        if tracing is not None:
            tracing.run_window(t0)
        time.sleep(max(t0 + args.seconds - time.monotonic(), 0.0))
        t1 = loop.next_done(t0 + args.seconds, EDGE_S)
        loop.close()
        counters1 = compiles.snapshot()
        if tracing is not None:
            tracing.end()
        # what is in flight finishes: late is late, not wrong, and the
        # latency counts the wait. An emptying queue launches waves of
        # new widths, and each may compile: hence two minutes
        while loop.in_flight() and not loop.errors \
                and time.monotonic() < t1 + DRAIN_S:
            time.sleep(0.05)
        drained_at = time.monotonic()
        say("window_s", f"{t1 - t0:.3f}")
        say("drain_s", f"{drained_at - t1:.2f}")
        for w in server.workers:
            if w.last_error is not None:
                loop.errors.append(f"worker {w.id}: {w.last_error}")
        memory_peak = max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in devices[:cell["chips"]])

        # -- correct ----------------------------------------------------
        t = time.monotonic()
        records = [r for r in list(loop.jobs.values()) if r.acked]
        node_index = {nid: i for i, nid in enumerate(plain["node_ids"])}
        by_job, unknown = check.fetch_run_allocs(api, records, node_index)
        in_window = [r for r in records if t0 <= r.t_send < t1]
        done = [r for r in records if r.t_done is not None]
        not_done = [r for r in records if r.t_done is None]
        wrongly = [r for r in not_done
                   if check.could_place_more(plain, r, records, by_job)]
        if not_done:
            say("not_done", json.dumps(
                [[r.id, r.plain["count"], len(by_job[r.id]),
                  "fits" if r in wrongly else "nothing fits"]
                 for r in not_done[:12]]))
        numbers = {
            "jobs_never_done": len(wrongly),
            "jobs_blocked_rightly": len(not_done) - len(wrongly),
            "alloc_count_wrong": check.count_wrong(
                done, by_job, workload["stop_when_done"]),
            "overcommitted_nodes": check.overcommitted(
                plain, records, by_job),
            "constraint_violations": unknown + check.constraint_violations(
                plain, records, by_job),
        }
        waves = check.waves_of(api, records, by_job)
        picked = check.sample_waves(
            waves, in_window, workload["check_sample_waves"], args.seed,
            always=[lone])
        compared, replayed = check.compare_waves(
            api, picked, waves, plain, config, records, by_job,
            workload["limits"])
        numbers.update(compared)
        replayable = {r.id for members in waves.values() for r in members}
        numbers["jobs_not_replayable"] = sum(
            1 for r in in_window
            if r.t_done is not None and r.id not in replayable)
        say("numbers", json.dumps(numbers))
        if args.control:
            from benchmark import reference

            low, _ = check.compare_waves(
                api, picked, waves, plain, config, records, by_job,
                workload["limits"], before=replayed,
                served=lambda job, node, prior, values, seen:
                    reference.expected_score(plain, node, job, prior,
                                             values, seen, args.control))
            say("control_numbers", json.dumps(low))
            say("control_correct",
                check.judge(dict(numbers, **low), workload["limits"])[0])
        del replayed
        correct, rows = check.judge(numbers, workload["limits"])
        if loop.errors:
            correct = False
        phases["check_s"] = time.monotonic() - t

        refused = [r for r in loop.jobs.values()
                   if not r.acked and r.t_send is not None
                   and t0 <= r.t_send < t1]
        wrong_ids = set()
        if numbers["alloc_count_wrong"]:
            wrong_ids = {r.id for r in in_window if r.t_done is not None
                         and check.count_wrong(
                             [r], by_job, workload["stop_when_done"])}
        failed = (len(refused) + sum(1 for r in in_window if r.t_done is None)
                  + len(wrong_ids))
        e2e = end_to_end(records, t0, t1, setup_s, drained_at)
        device = {"platform": platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": memory_peak}
        wanted = [m["name"] for m in bench["end_to_end"]
                  if "workloads" not in m or cell["name"] in m["workloads"]]
        metrics = {k: {"value": e2e[k][0], "unit": e2e[k][1]}
                   for k in wanted if k in e2e}
        say("end_to_end", json.dumps({k: round(v[0], 4)
                                      for k, v in e2e.items()}))
        breakdown = None
        if tracing is not None:
            ctx = tracing.context(
                bench=bench, cell=cell, config=config, workload=workload,
                records=records, t0=t0, t1=t1, plain=plain,
                device=devices[0], rehearsal=rehearsal,
                drained_at=drained_at)
            metrics = {}
            for mfile in metric_files:
                reader = importlib.import_module(
                    f"benchmark.readers.{mfile['reader']}")
                value = reader.read(mfile, ctx)
                if value is not None:
                    metrics[mfile["name"]] = {"value": value,
                                              "unit": mfile["unit"]}
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = ctx["trace"]["breakdown"]
            phases["trace_read_s"] = tracing.read_s
        say("phases", json.dumps({k: round(v, 2) for k, v in phases.items()}))
        say("jobs", f"{len(records)} acknowledged in the run, "
                    f"{len(in_window)} registered in the window, "
                    f"{len(waves)} waves the reference can replay, "
                    f"{len(picked)} sampled")
        say("compile_events_in_window", json.dumps(
            {k: counters1[k] - counters0[k] for k in counters1}))
        say("compile_misses_at_s", json.dumps(
            [round(at - t0, 1) for at, name in compiles.times
             if name == "cache_misses"]))
        say("compile_requests_at_s", json.dumps(
            [round(at - t0, 1) for at, name in compiles.times
             if name == "compile_requests_use_cache" and at >= t_warm]))
        per_s: dict = {}
        for r in done:
            per_s[int((r.t_done - t0) // 1)] = \
                per_s.get(int((r.t_done - t0) // 1), 0) + 1
        say("done_per_s_from_window_start", json.dumps(
            [[k, per_s[k]] for k in sorted(per_s)]))
        slow = sorted((r for r in in_window if r.t_done is not None),
                      key=lambda r: r.t_send - r.t_done)[:6]
        say("slowest", json.dumps([
            [r.plain["kind"], r.plain["count"],
             round(r.t_done - r.t_send, 2), round(r.t_send - t0, 1),
             [[e.get("TriggeredBy"), e.get("Status"), e.get("CreateIndex"),
               e.get("ModifyIndex")] for e in api.jobs.evaluations(r.id)]]
            for r in slow]))
        if loop.errors:
            say("errors", "; ".join(loop.errors[:5]))
        compared = {name: {"value": float(value) if isinstance(value, float)
                           else int(value), "limit": limit, "kind": kind}
                    for name, value, limit, kind in rows}
        result = {"correct": correct, "attempted": len(in_window)
                  + len(refused), "failed": failed, "metrics": metrics,
                  "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        if rehearsal:
            result["rehearsal"] = True
        result["compared"] = compared
        loop.quit()
    finally:
        agent.shutdown()
    for name, c in result["compared"].items():
        word = "at most" if c["kind"] == "max" else "at least"
        say(f"compared {name}", f"{c['value']!r} ({word} {c['limit']!r})")
    say("correct", result["correct"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the agent may still hold sockets: leave now
    os._exit(code)
