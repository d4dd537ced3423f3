#!/usr/bin/env python3
"""benchmark/sets.py: several runs of one cell, one after another, and
their spread: what a builder runs on the chip to set or check a bound.

    python3 benchmark/sets.py --workload <cell> --seeds 11,12,13 --seconds 30 [--trace 0] [--out chiprun_out/sets]

Each run is ``run.py`` in a process of its own (this parent never
touches jax, so the chip is the child's). Every result line is
appended to ``<out>/<cell>.jsonl`` with its seed and the end of its
standard error; a run that is not correct ends the call; the summary
gives each metric's median and its spread
(third quartile less first, ``statistics.quantiles(n=4)``, over the
median), the number the bounds in BENCHMARK.json are five times of.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q[2] - q[0]) / median if median else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sets"))
    ap.add_argument("--label", default="")
    args, extra = ap.parse_known_args()     # the rest goes on to run.py
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, args.workload + ".jsonl")
    lines = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(HERE, "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        cmd += extra
        t = time.monotonic()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.monotonic() - t
        out = proc.stdout.strip().splitlines()
        try:
            line = json.loads(out[-1]) if out else None
        except ValueError:
            line = None
        entry = {"label": args.label, "seed": int(seed), "rc": proc.returncode,
                 "seconds": args.seconds, "trace": args.trace,
                 "wall_s": wall, "line": line,
                 "stderr": proc.stderr[-12000:]}
        with open(log, "a") as f:
            f.write(json.dumps(entry) + "\n")
        ok = line is not None and line.get("correct")
        print(f"seed {seed}: rc {proc.returncode}, correct {ok}, "
              f"wall {wall:.1f} s", flush=True)
        if not ok:
            print(proc.stderr[-3000:], flush=True)
            # the cause first: the runs after it would spend the chip
            break
        if line is not None:
            print("  " + json.dumps({k: round(v["value"], 4) for k, v in
                                     line["metrics"].items()}), flush=True)
            lines.append(line)
    names = sorted({k for ln in lines for k in ln["metrics"]})
    for name in names:
        vals = [ln["metrics"][name]["value"] for ln in lines
                if name in ln["metrics"]]
        if len(vals) >= 2:
            print(f"{name}: median {statistics.median(vals):.6g}, spread "
                  f"{spread(vals) * 100:.2f}% of {len(vals)} "
                  f"({min(vals):.6g} .. {max(vals):.6g})", flush=True)
    return 0 if lines and all(ln["correct"] for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
