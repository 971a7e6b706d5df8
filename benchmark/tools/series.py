"""Runs of one cell, one process each, one after another; their result lines and
partition files gathered, and each metric's median and spread printed.

    python3 benchmark/tools/series.py --workload <name> --seeds 11,12,13 --seconds 20 \
        [--sets 2] [--trace 0|1] [--control 0|1] [--out chiprun_out/<dir>] [--keep-trace 1]

The parent never touches JAX, so each child has the chip to itself. A spread is
the distance between the first and third quartile (``statistics.quantiles(n=4)``)
as a share of the median: what the manifest's bounds are set from.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def spread(values):
    q = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q[2] - q[0]) / median if median else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=1, help="repeat the list of seeds this many times")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--keep-trace", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "series"))
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    log = os.path.join(args.out, f"{args.workload}.jsonl")
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace),
                   "--control", str(args.control)]
            if args.keep_trace:
                cmd += ["--keep-trace", os.path.join(args.out, "traces")]
            t0 = time.perf_counter()
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t0
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            try:
                rec = json.loads(last)
            except ValueError:
                rec = None
            if p.returncode != 0 or rec is None:
                print(f"set {k} seed {seed}: exit {p.returncode}\n{p.stderr[-3000:]}", flush=True)
                continue
            rec["wall_s"], rec["set"] = wall, k
            rows.append(rec)
            with open(log, "a") as f:
                f.write(json.dumps(rec) + "\n")
            part = os.path.join(BENCH_DIR, "out", f"{args.workload}-{seed}-{args.trace}.jsonl")
            if os.path.exists(part):
                shutil.copy(part, os.path.join(args.out, f"{args.workload}-{seed}-{args.trace}-set{k}.jsonl"))
            show = {n: round(m["value"], 4) for n, m in rec["metrics"].items()}
            cmpd = {n: (round(v["value"], 5) if isinstance(v["value"], float) else v["value"])
                    for n, v in rec["compared"].items()}
            print(f"set {k} seed {seed}: wall {wall:.1f}s correct={rec['correct']} {show} "
                  f"peak={rec['device']['memory_peak_bytes']} {cmpd}", flush=True)
        sets.append(rows)
    for k, rows in enumerate(sets):
        # the first run of the first set compiles: its set-up is recorded apart
        names = sorted({n for r in rows for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            warm = vals[1:] if (k == 0 and n.startswith("setup")) else vals
            if len(warm) >= 2:
                print(f"set {k} {n}: median {statistics.median(warm):.6g} spread {spread(warm):.4%} "
                      f"min {min(warm):.6g} max {max(warm):.6g} n={len(warm)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
