"""Readings for a cell's comparison limits, whatever the comparison compares:
every number the program read and, beside it, the control's (the reference one
precision step down, put in the program's place by the same comparison) on many
seeds, in one process, each over a short window at the cell's own sizes.
``tools/limits.py`` is the same for ``embedding_gap`` alone.

    python3 benchmark/tools/limits_any.py --workload <name> --seeds 1,2,3 [--seconds 5] [--out FILE]

Prints one line per seed and, last, for every number with a limit that is not 0:
the largest program reading (the lower reading), the smallest control reading
(the upper reading), their ratio and the committed limit; and whether the
program read correct and the control not correct on every seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    run = manifest.load_module(os.path.join(BENCH_DIR, "run.py"))
    cell = manifest.resolve(args.workload)

    from daft_tpu.device import require_tpu, setup_compile_cache

    require_tpu()
    setup_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = run.run_cell(cell, seed, args.seconds, trace_on=False, control=True)
        row = {"workload": cell.name, "seed": seed, "correct": rec["correct"],
               "control_correct": rec["control"]["correct"],
               "program": {k: v["value"] for k, v in rec["compared"].items()},
               "control": {k: v["value"] for k, v in rec["control"]["compared"].items()},
               "limits": {k: v["limit"] for k, v in rec["compared"].items()},
               "rows_per_s_per_chip": rec["run"]["rows_per_s_per_chip"], "setup_s": rec["run"]["setup_s"],
               "check_s": rec["run"]["check_s"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    summary = {"workload": cell.name, "seeds": len(rows), "all_correct": all(r["correct"] for r in rows),
               "control_never_correct": not any(r["control_correct"] for r in rows), "numbers": {}}
    for name, limit in rows[0]["limits"].items():
        if limit == 0:
            continue
        lower, upper = max(r["program"][name] for r in rows), min(r["control"][name] for r in rows)
        summary["numbers"][name] = {"lower_reading": lower, "upper_reading": upper,
                                    "ratio": upper / lower if lower else None, "limit": limit}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
