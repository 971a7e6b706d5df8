"""Readings for a cell's comparison limits: the program's number and the
control's (the reference in fp8, put in the program's place and judged by the
same comparison against the committed limit) on many seeds, in one process, each
over a short window at the cell's own sizes.

    python3 benchmark/tools/limits.py --workload <name> --seeds 1,2,3 [--seconds 5] [--out FILE]

Prints one line per seed and, last, the largest program reading (the lower
reading), the smallest control reading (the upper reading), their ratio, and
whether the program read correct and the control not correct on every seed.
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
        c = rec["compared"]
        row = {"workload": cell.name, "seed": seed, "correct": rec["correct"],
               "control_correct": rec["control"]["correct"],
               "embedding_gap": c["embedding_gap"]["value"],
               "control_embedding_gap": rec["control"]["compared"]["embedding_gap"]["value"],
               "rows_per_s_per_chip": rec["run"]["rows_per_s_per_chip"],
               "check_s": rec["run"]["check_s"],
               "rows_compared": c["rows_compared"]["value"],
               "rows_not_unit_norm": c["rows_not_unit_norm"]["value"],
               "ids_out_of_sequence": c["ids_out_of_sequence"]["value"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    lower = max(r["embedding_gap"] for r in rows)
    upper = min(r["control_embedding_gap"] for r in rows)
    print(json.dumps({"workload": cell.name, "seeds": len(rows), "lower_reading": lower,
                      "upper_reading": upper, "ratio": upper / lower,
                      "limit": cell.config["compare"]["embedding_gap_max"],
                      "all_correct": all(r["correct"] for r in rows),
                      "control_never_correct": not any(r["control_correct"] for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
