"""One traced run of a cell with the program's own spans read as well.

    python3 benchmark/tools/program_spans.py --workload <cell> --seed <n> --seconds <s> [--keep-trace DIR]

``benchmark/program_span_metrics.json`` holds sixteen per-layer entries that are
not yet in ``BENCHMARK.json`` (its note says why). This appends them to a copy of
the manifest under ``benchmark/out/``, resolves the cell from the copy and drives
``run.py``'s own ``run_cell`` with ``--trace 1``: the same run, the same window,
the accepted twelve metrics and the sixteen new ones in one line. After the
line it prints, on standard error, how the clocks were matched
(``lib/program_spans.py``), how long the forward's compile after the window took,
and ``lib/scopes.py``'s table of the ten longest device operations.

It needs a TPU, as ``run.py`` does; ``--rehearse-cpu`` drives the tiny cells of
``rehearsal.json`` instead (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import manifest, program_spans, scopes  # noqa: E402

ENTRIES = os.path.join(BENCH_DIR, "program_span_metrics.json")


def merged_manifest(base_path: str, out_dir: str, every_cell: bool = False) -> str:
    """A copy of the manifest at ``base_path`` with the sixteen entries appended
    (``every_cell``: without their ``workloads`` lists, for the rehearsal's cells)."""
    m = manifest.load_json(base_path)
    for entry in manifest.load_json(ENTRIES)["per_layer"]:
        if every_cell:
            entry = {k: v for k, v in entry.items() if k != "workloads"}
        m["per_layer"].append(entry)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "manifest_with_program_spans.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


def resolve_with_capture(workload: str, manifest_path: str):
    """The cell, with one more reader at the end that keeps the ``run`` the
    readers saw (its value is None, so the line leaves it out)."""
    cell = manifest.resolve(workload, manifest_path)
    seen = {}

    def capture(run):
        seen["run"] = run
        return None

    cell.per_layer.append({"name": "_run", "unit": "", "read": capture})
    return cell, seen


def report(run, keep_trace: str = None, tag: str = None) -> dict:
    """What the readers worked from, for PERF.md."""
    a = program_spans.aligned(run)
    info = {}
    if a is not None:
        info["clock"] = {"offset_ns": a.offset_ns, "bracket_ns": a.bracket_ns, "pairs": a.pairs}
        spans_file = os.path.join(keep_trace, tag + ".spans.json") if keep_trace and tag else None
        if spans_file and os.path.exists(spans_file):
            # trace clock = time.time_ns() - profile start; so the bracketed
            # time_ns() - span_clock_ns() is the profile's start less the offset
            from daft_tpu import profiling

            start_ns = manifest.load_json(spans_file)["window_ns"][0] - run.events["window"][0]
            info["clock"]["bracketed_wall_minus_span_clock_ns"] = start_ns - a.offset_ns
            info["clock"]["span_clock_offset_ns"] = profiling.span_clock_offset_ns()
        # what no metric reads yet: the third part of the PIL loop, pad, and where in the fetch the device idled
        parts = {key: program_spans.counter_sum(run, "image.preprocess", key + "_ns")
                 for key in ("decode", "resize", "copy")}
        info["preprocess_s_per_krow"] = {k: program_spans.per_krow(run, v / 1e9) for k, v in parts.items() if v}
        info["pad_s_per_krow"] = program_spans.per_krow(run, program_spans.span_s(run, "provider.pad"))
        split = program_spans.exposed_split_s(run, "provider.fetch")
        if split:
            info["fetch_exposed_s_per_krow"] = {k: program_spans.per_krow(run, v) for k, v in split.items()}
    if hasattr(run, "scopes_compile_s"):
        info["scopes_compile_s"] = run.scopes_compile_s
    got = scopes.analysis(run) if hasattr(run, "_scopes") else None
    if got:
        info["scopes"] = {"coverage": got["coverage"], "steps": got["steps"], "table": scopes.table(run)}
    elif hasattr(run, "scopes_coverage"):
        info["scopes"] = {"coverage": run.scopes_coverage}
    print("program_spans: " + json.dumps(info), file=sys.stderr)
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("program_spans.py: --rehearse-cpu refuses to run unless JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    bench_run = manifest.load_module(os.path.join(BENCH_DIR, "run.py"))
    base = os.path.join(BENCH_DIR, "rehearsal.json") if args.rehearse_cpu else os.path.join(ROOT, "BENCHMARK.json")
    cell, seen = resolve_with_capture(
        args.workload, merged_manifest(base, bench_run.OUT_DIR, every_cell=args.rehearse_cpu))

    from daft_tpu.device import describe_devices, require_tpu, setup_compile_cache

    compiles = bench_run.CompileCounter()
    if args.rehearse_cpu:
        describe_devices()
    else:
        require_tpu()
    setup_compile_cache()
    record = bench_run.run_cell(cell, args.seed, args.seconds, True, False, args.keep_trace,
                                time.perf_counter(), compiles)
    print(json.dumps(record), flush=True)
    report(seen["run"], args.keep_trace, f"{cell.name}-{args.seed}-1")
    return 0


if __name__ == "__main__":
    sys.exit(main())
