"""The device's idle time of a ``prompt`` cell's traced window by the program's own spans, as a table.

    python3 benchmark/tools/idle_by_span.py --workload <cell> --seed <n> --seconds <s> [--keep DIR]
    python3 benchmark/tools/idle_by_span.py --from DIR/<cell>-<seed>.idle_by_span.json

The first form is the same run as ``run.py --trace 1`` (its own ``run_cell``, the
same window, the same result line); after the line it prints, on standard error,
``lib/idle_by_span.py``'s table: for each leaf the device's idle seconds and the
host's seconds in it, then the per-step figures and the compile log by kind and
by span. ``--keep DIR`` writes what the table was made from (the window, the
device's busy intervals, the ring's spans, the clock offset, the compile log) to
a file of ten to twenty megabytes, from which the second form prints the table again
anywhere, without a chip. The first form needs a TPU, as ``run.py`` does.

The four rows of a decode step are as good as the trace's own clocks: its device
clock stands up to a millisecond off its host clock in about one run in four
(``lib/idle_by_span.py``), which moves the step's one gap between the rows and
leaves their sum. A run in which ``serve.dispatch`` shows no idle time at all is
such a run: read the sum there, which is what the benchmark's entries do.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import idle_by_span, manifest, program_spans, trace  # noqa: E402

SPAN_FIELDS = ("name", "start_ns", "end_ns", "span_id", "parent", "thread", "count")
#: The leaves under the names the metrics and PERF.md give them.
SHOWN = {"step.loop_top": "serve.decode_step before serve.dispatch", "step.dispatch": "serve.dispatch (to the fetch's start)",
         "step.fetch": "serve.fetch", "step.bookkeep": "serve.decode_step after serve.fetch",
         "prefill": "serve.prefill + serve.copy_state", "unfiled": "prompt.run outside any serve.* span",
         "outside_run": "outside prompt.run"}


def kept(run) -> dict:
    """What ``lib/idle_by_span.table`` needs of a run, as plain data: the device's
    operations reduced to its busy intervals."""
    c = idle_by_span.clock(run)
    t0, t1 = run.events["window"]
    spans = [{k: getattr(sp, k) for k in SPAN_FIELDS} for sp in program_spans.ring() or []
             if sp.end_ns - c.offset_ns > t0 - 1e9 and sp.start_ns - c.offset_ns < t1 + 1e9]
    busy = {d: [[a, b - a, "busy"] for a, b in ivs] for d, ivs in trace.busy(run.events).items()}
    return {"workload": run.cell.name, "offset_ns": c.offset_ns, "bracket_ns": c.bracket_ns, "trace_rows": run.trace_rows,
            "events": {"window": [t0, t1], "devices": {d: {"ops": ops, "modules": []} for d, ops in busy.items()},
                       "spans": {}},
            "spans": spans, "compile_log": idle_by_span.compile_log()}


def render(data: dict) -> str:
    spans = [SimpleNamespace(**d) for d in data["spans"]]
    got = idle_by_span.table(spans, data["offset_ns"], data["events"])
    if got is None:
        return "idle_by_span: no prompt.run of a program that opens serve.dispatch overlaps the window"
    lines = [f"{data['workload']}: window {got['window_s']:.3f} s, {got['runs']} prompt.run, {got['steps']} decode steps, "
             f"device idle {got['idle_total_s'] if got['idle_total_s'] is not None else float('nan'):.4f} s",
             f"{'under':<44}{'device idle s':>14}{'ms a step':>11}{'host s':>10}{'ms a step':>11}"]
    def per_step(leaf: str, seconds: float) -> str:
        return f"{1e3 * seconds / got['steps']:.3f}" if leaf.startswith("step.") and got["steps"] else ""

    for leaf in idle_by_span.LEAVES:
        idle, host = got["idle_s"][leaf] if got["idle_s"] else float("nan"), got["host_s"][leaf]
        lines.append(f"{SHOWN[leaf]:<44}{idle:>14.4f}{per_step(leaf, idle):>11}{host:>10.4f}{per_step(leaf, host):>11}")
    if got["dispatch_host_ms"]:
        lines.append(f"a step's start to its fetch's start, host ms: median {statistics.median(got['dispatch_host_ms']):.3f}, "
                     f"max {max(got['dispatch_host_ms']):.3f}; arrays a fetch {got['fetch_arrays'] / got['steps']:.1f}")
    log = data.get("compile_log") or []
    t0 = data["events"]["window"][0]
    for when, rows in (("before the window", [r for r in log if r[0] - data["offset_ns"] <= t0]),
                       ("after it opened", [r for r in log if r[0] - data["offset_ns"] > t0])):
        by = {}
        for _, kind, seconds, span in rows:
            by[(span or "(no span)", kind)] = by.get((span or "(no span)", kind), 0.0) + seconds
        total = {k: sum(v for (_, kind), v in by.items() if kind == k) for k in ("trace", "lower", "compile", "cache_load")}
        lines.append(f"compile log {when}: {len(rows)} entries, seconds by kind {({k: round(v, 3) for k, v in total.items()})}")
        for (span, kind), v in sorted(by.items(), key=lambda kv: -kv[1])[:8]:
            lines.append(f"    {span:<28}{kind:<12}{v:>9.3f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--from", dest="from_file", default=None)
    args = ap.parse_args(argv)
    if args.from_file:
        print(render(manifest.load_json(args.from_file)))
        return 0
    if args.workload is None or args.seed is None or args.seconds is None:
        ap.error("--workload, --seed and --seconds, or --from")
    bench_run = manifest.load_module(os.path.join(BENCH_DIR, "run.py"))
    program_spans_tool = manifest.load_module(os.path.join(BENCH_DIR, "tools", "program_spans.py"))
    cell, seen = program_spans_tool.resolve_with_capture(args.workload, None)

    from daft_tpu.device import require_tpu, setup_compile_cache

    compiles = bench_run.CompileCounter()
    require_tpu()
    setup_compile_cache()
    record = bench_run.run_cell(cell, args.seed, args.seconds, True, False, None, time.perf_counter(), compiles)
    print(json.dumps(record), flush=True)
    run = seen["run"]
    if idle_by_span.clock(run) is None:
        print("idle_by_span: the clocks were not matched in this run", file=sys.stderr)
        return 1
    data = kept(run)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, f"{cell.name}-{args.seed}.idle_by_span.json"), "w") as f:
            json.dump(data, f, default=str)
    print(render(json.loads(json.dumps(data, default=str))), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
