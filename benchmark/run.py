"""One run of one cell of ``BENCHMARK.json``: one process, one streaming query.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The run builds the cell's traffic and parameters from the seed, starts the
entry's query, takes its first partitions as warm-up (that is set-up), opens the
window at a partition's arrival, closes it at the first arrival ``--seconds``
later, and compares what those partitions delivered with the plain reference.
It needs a TPU and never falls back. The last line of standard output is the
result object; the run's partition file is ``benchmark/out/<workload>-<seed>-<trace>.jsonl``.

``--rehearse-cpu`` drives the same control flow on the tiny cells of
``benchmark/rehearsal.json``; it refuses unless ``JAX_PLATFORMS=cpu`` and prints
its record on an earlier line and no result line.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can see it

import argparse
import json
import os
import resource
import shutil
import sys
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import compare, manifest, window  # noqa: E402  (no JAX in these)

OUT_DIR = os.path.join(BENCH_DIR, "out")
#: The traced part of the window: this long (or half the window), and at least two partitions.
TRACE_SECONDS = 8.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the comparison's control, put in the program's place")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    ap.add_argument("--rehearse-cpu", action="store_true")
    return ap.parse_args(argv)


class CompileCounter:
    """Times at which JAX compiled a program or loaded one from its persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def between(self, t0, t1) -> int:
        return sum(1 for t in self.times if t0 < t <= t1)


def peak_bytes():
    """The fullest chip's peak: buffers (``peak_bytes_in_use``) plus what loaded
    programs reserve for their temporaries (``peak_bytes_reserved``), which the
    TPU runtime counts apart (ViT-L/14 at B=512: 1.97 GB and 4.68 GB)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in stats if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def consume(query, entry, seconds: float, trace_dir: str = None) -> SimpleNamespace:
    """Take the query's partitions one by one: warm-up until the window may open,
    then the window, closed at the first arrival ``seconds`` later. With
    ``trace_dir`` the profiler runs from the opening for ``TRACE_SECONDS`` (or
    half the window) and at least two partitions. Returns arrivals ``(t, rows)``,
    every partition's ids, the window's ``(ids, answers)``, the indices of the
    arrivals that open and close the window and that end the trace, the process
    CPU times at both ends, the process's CPU seconds (user, system) and minor page
    faults at every arrival, and the trace's ends on the ``time.time_ns()`` clock."""
    import jax

    from lib import trace

    c = SimpleNamespace(arrivals=[], id_stream=[], parts=[], open_index=None, close_index=None,
                        trace_close_index=None, trace_ns=None, cpu=[], usage=[])
    tracing = False
    it = query.iter_partitions()
    try:
        for part in it:
            ids, answers = entry.take(part)
            now = time.perf_counter()
            c.arrivals.append((now, len(ids)))
            ru = resource.getrusage(resource.RUSAGE_SELF)
            c.usage.append((ru.ru_utime, ru.ru_stime, ru.ru_minflt))
            c.id_stream.append(ids)
            last = len(c.arrivals) - 1
            if c.open_index is None:
                if window.warmed_up(c.arrivals):
                    c.open_index = last
                    c.cpu.append(os.times())
                    if trace_dir:
                        jax.profiler.start_trace(trace_dir, profiler_options=trace.profiler_options())
                        tracing, c.trace_ns = True, [time.time_ns(), None]
                continue
            c.parts.append((ids, answers))
            if tracing and last - c.open_index >= 2 \
                    and now - c.arrivals[c.open_index][0] >= min(TRACE_SECONDS, seconds / 2):
                c.trace_ns[1], c.trace_close_index = time.time_ns(), last
                jax.profiler.stop_trace()
                tracing = False
            if not tracing and window.closes(c.arrivals, c.open_index, seconds):
                c.close_index = last
                c.cpu.append(os.times())
                break
    finally:
        if tracing:
            jax.profiler.stop_trace()
        it.close()
    if c.close_index is None:
        raise RuntimeError(f"the source ran dry after {len(c.arrivals)} partitions, before the "
                           f"window closed: raise the traffic's source_rows_per_s")
    return c


def write_partition_file(path: str, c: SimpleNamespace) -> None:
    marks = {c.open_index: "open", c.close_index: "close"}
    with open(path, "w") as f:
        for i, (t, rows) in enumerate(c.arrivals):
            user, system, minflt = c.usage[i]
            rec = {"i": i, "t": t - _T0, "rows": rows, "user_s": user, "sys_s": system,
                   "minflt": minflt}
            if i in marks:
                rec["mark"] = marks[i]
            f.write(json.dumps(rec) + "\n")


def run_cell(cell, seed: int, seconds: float, trace_on: bool, control: bool = False,
             keep_trace: str = None, t_imported: float = None, compiles: CompileCounter = None):
    """Drive one run and return the record (see ``main`` for what is printed)."""
    import daft_tpu
    import jax

    from lib import spans, trace

    model_seed = seed % (2 ** 32)  # a PRNG key holds 32 bits of seed
    tag = f"{cell.name}-{seed}-{int(trace_on)}"
    workdir = os.path.join(OUT_DIR, "work", tag)
    trace_dir = os.path.join(OUT_DIR, "trace", tag)
    for d in (workdir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    entry = cell.entry

    t_data = time.perf_counter()
    traffic = cell.generator.build(cell.traffic, cell.config, model_seed, workdir, seconds + 60.0)
    data_s = time.perf_counter() - t_data

    with daft_tpu.execution_config_ctx(**entry.exec_config(cell.config)):
        query, handle = entry.build(traffic, cell.config, model_seed)
        wrapped = (entry.SPANS, entry.udf_of(handle)) if trace_on else ([], None)
        with spans.installed(*wrapped) as recorder:
            t_query = time.perf_counter()
            c = consume(query, entry, seconds, trace_dir if trace_on else None)

    peak = peak_bytes()
    n_devices = entry.n_devices(handle)
    entry.release(handle)
    pool, bytes_written = traffic.pool, traffic.bytes_written
    del query, handle, traffic
    write_partition_file(os.path.join(OUT_DIR, tag + ".jsonl"), c)

    win = window.measure(c.arrivals, c.open_index, c.close_index)
    events = None
    if c.trace_close_index is not None:
        xplane = trace.newest_xplane(trace_dir)
        events = trace.extract(xplane, c.trace_ns, recorder.spans)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace, tag + ".xplane.pb"))
            with open(os.path.join(keep_trace, tag + ".spans.json"), "w") as f:
                json.dump({"window_ns": c.trace_ns, "spans_ns": recorder.spans}, f)
        shutil.rmtree(trace_dir, ignore_errors=True)

    dev = jax.devices()[0]
    run = SimpleNamespace(
        cell=cell, window=win, n_devices=n_devices, device_kind=dev.device_kind,
        setup_s=win.open_t - _T0, import_s=(t_imported or t_data) - _T0, data_s=data_s,
        model_s=c.arrivals[0][0] - t_query,
        cpu_s=sum(c.cpu[1][:2]) - sum(c.cpu[0][:2]),
        compiles_in_window=compiles.between(win.open_t, win.close_t) if compiles else 0,
        peak_bytes=peak, events=events, span_order=entry.SPAN_ORDER,
        trace_rows=sum(r for _, r in c.arrivals[c.open_index + 1:(c.trace_close_index or 0) + 1]))
    metrics = {}
    for m in cell.metrics(trace_on):
        value = m["read"](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    compared = cell.comparison.compare(cell, model_seed, pool, c.id_stream, c.parts, control=control)
    numbers = compared["numbers"]
    check_s = time.perf_counter() - t_check
    shutil.rmtree(workdir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    record = {"correct": compare.verdict(numbers), "attempted": win.rows,
              "failed": compared["failed"],
              "metrics": metrics, "device": device}
    if trace.has_device(events):
        device["busy_s"] = trace.busy_s(events)
        device["window_s"] = trace.window_s(events)
        record["breakdown"] = {"device_ops": trace.device_ops(events),
                               "idle_gaps": trace.idle_gaps(events, entry.SPAN_ORDER)}
    record["run"] = {"workload": cell.name, "seed": seed, "window_s": win.seconds,
                     "partitions": win.partitions, "warmup_partitions": c.open_index + 1,
                     "data_s": data_s, "check_s": check_s, "bytes_written": bytes_written,
                     "rows_per_s_per_chip": win.rows_per_s / n_devices, "setup_s": run.setup_s}
    if control:  # the control put in the program's place has to read not correct
        record["control"] = {"correct": compare.verdict(compared["control"]),
                             "compared": compared["control"]}
    record["compared"] = numbers
    return record


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("run.py: --rehearse-cpu refuses to run unless JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    cell = manifest.resolve(
        args.workload, os.path.join(BENCH_DIR, "rehearsal.json") if args.rehearse_cpu else None)

    from daft_tpu.device import describe_devices, require_tpu, setup_compile_cache

    compiles = CompileCounter()
    # No TPU: require_tpu raises, nothing runs on the CPU in its place, no result is printed.
    device = describe_devices() if args.rehearse_cpu else require_tpu()
    if device["count"] < cell.chips:
        print(f"run.py: cell {cell.name!r} needs {cell.chips} chips, JAX found {device['count']}",
              file=sys.stderr)
        return 3
    setup_compile_cache()  # <checkout>/.jax_cache, or $JAX_COMPILATION_CACHE_DIR
    record = run_cell(cell, args.seed, args.seconds, bool(args.trace), bool(args.control),
                      args.keep_trace, time.perf_counter(), compiles)
    if "control" in record:
        print(f"control in the program's place: correct={record['control']['correct']} "
              f"{record['control']['compared']}", file=sys.stderr)
    for name, n in record["compared"].items():
        print(f"compared {name}: {n['value']!r} (limit {n['limit']!r})", file=sys.stderr)
    print(f"correct: {record['correct']}", file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        record["rehearsal"] = True
        print(json.dumps(record))
        print("rehearsal on the CPU: no result line", flush=True)
        return 0
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
