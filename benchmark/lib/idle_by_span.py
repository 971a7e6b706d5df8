"""The device's idle time in a ``prompt`` cell's traced window, filed under the
program's own spans, and the program's compile log before the window.

``trace.idle_gaps`` files the idleness under the wrappers that ``lib/spans.py``
puts around the program from outside, and nine tenths of it land under one,
``batcher`` (the whole of ``ContinuousBatcher.run``). The program's own spans
divide that call: on the thread that holds ``prompt.run`` every instant of the
window lies in exactly one of these leaves

    step.loop_top   ``serve.decode_step`` before its ``serve.dispatch`` (the key split)
    step.dispatch   from the start of ``serve.dispatch`` to the start of ``serve.fetch`` (the call of the decode program)
    step.fetch      ``serve.fetch`` (the wait for the step's tokens)
    step.bookkeep   ``serve.decode_step`` after its ``serve.fetch`` (counters, appends, retiring)
    prefill         ``serve.prefill`` and ``serve.copy_state`` (an admission round)
    unfiled         ``prompt.run`` under none of the above (admission's host arithmetic; a span this file does not know)
    outside_run     the window under no ``prompt.run`` (tokenizing, the operator's hand-off)

so the leaves' shares of the device's idle time sum to the window's by
construction, and the four parts of a step to the step's. The spans come from
the ring itself (``thread``, ``span_id`` and ``parent``, which ``aligned(run)``
drops) and are placed on the trace's clock by the offset ``aligned(run)``
found: no second clock match. ``table`` takes plain data, so a test hands it a
ring made by hand and ``tools/idle_by_span.py`` one kept in a file.

What the entries read of this, and what only the tool prints. The offset places
the ring on the trace's *host* clock to ten microseconds, but the trace's
device clock stands up to a millisecond off its host clock in about one traced
run in four (PERF.md section 6, PR 37: the device's key-split operation then
reads 0.9 ms before the step that calls it). A decode step's idle time is one
gap of 3 ms that begins in ``step.fetch`` and ends in the next step's
``step.dispatch``: a millisecond's error moves it between the four parts (and in
and out of ``prefill`` at a round's edge) and leaves their sum where it was, a
step being 17 ms. So the entries read the sum over a step, ``outside_run`` and
the share of ``unfiled``, whose leaves are long beside the error; the parts are
the tool's table, for a reader who looks at the run.

Where the program has no ``serve.dispatch`` span (an older program), no ring, or
the clocks were not matched, everything here returns None.
"""

from __future__ import annotations

import statistics
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

from lib import lm_scopes, program_spans, trace

RUN, STEP, DISPATCH, FETCH = "prompt.run", "serve.decode_step", "serve.dispatch", "serve.fetch"
ADMISSION = ("serve.prefill", "serve.copy_state")
STEP_PARTS = ("step.loop_top", "step.dispatch", "step.fetch", "step.bookkeep")
LEAVES = STEP_PARTS + ("prefill", "unfiled", "outside_run")
#: The four parts of a step have to sum to the step's idle time to this many nanoseconds a step.
CLOSURE_NS = 1000.0


def clock(run) -> Optional[SimpleNamespace]:
    """``aligned(run)`` of whichever matcher the entry's wrappers serve. Both cache
    on one attribute of ``run``, and ``program_spans.aligned`` called first in a
    ``prompt`` cell would leave None there for every reader after it."""
    if run.events is None:
        return None
    if lm_scopes.PROGRAM_OUTSIDE[1] in run.span_order:
        return lm_scopes.aligned(run)
    return program_spans.aligned(run)


def _within(child, parent) -> Optional[tuple]:
    a, b = max(child[0], parent[0]), min(child[1], parent[1])
    return (a, b) if a < b else None


def table(spans: Sequence, offset_ns: int, events: dict) -> Optional[dict]:
    """``spans``: the ring (objects with ``name``, ``start_ns``, ``end_ns``,
    ``span_id``, ``parent``, ``thread``, ``count``); ``events``: the trace as
    ``lib/trace.py`` has it. -> ``idle_s`` and ``host_s`` by leaf (seconds of
    the window), ``idle_total_s``, ``steps`` (decode steps that began in the
    window), ``dispatch_host_ms`` (a step's start to its fetch's start, one a
    step), ``fetch_arrays`` (sum over those steps); ``idle_s`` is None without a
    device in the trace. None where no ``prompt.run`` of a program that opens
    ``serve.dispatch`` overlaps the window."""
    t0, t1 = events["window"]
    at = lambda sp: (sp.start_ns - offset_ns, sp.end_ns - offset_ns)  # noqa: E731
    runs = [sp for sp in spans if sp.name == RUN and at(sp)[0] < t1 and at(sp)[1] > t0]
    if not runs:
        return None
    thread = max(runs, key=lambda sp: sp.start_ns).thread  # the thread that holds ``prompt.run``
    if not any(sp.name == DISPATCH and sp.thread == thread for sp in spans):
        return None  # an older program: its steps end before their bookkeeping and name no dispatch
    children: Dict[int, list] = {}
    for sp in spans:
        if sp.thread == thread:
            children.setdefault(sp.parent, []).append(sp)
    leaves: Dict[str, list] = {name: [] for name in LEAVES}
    steps, dispatch_host_ms, fetch_arrays, run_ivs = 0, [], 0, []
    for run in (sp for sp in runs if sp.thread == thread):
        run_iv = at(run)
        run_ivs.append(run_iv)
        for child in children.get(run.span_id, []):
            iv = _within(at(child), run_iv)
            if iv is None:
                continue
            if child.name in ADMISSION:
                leaves["prefill"].append(iv)
            elif child.name == STEP:
                inner = {c.name: c for c in children.get(child.span_id, [])}
                if DISPATCH not in inner or FETCH not in inner:
                    continue  # a step that an exception cut short: unfiled
                d, f = _within(at(inner[DISPATCH]), iv), _within(at(inner[FETCH]), iv)
                if d is None or f is None or f[0] < d[0]:
                    continue
                for name, part in zip(STEP_PARTS, ((iv[0], d[0]), (d[0], f[0]), f, (f[1], iv[1]))):
                    leaves[name].append(part)
                if t0 <= iv[0] <= t1:
                    steps += 1
                    dispatch_host_ms.append((f[0] - iv[0]) / 1e6)
                    fetch_arrays += inner[FETCH].count.get("arrays", 0)
    window = [(float(t0), float(t1))]
    clipped = {name: trace.intersect(trace.merge(ivs), window) for name, ivs in leaves.items()}
    in_run = trace.intersect(trace.merge(run_ivs), window)
    clipped["unfiled"] = trace.subtract(in_run, trace.merge(iv for ivs in clipped.values() for iv in ivs))
    clipped["outside_run"] = trace.subtract(window, in_run)
    out = {"host_s": {name: trace.total(ivs) / 1e9 for name, ivs in clipped.items()}, "idle_s": None,
           "idle_total_s": None, "steps": steps, "dispatch_host_ms": dispatch_host_ms, "fetch_arrays": fetch_arrays,
           "window_s": (t1 - t0) / 1e9, "runs": len(run_ivs)}
    if trace.has_device(events):
        idle = trace.subtract(window, trace.merge(iv for ivs in trace.busy(events).values() for iv in ivs))
        out["idle_s"] = {name: trace.total(trace.intersect(ivs, idle)) / 1e9 for name, ivs in clipped.items()}
        out["idle_total_s"] = trace.total(idle) / 1e9
        whole = trace.merge(iv for name in STEP_PARTS for iv in clipped[name])
        gap_ns = abs(trace.total(trace.intersect(whole, idle)) - 1e9 * sum(out["idle_s"][n] for n in STEP_PARTS))
        if gap_ns > CLOSURE_NS * max(1, steps):
            raise ValueError(f"idle_by_span: the four parts of the decode steps miss the steps' idle time by "
                             f"{gap_ns:.0f} ns over {steps} steps: the spans do not nest as the program opens them")
    return out


def read(run) -> Optional[dict]:
    """``table`` of this run, computed once; None where ``clock`` is None."""
    if not hasattr(run, "_idle_by_span"):
        run._idle_by_span = None
        c, spans = clock(run), program_spans.ring()
        if c is not None and spans:
            run._idle_by_span = table(spans, c.offset_ns, run.events)
    return run._idle_by_span


def step_idle_ms(run) -> Optional[float]:
    """Device idle milliseconds a decode step of the window under ``serve.decode_step``, its children included."""
    got = read(run)
    if got is None or got["idle_s"] is None or not got["steps"]:
        return None
    return 1e3 * sum(got["idle_s"][p] for p in STEP_PARTS) / got["steps"]


def outside_run_idle_s_per_krow(run) -> Optional[float]:
    got = read(run)
    if got is None or got["idle_s"] is None:
        return None
    return program_spans.per_krow(run, got["idle_s"]["outside_run"])


def dispatch_host_ms(run) -> Optional[float]:
    got = read(run)
    return statistics.median(got["dispatch_host_ms"]) if got and got["dispatch_host_ms"] else None


# -- the compile log --------------------------------------------------------------
def compile_log() -> Optional[List[tuple]]:
    """The program's log of traces, lowerings, compiles and cache loads; None where it keeps none."""
    try:
        from daft_tpu import profiling

        return profiling.recent_compiles()
    except (ImportError, AttributeError):
        return None


def setup_log_s(run, *kinds: str) -> Optional[float]:
    """Seconds the log holds under ``kinds`` (``trace``, ``lower``, ``compile``,
    ``cache_load``) that ended before the traced window opened, which is when the
    run's window opens. The log is on the ring's clock, so the ring's offset places it."""
    c, log = clock(run), compile_log()
    if c is None or log is None:
        return None
    t0 = run.events["window"][0]
    return float(sum(seconds for t, kind, seconds, _ in log if kind in kinds and t - c.offset_ns <= t0))
