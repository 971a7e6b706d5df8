"""What the readers of a ``prompt`` cell share: the program's serving spans on
the trace's clock, and device time by the model's own scopes over **both**
programs a run executes (the prefill chunk and the decode step).

**Clock.** ``lib/program_spans.py`` matches the two clocks through a pair of
nestings that only ``embed_image`` has. The same bracket, with this path's
pair: the program opens ``prompt.run`` around the call the ``batcher`` wrapper
sits in (``ContinuousBatcher.run``), and the ``prompter`` wrapper
(``FlaxPrompter.prompt``) is opened around the call whose first act is to open
``prompt.tokenize``. ``aligned`` leaves its result where ``program_spans``
keeps its own (``run._program_spans``), so that ``program_spans.in_window``,
``counter_sum``, ``span_s``, ``exposed_s`` and ``setup_span_s`` read this
path's spans unchanged. Where the program has no such span (an older program),
everything here returns None.

**Scopes.** ``lib/scopes.py`` reads one executable, CLIP's five classes. Here
its parsing (``parse_hlo``, ``scopes_of``, ``event_instruction``, the fusion
rule) is reused with this model's classes over every execution, in the traced
window, of each program the entry hands out as shapes (``entry.lowerables``:
name of the executions in the trace -> (jitted function, argument shapes)).
Classes by scope path: ``ssd_scan`` (inside ``mamba``), ``mamba``, ``attn``,
``experts`` (the routed experts' two grouped products, whatever computes them:
on a TPU ``ops/pallas_grouped_matmul.py``'s custom calls, filed by their scope;
on the CPU XLA's ``ragged-dot`` kernels, filed by kernel name), ``router``,
``shared_mlp``, ``head``; all else is ``other``. A loop (``while``) is listed by
the trace beside the operations of its body and is skipped, so that no time is
counted twice.
"""

from __future__ import annotations

import re
import statistics
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

from lib import program_spans, scopes, trace

#: (program span, wrapper of ``lib/spans.py``): the program's span is outside the wrapper.
PROGRAM_OUTSIDE = ("prompt.run", "batcher")
#: (program span, wrapper): the program's span is inside the wrapper, and opens first thing.
PROGRAM_INSIDE = ("prompt.tokenize", "prompter")
SCOPES = ("ssd_scan", "mamba", "attn", "experts", "router", "shared_mlp", "head")
PREFILL, DECODE = "jit__prefill_impl", "jit__decode_impl"
CONTROL_FLOW = ("while", "conditional", "call")


# -- the program's spans --------------------------------------------------------
def _match(spans, wrappers: Dict[str, list], window) -> SimpleNamespace:
    by_name: Dict[str, list] = {}
    for sp in sorted(spans, key=lambda s: s.start_ns):
        by_name.setdefault(sp.name, []).append(sp)
    outside = program_spans._pairs(by_name.get(PROGRAM_OUTSIDE[0], []), sorted(wrappers.get(PROGRAM_OUTSIDE[1], [])), window)
    inside = program_spans._pairs(by_name.get(PROGRAM_INSIDE[0], []), sorted(wrappers.get(PROGRAM_INSIDE[1], [])), window)
    if not outside or not inside:
        raise program_spans.ClockMismatch(
            f"no pair of a wrapper and a program span in the traced window "
            f"({len(outside)} of {PROGRAM_OUTSIDE}, {len(inside)} of {PROGRAM_INSIDE})")
    lo = statistics.median_low(int(p.start_ns - w[0]) for p, w in outside)
    hi = statistics.median_low(int(p.start_ns - w[0]) for p, w in inside)
    if not -program_spans.WIDEN_NS <= hi - lo <= program_spans.MAX_BRACKET_NS:
        raise program_spans.ClockMismatch(f"the pairs bracket the offset to {hi - lo} ns "
                                          f"(limit {program_spans.MAX_BRACKET_NS}): they are not the same calls")
    return SimpleNamespace(offset_ns=(lo + hi) // 2, bracket_ns=hi - lo, pairs=len(outside) + len(inside))


def aligned(run) -> Optional[SimpleNamespace]:
    """The ring's spans on the trace's clock (as ``program_spans.aligned`` gives
    them), matched through this path's pair; computed once a run."""
    if not getattr(run, "_lm_spans_done", False):
        run._lm_spans_done = True
        run._program_spans = None
        spans = program_spans.ring()
        if run.events is not None and spans:
            try:
                clock = _match(spans, run.events["spans"], run.events["window"])
            except program_spans.ClockMismatch as e:
                print(f"lm_scopes: no program span is read in this run: {e}", file=sys.stderr)
            else:
                clock.spans = {}
                for sp in sorted(spans, key=lambda s: s.start_ns):
                    clock.spans.setdefault(sp.name, []).append(
                        (sp.start_ns - clock.offset_ns, sp.end_ns - clock.offset_ns, sp.count, sp.error))
                run._program_spans = clock
    return run._program_spans


def tokens(run) -> Optional[SimpleNamespace]:
    """Tokens the traced window processed, from the spans that began in it:
    ``prefill`` (true prompt tokens), ``padded`` (tokens the prefill calls
    ran), ``calls``, ``rows`` and ``row_chunks`` (chunks that held a token, over
    rows) of prefill; ``decode`` (the positions the decode steps processed: a
    step's ``tokens`` counter where it carries one, a decoder whose step is not
    one token a slot, and its ``active`` slots where it does not), ``active``
    (sum of active slots), ``steps``, ``slots`` and the decode steps' ``moe.*``
    counters (``held``, ``assignments``, ``max_load``)."""
    if aligned(run) is None:
        return None
    pre = program_spans.in_window(run, "serve.prefill")
    dec = program_spans.in_window(run, "serve.decode_step")
    if not pre or not dec:
        return None
    s = lambda rows, key: float(sum(r[2].get(key, 0) for r in rows))  # noqa: E731
    processed = float(sum(r[2]["tokens"] if "tokens" in r[2] else r[2].get("active", 0) for r in dec))
    return SimpleNamespace(prefill=s(pre, "tokens"), padded=s(pre, "padded_tokens"), calls=s(pre, "chunks"),
                           rows=s(pre, "rows"), row_chunks=s(pre, "row_chunks"), decode=processed,
                           active=s(dec, "active"), steps=float(len(dec)), slots=s(dec, "slots"),
                           held=s(dec, "moe.held_assignments"), assignments=s(dec, "moe.assignments"),
                           max_load=s(dec, "moe.max_expert_load"))


# -- device time by program and by scope -----------------------------------------
def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def programs(run) -> Optional[Dict[str, List[float]]]:
    """Device nanoseconds of each whole execution in the traced window, by the
    program's name (averaged over devices by the caller: one device here)."""
    if not trace.has_device(run.events):
        return None
    t0, t1 = run.events["window"]
    out: Dict[str, List[float]] = {}
    for dev in run.events["devices"].values():
        for s, dur, name in dev["modules"]:
            if s >= t0 and s + dur <= t1:
                out.setdefault(_base(name), []).append(float(dur))
    return out or None


def classify(scope: Optional[str]) -> str:
    # Where ``lax.ragged_dot`` computes the routed experts' grouped products (the
    # CPU rehearsal; on a TPU the cell runs ``ops/pallas_grouped_matmul.py``, whose
    # custom call carries the scope ``experts``), XLA turns it into a kernel of its
    # own whose metadata keeps no scope (``op_name="ragged-dot-none"``,
    # ``"ragged-dot-metadata"``). Filed by kernel name, on the assumption that those
    # products are the only ``ragged_dot`` of the model: true of
    # ``models/granite_hybrid``; a model with a second one elsewhere needs a rule of
    # its own here.
    if (scope or "").startswith("ragged-dot"):
        return "experts"
    parts = (scope or "").split("/")
    for name in SCOPES:  # the innermost named first: ssd_scan lies inside mamba
        if name in parts:
            return name
    return "other"


def _texts(run) -> Optional[Dict[str, dict]]:
    """Each program's compiled text, parsed; the seconds it took are kept as ``run.scopes_compile_s``."""
    lower = getattr(run.cell.entry, "lowerables", None)
    if lower is None:
        return None
    t0 = time.perf_counter()
    out = {name: scopes.parse_hlo(fn.lower(*shapes).compile().as_text())
           for name, (fn, shapes) in lower(run.cell.config).items()}
    run.scopes_compile_s = time.perf_counter() - t0
    return out


def analysis(run) -> Optional[dict]:
    """-> ``{"ns": {program: {class: device ns in the window}}, "coverage"}``, once a
    run; None without a device trace, or where under ``scopes.MIN_COVERAGE`` of
    the programs' operation time found its instruction."""
    if not hasattr(run, "_lm_scopes"):
        run._lm_scopes = None
        if trace.has_device(run.events):
            texts = _texts(run)
            if texts:
                run._lm_scopes = _analyse(run.events, texts)
                got = run._lm_scopes
                if got is not None:
                    # ``lm.other_ms_per_ktoken`` is the busy time less the named classes, so the five sum to
                    # it by construction: what shows unclassified time is this line, not their sum.
                    by_class = {c: sum(d[c] for d in got["ns"].values()) / 1e9 for c in SCOPES + ("other",)}
                    print(f"lm_scopes: {100 * got['coverage']:.2f}% of the two programs' operation time found its "
                          f"instruction; seconds by class {({c: round(v, 4) for c, v in by_class.items()})}, "
                          f"their sum {sum(by_class.values()):.4f} of {trace.busy_s(run.events):.4f} busy",
                          file=sys.stderr)
                if got is not None and got["coverage"] < scopes.MIN_COVERAGE:
                    print(f"lm_scopes: {100 * got['coverage']:.1f}% of the traced operation time found its "
                          f"instruction in the compiled texts; no lm.* or kernel.* value is read", file=sys.stderr)
                    run._lm_scopes = None
    return run._lm_scopes


def _analyse(events: dict, texts: Dict[str, dict]) -> Optional[dict]:
    t0, t1 = events["window"]
    ns = {name: dict.fromkeys(SCOPES + ("other",), 0.0) for name in texts}
    ops: Dict[str, dict] = {}
    found = total = 0.0
    for dev in events["devices"].values():
        runs = sorted((s, s + dur, _base(name)) for s, dur, name in dev["modules"]
                      if _base(name) in texts and s >= t0 and s + dur <= t1)
        k = 0
        for s, dur, name in sorted(dev["ops"]):
            while k < len(runs) and runs[k][1] <= s:
                k += 1
            if k == len(runs) or not runs[k][0] <= s < runs[k][1]:
                continue
            program = runs[k][2]
            hlo = texts[program]
            ins = scopes.event_instruction(name)
            if hlo["instructions"].get(ins, {}).get("opcode") in CONTROL_FLOW:
                continue  # the trace lists a loop and, apart, every operation of its body
            total += dur
            scope = None
            if ins in hlo["instructions"]:
                found += dur
                scope, _ = scopes.scopes_of(hlo, ins)
            cls = classify(scope)
            ns[program][cls] += dur
            row = ops.setdefault(f"{program[5:-5]} {trace.op_kind(name)}", {"ns": 0.0, "class": cls})
            row["ns"] += dur
    if total <= 0:
        return None
    n = max(1, len(events["devices"]))
    return {"ns": {p: {c: v / n for c, v in d.items()} for p, d in ns.items()},
            "coverage": found / total,
            "ops": sorted(([k, v["ns"] / n / 1e9, v["class"]] for k, v in ops.items()), key=lambda r: -r[1])[:16]}


def class_ns(run, *classes: str, program: Optional[str] = None) -> Optional[float]:
    """Device nanoseconds in the traced window under the scopes ``classes``, in
    one program or in both."""
    got = analysis(run)
    if got is None:
        return None
    return sum(d[c] for p, d in got["ns"].items() if program in (None, p) for c in classes)


def per_ktoken_ms(run, ns: Optional[float]) -> Optional[float]:
    """Device milliseconds a thousand tokens processed (prompt tokens prefilled and tokens decoded)."""
    n = tokens(run)
    if ns is None or n is None or n.prefill + n.decode <= 0:
        return None
    return ns / 1e6 / ((n.prefill + n.decode) / 1e3)
