"""Device time of a ``prompt`` cell's two programs (the prefill chunk and the
decode step) by classes of scope that the caller names.

``lib/lm_scopes.py`` does this for one decoder's classes, fixed in the module.
Here the classes are an argument, so a further decoder brings metric files and
no library: ``class_ns(run, CLASSES, "mla_core")``. Everything else is
``lm_scopes``' and ``scopes``': the clock match, the programs' compiled texts
(``entry.lowerables``), the parsing, the fusion rule, the control-flow rule (a
loop is listed by the trace beside the operations of its body and is skipped).
An operation is filed under the innermost scope of its path that is one of the
classes, and under ``other`` where none is: ``other`` is *filed*, not a
remainder, so the classes' sum against the busy time shows what no program
covers (the coverage line on standard error).

``counters`` adds what ``lm_scopes.tokens`` does not sum: any further counter
of the window's spans, by name. ``beside`` hands a metric file the reader of an
accepted one that reads the same spans whatever the decoder (the batcher's, the
tokenizer's, the set-up's). It is for a cell appended between two ``benchmark``
PRs: a PR that may only add entries cannot put its cell into an accepted
entry's ``workloads``, so it brings an entry of its own that lists the cell and
whose file is one line, ``read = decoder_scopes.beside(__file__, "<accepted
name>")``; the next ``benchmark`` PR lists the cell in the accepted entry and
drops that entry and its file (PR 42 did so for 29 of them).
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Dict, Optional, Sequence

from lib import lm_scopes, manifest, program_spans, scopes, trace

OTHER = "other"


def classify(scope: Optional[str], classes: Sequence[str]) -> str:
    parts = (scope or "").split("/")
    for name in reversed(parts):  # the innermost first
        if name in classes:
            return name
    return OTHER


def _analyse(events: dict, texts: Dict[str, dict], classes: Sequence[str]) -> Optional[dict]:
    t0, t1 = events["window"]
    ns = {name: dict.fromkeys(tuple(classes) + (OTHER,), 0.0) for name in texts}
    ops: Dict[str, dict] = {}
    found = total = 0.0
    for dev in events["devices"].values():
        runs = sorted((s, s + dur, lm_scopes._base(name)) for s, dur, name in dev["modules"]
                      if lm_scopes._base(name) in texts and s >= t0 and s + dur <= t1)
        k = 0
        for s, dur, name in sorted(dev["ops"]):
            while k < len(runs) and runs[k][1] <= s:
                k += 1
            if k == len(runs) or not runs[k][0] <= s < runs[k][1]:
                continue
            program = runs[k][2]
            hlo = texts[program]
            ins = scopes.event_instruction(name)
            if hlo["instructions"].get(ins, {}).get("opcode") in lm_scopes.CONTROL_FLOW:
                continue
            total += dur
            scope = None
            if ins in hlo["instructions"]:
                found += dur
                scope, _ = scopes.scopes_of(hlo, ins)
            cls = classify(scope, classes)
            ns[program][cls] += dur
            row = ops.setdefault(f"{program[5:-5]} {trace.op_kind(name)}", {"ns": 0.0, "class": cls})
            row["ns"] += dur
    if total <= 0:
        return None
    n = max(1, len(events["devices"]))
    return {"ns": {p: {c: v / n for c, v in d.items()} for p, d in ns.items()},
            "coverage": found / total,
            "ops": sorted(([k, v["ns"] / n / 1e9, v["class"]] for k, v in ops.items()), key=lambda r: -r[1])[:16]}


def analysis(run, classes: Sequence[str]) -> Optional[dict]:
    """-> ``{"ns": {program: {class: device ns in the window}}, "coverage", "ops"}``,
    once a run and set of classes; None without a device trace, or where under
    ``scopes.MIN_COVERAGE`` of the programs' operation time found its instruction."""
    cache = run.__dict__.setdefault("_decoder_scopes", {})
    key = tuple(classes)
    if key not in cache:
        cache[key] = None
        texts = lm_scopes._texts(run) if trace.has_device(run.events) else None
        got = _analyse(run.events, texts, classes) if texts else None
        if got is not None:
            by_class = {c: round(sum(d[c] for d in got["ns"].values()) / 1e9, 4) for c in key + (OTHER,)}
            print(f"decoder_scopes: {100 * got['coverage']:.2f}% of the two programs' operation time found its "
                  f"instruction; seconds by class {by_class}, their sum {sum(by_class.values()):.4f} of "
                  f"{trace.busy_s(run.events):.4f} busy; longest operations {got['ops'][:12]}", file=sys.stderr)
            if got["coverage"] >= scopes.MIN_COVERAGE:
                cache[key] = got
            else:
                print("decoder_scopes: under the coverage the readers ask for; no value by scope is read", file=sys.stderr)
    return cache[key]


def class_ns(run, classes: Sequence[str], *wanted: str, program: Optional[str] = None) -> Optional[float]:
    """Device nanoseconds in the traced window filed under ``wanted`` (each one of
    ``classes`` or ``"other"``), in one program or in both."""
    got = analysis(run, classes)
    if got is None:
        return None
    return sum(d[c] for p, d in got["ns"].items() if program in (None, p) for c in wanted)


def counters(run, span: str, *keys: str) -> Optional[Dict[str, float]]:
    """Each of ``keys`` summed over the spans called ``span`` that began in the
    traced window; None where the clocks did not match or no such span carries
    every one of them (an older program)."""
    if lm_scopes.aligned(run) is None:
        return None
    rows = program_spans.in_window(run, span)
    if not rows or not all(k in r[2] for r in rows for k in keys):
        return None
    return {k: float(sum(r[2][k] for r in rows)) for k in keys}


def beside(metric_file: str, accepted: str) -> Callable:
    """The ``read`` of the accepted metric ``accepted``, whose file lies beside ``metric_file``:
    for an entry that lists a cell the accepted entry does not list yet (see the module's docstring)."""
    return manifest.load_module(os.path.join(os.path.dirname(os.path.abspath(metric_file)), accepted + ".py")).read


def per_ktoken(run, *wanted: str) -> Optional[float]:
    """Device milliseconds a thousand tokens processed (prefilled and decoded) filed
    under ``wanted``, by the classes the cell's configuration names (``scopes``)."""
    return lm_scopes.per_ktoken_ms(run, class_ns(run, run.cell.config["scopes"], *wanted))
