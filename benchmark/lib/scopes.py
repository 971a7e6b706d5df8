"""Device time of the forward by what the model calls its parts.

Flax runs every module under ``jax.named_scope``, so each instruction of the
compiled forward carries the path of the module it came from
(``op_name="jit(fwd)/CLIPModel.encode_image/vision/block_3/mlp/fc1/dot_general"``),
the instructions inside a fusion too. The trace names each device operation by
its instruction (``%fusion.12 = bf16[...] fusion(...)``). This module compiles
the entry's forward again after the window (the persistent cache should answer),
reads instruction -> ``op_name`` from ``compiled.as_text()``, and sums the traced
operations of each whole forward by class.

Rule: a fusion that holds a ``convolution`` or ``dot`` belongs to that
instruction's scope (the matrix product is what the fusion is for); any other
operation belongs to its own scope, which for a fusion is its root's.

Classes, by scope path: ``.../attn_core/...`` -> ``attn_core``; ``.../attn/qkv``
and ``.../attn/out`` -> ``attn_proj``; ``.../mlp/...`` -> ``mlp``; ``ln1``, ``ln2``,
``ln_pre``, ``ln_post``, ``ln_final`` -> ``layernorm``; all else, and operations
whose instruction the text does not have, -> ``other``. One more spelling counts
as ``attn_core``: ``.../attn/vmap(...)``, which is what ``dot_product_attention``
writes where no scope names it. The compile cache's key leaves metadata out, so
an executable cached by a program older than the ``attn_core`` scope answers with
the old names (every chip run of PR 26 did).
"""

from __future__ import annotations

import re
import statistics
import sys
import time
from typing import Dict, List, Optional, Tuple

from lib import trace

CLASSES = ("mlp", "attn_proj", "attn_core", "layernorm", "other")
LAYERNORMS = {"ln1", "ln2", "ln_pre", "ln_post", "ln_final"}
MATMULS = {"convolution", "dot"}
#: Below this share of the traced operation time found in the text, the compile
#: did not reproduce the program's executable and nothing is reported.
MIN_COVERAGE = 0.99

_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?<![\w.\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_EVENT_HEAD = re.compile(r"^%?([\w.\-]+)(?:\s*=|$)")


def classify(scope: Optional[str]) -> str:
    parts = (scope or "").split("/")
    if "attn_core" in parts:
        return "attn_core"
    for a, b in zip(parts, parts[1:]):
        if a == "attn" and b in ("qkv", "out"):
            return "attn_proj"
        if a == "attn" and b.startswith("vmap("):
            return "attn_core"
    if "mlp" in parts:
        return "mlp"
    if LAYERNORMS.intersection(parts):
        return "layernorm"
    return "other"


def parse_hlo(text: str) -> Dict[str, dict]:
    """Every instruction of every computation: name -> ``{"opcode", "scope",
    "calls", "root", "computation"}``; and under ``"computations"`` each
    computation's instruction names in order."""
    instructions: Dict[str, dict] = {}
    computations: Dict[str, List[str]] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and "=" not in line.split("(")[0]:
                current = m.group(1)
                computations[current] = []
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, name, rest = m.groups()
        body = rest.split(", metadata=")[0]
        opcode = _OPCODE.search(body)
        scope, calls = _OP_NAME.search(rest), _CALLS.search(rest)
        instructions[name] = {"opcode": opcode.group(1) if opcode else "",
                              "scope": scope.group(1) if scope else None,
                              "calls": calls.group(1) if calls else None,
                              "root": bool(root), "computation": current}
        computations[current].append(name)
    return {"instructions": instructions, "computations": computations}


def scopes_of(hlo: dict, name: str) -> Tuple[Optional[str], List[str]]:
    """-> (the scope ``name`` belongs to by the rule, every scope found in it)."""
    ins = hlo["instructions"][name]
    inner = [hlo["instructions"][n] for n in hlo["computations"].get(ins["calls"] or "", [])]
    found = sorted({i["scope"] for i in inner + [ins] if i["scope"]})
    matmuls = [i["scope"] for i in inner if i["opcode"] in MATMULS and i["scope"]]
    if matmuls:
        return matmuls[0], found
    if ins["scope"]:
        return ins["scope"], found
    roots = [i["scope"] for i in inner if i["root"] and i["scope"]]
    return (roots[0] if roots else None), found


def event_instruction(event_name: str) -> Optional[str]:
    """'%fusion.12 = bf16[512,257,1024]{...} fusion(...)' -> 'fusion.12'."""
    m = _EVENT_HEAD.match(event_name.strip())
    return m.group(1) if m else None


def forward_text(run) -> Optional[str]:
    """The optimised HLO of the entry's forward at the cell's batch, compiled on
    this process's device; the seconds it took are kept as ``run.scopes_compile_s``."""
    import jax

    t0 = time.perf_counter()
    fn, shapes = run.cell.entry.lowerable(run.cell.config)
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    run.scopes_compile_s = time.perf_counter() - t0
    return text


def _executions(events: dict) -> List[Tuple[dict, float, float]]:
    """Whole executions in the window of the program that took most device time
    (as ``trace.step_ms`` chooses it): (device, start, end)."""
    by_name: Dict[str, list] = {}
    t0, t1 = events["window"]
    for dev in events["devices"].values():
        for s, dur, name in dev["modules"]:
            if s >= t0 and s + dur <= t1:
                by_name.setdefault(re.sub(r"\(\d+\)$", "", name), []).append((dev, s, s + dur))
    return max(by_name.values(), key=lambda v: sum(b - a for _, a, b in v)) if by_name else []


def analyse(events: dict, hlo: dict) -> Optional[dict]:
    """-> ``{"classes": {class: median ms a step}, "coverage": share of traced
    operation time whose instruction the text has, "steps": n, "ops": {kind:
    {"ms", "class", "scopes"}}}``; None without a whole execution."""
    runs = _executions(events)
    if not runs:
        return None
    per_step: List[Dict[str, float]] = []
    ops: Dict[str, dict] = {}
    found_ns = total_ns = 0.0
    for dev, a, b in runs:
        sums = dict.fromkeys(CLASSES, 0.0)
        for s, dur, name in dev["ops"]:
            if not a <= s < b:
                continue
            ins = event_instruction(name)
            total_ns += dur
            scope, inside = None, []
            if ins in hlo["instructions"]:
                found_ns += dur
                scope, inside = scopes_of(hlo, ins)
            cls = classify(scope)
            sums[cls] += dur
            row = ops.setdefault(trace.op_kind(name), {"ns": 0.0, "classes": set(), "scopes": set()})
            row["ns"] += dur
            row["classes"].add(cls)
            row["scopes"].update(_short(x) for x in inside)
        per_step.append(sums)
    if total_ns <= 0:
        return None
    return {"classes": {c: statistics.median(s[c] for s in per_step) / 1e6 for c in CLASSES},
            "coverage": found_ns / total_ns, "steps": len(runs),
            "ops": {k: {"ms": v["ns"] / len(runs) / 1e6, "class": "+".join(sorted(v["classes"])),
                        "scopes": sorted(v["scopes"])} for k, v in ops.items()}}


def _short(scope: str) -> str:
    """Module path of a scope, blocks counted together: '.../vision/block_7/mlp/fc1/dot_general' -> 'block_*/mlp/fc1'."""
    parts = [re.sub(r"^block_\d+$", "block_*", p) for p in scope.split("/")[:-1]]
    for head in ("vision", "text"):
        if head in parts:
            parts = parts[parts.index(head) + 1:]
    return "/".join(parts) or scope


def analysis(run) -> Optional[dict]:
    """``analyse`` over this run's trace and its forward's text, once a run.
    None where no device was traced, or where under ``MIN_COVERAGE`` of the
    operation time found its instruction (said on standard error)."""
    if not hasattr(run, "_scopes"):
        run._scopes = None
        if trace.has_device(run.events):
            got = analyse(run.events, parse_hlo(forward_text(run)))
            if got is not None and got["coverage"] < MIN_COVERAGE:
                print(f"scopes: {100 * got['coverage']:.1f}% of the traced operation time found its "
                      f"instruction in the compiled text (at least {100 * MIN_COVERAGE:.0f}% needed): "
                      f"the compile did not reproduce the program's executable; no model.*_ms is read",
                      file=sys.stderr)
                run.scopes_coverage, got = got["coverage"], None
            run._scopes = got
    return run._scopes


def classes(run) -> Optional[Dict[str, float]]:
    """Per class, the median device milliseconds a step over the whole
    executions of the forward in the traced window."""
    got = analysis(run)
    return got["classes"] if got else None


def table(run) -> Optional[List[dict]]:
    """The ten operations with most device time: kind, ms a step, class, and
    every scope found inside, for PERF.md."""
    got = analysis(run)
    if not got:
        return None
    rows = sorted(got["ops"].items(), key=lambda kv: -kv[1]["ms"])[:10]
    return [dict(v, kind=k) for k, v in rows]
