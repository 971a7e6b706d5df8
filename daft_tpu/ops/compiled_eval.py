"""Whole-chain compiled evaluation: filter→project→agg as ONE XLA program.

PR 8 gave the executor pipelined stages; this module makes the hot path
*compile*. Where ops/device_eval.py fuses the numeric subgraph of a single
projection, this traces an entire relational chain — every Filter predicate
and Project expression between two pipeline breakers, optionally ending in
the partial phase of a global aggregation — into ONE jitted XLA computation
per micropartition (the pjit/donation discipline of SNIPPETS [1][2]: AOT
``lower().compile()`` with donated input buffers, so a q06-shaped scan is a
single HBM round-trip instead of one hop per operator).

Compile discipline:

* **Plan fingerprint** — programs are cached on a canonicalized chain
  fingerprint (step kinds + ``Expr.key()`` canon forms + input dtypes +
  trailing shapes), NOT on object identity, so the same query shape
  re-submitted by a dashboard tenant reuses the executable across plans.
  The fingerprint is a pure function of plan + schema + config.
* **Bucket shapes** — morsel row counts vary; inputs pad to the device-eval
  bucket ladder before dispatch so the cache sees O(#buckets) shapes per
  fingerprint. Elementwise chains reuse already-compiled larger buckets
  (``_bucket_reusing`` — outputs slice back to ``[:n]``, so padding never
  changes values); aggregation chains use the FIXED ladder (``_bucket``)
  because reductions are shape-sensitive and fixed bucketing keeps
  per-chunk float sums a pure function of the morsel stream — the
  thread-count determinism contract.
* **Compile cache metrics** — ``daft_compile_cache_{hits,misses}_total``
  and a ``daft_compile_seconds`` histogram (AOT trace+compile wall,
  measured tight around ``lower().compile()``), surfaced in EXPLAIN
  ANALYZE and the dashboard engine summary.

Self-disabling contract: the compiled path must beat the interpreted path
on q01/q06-shaped scans. :func:`run_ab_guard` measures fused-vs-interpreted
with ABBA-paired blocks (position-balanced, the PR 7 overhead-guard
discipline); if the compiled path loses it calls :func:`set_self_disabled`,
which flips a process-level kill switch consulted by every chain attempt
and drops the ``daft_compiled_eval_enabled`` gauge to 0 so the off state is
visible in metrics. ``DAFT_COMPILED_EVAL=0`` / ``compiled_eval_enabled=
False`` is the config spelling of the same switch.

Anything the tracer can't reproduce bit-compatibly falls back to the numpy
path, dtype-driven: 64-bit columns, non-``jax_exact`` kernels, Kleene null
rules, sum partials whose resolved field outgrows 32 bits. Correctness
never depends on compilation.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from daft_tpu.errors import DaftError
from daft_tpu.expressions.expr import AggOp, Alias, ColumnRef, Expr, Literal
from daft_tpu.micropartition import MicroPartition
from daft_tpu.recordbatch import RecordBatch
from daft_tpu.schema import Field, Schema
from daft_tpu.series import Series

logger = logging.getLogger(__name__)

_ELIGIBILITY_ERRORS = (DaftError, KeyError, TypeError, ValueError,
                       NotImplementedError, AttributeError)


# --------------------------------------------------------------------- #
# Process-level self-disable switch                                     #
# --------------------------------------------------------------------- #
_state_lock = threading.Lock()
_disabled_reason: Optional[str] = None
_gauge_primed = False


def _prime_gauge() -> None:
    global _gauge_primed
    if not _gauge_primed:
        from daft_tpu import metrics

        metrics.COMPILED_EVAL_ENABLED.set(0 if _disabled_reason else 1)
        _gauge_primed = True


def set_self_disabled(reason: str) -> None:
    """Flip the process-level compiled-eval kill switch (the self-disabling
    contract): every subsequent chain attempt takes the interpreted path,
    and the off state is visible as ``daft_compiled_eval_enabled 0``."""
    global _disabled_reason, _gauge_primed
    from daft_tpu import metrics

    with _state_lock:
        first = _disabled_reason is None
        _disabled_reason = reason
        metrics.COMPILED_EVAL_ENABLED.set(0)
        _gauge_primed = True
    if first:
        logger.warning("compiled eval self-disabled: %s "
                       "(interpreted path from here on)", reason)


def clear_self_disabled() -> None:
    global _disabled_reason, _gauge_primed
    from daft_tpu import metrics

    with _state_lock:
        _disabled_reason = None
        metrics.COMPILED_EVAL_ENABLED.set(1)
        _gauge_primed = True


def self_disabled_reason() -> Optional[str]:
    return _disabled_reason


def enabled(cfg) -> bool:
    """Config knob AND the runtime self-disable switch."""
    if not getattr(cfg, "compiled_eval_enabled", False):
        return False
    _prime_gauge()
    return _disabled_reason is None


# --------------------------------------------------------------------- #
# Compile cache: fingerprint + bucket shapes -> AOT-compiled executable #
# --------------------------------------------------------------------- #
_cache_lock = threading.Lock()
_EXECUTABLES: Dict[tuple, object] = {}


def reset_cache() -> None:
    with _cache_lock:
        _EXECUTABLES.clear()


def cache_len() -> int:
    with _cache_lock:
        return len(_EXECUTABLES)


def compile_cache_snapshot() -> dict:
    """Compile-cache health for the dashboard engine summary / tests."""
    from daft_tpu import metrics

    snap = metrics.get_registry().snapshot()
    return {
        "cache_hits": int(snap.counter_total("daft_compile_cache_hits_total")),
        "cache_misses": int(
            snap.counter_total("daft_compile_cache_misses_total")),
        "compile_seconds": round(snap.hist("daft_compile_seconds")["sum"], 4),
        "chain_morsels": int(
            snap.counter_total("daft_compiled_chain_morsels_total")),
        "enabled": int(_disabled_reason is None),
    }


def _compiled_executable(shape_key: tuple, run_fn, example_args: tuple,
                         donate: bool = True):
    """The AOT-compiled executable for this (fingerprint, shapes) key —
    compiling (and timing the compile) on first sight. ``jit().lower()``
    + ``.compile()`` gives an exact trace+compile wall measurement and an
    executable the cache hands straight back on hits (the pjit AOT
    pattern, SNIPPETS [1]); input column buffers are donated — each morsel
    stages fresh arrays, so XLA may reuse them for outputs. ``donate=False``
    is for programs whose outputs cannot take an input's buffer."""
    from daft_tpu import metrics

    with _cache_lock:
        fn = _EXECUTABLES.get(shape_key)
    if fn is not None:
        metrics.COMPILE_CACHE_HITS.inc()
        return fn
    # Donation lets XLA alias morsel input buffers into outputs (they are
    # staged fresh per call, never reused) — a real win on TPU HBM; the
    # CPU backend can't use it and would warn per compile.
    argnums = (0,) if donate and jax.default_backend() != "cpu" else ()
    t0 = time.perf_counter()
    fn = jax.jit(run_fn, donate_argnums=argnums).lower(*example_args).compile()
    dt = time.perf_counter() - t0
    metrics.COMPILE_CACHE_MISSES.inc()
    metrics.COMPILE_SECONDS.observe(dt)
    with _cache_lock:
        # A racing compile of the same key keeps the first-stored
        # executable; both are valid, the loser is garbage-collected.
        fn = _EXECUTABLES.setdefault(shape_key, fn)
    return fn


# --------------------------------------------------------------------- #
# Shared helpers                                                        #
# --------------------------------------------------------------------- #
def _unalias(e: Expr) -> Expr:
    while isinstance(e, Alias):
        e = e.child
    return e


def _trivial_source(e: Expr) -> Optional[str]:
    """The source column name when ``e`` is a bare passthrough (possibly
    renamed) column reference; None for anything computed."""
    inner = _unalias(e)
    return inner.name_ if isinstance(inner, ColumnRef) else None


def _trivial_literal(e: Expr) -> Optional[Literal]:
    inner = _unalias(e)
    return inner if isinstance(inner, Literal) else None


def _dtype_sig(cols_np: Dict[str, np.ndarray]) -> tuple:
    return tuple(sorted(
        (k, str(jax.dtypes.canonicalize_dtype(v.dtype)), v.shape[1:])
        for k, v in cols_np.items()))


def _pad_to(v: np.ndarray, padded: int, n: int, fill=0) -> np.ndarray:
    if padded == n:
        return v
    return np.pad(v, [(0, padded - n)] + [(0, 0)] * (v.ndim - 1),
                  constant_values=fill)


class _ChainWalk:
    """Forward walk of a filter/project chain that validates tracability
    and resolves, per step, which names live in the traced device env vs
    pass through host-side. Pure function of plan + schema (+ config via
    the callers), so eligibility can never vary with thread count or
    data; raises _ChainIneligible on the first untraceable construct."""

    def __init__(self, steps, input_schema: Schema):
        from daft_tpu.expressions.evaluator import resolve_schema
        from daft_tpu.ops.device_eval import _dtype_ok, _is_fusable

        self.steps = list(steps)
        self.input_schema = input_schema
        schema = input_schema
        # Device env membership + transitive input deps per current name.
        env_deps: Dict[str, Set[str]] = {
            f.name: {f.name} for f in schema if _dtype_ok(f.dtype)}
        # Host passthrough: current name -> source input column.
        host: Dict[str, str] = {f.name: f.name for f in schema}
        literals: Dict[str, Literal] = {}
        self.preds: List[Expr] = []
        self.pred_deps: Set[str] = set()
        prog_steps: List[tuple] = []
        for kind, payload in self.steps:
            if kind == "filter":
                pred = payload
                refs = pred.column_refs()
                if not _is_fusable(pred, schema) or \
                        not refs <= set(env_deps):
                    raise _ChainIneligible(f"filter on {sorted(refs)}")
                self.preds.append(pred)
                for r in refs:
                    self.pred_deps |= env_deps[r]
                prog_steps.append(("filter", pred))
                continue
            exprs = payload
            new_env: Dict[str, Set[str]] = {}
            new_host: Dict[str, str] = {}
            new_literals: Dict[str, Literal] = {}
            proj: List[Tuple[str, Expr]] = []  # traced outputs only
            for e in exprs:
                name = e.name()
                src = _trivial_source(e)
                lit = _trivial_literal(e)
                if src is not None:
                    if src in host:
                        new_host[name] = host[src]
                    if src in env_deps:
                        new_env[name] = env_deps[src]
                        proj.append((name, e))
                    if src not in host and src not in env_deps:
                        raise _ChainIneligible(f"unknown column {src!r}")
                elif lit is not None:
                    new_literals[name] = lit
                elif _is_fusable(e, schema) and \
                        e.column_refs() <= set(env_deps):
                    new_env[name] = set().union(
                        *(env_deps[r] for r in e.column_refs())) \
                        if e.column_refs() else set()
                    proj.append((name, e))
                else:
                    raise _ChainIneligible(f"expr {name!r} not fusable")
            env_deps, host, literals = new_env, new_host, new_literals
            prog_steps.append(("project", proj))
            schema = resolve_schema(exprs, schema)
        self.env_deps = env_deps
        self.host = host
        self.literals = literals
        self.prog_steps = prog_steps
        self.final_schema = schema

    def fingerprint_steps(self) -> tuple:
        return tuple(
            (k, p.key()) if k == "filter"
            else (k, tuple(e.key() for e in p))
            for k, p in self.steps)

    def nullable_gate(self, masked: Set[str]) -> bool:
        """True when every traced expression's null propagation matches
        the AND-reduce law for the masks actually present (data-driven;
        identical at every thread count because masks are data). Walks
        with the evolving transitively-masked name set, so a filter ABOVE
        a projection is checked against the projected namespace, not the
        input one."""
        from daft_tpu.ops.device_eval import _nullable_safe

        if not masked:
            return True
        cur = set(masked)
        for kind, payload in self.prog_steps:
            if kind == "filter":
                if (payload.column_refs() & cur) and \
                        not _nullable_safe(payload):
                    return False
                continue
            nxt = set()
            for name, e in payload:
                if (e.column_refs() & cur):
                    if _trivial_source(e) is None and not _nullable_safe(e):
                        return False
                    nxt.add(name)
            cur = nxt
        return True

    def pred_null_mask(self, null_masks: Dict[str, np.ndarray]
                       ) -> Optional[np.ndarray]:
        """OR of every predicate's null mask, each resolved in the
        predicate's OWN (possibly post-projection) namespace — a null in
        any predicate input invalidates the row (SQL filter semantics).
        None when no predicate touches a masked column."""
        cur: Dict[str, Optional[np.ndarray]] = dict(null_masks)
        combined = None
        for kind, payload in self.prog_steps:
            if kind == "filter":
                m = None
                for ref in payload.column_refs():
                    rm = cur.get(ref)
                    if rm is not None:
                        m = rm if m is None else (m | rm)
                if m is not None:
                    combined = m if combined is None else (combined | m)
                continue
            nxt: Dict[str, Optional[np.ndarray]] = {}
            for name, e in payload:
                m = None
                for ref in e.column_refs():
                    rm = cur.get(ref)
                    if rm is not None:
                        m = rm if m is None else (m | rm)
                nxt[name] = m
            cur = nxt
        return combined

    def mask_env(self, null_masks: Dict[str, np.ndarray]
                 ) -> Dict[str, Optional[np.ndarray]]:
        """Final-namespace null masks: OR-reduce of each output's
        referenced input masks, resolved through the project steps."""
        cur: Dict[str, Optional[np.ndarray]] = dict(null_masks)
        for kind, payload in self.prog_steps:
            if kind != "project":
                continue
            nxt: Dict[str, Optional[np.ndarray]] = {}
            for name, e in payload:
                m = None
                for ref in e.column_refs():
                    rm = cur.get(ref)
                    if rm is not None:
                        m = rm if m is None else (m | rm)
                nxt[name] = m
            cur = nxt
        return cur


class _ChainIneligible(Exception):
    pass


def _prune_prog(prog_steps, out_needed: Set[str]) -> Tuple[list, Set[str]]:
    """Dead-code-eliminate the traced program: keep only project outputs
    that later steps (or the final outputs) actually read — host
    passthroughs must never stage or trace. Returns the pruned steps and
    the set of INPUT-namespace columns the program reads."""
    needed = set(out_needed)
    pruned: List[tuple] = []
    for kind, payload in reversed(prog_steps):
        if kind == "filter":
            needed |= payload.column_refs()
            pruned.append((kind, payload))
            continue
        kept = [(name, e) for name, e in payload if name in needed]
        needed = set()
        for _, e in kept:
            needed |= e.column_refs()
        pruned.append((kind, kept))
    pruned.reverse()
    return pruned, needed


def _trace_env_fn(prog_steps):
    """The traced chain body over a device column env: folds project steps
    into the env and ANDs filter masks; returns (keep_or_None, env)."""
    def fold(cols: Dict[str, "jax.Array"]):
        from daft_tpu.ops.device_eval import _eval_tree

        env = dict(cols)
        n = next(iter(env.values())).shape[0] if env else 0
        keep = None
        for kind, payload in prog_steps:
            if kind == "filter":
                m = _eval_tree(payload, env, n).astype(bool)
                keep = m if keep is None else (keep & m)
            else:
                env = {name: _eval_tree(_unalias(e), env, n)
                       for name, e in payload}
        return keep, env

    return fold


# --------------------------------------------------------------------- #
# Filter/project chain programs                                         #
# --------------------------------------------------------------------- #
class ChainSpec:
    """A validated, fingerprinted filter/project chain ready to compile.

    Built ONCE per stage construction (executor chain collection) from
    plan + schema + config. Per-morsel calls then either run the compiled
    program or return None for data-driven fallbacks (nullable columns
    under non-AND-reduce null rules, device errors)."""

    def __init__(self, walk: _ChainWalk, out_schema: Schema, cfg):
        from daft_tpu.ops.device_eval import _dtype_ok

        self.walk = walk
        self.out_schema = out_schema
        self.min_rows = cfg.device_eval_min_rows
        self.buckets = cfg.device_batch_buckets
        self.out_names = [f.name for f in out_schema]
        # Assembly prefers the host source for pure passthroughs (no
        # device round-trip for untouched columns); only computed outputs
        # fetch from the program.
        self.dev_out = [n for n in self.out_names
                        if n in walk.env_deps and n not in walk.host
                        and n not in walk.literals]
        for n in self.out_names:
            if n not in walk.env_deps and n not in walk.host \
                    and n not in walk.literals:
                raise _ChainIneligible(f"output {n!r} unresolvable")
        for n in self.dev_out:
            f = walk.final_schema.get(n)
            if f is None or not _dtype_ok(f.dtype):
                raise _ChainIneligible(f"output {n!r} dtype")
        if not self.dev_out and not walk.preds:
            raise _ChainIneligible("nothing to compute on device")
        # Dead-code-eliminate host passthroughs from the traced program and
        # stage only the input columns the pruned program reads.
        self.prog_steps, needed = _prune_prog(walk.prog_steps,
                                              set(self.dev_out))
        self.src_cols = sorted(needed)
        self.fingerprint = (
            "chain", walk.fingerprint_steps(), tuple(self.out_names),
            tuple((n, str(walk.input_schema.get(n).dtype))
                  for n in self.src_cols))

    def _build_run(self, has_filter: bool):
        fold = _trace_env_fn(self.prog_steps)
        dev_out = self.dev_out

        def run(cols: Dict[str, "jax.Array"]):
            keep, env = fold(cols)
            outs = [env[n] for n in dev_out]
            if has_filter:
                return keep, outs
            return outs

        return run

    def run_morsel(self, mp: MicroPartition) -> Optional[MicroPartition]:
        """One compiled evaluation of the whole chain over a morsel, or
        None to take the interpreted per-step path."""
        from daft_tpu.ops.device_eval import (
            _bucket_reusing,
            device_eval_metrics,
        )

        rb = mp.combined()
        n = len(rb)
        if n < self.min_rows:
            return None
        cols_np: Dict[str, np.ndarray] = {}
        null_masks: Dict[str, np.ndarray] = {}
        for name in self.src_cols:
            vals, mask = rb.get_column(name).to_numpy_masked()
            cols_np[name] = vals
            if mask is not None:
                null_masks[name] = mask
        if not self.walk.nullable_gate(set(null_masks)):
            device_eval_metrics.record_fallback("nullable_unsafe", rows=n)
            return None
        has_filter = bool(self.walk.preds)
        shape_key = (self.fingerprint, _dtype_sig(cols_np))
        # Elementwise outputs slice back to [:n], so bucket reuse is safe.
        padded = _bucket_reusing(n, self.buckets, shape_key)
        try:
            cols_dev = {name: jnp.asarray(_pad_to(v, padded, n))
                        for name, v in cols_np.items()}
            fn = _compiled_executable(shape_key + (padded,),
                                      self._build_run(has_filter),
                                      (cols_dev,))
            if has_filter:
                keep_dev, outs = fn(cols_dev)
                fetched = jax.device_get(
                    [keep_dev[:n]] + [o[:n] for o in outs])
                keep_np, outs_np = fetched[0], fetched[1:]
                # Pred null lanes drop (SQL filter semantics), with each
                # predicate's mask resolved in ITS OWN namespace — a
                # filter above a projection masks on the projected
                # columns' propagated nulls, not the raw inputs.
                pred_mask = self.walk.pred_null_mask(null_masks)
                if pred_mask is not None:
                    keep_np = keep_np & ~pred_mask
            else:
                keep_np = None
                outs_np = jax.device_get([o[:n] for o in fn(cols_dev)])
        except Exception:
            device_eval_metrics.record_device_error()
            device_eval_metrics.record_fallback("chain_device_error",
                                                rows=n)
            logger.warning("compiled chain failed; interpreted fallback",
                           exc_info=True)
            return None
        return self._assemble(rb, n, keep_np, outs_np, null_masks)

    def _assemble(self, rb: RecordBatch, n: int,
                  keep_np: Optional[np.ndarray], outs_np,
                  null_masks: Dict[str, np.ndarray]) -> MicroPartition:
        from daft_tpu import metrics
        from daft_tpu.ops.device_eval import (
            _np_result_dtype,
            device_eval_metrics,
        )

        final_masks = self.walk.mask_env(null_masks)
        out_n = int(keep_np.sum()) if keep_np is not None else n
        keep_series = None
        if keep_np is not None:
            keep_series = Series.from_numpy(keep_np, "__keep")
        dev_arrays = dict(zip(self.dev_out, outs_np))
        cols: List[Series] = []
        for name in self.out_names:
            target = self.out_schema.get(name).dtype
            if name in dev_arrays:
                arr = dev_arrays[name]
                mask = final_masks.get(name)
                if keep_np is not None:
                    arr = arr[keep_np]
                    mask = mask[keep_np] if mask is not None else None
                s = Series.from_numpy(np.ascontiguousarray(arr), name,
                                      _np_result_dtype(target, arr))
                if s.dtype != target:
                    s = s.cast(target)
                if mask is not None:
                    s = s._with_mask(np.ascontiguousarray(mask))
            elif name in self.walk.literals:
                lit = self.walk.literals[name]
                s = Series.full(name, lit.value, out_n, lit.dtype)
                if s.dtype != target:
                    s = s.cast(target)
            else:
                src = self.walk.host[name]
                s = rb.get_column(src)
                if keep_series is not None:
                    one = RecordBatch(Schema([Field(src, s.dtype)]), [s], n)
                    s = one.filter(keep_series).get_column(src)
                if s.name != name:
                    s = s.rename(name)
                if s.dtype != target:
                    s = s.cast(target)
            cols.append(s)
        metrics.COMPILED_CHAIN_MORSELS.labels("filter_project").inc()
        metrics.COMPILED_CHAIN_ROWS.labels("filter_project").inc(n)
        device_eval_metrics.record_fused(
            max(len(self.dev_out) + len(self.walk.preds), 1), n)
        out_rb = RecordBatch(self.out_schema, cols, out_n)
        return MicroPartition(self.out_schema, [out_rb])


def build_chain_spec(steps, input_schema: Schema, out_schema: Schema,
                     cfg) -> Optional[ChainSpec]:
    """A compiled-chain spec when the WHOLE chain traces (pure plan+config
    eligibility — thread count never enters), else None."""
    if not enabled(cfg) or not steps:
        return None
    try:
        return ChainSpec(_ChainWalk(steps, input_schema), out_schema, cfg)
    except (_ChainIneligible, *_ELIGIBILITY_ERRORS):
        return None


# --------------------------------------------------------------------- #
# Chain + global-aggregation partial phase                              #
# --------------------------------------------------------------------- #
#: Reduction row-mask input name (daft columns can't collide with it).
_ROWS_INPUT = "__rows__"
_PRED_VALID = "__pred_valid__"


class AggChainSpec:
    """Filter/project chain fused with the PARTIAL phase of a global
    (no-group-by) aggregation: one program computes the keep mask, the
    projected environment, and masked partial reductions, returning
    O(aggs) scalars per chunk instead of a filtered morsel.

    Reductions are shape-sensitive, so this spec pads with the FIXED
    bucket ladder (never the reuse ladder): padded length is a pure
    function of the row count, keeping per-chunk float sums byte-identical
    at any thread count (the determinism contract). Row/validity masks
    ride as *input arrays* (not shapes), so varying ``n`` within a bucket
    never recompiles.
    """

    def __init__(self, walk: _ChainWalk, agg_plan, partial_schema: Schema,
                 cfg):
        from daft_tpu.ops.device_eval import _dtype_ok, _is_fusable

        if agg_plan.group_by:
            raise _ChainIneligible("grouped agg")
        self.walk = walk
        self.buckets = cfg.device_batch_buckets
        # Same floor as the elementwise path: a 50-row interactive agg
        # must not pay device staging + a cold XLA compile for work the
        # host does in microseconds.
        self.min_rows = cfg.device_eval_min_rows
        self.partial_schema = partial_schema
        schema = walk.final_schema
        # Partial aggs: Alias(AggOp(op, child), "__p<i>_<s>"). Fusable ops
        # are {sum, count, min, max} whose resolved partial field stays
        # device-representable (dtype-driven fallback: i32 sums promote
        # to i64 on the host and stay there).
        self.aggs: List[Tuple[str, str, Expr, object, str]] = []
        for pe in agg_plan.partial_exprs:
            name = pe.name()
            agg = _unalias(pe)
            if not isinstance(agg, AggOp) or agg.op not in (
                    "sum", "count", "min", "max"):
                raise _ChainIneligible(f"agg op {getattr(agg, 'op', '?')}")
            child = agg.child
            field = partial_schema.get(name)
            if field is None:
                raise _ChainIneligible(f"partial field {name!r}")
            refs = child.column_refs()
            if not refs <= set(walk.env_deps):
                raise _ChainIneligible(f"agg child refs {sorted(refs)}")
            if agg.op == "count":
                mode = (agg.kwargs or {}).get("mode", "valid")
                if mode not in ("valid", "all"):
                    raise _ChainIneligible(f"count mode {mode!r}")
                if _trivial_source(child) is None and \
                        not _is_fusable(child, schema):
                    raise _ChainIneligible("count child")
                self.aggs.append((name, "count", child, field.dtype, mode))
                continue
            if not _is_fusable(child, schema) or not _dtype_ok(field.dtype):
                raise _ChainIneligible(f"agg child {name!r}")
            child_np = child.to_field(schema).dtype.to_numpy()
            if child_np.kind not in "fiu":
                raise _ChainIneligible("agg child kind")
            if agg.op == "sum" and child_np.kind != "f":
                # Integer sums promote past 32 bits on the host; floats
                # keep their width, so f32 sums match the partial field.
                raise _ChainIneligible("int sum promotes")
            self.aggs.append((name, agg.op, child, field.dtype, ""))
        if not self.aggs:
            raise _ChainIneligible("no partial aggs")
        final_refs: Set[str] = set()
        for _, _, child, _, _ in self.aggs:
            final_refs |= child.column_refs()
        self.prog_steps, needed = _prune_prog(walk.prog_steps, final_refs)
        self.src_cols = sorted(needed)
        self.fingerprint = (
            "agg_chain", walk.fingerprint_steps(),
            tuple((nm, op, child.key(), mode)
                  for nm, op, child, _, mode in self.aggs),
            tuple((nm, str(walk.input_schema.get(nm).dtype))
                  for nm in self.src_cols))

    def _agg_nullable_gate(self, masked: Set[str]) -> bool:
        from daft_tpu.ops.device_eval import _nullable_safe

        if not masked:
            return True
        if not self.walk.nullable_gate(masked):
            return False
        # Masked names in the FINAL namespace that agg children touch.
        final_masked = set()
        cur = set(masked)
        for kind, payload in self.walk.prog_steps:
            if kind != "project":
                continue
            cur = {name for name, e in payload
                   if e.column_refs() & cur}
        final_masked = cur
        for _, _, child, _, _ in self.aggs:
            if (child.column_refs() & final_masked) and \
                    _trivial_source(child) is None and \
                    not _nullable_safe(child):
                return False
        return True

    def _build_run(self):
        fold = _trace_env_fn(self.prog_steps)
        aggs = [(name, op, child, mode)
                for name, op, child, _dt, mode in self.aggs]

        def run(cols: Dict[str, "jax.Array"],
                valids: Dict[str, "jax.Array"]):
            from daft_tpu.ops.device_eval import _eval_tree

            keep, env = fold(cols)
            rows = valids[_ROWS_INPUT]
            keep = rows if keep is None else (keep & rows)
            if _PRED_VALID in valids:
                keep = keep & valids[_PRED_VALID]
            n = rows.shape[0]
            outs = []
            for name, op, child, mode in aggs:
                avalid = valids.get(f"__v_{name}")
                sel = keep if avalid is None else (keep & avalid)
                cnt = jnp.sum(sel.astype(jnp.int32))
                if op == "count":
                    base = keep if mode == "all" else sel
                    c = jnp.sum(base.astype(jnp.int32))
                    outs.append((c, c))
                    continue
                v = _eval_tree(_unalias(child), env, n)
                if op == "sum":
                    outs.append((jnp.sum(jnp.where(sel, v, 0)), cnt))
                    continue
                if jnp.issubdtype(v.dtype, jnp.floating):
                    lo = jnp.asarray(jnp.inf, v.dtype)
                    hi = jnp.asarray(-jnp.inf, v.dtype)
                else:
                    info = jnp.iinfo(v.dtype)
                    lo = jnp.asarray(info.max, v.dtype)
                    hi = jnp.asarray(info.min, v.dtype)
                if op == "min":
                    outs.append((jnp.min(jnp.where(sel, v, lo)), cnt))
                else:
                    outs.append((jnp.max(jnp.where(sel, v, hi)), cnt))
            return outs

        return run

    def run_chunk(self, rb: RecordBatch) -> Optional[RecordBatch]:
        """Partial-aggregate one chunk through the compiled program; None
        falls back to the interpreted steps + host aggregation."""
        from daft_tpu import metrics
        from daft_tpu.ops.device_eval import (
            _bucket,
            _np_result_dtype,
            device_eval_metrics,
        )

        n = len(rb)
        if n < max(self.min_rows, 1):
            return None
        cols_np: Dict[str, np.ndarray] = {}
        null_masks: Dict[str, np.ndarray] = {}
        for name in self.src_cols:
            vals, mask = rb.get_column(name).to_numpy_masked()
            cols_np[name] = vals
            if mask is not None:
                null_masks[name] = mask
        if not self._agg_nullable_gate(set(null_masks)):
            device_eval_metrics.record_fallback("nullable_unsafe", rows=n)
            return None
        # FIXED bucketing: reductions must see a padded length that is a
        # pure function of n (class docstring).
        padded = _bucket(n, self.buckets)
        rows = np.zeros(padded, dtype=bool)
        rows[:n] = True
        valids: Dict[str, np.ndarray] = {_ROWS_INPUT: rows}
        # Each predicate's null mask resolved in its own namespace (a
        # filter above a projection masks on propagated nulls).
        pred_mask = self.walk.pred_null_mask(null_masks)
        if pred_mask is not None:
            valids[_PRED_VALID] = _pad_to(~pred_mask, padded, n, fill=False)
        mask_env = self.walk.mask_env(null_masks)
        for name, op, child, _dt, mode in self.aggs:
            m = None
            for ref in child.column_refs():
                rm = mask_env.get(ref)
                if rm is not None:
                    m = rm if m is None else (m | rm)
            if m is not None:
                valids[f"__v_{name}"] = _pad_to(~m, padded, n, fill=False)
        try:
            cols_dev = {nm: jnp.asarray(_pad_to(v, padded, n))
                        for nm, v in cols_np.items()}
            valids_dev = {nm: jnp.asarray(v) for nm, v in valids.items()}
            shape_key = (self.fingerprint, padded, _dtype_sig(cols_np),
                         tuple(sorted(valids)))
            # An aggregation's outputs are scalars: nothing can alias a
            # donated column, and the TPU compiler warns on every compile.
            fn = _compiled_executable(shape_key, self._build_run(),
                                      (cols_dev, valids_dev), donate=False)
            host = jax.device_get(fn(cols_dev, valids_dev))
        except Exception:
            device_eval_metrics.record_device_error()
            device_eval_metrics.record_fallback("chain_device_error",
                                                rows=n)
            logger.warning("compiled agg chain failed; interpreted "
                           "fallback", exc_info=True)
            return None
        # ONE device->host transfer already happened above (device_get on
        # the whole output pytree); stage the per-agg 1-row arrays BEFORE
        # the assembly loop (daftlint DTL005).
        counts = np.asarray([int(c) for _, c in host], dtype=np.uint64)
        # np.atleast_1d: the values are already host np scalars (fetched in
        # the batched device_get), this only reshapes.
        val_arrays = [np.atleast_1d(v) for v, _ in host]
        null_one = np.ones(1, dtype=bool)
        cols: List[Series] = []
        for i, (name, op, child, dtype, mode) in enumerate(self.aggs):
            if op == "count":
                s = Series.from_numpy(counts[i:i + 1].copy(), name)
            else:
                arr = val_arrays[i]
                s = Series.from_numpy(arr, name,
                                      _np_result_dtype(dtype, arr))
                if counts[i] == 0:
                    # Host partials over zero qualifying rows are null
                    # (arrow min_count=1 semantics).
                    s = s._with_mask(null_one)
            if s.dtype != dtype:
                s = s.cast(dtype)
            cols.append(s)
        metrics.COMPILED_CHAIN_MORSELS.labels("filter_project_agg").inc()
        metrics.COMPILED_CHAIN_ROWS.labels("filter_project_agg").inc(n)
        device_eval_metrics.record_fused(max(len(self.aggs), 1), n)
        schema = Schema([Field(c.name, c.dtype) for c in cols])
        return RecordBatch(schema, cols, 1)


def build_agg_chain_spec(steps, agg_plan, input_schema: Schema,
                         partial_schema: Schema, cfg
                         ) -> Optional[AggChainSpec]:
    """A compiled chain+partial-agg spec when the whole chain INCLUDING
    every partial aggregation traces; else None (pure plan+config)."""
    if not enabled(cfg):
        return None
    try:
        return AggChainSpec(_ChainWalk(steps, input_schema), agg_plan,
                            partial_schema, cfg)
    except (_ChainIneligible, *_ELIGIBILITY_ERRORS):
        return None


# --------------------------------------------------------------------- #
# Fused-vs-interpreted ABBA A/B guard (the self-disabling contract)     #
# --------------------------------------------------------------------- #
def _guard_tables(rows: int):
    import daft_tpu

    rng = np.random.default_rng(11)
    return daft_tpu.from_pydict({
        "price": rng.uniform(900, 105000, rows).astype(np.float32),
        "disc": rng.uniform(0.0, 0.1, rows).astype(np.float32),
        "tax": rng.uniform(0.0, 0.08, rows).astype(np.float32),
        "qty": rng.uniform(1, 50, rows).astype(np.float32),
        "flag": rng.integers(0, 3, rows).astype(np.int32),
    })


def _guard_queries(df):
    from daft_tpu import col

    def q06_shape():
        return (df.where((col("qty") < 24.0) & (col("disc") >= 0.02)
                         & (col("disc") <= 0.09))
                .agg((col("price") * col("disc")).sum().alias("revenue")))

    def q01_shape():
        return (df.where(col("qty") < 48.0)
                .with_columns({
                    "disc_price": col("price") * (1 - col("disc")),
                    "charge": col("price") * (1 - col("disc"))
                              * (1 + col("tax")),
                })
                .groupby("flag")
                .agg(col("disc_price").sum().alias("rev"),
                     col("charge").sum().alias("charge"),
                     col("qty").count().alias("n"))
                .sort("flag"))

    return [("q06_shape", q06_shape), ("q01_shape", q01_shape)]


def run_ab_guard(rows: int = 400_000, blocks: int = 4,
                 tolerance_pct: float = 5.0,
                 self_disable: bool = True) -> dict:
    """ABBA-paired fused-vs-interpreted A/B on q01/q06-shaped scans.

    Each block runs fused,interp,interp,fused (position-balanced — the
    first run of a back-to-back pair measures consistently slower, and
    A,B,B,A cancels that drift to first order, the PR 7 discipline). If
    the compiled path loses by more than ``tolerance_pct`` on the median
    block, the contract fires: :func:`set_self_disabled` turns the
    feature off process-wide (when ``self_disable``), visible as
    ``daft_compiled_eval_enabled 0``.

    The guard is the ARBITER of the switch: a pre-existing self-disable
    is cleared before measuring (otherwise the "fused" arm would silently
    run interpreted and the comparison would be vacuous), re-armed only
    if the fused path loses again.
    """
    import statistics

    import daft_tpu

    previously_disabled = self_disabled_reason()
    if previously_disabled is not None:
        clear_self_disabled()
    df = _guard_tables(rows)
    queries = _guard_queries(df)

    def once(compiled: bool) -> float:
        with daft_tpu.execution_config_ctx(
                compiled_eval_enabled=compiled):
            t0 = time.perf_counter()
            for _, build in queries:
                build().collect()
            return time.perf_counter() - t0

    # Warm both paths (plan caches + XLA compiles) outside the clock.
    once(True)
    once(False)
    deltas, fused_s, interp_s = [], [], []
    for b in range(blocks):
        a_is_fused = (b % 2 == 0)
        t1 = once(a_is_fused)
        t2 = once(not a_is_fused)
        t3 = once(not a_is_fused)
        t4 = once(a_is_fused)
        f, i = (t1 + t4, t2 + t3) if a_is_fused else (t2 + t3, t1 + t4)
        fused_s.append(f / 2)
        interp_s.append(i / 2)
        deltas.append((f - i) / 2)
    fused_med = statistics.median(fused_s)
    interp_med = statistics.median(interp_s)
    delta_med = statistics.median(deltas)
    loss_pct = (delta_med / interp_med * 100.0) if interp_med > 0 else 0.0
    fused_wins = loss_pct <= tolerance_pct
    result = {
        "fused_s": round(fused_med, 4),
        "interpreted_s": round(interp_med, 4),
        "delta_pct": round(loss_pct, 2),
        "tolerance_pct": tolerance_pct,
        "fused_wins": fused_wins,
        "blocks": blocks,
        "rows": rows,
        "self_disabled": False,
        "previously_disabled": previously_disabled,
    }
    if not fused_wins and self_disable:
        set_self_disabled(
            f"ab_guard: compiled path {loss_pct:.1f}% slower than "
            f"interpreted on q01/q06-shaped scans")
        result["self_disabled"] = True
    return result
