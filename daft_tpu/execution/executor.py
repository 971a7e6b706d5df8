"""Single-host streaming execution engine — morsel-parallel and pipelined.

Re-designs the reference's Swordfish push-based morsel engine
(src/daft-local-execution: run.rs:408 NativeExecutor; sources / intermediate
ops / streaming sinks / blocking sinks; pipeline.rs message flow) as a
pipeline of stages over ONE shared compute pool (execution/pipeline.py):

* **pipelined streaming ops** — every Project / Filter / UDF-project /
  join-probe becomes a stage: its input is morselized (oversized morsels
  split at ``default_morsel_size``, undersized ones coalesced so queue +
  span overhead never dominates tiny-row queries), a feeder pulls the
  child and submits per-morsel work to the shared pool through a bounded
  queue (the backpressure), and results yield in input order. Stacked
  stages run CONCURRENTLY — while a join probes morsel i, the filter
  below it evaluates morsel i+1 — and compete for ``num_compute_threads``
  workers instead of multiplying threads per stage.
* **parallel blocking sinks** — grouped aggregation consumes its upstream
  in parallel: low-cardinality aggs partial-aggregate fixed row-chunks
  across the pool and merge in chunk order; high-cardinality aggs hash-
  partition morsels and aggregate each bucket single-shot in parallel.
  Chunk/bucket structure is thread-count-invariant, so serial and
  parallel runs produce byte-identical per-group float sums.
* **build-once probe-many joins** — the in-memory hash-join path builds a
  reusable sorted-key index over the build side (execution/join_index.py)
  and probes morsels in parallel with zero per-morsel rebuild; shapes the
  index can't serve fall back to per-call Acero on coarse morsels.
* **scan prefetch** — scan tasks read concurrently on an IO thread pool with
  bounded per-task queues, yielding morsels in task order.
* **UDF concurrency** — UDFProject dispatches morsels to a worker pool of
  ``max_concurrency`` replicas (the reference's actor-pool UDF operator);
  TPU inference UDFs hold chip slots.

Sharing one pool is deadlock-free because pooled tasks are pure morsel
functions — only feeder threads (never pool workers) wait on futures.
Cancellation is observed at every morsel boundary (feeders pull through
``_cancel_checked``); any failure poisons the MemoryManager's current
waiters on the way out. Sort/limit/distinct and every other
order-sensitive consumer see the serial sequence (ordered stages restore
input order); Arrow/Acero kernels and XLA computations release the GIL,
so the thread pool gives real parallelism on multi-core hosts.
"""

from __future__ import annotations

import contextlib
import contextvars
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, Iterator, List, Optional

import numpy as np

from daft_tpu.errors import DaftExecutionError, DaftPlanError
from daft_tpu.execution.aggregation import AggState
from daft_tpu.execution.pipeline import (
    chunk_morsels,
    collect_parallel,
    map_stage,
    morselize,
)
from daft_tpu.expressions.evaluator import evaluate
from daft_tpu.micropartition import MicroPartition
from daft_tpu.physical import plan as pp
from daft_tpu.recordbatch import RecordBatch
from daft_tpu.schema import Field, Schema
from daft_tpu.series import Series

_SENTINEL = object()


class Executor:
    """Runs a local physical plan, yielding result MicroPartitions."""

    def __init__(self, cfg, num_io_threads: int = 8, partition_offset: int = 0,
                 stats=None, cancel_token=None, profiler=None):
        import os

        from daft_tpu.execution.resource_manager import get_memory_manager

        self.cfg = cfg
        self.num_io_threads = num_io_threads
        self.partition_offset = partition_offset
        self.stats = stats  # RuntimeStats | None
        # Cooperative cancellation (cancellation.py): observed at morsel
        # boundaries, memory-permit waits, and fault-injection points.
        self.cancel_token = cancel_token
        # Query profiler (profiling.py TaskProfiler | None): when present,
        # every operator's morsel loop runs inside a span keyed by plan-node
        # id. None is the DAFT_PROFILE=0 fast path — zero per-morsel cost.
        self.profiler = profiler
        self._profile_node_ids: Dict[int, int] = {}
        # Live _OpFrame per plan node while its operator span is open:
        # stages hand this to pipeline workers so per-morsel wall/CPU is
        # measured ON THE WORKER (tight around the kernel) and aggregated
        # into the ONE span for that plan node.
        self._op_frames: Dict[int, object] = {}
        self.memory = get_memory_manager()
        self._held_bytes = 0
        # Per-operator breakdown of _held_bytes for the memory ledger:
        # cleanup releases EXACTLY what this executor charged (concurrent
        # executors of one distributed query share a query id — a bulk
        # query-wide drain here would zero a sibling's live attribution).
        self._held_by_op: Dict[str, int] = {}
        # Set under _state_lock when run()'s cleanup has already returned
        # this executor's held permits: a Prefetch/feeder thread whose
        # acquire succeeded JUST as the query unwound (cancel landing
        # between acquire and the first morsel) must hand its permit
        # straight back instead of adding to a counter nobody will ever
        # release again (the permit-leak window, ISSUE 10).
        self._permits_closed = False
        # Guards executor state that the probe-side Prefetch thread can
        # touch concurrently with the main pull chain: the shared-subtree
        # cache (double materialization) and _held_bytes (lost updates
        # would under-release permits at query end). RLock: a shared
        # subtree may nest another shared subtree on the same thread.
        self._state_lock = threading.RLock()
        # Per-THREAD pull-chain stack: with worker-pool stages, nested
        # _instrumented frames run in different feeder threads; a shared list
        # would interleave pushes/pops across chains (stats corruption and
        # races). Exclusive-time attribution is per pull chain.
        self._op_stacks = threading.local()
        # Memory observatory (execution/memledger.py): every byte this
        # executor holds — permits, stage-queue residency, spill files —
        # is charged to (query_id, operator) and drained at run() cleanup.
        from daft_tpu.execution.memledger import get_ledger

        self._ledger = get_ledger()
        self._ledger_qid = getattr(cancel_token, "query_id", "") \
            or (stats.query_id if stats is not None else "") or ""
        n = getattr(cfg, "num_compute_threads", 0)
        self.compute_threads = n if n > 0 else (os.cpu_count() or 1)
        # Morselization bounds for pipeline stages. The floor coalesces
        # tiny morsels so per-morsel queue + span overhead can't dominate
        # small-row (q11/q16-shaped) queries; both bounds are pure config
        # (never thread-count), keeping the morsel stream identical at
        # any num_compute_threads.
        self.max_morsel_rows = cfg.default_morsel_size
        self.min_morsel_rows = min(
            getattr(cfg, "min_morsel_size", 16 * 1024), self.max_morsel_rows)
        self._compute_pool: Optional[ThreadPoolExecutor] = None
        self._spill_dir = None
        # Feedback plane (daft_tpu/feedback.py). Observation counts every
        # stamped operator's actual rows/bytes (innermost wrapper — the
        # counts are the operator's true output, before cancel/profile
        # frames). Corrections additionally let runtime strategy choices
        # consult the stamped estimates (grace bucket sizing, est-driven
        # early spill). Both gates are resolved ONCE per executor: a
        # mid-query env flip must not change strategy between operators.
        from daft_tpu import feedback as _feedback

        self._fb_observe = _feedback.observation_enabled(cfg)
        self._fb_correct = _feedback.corrections_enabled(cfg)
        self._fb_obs: Dict[int, dict] = {}
        self._fb_root: Optional[pp.PhysicalPlan] = None

    def _spill(self):
        """Lazy query-scoped spill directory (cleaned up at query end)."""
        if self._spill_dir is None:
            from daft_tpu.execution.spill import SpillDir

            self._spill_dir = SpillDir(query_id=self._ledger_qid)
        return self._spill_dir

    def _stage_ledger(self, op: str):
        """The ``(query_id, operator)`` tag pipeline stages charge their
        bounded-queue residency under, or None when the plane is off."""
        if not self._ledger.enabled:
            return None
        return (self._ledger_qid, op)

    def _sink_budget(self) -> Optional[int]:
        """In-memory working-set budget per blocking sink; None = unbounded
        (no DAFT_MEMORY_LIMIT set), matching the pre-out-of-core behavior."""
        from daft_tpu.execution.spill import sink_budget

        return sink_budget(self.memory.limit)

    def _pool(self) -> ThreadPoolExecutor:
        """The executor-wide compute pool, shared by all streaming stages so
        stacked operators compete for core-count workers instead of
        spawning a pool each."""
        if self._compute_pool is None:
            self._compute_pool = ThreadPoolExecutor(
                max_workers=self.compute_threads, thread_name_prefix="daft-compute")
        return self._compute_pool

    #: How long run()'s teardown retries the close of an operator iterator
    #: that a feeder thread is still executing.
    TEARDOWN_RETRY_S = 2.0

    def run(self, plan: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        # Plans are DAGs: subquery decorrelation references the same subtree
        # object from multiple parents (e.g. the row-id EXISTS technique).
        # Shared nodes materialize ONCE — without this, nested EXISTS
        # re-executes the base 2^depth times.
        self._shared_ids = pp.shared_subtree_ids(plan)
        self._shared_cache = {}
        # Re-runnable executors restart observation from zero; the root is
        # kept so feedback_report can mark nodes below a Limit/TopN as
        # inexact (their drained counts are truncated, not cardinalities).
        self._fb_root = plan
        with self._state_lock:
            self._fb_obs = {}
        with self._state_lock:
            self._permits_closed = False  # executors are re-runnable
            self._live_iters: List = []
        try:
            yield from self._run(plan)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            # The executor is dying: any sink thread still blocked in a
            # memory-permit wait would otherwise sleep until its timeout
            # (or forever, for unbounded waits). Poison wakes every CURRENT
            # waiter with this failure; later queries are untouched
            # (generation-scoped, and query-scoped when we know our query:
            # concurrent healthy queries' waiters keep waiting).
            # GeneratorExit is NORMAL early close (limit pushdown,
            # abandoned iteration) — never a poison.
            if not isinstance(e, GeneratorExit):
                qid = getattr(self.cancel_token, "query_id", None) \
                    or (self.stats.query_id if self.stats is not None else None)
                self.memory.poison(e, query_id=qid or None)
            raise
        finally:
            # Close every operator iterator DETERMINISTICALLY, children
            # first. A failure that surfaces BETWEEN operators (the
            # cancel-check wrapper raising after a pull) unwinds without
            # passing through sibling handler generators' frames — and the
            # exception's traceback then pins those suspended frames in a
            # reference cycle, so their finallys (budget-reservation
            # releases, spill cleanup, stage teardown) would otherwise wait
            # for a cyclic GC pass. The memory ledger's drains-to-zero
            # audit is what made this window visible.
            with self._state_lock:
                live, self._live_iters = list(self._live_iters), []

            def closed(g) -> bool:
                try:
                    g.close()
                except ValueError:
                    # "already executing": the stage above is pulling it on
                    # its feeder thread, which that stage's close (later in
                    # this pass) releases.
                    return False
                # daftlint: disable=DTL002 -- teardown close of an already-unwinding iterator; an error here must not mask the query's own outcome
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass
                return True

            busy = [g for g in reversed(live) if not closed(g)]
            # An operator that holds threads or device memory (the UDF
            # operator's host stage) lets go in its finally, so give the
            # feeders a moment to come out of their pull; what is still
            # executing after that is closed when its feeder drops it.
            give_up = time.monotonic() + self.TEARDOWN_RETRY_S
            while busy and time.monotonic() < give_up:
                time.sleep(0.005)
                busy = [g for g in busy if not closed(g)]
            self._shared_cache = {}
            if self._compute_pool is not None:
                self._compute_pool.shutdown(wait=False, cancel_futures=True)
                self._compute_pool = None
            if self._spill_dir is not None:
                self._spill_dir.cleanup()
                self._spill_dir = None
            # Close the permit window ATOMICALLY with reading the held
            # total: a side-thread acquire that lands after this point
            # self-releases in _add_held instead of incrementing a counter
            # that has already been drained (the cancel-between-acquire-
            # and-first-morsel leak).
            with self._state_lock:
                held, self._held_bytes = self._held_bytes, 0
                by_op, self._held_by_op = self._held_by_op, {}
                self._permits_closed = True
            if held:
                self.memory.release(held)
            # The ledger's permit drain is byte-symmetric with the permit
            # drain above — EVERY exit (success, poison-woken waiters,
            # cancel mid-acquire) returns this executor's held-byte
            # attribution to zero here, so an aborted query can't leave
            # phantom held bytes behind (the reconciliation audit's
            # contract).
            for op, nbytes in by_op.items():
                self._ledger.release(self._ledger_qid, op, nbytes,
                                     kind="permit")
            if self.stats is not None:
                self.stats.flush()

    # ------------------------------------------------------------------ #
    def _run(self, node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        if id(node) in getattr(self, "_shared_ids", ()):
            return iter(self._shared_subtree(node))
        return self._run_uncached(node)

    def _shared_subtree(self, node: pp.PhysicalPlan) -> List[MicroPartition]:
        """Materialize a shared subtree exactly once even when the probe-
        side Prefetch thread races the main pull chain. Coordination is a
        per-node fill event — the lock is held only for bookkeeping,
        never across the materialization itself, so a stage feeder inside
        the fill can hit another shared node without deadlocking (fill
        dependencies follow the acyclic plan DAG)."""
        while True:
            with self._state_lock:
                entry = self._shared_cache.get(id(node))
                if entry is None:
                    evt = threading.Event()
                    self._shared_cache[id(node)] = ("filling", evt)
                    break
                if entry[0] == "done":
                    return entry[1]
                waiting = entry[1]
            waiting.wait()
            # Loop: the filler may have failed and cleared the slot — the
            # next thread through re-fills instead of hanging on a stale
            # in-progress marker.
        try:
            cached: List[MicroPartition] = []
            gate_on = True
            for mp in self._run_uncached(node):
                # Pinning a shared subtree's output is buffered state:
                # account it like a blocking sink. Same self-deadlock
                # guard as _collect — the only releaser is THIS executor
                # at query end, so a failed acquire disengages the gate
                # instead of waiting forever.
                nbytes = mp.size_bytes()
                if gate_on:
                    if self.memory.acquire(nbytes, timeout=5.0,
                                           token=self.cancel_token):
                        # Track what acquire actually granted (it clamps
                        # oversized requests to the limit) so the unwind
                        # release is byte-symmetric with the grant.
                        limit = self.memory.limit
                        self._add_held(nbytes if limit is None
                                       else min(nbytes, limit),
                                       op="SharedSubtree")
                    else:
                        gate_on = False
                cached.append(mp)
        except BaseException:
            with self._state_lock:
                self._shared_cache.pop(id(node), None)
            evt.set()
            raise
        with self._state_lock:
            self._shared_cache[id(node)] = ("done", cached)
        evt.set()
        return cached

    def _add_held(self, nbytes: int, op: str = "") -> None:
        with self._state_lock:
            if not self._permits_closed:
                self._held_bytes += nbytes
                self._held_by_op[op] = self._held_by_op.get(op, 0) + nbytes
                self._ledger.charge(self._ledger_qid, op, nbytes,
                                    kind="permit")
                return
        # Query already unwound and released its held total: this acquire
        # raced the cleanup (side thread past its token check). Releasing
        # here — outside the state lock — keeps available_permits at
        # baseline instead of leaking until process exit. The ledger was
        # never charged on this path, so nothing phantom remains there
        # either (the poison/cancel-mid-acquire regression pins this).
        self.memory.release(nbytes)

    def _run_uncached(self, node: pp.PhysicalPlan) -> Iterator[MicroPartition]:
        handler = getattr(self, f"_run_{type(node).__name__}", None)
        if handler is None:
            raise DaftPlanError(f"No executor for physical node {node.name()}")
        it = self._track_iter(handler(node))
        if self._fb_observe and getattr(node, "_fb_fp", None) is not None:
            it = self._track_iter(self._fb_counted(node, it))
        if self.cancel_token is not None:
            it = self._track_iter(self._cancel_checked(node.name(), it))
        if self.profiler is not None:
            it = self._track_iter(self._profiled(node, it))
        if self.stats is None:
            return it
        return self._track_iter(self._instrumented(node.name(), it))

    def _track_iter(self, it):
        """Register an operator iterator for deterministic close at run()
        cleanup (closing exhausted/closed generators is a no-op)."""
        with self._state_lock:
            live = getattr(self, "_live_iters", None)
            if live is not None:
                live.append(it)
        return it

    def _fb_counted(self, node: pp.PhysicalPlan,
                    it: Iterator[MicroPartition]) -> Iterator[MicroPartition]:
        """Count an operator's ACTUAL output rows/bytes against its stamped
        estimate. One registered dict per physical node; the per-morsel
        increments run on the single thread pulling this iterator."""
        with self._state_lock:
            rec = self._fb_obs.setdefault(id(node), {
                "node": node._fb_fp, "op": type(node).__name__,
                "est_rows": getattr(node, "_est_rows", None),
                "est_bytes": getattr(node, "_est_bytes", None),
                "rows": 0, "bytes": 0, "done": False})
        for mp in it:
            rec["rows"] += len(mp)
            rec["bytes"] += mp.size_bytes()
            yield mp
        rec["done"] = True

    def feedback_report(self, complete: bool = True) -> "Optional[list]":
        """The estimate-vs-actual pairs for this run — one dict per
        observed node, for the flight record's v6 ``estimates`` block. An
        observation is ``exact`` only when the node fully drained, the
        query fully drained (``complete``), and the node is not beneath a
        Limit/TopN (early close truncates its counts): the store learns
        only from exact observations, everything else is display-only."""
        if not self._fb_observe:
            return None
        from daft_tpu import feedback

        root = self._fb_root
        truncated = feedback.truncated_ids(root) if root is not None else set()
        with self._state_lock:
            obs = {nid: dict(rec) for nid, rec in self._fb_obs.items()}
            seqs = dict(self._profile_node_ids)
        out = []
        for nid, rec in sorted(obs.items(), key=lambda kv: kv[1]["node"]):
            seq = seqs.get(nid)
            out.append({
                "node": rec["node"],
                "op": rec["op"],
                "label": f"{rec['op']}#{seq}" if seq is not None else rec["op"],
                "est_rows": rec["est_rows"],
                "est_bytes": rec["est_bytes"],
                "rows": rec["rows"],
                "bytes": rec["bytes"],
                "exact": bool(rec["done"]) and bool(complete)
                and nid not in truncated,
            })
        return out

    def _fb_emit_correction(self, node, kind: str, estimated: float,
                            observed: float, action: str) -> None:
        """A runtime strategy switch driven by an estimate-vs-observation
        contradiction: metered, evented, never fatal."""
        try:
            from daft_tpu import metrics
            from daft_tpu.context import get_context
            from daft_tpu.subscribers.events import PlanCorrected

            metrics.PLAN_CORRECTED.labels(kind).inc()
            get_context().notify(PlanCorrected(
                query_id=self._ledger_qid,
                node=getattr(node, "_fb_fp", "") or type(node).__name__,
                kind=kind, estimated=float(estimated),
                observed=float(observed), action=action))
        except Exception:  # daftlint: disable=DTL002 -- observability, never a gate
            pass

    def _cancel_checked(self, op: str,
                        it: Iterator[MicroPartition]) -> Iterator[MicroPartition]:
        """Observe the query's cancel token at every morsel boundary: a
        cancelled/expired query fails out of the pull chain at the next
        morsel instead of running the plan to completion."""
        token = self.cancel_token
        for mp in it:
            token.check(op)
            yield mp

    def _profiled(self, node: pp.PhysicalPlan,
                  it: Iterator[MicroPartition]) -> Iterator[MicroPartition]:
        """One profiler span per operator iterator (profiling.py): wall and
        thread-CPU time per pull, rows/bytes out per morsel, plus spill /
        permit-wait / device-path tallies attributed through the ambient
        frame stack. The span opens at the FIRST pull and closes on
        exhaustion or abandonment (limit pushdown's GeneratorExit exits the
        context manager, so abandoned operators still export)."""
        prof = self.profiler
        op = type(node).__name__
        # Locked: first pulls race across the Prefetch/feeder threads, and
        # an unguarded read-then-write could hand two nodes one sequence
        # number (two spans labelled "Project#3").
        with self._state_lock:
            seq = self._profile_node_ids.setdefault(
                id(node), len(self._profile_node_ids))
        with prof.operator_span(op, f"{op}#{seq}") as frame:
            # Publish the frame for the node's stage workers: pipelined
            # operators time per-morsel work AT THE WORKER (run_timed),
            # and the frame then reports worker-side work as busy/cpu
            # while the consumer-side pull timing below degrades to wait
            # attribution (self_timed spans in profiling.py).
            self._op_frames[id(node)] = frame
            try:
                while True:
                    frame.begin_pull()
                    try:
                        mp = next(it)
                    except StopIteration:
                        return
                    finally:
                        frame.end_pull()
                    frame.add_output(len(mp), mp)
                    yield mp
            finally:
                self._op_frames.pop(id(node), None)

    def _instrumented(self, op: str, it: Iterator[MicroPartition]) -> Iterator[MicroPartition]:
        """Per-operator counters with EXCLUSIVE cpu attribution: each level
        subtracts its inclusive time from its parent (the op stack tracks the
        current pull chain, per thread), so summing operator cpu ~= query cpu
        on a serial chain; with parallel stages each thread's chain is
        attributed independently."""
        import time as _time

        from daft_tpu import metrics

        # Children resolved ONCE per operator iterator, not per morsel: the
        # hot loop below pays one method call + one lock-cheap add.
        morsels = metrics.MORSELS.labels(op)
        morsel_rows = metrics.MORSEL_ROWS.labels(op)
        stack = getattr(self._op_stacks, "stack", None)
        if stack is None:
            stack = self._op_stacks.stack = []
        while True:
            t0 = _time.perf_counter_ns()
            # Unique frame entry: identity-checked pop so adjacent same-named
            # operators (Project over Project) can never double-pop.
            entry = (object(), op)
            stack.append(entry)
            try:
                mp = next(it)
            except StopIteration:
                return
            finally:
                if stack and stack[-1] is entry:
                    stack.pop()
            dt = _time.perf_counter_ns() - t0
            morsels.inc()
            morsel_rows.inc(len(mp))
            self.stats.record(op, rows_out=len(mp), cpu_ns=dt)
            if stack:
                # Parent's timed region includes ours: remove the double count
                # and credit it with the rows flowing in.
                self.stats.record(stack[-1][1], rows_in=len(mp), cpu_ns=-dt)
            yield mp

    # -- sources ---------------------------------------------------------
    def _run_InMemorySource(self, node: pp.InMemorySource) -> Iterator[MicroPartition]:
        for p in node.partitions:
            yield p


    def _run_PhysicalScan(self, node: pp.PhysicalScan) -> Iterator[MicroPartition]:
        """Scan with the hot-scan-output cache tier in front: repeated
        scans of unchanged files (by mtime/size fingerprint) serve their
        morsel stream from memory instead of re-reading + re-decoding.
        The cached stream IS the fresh stream (same morsel boundaries),
        so everything downstream keyed on morsel boundaries — the PR 8
        determinism contract — is unaffected by hit-vs-miss."""
        cfg = self.cfg
        if not (getattr(cfg, "result_cache_enabled", True)
                and getattr(cfg, "result_cache_scan_outputs", True)) \
                or not node.scan_tasks \
                or not all(hasattr(t, "files") and hasattr(t, "pushdowns")
                           for t in node.scan_tasks) \
                or any(getattr(t, "ephemeral", False)
                       for t in node.scan_tasks):
            yield from self._scan_stream(node)
            return
        from daft_tpu import plancache
        from daft_tpu.execution.admission import current_tenant

        try:
            # The morsel width shapes the cached stream's boundaries (PR 8
            # determinism contract), so it is part of the key: a config
            # change re-reads rather than serving differently-shaped
            # morsels.
            key = "scan:" + plancache.fingerprint(
                self._scan_key_text(node)
                + f"\nmorsel={cfg.default_morsel_size}")
        except (AttributeError, TypeError, ValueError):
            # Unfingerprintable scan: read uncached (the cache is an
            # optimization, never a gate).
            yield from self._scan_stream(node)
            return
        cache = plancache.get_result_cache(cfg)
        outcome, payload = cache.lookup_or_claim(
            key, "scan", current_tenant(), token=self.cancel_token)
        if outcome == "hit":
            yield from payload.partitions
            return
        sources, roots = self._scan_sources(node)
        payload.set_provenance(sources, roots)
        try:
            for mp in self._scan_stream(node):
                payload.add(mp)
                yield mp
            # Full drain only: an abandoned scan (limit pushdown, error
            # downstream) aborts in the finally — never a partial entry.
            payload.commit()
        finally:
            payload.abort()

    @staticmethod
    def _scan_key_text(node: pp.PhysicalScan) -> str:
        parts = []
        for t in node.scan_tasks:
            pd = t.pushdowns
            filt = pd.filters.key() if pd.filters is not None else None
            ro = sorted((k, repr(v)) for k, v in t.read_options.items()
                        if k != "io_config")
            files = ",".join(
                f"{f.path}:{f.size_bytes}:{f.partition_values}"
                for f in t.files)
            parts.append(f"{t.file_format};cols={pd.columns};"
                         f"limit={pd.limit};shard={pd.shard};filt={filt};"
                         f"opts={ro};files={files}")
        parts.append(f"schema={node.schema.column_names()}")
        return "\n".join(parts)

    @staticmethod
    def _scan_sources(node: pp.PhysicalScan):
        from daft_tpu.plancache import file_fingerprint

        sources, roots = [], []
        for t in node.scan_tasks:
            for f in t.files:
                roots.append(f.path)
                sources.append(file_fingerprint(f.path, f.size_bytes))
        return sources, roots

    def _scan_stream(self, node: pp.PhysicalScan) -> Iterator[MicroPartition]:
        from daft_tpu.io.formats import read_scan_task

        tasks = node.scan_tasks
        if not tasks:
            yield MicroPartition.empty(node.schema)
            return
        morsel_rows = self.cfg.default_morsel_size
        if len(tasks) == 1:
            yield from read_scan_task(tasks[0], morsel_rows)
            return
        # Parallel prefetch with per-task bounded queues; yield in task order.
        # Readers poll a stop flag so an abandoned consumer (error in another
        # task, early generator close) can't leave them blocked on a full
        # queue, which would hang interpreter exit on non-daemon pool threads.
        queues: List[queue.Queue] = [queue.Queue(maxsize=4) for _ in tasks]
        stop = threading.Event()
        pool = ThreadPoolExecutor(max_workers=min(self.num_io_threads, len(tasks)),
                                  thread_name_prefix="daft-scan")

        def put_or_stop(q, item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def reader(task, q):
            try:
                for mp in read_scan_task(task, morsel_rows):
                    if not put_or_stop(q, mp):
                        return
                put_or_stop(q, _SENTINEL)
            except BaseException as e:  # noqa: BLE001
                put_or_stop(q, e)

        # Reader threads inherit the caller's contextvars (per-query frozen
        # clock etc.) — a bare Thread/pool task starts with an empty context.
        ambient = contextvars.copy_context()
        try:
            for task, q in zip(tasks, queues):
                pool.submit(ambient.copy().run, reader, task, q)
            for q in queues:
                while True:
                    item = q.get()
                    if item is _SENTINEL:
                        break
                    if isinstance(item, BaseException):
                        # `from item` preserves the cause chain, which is how
                        # the distributed dispatcher classifies transiency
                        # (scheduler.is_transient_failure walks __cause__) —
                        # the user-facing type stays DaftExecutionError.
                        raise DaftExecutionError(f"Scan failed: {item}") from item
                    yield item
        finally:
            stop.set()
            pool.shutdown(wait=False, cancel_futures=True)

    def _run_ShuffleReadSource(self, node) -> Iterator[MicroPartition]:
        entries = getattr(node, "entries", None)
        if entries is not None:
            # Streaming reduce-side shuffle input (distributed/shuffle.py):
            # the reader's pipelined prefetch overlaps chunk fetch with
            # whatever this executor computes downstream, its merge order
            # is a pure function of the ticket list (PR 8 byte-identity
            # contract), and fetch backlogs spill under THIS executor's
            # memory permits.
            from daft_tpu.distributed.shuffle import ShuffleReader

            yield from ShuffleReader(entries, node.schema, cfg=self.cfg,
                                     memory=self.memory,
                                     token=self.cancel_token,
                                     profiler=self.profiler)
            return
        for ref in node.partition_refs:
            yield ref.fetch()

    # -- intermediate (streaming) ops ------------------------------------
    def _stage_frame(self, node):
        """The node's live profiler _OpFrame (None when unprofiled) — the
        worker-side timing hook pipeline stages thread through run_timed."""
        return self._op_frames.get(id(node))

    def _node_timed(self, node, fn, *args):
        """Run a sink-side kernel (partial merge, finalize) under the
        node's frame so its work is attributed even though it executes
        outside the stage workers."""
        frame = self._stage_frame(node)
        if frame is None:
            return fn(*args)
        return frame.run_timed(lambda _: fn(*args), None)

    def _streaming_map(self, node, fn, *, split: bool = True,
                       ordered: Optional[bool] = None,
                       source: Optional[Iterator[MicroPartition]] = None
                       ) -> Iterator[MicroPartition]:
        """Pipelined per-morsel map: the node becomes a stage fed by a
        bounded morsel queue and driven by the shared compute pool. The
        input is morselized at BOTH thread counts (split oversized,
        coalesce undersized) so the morsel sequence — and every
        downstream boundary keyed on it — is identical at
        num_compute_threads=1 and =N; only scheduling changes. Ordered
        unless the plan waived order (default_maintain_order=False).
        ``source`` substitutes a pre-built child iterator (the hash join
        passes its prefetched probe stream)."""
        it = source if source is not None else self._run(node.children[0])
        if split:
            it = morselize(it, self.min_morsel_rows, self.max_morsel_rows)
        if ordered is None:
            ordered = getattr(self.cfg, "default_maintain_order", True)
        yield from map_stage(
            it, fn, pool=self._pool(), workers=self.compute_threads,
            name=type(node).__name__, ordered=ordered,
            timer=self._stage_frame(node),
            ledger=self._stage_ledger(type(node).__name__))

    def _run_Project(self, node: pp.Project) -> Iterator[MicroPartition]:
        yield from self._run_relational_chain(node)

    def _run_Filter(self, node: pp.Filter) -> Iterator[MicroPartition]:
        yield from self._run_relational_chain(node)

    # -- stage + kernel fusion -------------------------------------------
    @staticmethod
    def _node_kernel(nd):
        """The interpreted per-morsel kernel for one Project/Filter node."""
        if isinstance(nd, pp.Filter):
            return lambda mp: mp.filter(nd.predicate)
        return lambda mp: mp.eval_expression_list(nd.exprs)

    def _collect_stage_chain(self, head) -> List[pp.PhysicalPlan]:
        """The maximal Project/Filter chain rooted at ``head``, top-first.

        Fusion decisions are a PURE function of plan + config — never
        thread count — preserving the determinism contract. The chain
        stops at shared subtrees (their output must materialize once at
        that boundary for every parent)."""
        if not getattr(self.cfg, "stage_fusion_enabled", True):
            return [head]
        nodes = [head]
        shared = getattr(self, "_shared_ids", ())
        cur = head
        while True:
            child = cur.children[0]
            if not isinstance(child, (pp.Project, pp.Filter)) \
                    or id(child) in shared:
                return nodes
            nodes.append(child)
            cur = child

    @staticmethod
    def _chain_steps(nodes) -> List[tuple]:
        """(kind, payload) steps in EXECUTION (bottom-up) order for a
        top-first node chain."""
        steps = []
        for nd in reversed(nodes):
            if isinstance(nd, pp.Filter):
                steps.append(("filter", nd.predicate))
            else:
                steps.append(("project", list(nd.exprs)))
        return steps

    def _member_frames(self, stack, members) -> Dict[int, object]:
        """Open one profiler operator span per fused member node for the
        stage's lifetime, so fused chains stay per-plan-node attributable:
        interpreted fallback kernels time under their own node's frame,
        and every fused-away operator still exports a span."""
        frames: Dict[int, object] = {}
        if self.profiler is None:
            return frames
        for nd in members:
            op = type(nd).__name__
            with self._state_lock:
                seq = self._profile_node_ids.setdefault(
                    id(nd), len(self._profile_node_ids))
            frames[id(nd)] = stack.enter_context(
                self.profiler.operator_span(op, f"{op}#{seq}"))
        return frames

    def _compiled_suffix(self, nodes, steps, out_schema):
        """The longest compilable SUFFIX of a bottom-up step chain (real
        plans often carry an untraceable prefix — the cast-projection off a
        64-bit source): returns ``(k, spec)`` where steps[:k] stay
        interpreted and steps[k:] run as one program, or ``(0, None)``.
        Pure plan+config, like every other fusion decision."""
        from daft_tpu.ops import compiled_eval

        exec_order = list(reversed(nodes))  # exec_order[i] produced steps[i]
        tail = nodes[-1]
        for k in range(len(steps)):
            input_schema = tail.children[0].schema if k == 0 \
                else exec_order[k - 1].schema
            spec = compiled_eval.build_chain_spec(
                steps[k:], input_schema, out_schema, self.cfg)
            if spec is not None:
                return k, spec
        return 0, None

    def _run_relational_chain(self, head) -> Iterator[MicroPartition]:
        """Fused Project/Filter execution: adjacent streaming stages
        collapse into ONE composed morsel stage (a chain costs one queue
        hop instead of N — the PR 8 hop tax), and the longest traceable
        suffix of the chain (ops/compiled_eval.py) runs each morsel as a
        single jitted XLA program with interpreted per-step fallback."""
        import contextlib

        from daft_tpu import metrics
        from daft_tpu.execution.pipeline import map_stage

        nodes = self._collect_stage_chain(head)
        steps = self._chain_steps(nodes)
        split, spec = self._compiled_suffix(nodes, steps, head.schema)
        if len(nodes) == 1:
            # Single stage: previous behavior, plus the compiled path for
            # one-node "chains" (a lone big Filter still wins by tracing).
            kern = self._node_kernel(head)
            if spec is None:
                yield from self._streaming_map(head, kern)
                return

            def one(mp: MicroPartition) -> MicroPartition:
                out = spec.run_morsel(mp)
                return out if out is not None else kern(mp)

            yield from self._streaming_map(head, one)
            return
        metrics.STAGE_FUSIONS.inc(len(nodes) - 1)
        members = nodes[1:]
        exec_order = list(reversed(nodes))  # bottom-up kernels
        kernels = [(nd, self._node_kernel(nd)) for nd in exec_order]
        tail = nodes[-1]
        with contextlib.ExitStack() as stack:
            frames = self._member_frames(stack, members)

            def run_step(nd, kern, mp, head_frame):
                if nd is head:
                    # The head's add_output happens at the consumer
                    # (_profiled); only time the kernel here.
                    return kern(mp) if head_frame is None \
                        else head_frame.run_timed(kern, mp)
                frame = frames.get(id(nd))
                if frame is None:
                    return kern(mp)
                out = frame.run_timed(kern, mp)
                frame.add_worker_output(len(out), out)
                return out

            def composed(mp: MicroPartition) -> MicroPartition:
                head_frame = self._stage_frame(head)
                for nd, kern in kernels[:split]:
                    mp = run_step(nd, kern, mp, head_frame)
                if spec is not None:
                    run = spec.run_morsel
                    out = run(mp) if head_frame is None \
                        else head_frame.run_timed(run, mp)
                    if out is not None:
                        return out
                for nd, kern in kernels[split:]:
                    mp = run_step(nd, kern, mp, head_frame)
                return mp

            it = morselize(self._run(tail.children[0]),
                           self.min_morsel_rows, self.max_morsel_rows)
            ordered = getattr(self.cfg, "default_maintain_order", True)
            yield from map_stage(
                it, composed, pool=self._pool(),
                workers=self.compute_threads,
                name=type(head).__name__, ordered=ordered,
                ledger=self._stage_ledger(type(head).__name__))

    def _run_Explode(self, node: pp.Explode) -> Iterator[MicroPartition]:
        names = [e.name() for e in node.to_explode]
        ignore = getattr(node, "ignore_empty_and_null", False)
        for mp in self._run(node.children[0]):
            yield mp.explode(names, ignore_empty_and_null=ignore)

    def _run_Unpivot(self, node: pp.Unpivot) -> Iterator[MicroPartition]:
        id_names = [e.name() for e in node.ids]
        val_names = [e.name() for e in node.values]
        for mp in self._run(node.children[0]):
            out = [b.unpivot(id_names, val_names, node.variable_name, node.value_name)
                   for b in mp.record_batches()]
            yield MicroPartition(node.schema, out)

    def _run_Sample(self, node: pp.Sample) -> Iterator[MicroPartition]:
        if node.size is not None:
            combined = MicroPartition.concat(list(self._run(node.children[0])))
            yield combined.sample(size=node.size, with_replacement=node.with_replacement,
                                  seed=node.seed)
            return
        seed = node.seed
        for i, mp in enumerate(self._run(node.children[0])):
            yield mp.sample(fraction=node.fraction, with_replacement=node.with_replacement,
                            seed=None if seed is None else seed + i)

    def _run_MonotonicallyIncreasingId(self, node) -> Iterator[MicroPartition]:
        # id = (partition_index << 36) | row_in_partition (reference:
        # ops/monotonically_increasing_id.rs bit layout).
        offset = 0
        part_hi = np.uint64((self.partition_offset + node.partition_offset) << 36)
        for mp in self._run(node.children[0]):
            rb = mp.combined()
            ids = part_hi | np.arange(offset, offset + len(rb), dtype=np.uint64)
            offset += len(rb)
            id_col = Series.from_numpy(ids, node.column_name)
            cols = [id_col] + rb.columns()
            out = RecordBatch(node.schema, cols, len(rb))
            yield MicroPartition(node.schema, [out])

    def _run_UDFProject(self, node: pp.UDFProject) -> Iterator[MicroPartition]:
        from daft_tpu.expressions.expr import UdfCall

        udf = None
        for n in node.udf_expr.walk():
            if isinstance(n, UdfCall):
                udf = n.udf
                break
        concurrency = max(1, getattr(udf, "max_concurrency", None) or 1)
        # chips_per_replica: partition visible chips into replica slots; each
        # concurrent morsel evaluation owns one slot's ICI mesh slice
        # (reference: gpus_per_actor on the vLLM expr + GPU-slot pinning in
        # intermediate_ops/udf.rs:391-406; SURVEY §7.8).
        slots = None
        cpr = getattr(udf, "chips_per_replica", None)
        if cpr:
            from daft_tpu.parallel.replica import ReplicaSlots

            slots = ReplicaSlots(cpr)
            if getattr(udf, "max_concurrency", None) is None:
                concurrency = slots.num_replicas
            else:
                concurrency = min(concurrency, slots.num_replicas)
        exprs = node.passthrough + [node.udf_expr]
        # Re-morselize so oversized in-memory partitions don't reach the UDF
        # as one giant batch (bounds host memory + enables replica
        # concurrency). A UDF with a declared device batch_size gets morsels
        # of 16 device-batches — enough chunks for async transfer/compute
        # overlap inside the impl without unbounded host buffers — or, where
        # it declares a host stage (Udf.host_stage), of one: the overlap is
        # then between morsels, and what runs ahead is held a morsel at a
        # time. Host UDFs with no device batch shape instead follow the
        # latency-constrained feedback loop (execution/dynamic_batching.py).
        from daft_tpu.execution.pipeline import split_morsels

        udf_bs = getattr(udf, "batch_size", None)
        host_stage = getattr(udf, "host_stage", None) \
            if udf_bs and concurrency == 1 and slots is None else None
        batch_state = None
        if udf_bs:
            morsel_rows = min(udf_bs * (1 if host_stage else 16), self.cfg.default_morsel_size)
            child_iter = split_morsels(self._run(node.children[0]), morsel_rows)
        elif getattr(self.cfg, "udf_dynamic_batching", False) and slots is None:
            from daft_tpu.execution.dynamic_batching import (
                LatencyConstrainedBatching,
                dynamic_remorsel,
            )

            batch_state = LatencyConstrainedBatching(
                target_latency_s=self.cfg.udf_target_batch_latency_s,
                b_max=self.cfg.default_morsel_size).make_state()
            child_iter = dynamic_remorsel(self._run(node.children[0]), batch_state)
        else:
            child_iter = split_morsels(self._run(node.children[0]),
                                       self.cfg.default_morsel_size)
        if batch_state is None:
            eval_mp = (lambda mp: slots.run(mp.eval_expression_list, exprs)) if slots \
                else (lambda mp: mp.eval_expression_list(exprs))
        else:
            import time as _time

            def eval_mp(mp):
                t0 = _time.perf_counter()
                out = mp.eval_expression_list(exprs)
                batch_state.record(len(mp), _time.perf_counter() - t0)
                return out
        from daft_tpu.profiling import device_span

        frame = self._stage_frame(node)

        def pulled(it):
            # Each pull of the child as a span; it is closed before the
            # morsel is handed on (a generator never yields inside a span).
            # With a host stage the pull is on its feeder's thread, where the
            # span still names this operator.
            it = iter(it)
            named = contextlib.nullcontext if frame is None or host_stage is None else frame.attributing
            while True:
                with named(), device_span("udf.pull") as sp:
                    mp = next(it, None)
                    if mp is not None:
                        sp.count["rows"] = len(mp)
                if mp is None:
                    return
                yield mp

        def call_mp(mp, prepared=None):
            # The root that one morsel's device-path spans name as their cause.
            with device_span("udf.call", rows=len(mp)):
                return eval_mp(mp) if host_stage is None else device_stage(mp, prepared)

        child_iter = pulled(child_iter)
        if concurrency == 1:
            # One loop: a UDF with no host stage is handed each morsel as it is
            # pulled, on this thread; one with a host stage is handed morsels
            # whose host stage has run ahead (_host_stage_ahead).
            if host_stage is None:
                ahead = ((mp, None) for mp in child_iter)
            else:
                device_stage, ahead = self._host_stage_ahead(node, udf, child_iter)
            with contextlib.closing(ahead):
                for mp, prepared in ahead:
                    yield call_mp(mp, prepared)
            return
        # Ordered stage over morsels (actor-pool analogue). UDFs get their
        # OWN pool: replica-slot acquisition can block a worker, which
        # must never starve the shared relational compute pool.
        from daft_tpu.execution.pipeline import run_stage

        udf_pool = ThreadPoolExecutor(max_workers=concurrency,
                                      thread_name_prefix="daft-udf")
        yield from run_stage(child_iter, call_mp, pool=udf_pool,
                             workers=concurrency, name="UDFProject",
                             owns_pool=True, timer=self._stage_frame(node),
                             ledger=self._stage_ledger("UDFProject"))

    #: Host-stage workers of one UDF operator: enough to decode two or three
    #: times faster than one thread and so hand the pace to the chip, few
    #: enough that what is prepared ahead (twice this many morsels) stays small.
    HOST_STAGE_WORKERS = 4
    #: Morsels whose input may lie on the device ahead of the one that runs.
    STAGED_AHEAD = 2

    def _host_stage_ahead(self, node: pp.UDFProject, udf, child_iter):
        """-> (device stage, iterator of ``(morsel, prepared)``) for a UDF that
        declares a host stage (``Udf.host_stage``).

        ``udf.host_stage`` runs over the pulled morsels as an ordered
        ``run_stage`` on a pool of this operator's own (span ``udf.host_stage``,
        on the worker); ``udf.transfer`` runs on the one thread of a
        ``Prefetch`` over its results, so in morsel order and at most
        ``STAGED_AHEAD`` morsels ahead of the one the device stage holds; the
        iterator hands them to the operator's thread, which waits for each
        under the span ``udf.wait`` (``ready`` = 1 where it was prepared before
        it was asked for). A failure in either reaches the consumer in its
        morsel's place, as ``Udf.evaluate`` would have raised it; closing the
        iterator releases both threads' work and drops what was staged.

        The device stage evaluates the operator's expressions with the UDF's
        call replaced by its result, ``udf.evaluate`` over the arguments the
        host stage computed and ``prepared``: nothing is evaluated twice."""
        from daft_tpu.execution.pipeline import Prefetch, run_stage
        from daft_tpu.expressions.expr import ColumnRef, UdfCall
        from daft_tpu.profiling import device_span

        call = next(n for n in node.udf_expr.walk() if isinstance(n, UdfCall))
        result = "__udf_result__"
        exprs = node.passthrough + [node.udf_expr.transform(
            lambda n: ColumnRef(result) if isinstance(n, UdfCall) and n.udf is udf else None)]
        frame = self._stage_frame(node)
        timed = (lambda fn, x: fn(x)) if frame is None else frame.run_timed

        def failing_as_the_call(fn, *args):
            try:
                return fn(*args)
            except Exception as e:  # noqa: BLE001 -- as Udf.evaluate wraps a failing fn
                raise DaftExecutionError(f"UDF {udf.name!r} failed in its host stage: {e}") from e

        host_stage, to_device = udf.host_stage, udf.transfer

        def host(mp):
            with device_span("udf.host_stage", rows=len(mp)):
                rb = mp.combined()
                args = [evaluate(a, rb) for a in call.args]
                return rb, args, failing_as_the_call(host_stage, *args)

        def transfer(item):
            rb, args, batch = item
            return rb, args, failing_as_the_call(to_device, batch)

        def device_stage(item):
            rb, args, prepared = item
            res = udf.evaluate(args, dict(call.kwargs, prepared=prepared)).rename(result)
            schema = Schema(list(rb.schema) + [Field(result, res.dtype)])
            out = RecordBatch(schema, rb.columns() + [res], len(rb)).eval_expression_list(exprs)
            return MicroPartition(out.schema, [out])

        workers = max(1, min(self.HOST_STAGE_WORKERS, int(udf.cpus or self.compute_threads)))

        def ahead():
            pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="daft-udf-host")
            hosted = run_stage(child_iter, host, pool=pool, workers=workers, name="UDFHostStage",
                               owns_pool=True, timer=frame)
            staged = Prefetch((timed(transfer, item) for item in hosted),
                              capacity=self.STAGED_AHEAD - 1, name="udf-transfer")
            it = iter(staged)
            try:
                while True:
                    with device_span("udf.wait") as sp:
                        sp.count["ready"] = int(staged.ready())
                        item = next(it, None)
                        if item is not None:
                            sp.count["rows"] = len(item[0])
                    if item is None:
                        return
                    yield item[0], item
            finally:
                # The transfer in flight ends, then the host stages in flight:
                # no thread of this operator outlives it, and no staged batch.
                staged.close(wait_s=2.0)
                with contextlib.suppress(ValueError):  # its thread is still in a pull that outlasted the wait
                    hosted.close()
                pool.shutdown(wait=True, cancel_futures=True)

        return (lambda rb, item: timed(device_stage, item)), ahead()

    # -- streaming sinks --------------------------------------------------
    def _run_Limit(self, node: pp.Limit) -> Iterator[MicroPartition]:
        to_skip = node.offset
        remaining = node.limit
        for mp in self._run(node.children[0]):
            if to_skip > 0:
                n = len(mp)
                if n <= to_skip:
                    to_skip -= n
                    continue
                mp = mp.slice(to_skip, n - to_skip)
                to_skip = 0
            if remaining <= 0:
                break
            if len(mp) > remaining:
                mp = mp.head(remaining)
            remaining -= len(mp)
            yield mp
            if remaining <= 0:
                break

    # -- blocking sinks ---------------------------------------------------
    def _collect(self, node: pp.PhysicalPlan,
                 source: Optional[Iterator[MicroPartition]] = None,
                 op: Optional[str] = None) -> MicroPartition:
        """Materialise a blocking-sink input under memory permits
        (reference: resource_manager.rs memory manager + DAFT_MEMORY_LIMIT).
        ``op`` is the memory-ledger attribution — the SINK doing the
        buffering (callers pass their own name; the default blames the
        collected node, which is the sink itself on most paths)."""
        parts = []
        limit = self.memory.limit
        gate_on = limit is not None
        op = op or type(node).__name__
        for mp in (source if source is not None else self._run(node)):
            nbytes = mp.size_bytes()
            # Permits bound memory across CONCURRENT executors (distributed
            # workers); within one oversized blocking sink they degrade to
            # best-effort. After the first failed acquire the gate disengages
            # for this sink — the only releaser is this executor at query end,
            # so further waits are pure self-deadlock stalls.
            if gate_on and self._held_bytes < limit:
                if self.memory.acquire(nbytes, timeout=5.0,
                                       token=self.cancel_token):
                    self._add_held(min(nbytes, limit), op=op)
                else:
                    gate_on = False
            parts.append(mp)
        if not parts:
            return MicroPartition.empty(node.schema)
        return MicroPartition.concat(parts)

    def _run_Sort(self, node: pp.Sort) -> Iterator[MicroPartition]:
        budget = self._sink_budget()
        if budget is None:
            combined = self._collect(node.children[0], op="Sort")
            yield combined.sort(node.sort_by, node.descending, node.nulls_first)
            return
        # Out-of-core: sorted-run generation + k-way streaming merge.
        from daft_tpu.execution.spill import ExternalSort, budget_reservation

        with budget_reservation(self.memory, budget, token=self.cancel_token,
                                op="Sort"):
            state = ExternalSort(node.sort_by, node.descending, node.nulls_first,
                                 node.schema, budget, self._spill(),
                                 morsel_rows=self.cfg.default_morsel_size)
            for mp in self._run(node.children[0]):
                state.add(mp)
            yield from state.results()

    def _run_TopN(self, node: pp.TopN) -> Iterator[MicroPartition]:
        k = node.limit + node.offset
        buffer: Optional[RecordBatch] = None
        for mp in self._run(node.children[0]):
            rb = mp.combined()
            buffer = rb if buffer is None else RecordBatch.concat([buffer, rb])
            if len(buffer) > 4 * max(k, 1):
                buffer = self._topk(buffer, node, k)
        if buffer is None:
            yield MicroPartition.empty(node.schema)
            return
        buffer = self._topk(buffer, node, k)
        yield MicroPartition(node.schema, [buffer.slice(node.offset, node.limit)])

    def _topk(self, rb: RecordBatch, node, k: int) -> RecordBatch:
        keys = [evaluate(e, rb) for e in node.sort_by]
        return rb.sort(keys, node.descending, node.nulls_first).head(k)

    #: Rows per parallel partial-aggregation chunk. Smaller than AggState's
    #: flush threshold so chunk partials actually spread across a handful
    #: of workers (one 1M-row chunk would serialize a 1.3M-row groupby);
    #: FIXED so float partial-sum association never depends on thread
    #: count — chunk boundaries are part of the determinism contract.
    AGG_CHUNK_ROWS = 256 * 1024

    def _run_Aggregate(self, node: pp.Aggregate) -> Iterator[MicroPartition]:
        budget = self._sink_budget()

        def fresh_state() -> AggState:
            return AggState(node.agg_exprs, node.group_by, node.schema,
                            input_schema=node.children[0].schema)

        if budget is None:
            # In-memory path: the blocking sink consumes its upstream IN
            # PARALLEL (chunked partials or hash-partitioned buckets).
            yield from self._pipelined_agg(node, fresh_state)
            return
        state = fresh_state()
        if not node.group_by:
            # Global aggs reduce to O(1) MERGED state, but raw morsels buffer
            # by row count — under a budget, compress eagerly so raw buffers
            # never exceed it (no disk needed: the partial state is ~1 row).
            for mp in self._run(node.children[0]):
                state.accumulate(mp)
                if state.approx_size_bytes() > budget:
                    state.partial_batches()  # flush raw + merge in place
            yield MicroPartition(node.schema, [state.finalize()])
            return
        yield from self._grace_grouped_agg(
            self._run(node.children[0]), fresh_state, budget, node.schema,
            ingest=lambda st, mp: st.accumulate(mp))

    def _pipelined_agg(self, node: pp.Aggregate,
                       fresh_state) -> Iterator[MicroPartition]:
        """Parallel in-memory aggregation with a cardinality-adaptive
        strategy, structured identically at every thread count:

        * the input is morselized and packed into row-chunks at AggState's
          flush threshold (pure functions of the stream);
        * the FIRST chunk's partial aggregation measures group reduction;
        * low-cardinality aggs partial-aggregate the remaining chunks on
          the compute pool and merge partials in chunk order (each group's
          per-chunk sums associate at fixed chunk boundaries);
        * high-cardinality aggs (partials barely shrink, so a merge pass
          would nearly double the work) hash-partition instead.
        """
        import contextlib
        import itertools

        state: AggState = fresh_state()
        plan = state.plan
        # Global (no-group-by) aggs can absorb the Filter/Project chain
        # below them: the whole filter→project→partial-agg pipeline
        # compiles into ONE jitted program per chunk (ops/compiled_eval),
        # eliminating even the chain's single fused stage hop. Pure
        # plan+config eligibility; ineligible plans keep the normal
        # stage-fed path.
        from daft_tpu.ops import compiled_eval

        agg_spec = None
        agg_split = 0
        chain_nodes: List[pp.PhysicalPlan] = []
        cur = node.children[0]
        if not plan.group_by:
            # Chain absorption collapses stages, so it honors the stage-
            # fusion off switch; with fusion disabled only the bare
            # partial-reduction program (empty chain) may still compile.
            if getattr(self.cfg, "stage_fusion_enabled", True):
                shared = getattr(self, "_shared_ids", ())
                while isinstance(cur, (pp.Project, pp.Filter)) \
                        and id(cur) not in shared:
                    chain_nodes.append(cur)
                    cur = cur.children[0]
            steps = self._chain_steps(chain_nodes)
            exec_order = list(reversed(chain_nodes))
            partial_schema = state.partial_schema(node.children[0].schema)
            # Longest compilable suffix, like _compiled_suffix — k may
            # reach len(steps): a bare partial-reduction program still
            # fuses the agg even when the whole chain stays interpreted.
            for k in range(len(steps) + 1):
                input_schema = cur.schema if k == 0 \
                    else exec_order[k - 1].schema
                agg_spec = compiled_eval.build_agg_chain_spec(
                    steps[k:], plan, input_schema, partial_schema, self.cfg)
                if agg_spec is not None:
                    agg_split = k
                    break
        with contextlib.ExitStack() as stack:
            if agg_spec is not None:
                frames = self._member_frames(stack, chain_nodes)
                source = self._run(cur)
            else:
                frames = {}
                source = self._run(node.children[0])
            it = morselize(source, self.min_morsel_rows,
                           self.max_morsel_rows)
            chunks = chunk_morsels(it, self.AGG_CHUNK_ROWS)
            first = next(chunks, None)
            if first is None:
                yield MicroPartition(node.schema, [state.finalize()])
                return

            chain_kernels = [(nd, self._node_kernel(nd))
                             for nd in reversed(chain_nodes)]

            def run_chain_step(nd, kern, mp):
                frame = frames.get(id(nd))
                if frame is None:
                    return kern(mp)
                out = frame.run_timed(kern, mp)
                frame.add_worker_output(len(out), out)
                return out

            def partial_of(chunk: List[MicroPartition]) -> RecordBatch:
                rb = RecordBatch.concat(
                    [b for mp in chunk for b in mp.record_batches()])
                if agg_spec is not None:
                    # Interpreted prefix (untraceable bottom steps), then
                    # the compiled suffix as one program per chunk.
                    mp = MicroPartition(cur.schema, [rb])
                    for nd, kern in chain_kernels[:agg_split]:
                        mp = run_chain_step(nd, kern, mp)
                    rb = mp.combined()
                    out = agg_spec.run_chunk(rb)
                    if out is not None:
                        return out
                    # Data-driven fallback: finish the suffix interpreted,
                    # timed under each node's frame.
                    mid_schema = cur.schema if agg_split == 0 \
                        else chain_kernels[agg_split - 1][0].schema
                    mp = MicroPartition(mid_schema, [rb])
                    for nd, kern in chain_kernels[agg_split:]:
                        mp = run_chain_step(nd, kern, mp)
                    rb = mp.combined()
                return rb.agg(plan.partial_exprs, plan.group_by)

            yield from self._pipelined_agg_body(
                node, fresh_state, state, plan, first, chunks, partial_of)

    def _pipelined_agg_body(self, node, fresh_state, state, plan, first,
                            chunks, partial_of) -> Iterator[MicroPartition]:
        import itertools

        if plan.group_by:
            # Cardinality probe on the FIRST MORSEL only (bounded waste —
            # probing a whole chunk would hash-aggregate 2x the chunk on
            # the high-cardinality path). Data-driven, so every thread
            # count takes the same branch.
            probe = partial_of(first[:1])
            threshold = self.cfg.high_cardinality_aggregation_threshold
            if len(probe) > len(first[0]) * threshold:
                # The first-chunk probe contradicted the planner's grouped-
                # cardinality estimate (PR 8's adaptive switch) — surface
                # the correction on the feedback plane. The switch itself
                # stays purely data-driven: emission never gates it.
                if self._fb_observe:
                    self._fb_emit_correction(
                        node, kind="agg-partition",
                        estimated=getattr(node, "_est_rows", 0.0) or 0.0,
                        observed=float(len(probe)),
                        action="switched to partitioned aggregation")
                yield from self._partitioned_agg(
                    node, fresh_state, itertools.chain([first], chunks))
                return
        # add_partial defers merging to ONE pass at finalize — the
        # incremental threshold merge would re-aggregate the whole merged
        # state once per chunk as soon as it outgrows the threshold.
        for partial in map_stage(itertools.chain([first], chunks), partial_of,
                                 pool=self._pool(),
                                 workers=self.compute_threads,
                                 name="AggPartial",
                                 timer=self._stage_frame(node),
                                 ledger=self._stage_ledger("Aggregate")):
            state.add_partial(partial)
        yield MicroPartition(node.schema,
                             [self._node_timed(node, state.finalize)])

    def _partitioned_agg(self, node: pp.Aggregate, fresh_state,
                         chunks) -> Iterator[MicroPartition]:
        """High-cardinality grouped aggregation: hash-partition each chunk
        by group key into one bucket per worker, then aggregate every
        bucket SINGLE-SHOT in parallel. A group's rows land whole in one
        bucket with input order preserved (stable partitioning), so
        per-group float accumulation order — and thus every sum — is
        identical at any worker count; only output ROW order varies with
        the bucket count, and grouped output order is unspecified
        engine-wide."""
        buckets_n = max(self.compute_threads, 1)

        def split_chunk(chunk: List[MicroPartition]) -> List[RecordBatch]:
            rb = RecordBatch.concat(
                [b for mp in chunk for b in mp.record_batches()])
            keys = [evaluate(g, rb) for g in node.group_by]
            parts = self._cheap_int_partition(rb, keys, buckets_n)
            if parts is not None:
                return parts
            return rb.partition_by_hash(keys, buckets_n)

        buckets: List[List[RecordBatch]] = [[] for _ in range(buckets_n)]
        for parts in map_stage(chunks, split_chunk, pool=self._pool(),
                               workers=self.compute_threads,
                               name="AggPartition",
                               timer=self._stage_frame(node),
                               ledger=None):  # lists, not morsels
            for i, rb in enumerate(parts):
                if len(rb):
                    buckets[i].append(rb)

        def agg_bucket(rbs: List[RecordBatch]) -> RecordBatch:
            st: AggState = fresh_state()
            if rbs:
                rb = rbs[0] if len(rbs) == 1 else RecordBatch.concat(rbs)
                # One partial pass over the whole bucket (bypassing the
                # incremental flush threshold keeps per-group association
                # a single in-order arrow pass, invariant to bucket count).
                st.accumulate_partial(
                    rb.agg(st.plan.partial_exprs, st.plan.group_by))
            return st.finalize()

        for out in collect_parallel(buckets, agg_bucket, pool=self._pool(),
                                    workers=self.compute_threads,
                                    timer=self._stage_frame(node)):
            if len(out):
                yield MicroPartition(node.schema, [out])

    @staticmethod
    def _cheap_int_partition(rb: RecordBatch, keys,
                             n_buckets: int) -> Optional[List[RecordBatch]]:
        """Bucket rows on a SINGLE int-like group key with one vector
        multiply-shift and per-bucket mask filters — ~2x cheaper than the
        generic row-hash + stable-sort partitioner for the small bucket
        counts the partitioned aggregation uses. Order within a bucket is
        input order (pc.filter is stable), which is the property the
        float-determinism contract rests on; None defers to the generic
        path. Bucket assignment depends only on key values (thread count
        enters only through the modulus — and per-GROUP rows stay whole
        in one bucket for any modulus)."""
        from daft_tpu.execution.join_index import _key_values

        if len(keys) != 1:
            return None
        kv = _key_values(keys[0])  # the ONE int-like-key eligibility rule
        if kv is None:
            return None
        vals, mask = kv
        # Eligibility must be DTYPE-only, never data-dependent: chunks of
        # one aggregation that disagreed on the bucket function would
        # split a group across buckets (duplicate output rows). Bucketing
        # needs no order preservation, so any int width maps through a
        # plain wrap-around uint64 cast — identical for every chunk.
        if vals.dtype.kind == "M":
            h = vals.view(np.int64).astype(np.uint64)
        else:
            h = vals.astype(np.uint64)
        # Fibonacci multiplicative hash: one multiply + shift scrambles
        # strided key sets (all-even keys etc.) that a bare modulo clumps.
        h = (h * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(17)
        ids = (h % np.uint64(n_buckets)).astype(np.int64)
        if mask is not None:
            ids[mask] = 0  # null group rows all land in bucket 0
        return [rb.filter(Series.from_numpy(ids == b, "m"))
                for b in range(n_buckets)]

    def _grace_grouped_agg(self, items, fresh_state, budget, schema,
                           ingest, op: str = "Aggregate"
                           ) -> Iterator[MicroPartition]:
        """Grace aggregation: whenever the merged partial state outgrows the
        budget, hash-partition it by group key into disk buckets; each
        bucket is then merged + finalized independently (keys of one group
        land in exactly one bucket, so per-bucket finalize is exact).
        ``ingest`` feeds one input item into the state — raw morsels for the
        single-phase Aggregate, partial batches for the distributed
        AggregateFinal."""
        from daft_tpu.execution.spill import GracePartitioner, budget_reservation

        state: AggState = fresh_state()
        key_names = state.plan.key_names
        grace: Optional[GracePartitioner] = None

        def spill_state(st: AggState) -> None:
            nonlocal grace
            if grace is None:
                grace = GracePartitioner(
                    lambda rb: [rb.get_column(n) for n in key_names],
                    num_buckets=self.GRACE_BUCKETS, spill=self._spill(),
                    total_buffer_bytes=budget, op=op)
            for partial in st.partial_batches():
                grace.add(partial)

        with budget_reservation(self.memory, budget, token=self.cancel_token,
                                op=op):
            for item in items:
                ingest(state, item)
                if state.approx_size_bytes() > budget:
                    spill_state(state)
                    state = fresh_state()
            if grace is None:
                yield MicroPartition(schema, [state.finalize()])
                return
            spill_state(state)
            grace.finish()
            for b in range(grace.num_buckets):
                # Stream the bucket into the merge state (never materialize
                # it whole — a skew-hot bucket stays budget-bounded because
                # merged partial state has one row per group).
                bstate = fresh_state()
                seen = False
                for rb in grace.stream_bucket(b):
                    seen = True
                    # Bucket batches coalesce fragments from several spill
                    # events, so group keys can repeat WITHIN one — force-merge.
                    bstate.accumulate_unmerged_partial(rb)
                    if bstate.approx_size_bytes() > budget:
                        bstate.partial_batches()  # merge in place
                if not seen:
                    continue
                out = bstate.finalize()
                if len(out):
                    yield MicroPartition(schema, [out])

    def _run_AggregatePartial(self, node: pp.AggregatePartial) -> Iterator[MicroPartition]:
        import contextlib

        from daft_tpu.execution.spill import budget_reservation

        state: AggState = node.two_phase() if callable(node.two_phase) else node.two_phase
        budget = self._sink_budget()
        with budget_reservation(self.memory, budget, token=self.cancel_token,
                                op="AggregatePartial") if budget is not None \
                else contextlib.nullcontext():
            emitted = False
            for mp in self._run(node.children[0]):
                state.accumulate(mp)
                if budget is not None and callable(node.two_phase) \
                        and state.approx_size_bytes() > budget:
                    # First COMPRESS in place: raw morsel buffers merge into
                    # one partial batch (bounded by group count, not rows).
                    state.partial_batches()
                    # Hysteresis: only keep the compressed state when it
                    # leaves real headroom — a state hovering just under
                    # budget would otherwise re-merge per morsel (O(groups)
                    # work each time). Near-budget state EMITS early instead:
                    # partial batches are mergeable downstream, the final
                    # stage re-aggregates.
                    if state.approx_size_bytes() <= budget // 2:
                        continue
                    batches = state.partial_batches()
                    if batches:
                        emitted = True
                        yield MicroPartition(node.schema, batches)
                    state = node.two_phase()
            batches = state.partial_batches()
            if batches or not emitted:
                yield MicroPartition(node.schema,
                                     batches or [RecordBatch.empty(node.schema)])

    def _run_AggregateFinal(self, node: pp.AggregateFinal) -> Iterator[MicroPartition]:
        make = node.two_phase if callable(node.two_phase) \
            else (lambda: node.two_phase)
        budget = self._sink_budget()
        probe: AggState = make()
        # Emit-early partials upstream + shuffle-map concat mean a received
        # batch CAN repeat a group key within itself — always force a merge
        # pass before finalize (accumulate_unmerged_partial).
        if budget is None or not probe.plan.group_by or not callable(node.two_phase):
            state = probe
            for mp in self._run(node.children[0]):
                for rb in mp.record_batches():
                    state.accumulate_unmerged_partial(rb)
            yield MicroPartition(node.schema, [state.finalize()])
            return

        def rb_stream():
            for mp in self._run(node.children[0]):
                yield from mp.record_batches()

        yield from self._grace_grouped_agg(
            rb_stream(), make, budget, node.schema,
            ingest=lambda st, rb: st.accumulate_unmerged_partial(rb),
            op="AggregateFinal")

    def _run_SortSample(self, node: pp.SortSample) -> Iterator[MicroPartition]:
        combined = self._collect(node.children[0], op="SortSample").combined()
        keys = [evaluate(e, combined).rename(f"__sk_{i}") for i, e in enumerate(node.sort_by)]
        keys_rb = RecordBatch(node.schema, keys, len(combined)) if keys else RecordBatch.empty(node.schema)
        sorted_rb = keys_rb.sort(list(keys_rb.columns()), node.descending, node.nulls_first)
        n = len(sorted_rb)
        if n == 0:
            yield MicroPartition(node.schema, [])
            return
        take = min(node.num, n)
        idx = (np.arange(take) * n // take).clip(0, n - 1)
        yield MicroPartition(node.schema, [sorted_rb.take(idx.astype(np.uint64))])

    def _run_Pivot(self, node: pp.Pivot) -> Iterator[MicroPartition]:
        from daft_tpu.expressions.expr import AggOp, Alias

        # Pre-aggregate (group_by + pivot) then pivot to columns.
        agg = Alias(AggOp(node.agg_fn, node.value_col), "__pivot_value")
        combined = self._collect(node.children[0], op="Pivot").combined()
        pre = combined.agg([agg], node.group_by + [node.pivot_col])
        group_keys = [pre.get_column(g.name()) for g in node.group_by]
        out = pre.pivot(group_keys, pre.get_column(node.pivot_col.name()),
                        pre.get_column("__pivot_value"), node.names)
        casted_cols = []
        for f in node.schema:
            c = out.get_column(f.name)
            casted_cols.append(c.cast(f.dtype) if c.dtype != f.dtype else c)
        yield MicroPartition(node.schema, [RecordBatch(node.schema, casted_cols, len(out))])

    def _run_Distinct(self, node: pp.Distinct) -> Iterator[MicroPartition]:
        from daft_tpu.execution.spill import GracePartitioner, budget_reservation

        on = [e.name() for e in node.on] if node.on else None
        budget = self._sink_budget()
        key_names = on or node.schema.column_names()
        import contextlib

        with budget_reservation(self.memory, budget, token=self.cancel_token,
                                op="Distinct") if budget is not None \
                else contextlib.nullcontext():
            grace: Optional[GracePartitioner] = None
            buffer: List[RecordBatch] = []
            buf_bytes = 0
            for mp in self._run(node.children[0]):
                rb = mp.combined().distinct(on)
                buffer.append(rb)
                buf_bytes += rb.size_bytes()
                if budget is not None and buf_bytes > budget:
                    # Grace distinct: dedupe-within-morsel already applied;
                    # cross-morsel dedupe happens per disk bucket.
                    if grace is None:
                        grace = GracePartitioner(
                            lambda b: [b.get_column(n) for n in key_names],
                            num_buckets=self.GRACE_BUCKETS, spill=self._spill(),
                            total_buffer_bytes=budget, op="Distinct")
                    for b in buffer:
                        grace.add(b)
                    buffer, buf_bytes = [], 0
            if grace is not None:
                for b in buffer:
                    grace.add(b)
                grace.finish()
                for i in range(grace.num_buckets):
                    # Incremental fold: resident memory tracks the bucket's
                    # DISTINCT output, not its raw (possibly skew-hot) size.
                    acc: Optional[RecordBatch] = None
                    for rb in grace.stream_bucket(i):
                        d = rb.distinct(on)
                        acc = d if acc is None else \
                            RecordBatch.concat([acc, d]).distinct(on)
                    if acc is not None and len(acc):
                        yield MicroPartition(node.schema, [acc])
                return
            if not buffer:
                yield MicroPartition.empty(node.schema)
                return
            yield MicroPartition(node.schema, [RecordBatch.concat(buffer).distinct(on)])

    def _run_Window(self, node: pp.Window) -> Iterator[MicroPartition]:
        from daft_tpu.execution.window_eval import eval_windows

        budget = self._sink_budget()
        part_keys = self._common_window_partition_keys(node.window_exprs)
        if budget is None or part_keys is None:
            # Unpartitioned windows (or no memory limit) need the whole
            # input in one batch.
            combined = self._collect(node.children[0], op="Window").combined()
            yield MicroPartition(node.schema,
                                 [eval_windows(combined, node.window_exprs,
                                               node.schema)])
            return
        # Grace windows: every window spec partitions by the same keys, so
        # rows of one window-partition land in one disk bucket and each
        # bucket evaluates independently (row order across buckets is
        # unspecified, as everywhere else in the engine outside Sort).
        from daft_tpu.execution.spill import GracePartitioner, budget_reservation

        with budget_reservation(self.memory, budget, token=self.cancel_token,
                                op="Window"):
            grace: Optional[GracePartitioner] = None
            buffer: List[RecordBatch] = []
            buf_bytes = 0
            for mp in self._run(node.children[0]):
                rb = mp.combined()
                buffer.append(rb)
                buf_bytes += rb.size_bytes()
                if grace is None and buf_bytes > budget:
                    grace = GracePartitioner(
                        lambda b: [evaluate(k, b) for k in part_keys],
                        num_buckets=self.GRACE_BUCKETS, spill=self._spill(),
                        total_buffer_bytes=budget, op="Window")
                if grace is not None:
                    for b in buffer:
                        grace.add(b)
                    buffer, buf_bytes = [], 0
            if grace is None:
                if not buffer:
                    yield MicroPartition.empty(node.schema)
                    return
                combined = RecordBatch.concat(buffer)
                yield MicroPartition(node.schema,
                                     [eval_windows(combined, node.window_exprs,
                                                   node.schema)])
                return
            grace.finish()
            for b in range(grace.num_buckets):
                # Window evaluation needs each window-partition whole, so one
                # BUCKET (~input/32, or a skew-hot partition key) must fit in
                # memory — the same single-level-grace bound as right/outer
                # joins; 32x better than the pre-spill full materialization.
                batches = list(grace.stream_bucket(b))
                if not batches:
                    continue
                combined = RecordBatch.concat(batches)
                yield MicroPartition(node.schema,
                                     [eval_windows(combined, node.window_exprs,
                                                   node.schema)])

    @staticmethod
    def _common_window_partition_keys(window_exprs):
        """The shared partition_by exprs when EVERY window spec in the
        projection partitions by the same non-empty key set; None otherwise
        (those windows are global and cannot bucket)."""
        from daft_tpu.expressions.expr import WindowExpr

        common_key = None
        keys = None
        for e in window_exprs:
            for n in e.walk():
                if isinstance(n, WindowExpr):
                    if not n.partition_by:
                        return None
                    k = frozenset(p.key() for p in n.partition_by)
                    if common_key is None:
                        common_key, keys = k, list(n.partition_by)
                    elif k != common_key:
                        return None
        return keys

    # -- joins ------------------------------------------------------------
    GRACE_BUCKETS = 32

    def _collect_or_grace(self, child: pp.PhysicalPlan, key_exprs, budget,
                          key_dtypes=None, num_buckets: Optional[int] = None,
                          source: Optional[Iterator[MicroPartition]] = None,
                          op: str = "HashJoin",
                          est_bytes: Optional[float] = None):
        """Materialize a join side in memory, or — once it outgrows the
        budget — hash-partition it by join key into disk buckets (grace hash
        join). ``key_dtypes`` are the UNIFIED join-key dtypes: both sides must
        hash identical key values identically, and the row hash is
        byte-width-sensitive, so keys are cast before bucketing (the
        in-memory join casts the same way, recordbatch.py hash_join).
        ``source`` substitutes a pre-built child iterator (the hash join's
        probe-side prefetch). ``est_bytes`` is the side's stamped planner
        estimate: under corrections, a side whose buffered bytes already
        contradict it by the probe factor engages grace EARLY — the
        estimate said "fits easily", the data says otherwise, so stop
        buffering toward the budget cliff. The trigger is a pure function
        of the (thread-count-invariant) morsel stream and config, per the
        PR 8 determinism contract. Returns ("mem", MicroPartition) or
        ("grace", GracePartitioner)."""
        if budget is None:
            return "mem", self._collect(child, source=source, op=op)
        from daft_tpu.execution.spill import GracePartitioner

        probe_trip = None
        if self._fb_correct and est_bytes:
            factor = max(getattr(self.cfg, "feedback_probe_factor", 8.0), 1.0)
            # 1 MiB floor: tiny estimates must not make tiny sides spill.
            probe_trip = max(float(est_bytes) * factor, 1 << 20)

        key_fn = lambda rb: self._unified_keys(rb, key_exprs, key_dtypes)  # noqa: E731
        buffer: List[MicroPartition] = []
        buf_bytes = 0
        grace: Optional[GracePartitioner] = None
        for mp in (source if source is not None else self._run(child)):
            if grace is not None:
                for rb in mp.record_batches():
                    grace.add(rb)
                continue
            buffer.append(mp)
            buf_bytes += mp.size_bytes()
            if buf_bytes > budget or \
                    (probe_trip is not None and buf_bytes > probe_trip):
                if buf_bytes <= budget:
                    self._fb_emit_correction(
                        child, kind="join-spill",
                        estimated=float(est_bytes), observed=float(buf_bytes),
                        action="engaged grace partitioning early")
                grace = GracePartitioner(key_fn,
                                         num_buckets or self.GRACE_BUCKETS,
                                         self._spill(),
                                         total_buffer_bytes=budget, op=op)
                for buffered in buffer:
                    for rb in buffered.record_batches():
                        grace.add(rb)
                buffer = []
        if grace is not None:
            grace.finish()
            return "grace", grace
        if not buffer:
            return "mem", MicroPartition.empty(child.schema)
        return "mem", MicroPartition.concat(buffer)

    @staticmethod
    def _unified_keys(rb: RecordBatch, key_exprs, key_dtypes) -> List[Series]:
        keys = [evaluate(e, rb) for e in key_exprs]
        if key_dtypes is None:
            return keys
        return [k.cast(dt) if dt is not None and k.dtype != dt else k
                for k, dt in zip(keys, key_dtypes)]

    def _grace_bucket_rbs(self, grace_or_parts, b: int, schema) -> RecordBatch:
        """Bucket b of a graced side (or of an in-memory pre-partitioned
        list), as a RecordBatch; empty batch when the bucket has no rows."""
        if isinstance(grace_or_parts, list):
            return grace_or_parts[b]
        bucket = grace_or_parts.read_bucket(b)
        if bucket is None or len(bucket) == 0:
            return RecordBatch.empty(schema)
        return bucket.combined()

    def _grace_bucket_stream(self, grace_or_parts, b: int) -> Iterator[RecordBatch]:
        if isinstance(grace_or_parts, list):
            yield grace_or_parts[b]
            return
        yield from grace_or_parts.stream_bucket(b)

    def _run_HashJoin(self, node: pp.HashJoin) -> Iterator[MicroPartition]:
        import contextlib

        from daft_tpu.execution.spill import budget_reservation

        budget = self._sink_budget()
        with budget_reservation(self.memory, budget, token=self.cancel_token,
                                op="HashJoin") if budget is not None \
                else contextlib.nullcontext():
            yield from self._hash_join_impl(node, budget)

    def _hash_join_impl(self, node: pp.HashJoin, budget) -> Iterator[MicroPartition]:
        from daft_tpu.datatype import unify_dtypes

        lschema0, rschema0 = node.children[0].schema, node.children[1].schema
        key_dtypes = [
            unify_dtypes(lt, rt) if lt != rt else None
            for lt, rt in ((le.to_field(lschema0).dtype,
                            re.to_field(rschema0).dtype)
                           for le, re in zip(node.left_on, node.right_on))
        ]
        from daft_tpu.execution.pipeline import Prefetch

        # Overlap the build with the probe-side upstream: while the right
        # child materializes, a bounded prefetch warms the left subtree's
        # stages so the probe starts on hot queues the moment the build
        # lands. Memory-budgeted plans skip the look-ahead (the budget
        # paths own their buffering); the prefetch closes on ANY exit so
        # a build failure can't leak the puller thread.
        left_prefetch: Optional[Prefetch] = None
        if budget is None and self.compute_threads > 1:
            left_prefetch = Prefetch(self._run(node.children[0]),
                                     capacity=4, name="probe-side")
        try:
            yield from self._hash_join_sides(node, budget, key_dtypes,
                                             left_prefetch)
        finally:
            if left_prefetch is not None:
                left_prefetch.close()

    def _fb_join_buckets(self, node: pp.PhysicalPlan, budget) -> int:
        """Grace bucket count for one join. Default GRACE_BUCKETS; under
        corrections, sized so each bucket of the LARGER estimated side
        fits in half the sink budget (clamped to [GRACE_BUCKETS, 64]) — a
        side the store observed at 10x the budget gets more, smaller
        buckets instead of per-bucket overflow. Pure function of the
        stamped estimates + config, so both sides and the merge loop
        agree on it at any thread count."""
        if not self._fb_correct or budget is None or budget <= 0:
            return self.GRACE_BUCKETS
        est = max(float(getattr(node.children[0], "_est_bytes", 0) or 0),
                  float(getattr(node.children[1], "_est_bytes", 0) or 0))
        if est <= 0:
            return self.GRACE_BUCKETS
        import math

        nb = min(max(math.ceil(est / max(budget / 2.0, 1.0)),
                     self.GRACE_BUCKETS), 64)
        if nb != self.GRACE_BUCKETS:
            self._fb_emit_correction(
                node, kind="shuffle-buckets",
                estimated=float(self.GRACE_BUCKETS), observed=float(nb),
                action=f"scaled grace buckets to {nb}")
        return nb

    def _hash_join_sides(self, node: pp.HashJoin, budget, key_dtypes,
                         left_prefetch) -> Iterator[MicroPartition]:
        # ONE bucket count per join, used by every graced side, every
        # in-memory partition_by_hash, and the merge loop below — equal
        # keys must land in equal bucket indices on both sides.
        nb = self._fb_join_buckets(node, budget)
        right_state, right_side = self._collect_or_grace(
            node.children[1], node.right_on, budget, key_dtypes,
            num_buckets=nb,
            est_bytes=getattr(node.children[1], "_est_bytes", None))
        if right_state == "mem" and node.how not in ("right", "outer"):
            from daft_tpu.execution.join_index import JoinIndex

            right = right_side.combined()
            right_keys = [evaluate(e, right) for e in node.right_on]
            right_data, coalesce = self._prep_join_right(right, node)
            # Build-once probe-many: a reusable sorted-key index over the
            # build side, so parallel probe morsels never rebuild the hash
            # table. Eligibility is plan/data-driven (single sortable key,
            # probe-driven join type) — identical at every thread count.
            index = JoinIndex.try_build(
                self._unified_keys(right, node.right_on, key_dtypes),
                node.how, right_data)
            build_rb = right_data
            if index is not None and node.how not in ("semi", "anti"):
                lnames = set(node.children[0].schema.column_names())
                ren = {n: f"{node.suffix}{n}"
                       for n in right_data.schema.column_names()
                       if n in lnames}
                if ren:
                    cols = [c.rename(ren[c.name]) if c.name in ren else c
                            for c in right_data.columns()]
                    build_rb = RecordBatch(
                        Schema([Field(c.name, c.dtype) for c in cols]),
                        cols, len(right_data))

            # Stream the probe (left) side morsel-by-morsel against the built
            # side, probing morsels in parallel on multi-core hosts. Without
            # an index the per-morsel Acero join re-hashes the build side
            # each call, so the probe keeps its natural (coarse) morsels.
            def probe(mp: MicroPartition) -> MicroPartition:
                left = mp.combined()
                if index is not None:
                    joined = index.probe(
                        left, self._unified_keys(left, node.left_on, key_dtypes),
                        build_rb, node.how)
                    if joined is not None:
                        return MicroPartition(
                            node.schema,
                            [self._finish_join(joined, coalesce, node)])
                left_keys = [evaluate(e, left) for e in node.left_on]
                out = self._join_and_fix(left, right, left_keys, right_keys, node)
                return MicroPartition(node.schema, [out])

            yield from self._streaming_map(
                node, probe, split=index is not None,
                source=iter(left_prefetch) if left_prefetch is not None
                else None)
            return
        # Right/outer joins need the left side materialized too; an oversized
        # build side forces grace mode for ALL join types.
        left_state, left_side = self._collect_or_grace(
            node.children[0], node.left_on, budget, key_dtypes,
            num_buckets=nb,
            source=iter(left_prefetch) if left_prefetch is not None else None,
            est_bytes=getattr(node.children[0], "_est_bytes", None))
        if right_state == "mem" and left_state == "mem":
            left, right = left_side.combined(), right_side.combined()
            left_keys = [evaluate(e, left) for e in node.left_on]
            right_keys = [evaluate(e, right) for e in node.right_on]
            yield MicroPartition(node.schema, [
                self._join_and_fix(left, right, left_keys, right_keys, node)
            ])
            return
        # Grace hash join: equal keys hash to the same bucket on both sides,
        # so each bucket joins independently with exact semantics (including
        # unmatched left/right rows for outer joins).
        if right_state == "mem":
            rb = right_side.combined()
            keys = self._unified_keys(rb, node.right_on, key_dtypes)
            right_side = rb.partition_by_hash(keys, nb)
        if left_state == "mem":
            rb = left_side.combined()
            keys = self._unified_keys(rb, node.left_on, key_dtypes)
            left_side = rb.partition_by_hash(keys, nb)
        lschema, rschema = node.children[0].schema, node.children[1].schema
        for b in range(nb):
            right = self._grace_bucket_rbs(right_side, b, rschema)
            if node.how in ("inner", "left", "semi", "anti"):
                if len(right) == 0 and node.how in ("inner", "semi"):
                    continue
                # Left-driven types stream the probe bucket morsel-by-morsel:
                # only the build bucket must fit in memory, so probe-side key
                # skew never materializes a hot bucket whole.
                right_keys = [evaluate(e, right) for e in node.right_on]
                for left in self._grace_bucket_stream(left_side, b):
                    if len(left) == 0:
                        continue
                    left_keys = [evaluate(e, left) for e in node.left_on]
                    out = self._join_and_fix(left, right, left_keys,
                                             right_keys, node)
                    if len(out):
                        yield MicroPartition(node.schema, [out])
                continue
            # right/outer track unmatched build rows across the whole probe
            # side, so both buckets materialize (hot-KEY skew beyond one
            # bucket's budget is the known limit of single-level grace).
            left = self._grace_bucket_rbs(left_side, b, lschema)
            if len(left) == 0 and len(right) == 0:
                continue
            if len(right) == 0 and node.how == "right":
                continue
            left_keys = [evaluate(e, left) for e in node.left_on]
            right_keys = [evaluate(e, right) for e in node.right_on]
            out = self._join_and_fix(left, right, left_keys, right_keys, node)
            if len(out):
                yield MicroPartition(node.schema, [out])

    @staticmethod
    def _conform_to_schema(rb: RecordBatch, schema: Schema) -> RecordBatch:
        """Reorder/cast columns to the planned output schema."""
        import pyarrow as pa

        cols = []
        for f in schema:
            c = rb.get_column(f.name)
            if c.dtype != f.dtype:
                if f.dtype.is_null() and c.to_arrow().null_count == len(rb):
                    # A null-planned column whose runtime values ARE all null
                    # (e.g. the upcast key of a semi join on an all-None
                    # column) substitutes cleanly; arrow has no cast INTO
                    # null. Real values against a null plan still fail loud.
                    c = Series.from_arrow(pa.nulls(len(rb)), f.name, f.dtype)
                else:
                    c = c.cast(f.dtype)
            cols.append(c)
        return RecordBatch(schema, cols, len(rb))

    def _prep_join_right(self, right: RecordBatch, node):
        """Node-constant right-side prep shared by the Acero and probe-index
        paths: drop merged join keys from the right copy and, for
        right/outer joins, carry the right copy under a reserved ``__rk_``
        name so right-only rows can coalesce the null left key after the
        join (the reference coalesces common join columns in
        hash_outer_join). Returns ``(right_data, coalesce_names)``."""
        merged = sorted(node.merged_keys) if node.merged_keys and node.how not in ("semi", "anti") else []
        coalesce = merged if node.how in ("right", "outer") else []
        if not merged:
            return right, coalesce
        keep = right.schema.exclude(merged)
        cols = [right.get_column(n) for n in keep.column_names()]
        cols += [right.get_column(n).rename(f"__rk_{n}") for n in coalesce]
        schema = Schema([Field(c.name, c.dtype) for c in cols])
        return RecordBatch(schema, cols, len(right)), coalesce

    def _finish_join(self, joined: RecordBatch, coalesce, node) -> RecordBatch:
        if coalesce:
            cols = [c.coalesce(joined.get_column(f"__rk_{c.name}")) if c.name in coalesce
                    else c for c in joined.columns() if not c.name.startswith("__rk_")]
            joined = RecordBatch(Schema([Field(c.name, c.dtype) for c in cols]),
                                 cols, len(joined))
        return self._conform_to_schema(joined, node.schema)

    def _join_and_fix(self, left, right, left_keys, right_keys, node) -> RecordBatch:
        right_data, coalesce = self._prep_join_right(right, node)
        joined = left.hash_join(right_data, left_keys, right_keys, node.how, node.suffix)
        return self._finish_join(joined, coalesce, node)

    def _run_AsofJoin(self, node: pp.AsofJoin) -> Iterator[MicroPartition]:
        right = self._collect(node.children[1], op="AsofJoin").combined()
        right_on = evaluate(node.right_on, right)
        right_by = [evaluate(e, right) for e in node.right_by]
        for mp in self._run(node.children[0]):
            left = mp.combined()
            left_on = evaluate(node.left_on, left)
            left_by = [evaluate(e, left) for e in node.left_by]
            joined = left.asof_join(right, left_on, right_on, left_by, right_by,
                                    node.direction, node.suffix)
            yield MicroPartition(node.schema, [self._conform_to_schema(joined, node.schema)])

    def _run_CrossJoin(self, node: pp.CrossJoin) -> Iterator[MicroPartition]:
        right = self._collect(node.children[1], op="CrossJoin").combined()
        for mp in self._run(node.children[0]):
            joined = mp.combined().cross_join(right, node.suffix)
            yield MicroPartition(node.schema, [self._conform_to_schema(joined, node.schema)])

    # -- multi-input / partitioning --------------------------------------
    def _run_Concat(self, node: pp.Concat) -> Iterator[MicroPartition]:
        for child in node.children:
            yield from self._run(child)

    def _run_Repartition(self, node: pp.Repartition) -> Iterator[MicroPartition]:
        scheme = node.scheme
        kind = scheme[0]
        if kind == "shard":
            _, world, rank = scheme
            for mp in self._run(node.children[0]):
                rb = mp.combined()
                hashes = rb.hash_rows()
                mask = Series.from_numpy((hashes % np.uint64(world)) == np.uint64(rank), "m")
                yield MicroPartition(node.schema, [rb.filter(mask)])
            return
        if kind == "hash":
            _, exprs, n = scheme
            budget = self._sink_budget()
            if budget is not None:
                # Buffer in memory until the sink budget trips, THEN stream
                # into n disk buckets with the same hash the in-memory
                # partitioner uses (the shared _collect_or_grace machinery) —
                # small repartitions never pay a disk round-trip. Every
                # bucket yields, including empty ones (the n-partitions
                # contract).
                from daft_tpu.execution.spill import budget_reservation

                with budget_reservation(self.memory, budget,
                                        token=self.cancel_token,
                                        op="Repartition"):
                    state, side = self._collect_or_grace(
                        node.children[0], exprs, budget,
                        num_buckets=max(n, 1), op="Repartition")
                    if state == "mem":
                        for part in side.partition_by_hash(exprs, n):
                            yield part
                        return
                    for b in range(max(n, 1)):
                        yield MicroPartition(node.schema,
                                             list(side.stream_bucket(b)))
                return
            combined = self._collect(node.children[0], op="Repartition")
            for part in combined.partition_by_hash(exprs, n):
                yield part
            return
        combined = self._collect(node.children[0], op="Repartition")
        if kind == "range_bound":
            # Range partition against precomputed boundary rows (distributed
            # sort stage 2).
            _, exprs, descending, nulls_first, boundaries = scheme
            rb = combined.combined()
            keys = [evaluate(e, rb) for e in exprs]
            for part in rb.partition_by_range(keys, boundaries, list(descending),
                                              list(nulls_first)):
                yield MicroPartition(node.schema, [part])
        elif kind == "random":
            _, n = scheme
            for part in combined.partition_by_random(n, seed=42):
                yield part
        elif kind == "into":
            _, n = scheme
            rb = combined.combined()
            total = len(rb)
            base, extra = divmod(total, max(n, 1))
            start = 0
            for i in range(n):
                size = base + (1 if i < extra else 0)
                yield MicroPartition(node.schema, [rb.slice(start, size)])
                start += size
        else:
            raise DaftPlanError(f"Unknown repartition scheme {kind}")

    # -- write ------------------------------------------------------------
    def _run_Write(self, node: pp.Write) -> Iterator[MicroPartition]:
        from daft_tpu.io.writers import make_writer

        child = node.children[0]
        writer = make_writer(node.write_info, child.schema, self.cfg)
        for mp in self._run(child):
            writer.write(mp)
        results = writer.close()
        yield MicroPartition.from_pydict({
            "path": [r["path"] for r in results],
            "num_rows": np.array([r["num_rows"] for r in results], dtype=np.uint64),
        }) if results else MicroPartition.empty(node.schema)
