"""Morsel-parallel pipelined execution primitives.

The local executor's intra-query parallelism layer (reference: the
Swordfish pipeline in src/daft-local-execution — sources / intermediate
ops / sinks connected by bounded channels, pipeline.rs message flow; the
dataflow-graph execution model of TensorFlow applied to one host): each
streaming operator becomes a *stage* — a feeder thread pulls the child
iterator and submits per-morsel work to the executor's SHARED compute
pool through a bounded in-flight queue, and the consumer drains results.
Backpressure is the queue bound (at most ~2x ``workers`` morsels
completed-or-running per stage); cancellation is observed at every morsel
boundary (the feeder pulls through the executor's ``_cancel_checked``
wrapper, and an abandoned consumer flips a stop flag that releases the
feeder); a failure anywhere poisons the stream by propagating the ORIGINAL
exception to the consumer, unwrapped, so error types match the serial
path regardless of core count.

Stage fusion (PR 11): the executor no longer creates one stage per
streaming operator — adjacent Project/Filter nodes collapse into ONE
composed morsel function run through a single ``map_stage`` call
(executor._run_relational_chain), so a chain costs one queue hop instead
of N, and the traceable suffix of the chain can run as one jitted XLA
program per morsel (ops/compiled_eval.py). The primitives below are
unchanged: a fused chain is just a stage whose ``fn`` happens to be a
composition.

Determinism contract (the parallel-vs-serial equality suite): everything
here that shapes *what* is computed — morsel split points, coalesce
boundaries, aggregation chunk boundaries — is a pure function of the
input stream, never of ``workers`` or scheduling. Thread count changes
only *where* a morsel runs. Ordered stages additionally restore input
order on the way out (futures queue in submission order), so
order-sensitive consumers (sort / limit / distinct on ordered inputs)
see the serial sequence; unordered stages (``ordered=False``) yield in
completion order and are reserved for order-insensitive sinks.
"""

from __future__ import annotations

import contextvars
import queue
import threading
import time
from typing import Callable, Iterator, List, Optional

_SENTINEL = object()

#: Floor below which morsels are coalesced before entering a stage: a
#: q11/q16-shaped query (small dimension tables, selective filters) emits
#: hundreds of tiny morsels whose per-morsel queue + span + dispatch
#: overhead would dominate the actual kernel work. Merging batch LISTS is
#: O(1) per morsel (MicroPartition.concat never copies buffers).
DEFAULT_MIN_MORSEL_ROWS = 16 * 1024


def split_morsels(it, max_rows: int):
    """Split oversized morsels at ``max_rows`` boundaries; smaller morsels
    pass through untouched. Split points depend only on the incoming
    stream (deterministic across thread counts)."""
    for mp in it:
        n = len(mp)
        if n <= max_rows:
            yield mp
            continue
        for start in range(0, n, max_rows):
            yield mp.slice(start, min(max_rows, n - start))


def coalesce_morsels(it, min_rows: int):
    """Merge undersized morsels until they reach ``min_rows``. Zero-row
    morsels are absorbed (never emitted alone mid-stream); an empty or
    all-empty stream still yields its (empty) tail morsel so schema-only
    results survive."""
    pending: List = []
    pending_rows = 0
    emitted = False
    tail = None
    for mp in it:
        tail = mp
        n = len(mp)
        if n == 0:
            continue
        pending.append(mp)
        pending_rows += n
        if pending_rows >= min_rows:
            yield _concat(pending)
            pending, pending_rows = [], 0
            emitted = True
    if pending:
        yield _concat(pending)
    elif not emitted and tail is not None:
        yield tail


def _concat(parts):
    from daft_tpu.micropartition import MicroPartition

    return parts[0] if len(parts) == 1 else MicroPartition.concat(parts)


def morselize(it, min_rows: int, max_rows: int):
    """Canonical stage-input morsel stream: split oversized, coalesce
    undersized. Applied at BOTH thread counts so the morsel sequence —
    and everything downstream keyed on it (aggregation chunk boundaries,
    float summation order) — is identical at ``num_compute_threads=1``
    and ``=N``."""
    if min_rows > 1:
        it = coalesce_morsels(it, min(min_rows, max_rows))
    return split_morsels(it, max_rows)


def chunk_morsels(it, chunk_rows: int):
    """Group a morsel stream into lists whose cumulative rows first
    exceed ``chunk_rows`` (the flush rule AggState uses): yields
    ``List[MicroPartition]``. Boundaries are a pure function of the
    stream — the parallel-aggregation chunking that keeps partial-sum
    float association thread-count-invariant."""
    chunk: List = []
    rows = 0
    for mp in it:
        n = len(mp)
        if n == 0:
            continue
        chunk.append(mp)
        rows += n
        if rows > chunk_rows:
            yield chunk
            chunk, rows = [], 0
    if chunk:
        yield chunk


class _StageAccount:
    """Byte accounting for one stage's bounded queue (memory observatory).

    A morsel is CHARGED the moment a stage worker completes it (it is now
    completed-or-queued residency nobody downstream has consumed) and
    RELEASED when the consumer takes it — so the ledger's ``queue`` kind
    tracks real backpressure-buffer occupancy. ``drain()`` zeroes whatever
    is still outstanding on ANY stage exit (abandonment, failure), keeping
    the drains-to-zero contract.

    Sizing is TEMPLATE-based, not a per-morsel buffer walk: a stage's
    outputs share one schema, so fixed-width columns are sized as
    ``rows x dtype-width`` (a pure function of schema + morsel rows —
    order-independent, so cumulative charged bytes per operator stay
    thread-count invariant, which the tests pin) and only var-width
    columns (strings/lists) pay an exact per-column buffer read. An
    already-memoized exact ``size_bytes`` is used when a batch carries
    one; fresh all-numeric morsels — the hot case — cost a multiply."""

    __slots__ = ("qid", "op", "outstanding", "closed", "lock", "ledger",
                 "_fixed_bits", "_var", "_sizes")

    def __init__(self, qid: str, op: str):
        from daft_tpu.execution.memledger import get_ledger

        self.qid = qid
        self.op = op
        self.outstanding = 0
        self.closed = False
        self.lock = threading.Lock()
        self.ledger = get_ledger()
        self._fixed_bits = None  # per-row BITS of the fixed-width columns
        self._var = ()           # indices of var-width columns (exact walk)
        # id(morsel) -> measured bytes, written at produced(), popped at
        # consumed(): one sizing pass per morsel, not two (var-width
        # columns walk buffers). Pop-on-consume keeps id reuse safe.
        self._sizes: dict = {}

    def _sized_batch(self, rb) -> int:
        # Always the template, never an opportunistic exact memo: memo
        # presence depends on who ELSE sized the batch (profiler sampling,
        # sink collection), and mixing exact and template values would
        # make charged totals depend on that — not on the morsel stream.
        cols = rb.columns()
        if self._fixed_bits is None:
            bits, var = 0, []
            for i, c in enumerate(cols):
                if c.dtype.is_python():
                    bits += 64 * 8  # the engine's flat python-object estimate
                    continue
                try:
                    # Accumulated in BITS so packed types (bool, width 1)
                    # still count instead of flooring to zero per column.
                    bits += c.to_arrow().type.bit_width
                except (ValueError, AttributeError):
                    var.append(i)  # var-width: offsets make width data-bound
            self._fixed_bits, self._var = bits, tuple(var)
        total = (self._fixed_bits * len(rb)) // 8
        for i in self._var:
            total += cols[i].to_arrow().nbytes
        return total

    def measure(self, mp) -> int:
        if hasattr(mp, "record_batches"):
            return sum(self._sized_batch(rb) for rb in mp.record_batches())
        if hasattr(mp, "columns"):
            return self._sized_batch(mp)
        return int(mp.size_bytes())  # batch-shaped stand-ins (tests)

    def produced(self, mp) -> None:
        try:
            nbytes = self.measure(mp)
        except (AttributeError, TypeError):
            return
        # Charge FIRST, book under the lock after: a worker completing a
        # morsel just as the consumer abandons the stage either lands in
        # ``outstanding`` (drained below) or is undone right here — the
        # ledger can never be left holding a morsel nobody will release.
        self.ledger.charge(self.qid, self.op, nbytes, kind="queue")
        with self.lock:
            if not self.closed:
                self.outstanding += nbytes
                self._sizes[id(mp)] = nbytes
                return
        self.ledger.release(self.qid, self.op, nbytes, kind="queue")

    def consumed(self, mp) -> None:
        with self.lock:
            nbytes = self._sizes.pop(id(mp), None)
            if nbytes is None:
                return  # never produced here (or already drained)
            nbytes = min(nbytes, self.outstanding)
            self.outstanding -= nbytes
        if nbytes:
            self.ledger.release(self.qid, self.op, nbytes, kind="queue")

    def stalled(self, seconds: float) -> None:
        self.ledger.note_stall(self.qid, self.op, seconds)

    def drain(self) -> None:
        with self.lock:
            self.closed = True
            leftover, self.outstanding = self.outstanding, 0
            self._sizes.clear()
        if leftover:
            self.ledger.release(self.qid, self.op, leftover, kind="queue")


def _stage_account(ledger: "Optional[tuple]", name: str
                   ) -> Optional[_StageAccount]:
    """Build the stage's byte account from the executor's ``(query_id,
    op)`` tag, or None when untagged / the ledger plane is disabled (the
    zero-cost path: no per-morsel work at all)."""
    if ledger is None:
        return None
    from daft_tpu.execution.memledger import get_ledger

    if not get_ledger().enabled:
        return None
    qid, op = ledger
    return _StageAccount(qid, op or name)


def run_stage(child_iter: Iterator, fn: Callable, *, pool, workers: int,
              name: str = "stage", ordered: bool = True, timer=None,
              owns_pool: bool = False,
              ledger: "Optional[tuple]" = None) -> Iterator:
    """Run ``fn`` over every item of ``child_iter`` on ``pool`` workers,
    yielding results — THE pipeline stage primitive.

    A feeder thread pulls the child and submits work through a bounded
    in-flight queue (capacity ~2x ``workers``: the backpressure bound);
    the caller's generator is the consumer. ``ordered=True`` (the
    default, the reference's maintain_order) yields results in input
    order — the order-restoring merge is the future queue itself, which
    holds futures in submission order. ``ordered=False`` yields in
    completion order for order-insensitive consumers.

    Exceptions from the child iterator or from ``fn`` reach the consumer
    UNWRAPPED. The stop flag lets an abandoned consumer (limit pushdown,
    a failure in a sibling stage) release the feeder without draining.
    Feeder and workers inherit the caller's contextvars (per-query frozen
    clock, ambient profiler). ``timer`` is an optional profiling hook
    with a ``run_timed(fn, item)`` method (the operator's _OpFrame):
    per-morsel wall/CPU is then measured ON THE WORKER, tight around the
    kernel, instead of at the consumer where queue waits would pollute
    attribution.
    """
    inflight: "queue.Queue" = queue.Queue(maxsize=max(workers * 2, 2))
    stop = threading.Event()
    ambient = contextvars.copy_context()
    run_one = fn if timer is None else (lambda item: timer.run_timed(fn, item))
    # Memory-observatory account for this stage's bounded queue (None =
    # untagged stage / plane disabled — the zero-cost path).
    acct = _stage_account(ledger, name)
    if acct is not None:
        base_run = run_one

        def run_one(item, _run=base_run):
            out = _run(item)
            acct.produced(out)
            return out

    def put_or_stop(item) -> bool:
        stall_t0 = None
        while not stop.is_set():
            try:
                inflight.put(item, timeout=0.1)
                if stall_t0 is not None and acct is not None:
                    acct.stalled(time.monotonic() - stall_t0)
                return True
            except queue.Full:
                # Blocked producer: the bounded queue is full, backpressure
                # is engaged. Timed from the FIRST Full (the fast path pays
                # zero clock reads).
                if stall_t0 is None:
                    stall_t0 = time.monotonic()
                continue
        if stall_t0 is not None and acct is not None:
            acct.stalled(time.monotonic() - stall_t0)
        return False

    if ordered:
        def submit_all():
            try:
                for item in child_iter:
                    fut = pool.submit(ambient.copy().run, run_one, item)
                    if not put_or_stop(fut):
                        fut.cancel()
                        return
            except BaseException as e:  # noqa: BLE001 — delivered to consumer
                put_or_stop(e)
                return
            put_or_stop(_SENTINEL)

        feeder = threading.Thread(target=ambient.copy().run,
                                  args=(submit_all,), daemon=True,
                                  name=f"daft-feed-{name}")
        feeder.start()
        try:
            while True:
                item = inflight.get()
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item  # child-iterator failure: the original
                res = item.result()  # fn failure: future re-raises it
                if acct is not None:
                    acct.consumed(res)
                yield res
        finally:
            stop.set()
            if acct is not None:
                acct.drain()
            if owns_pool:
                pool.shutdown(wait=False, cancel_futures=True)
        return

    # Unordered: completions push results directly; a semaphore bounds
    # in-flight work (the queue alone can't — results arrive out of order).
    slots = threading.Semaphore(max(workers * 2, 2))
    state_lock = threading.Lock()
    state = {"submitted": 0, "done": 0, "feeding": True}

    def finish_one(payload) -> None:
        slots.release()
        put_or_stop(payload)
        with state_lock:
            state["done"] += 1
            last = (not state["feeding"]
                    and state["done"] == state["submitted"])
        if last:
            put_or_stop(_SENTINEL)

    def run_and_push(item) -> None:
        try:
            finish_one(run_one(item))
        except BaseException as e:  # noqa: BLE001 — delivered to consumer
            finish_one(e)

    def submit_all():
        try:
            for item in child_iter:
                while not slots.acquire(timeout=0.1):
                    if stop.is_set():
                        return
                with state_lock:
                    state["submitted"] += 1
                pool.submit(ambient.copy().run, run_and_push, item)
        except BaseException as e:  # noqa: BLE001 — delivered to consumer
            put_or_stop(e)
            return
        finally:
            with state_lock:
                state["feeding"] = False
                drained = state["done"] == state["submitted"]
            if drained:
                put_or_stop(_SENTINEL)

    feeder = threading.Thread(target=ambient.copy().run, args=(submit_all,),
                              daemon=True, name=f"daft-feed-{name}")
    feeder.start()
    try:
        while True:
            item = inflight.get()
            if item is _SENTINEL:
                return
            if isinstance(item, BaseException):
                raise item
            if acct is not None:
                acct.consumed(item)
            yield item
    finally:
        stop.set()
        if acct is not None:
            acct.drain()
        if owns_pool:
            pool.shutdown(wait=False, cancel_futures=True)


def map_stage(child_iter: Iterator, fn: Callable, *, pool, workers: int,
              name: str = "stage", ordered: bool = True, timer=None,
              owns_pool: bool = False,
              ledger: "Optional[tuple]" = None) -> Iterator:
    """``run_stage`` when ``workers > 1``, an inline serial map otherwise
    (same stream shape either way — the stage machinery only changes
    where morsels run, never what they contain)."""
    if workers > 1:
        return run_stage(child_iter, fn, pool=pool, workers=workers,
                         name=name, ordered=ordered, timer=timer,
                         owns_pool=owns_pool, ledger=ledger)
    # Serial path keeps the SAME timer hook: a 1-thread profiled run must
    # attribute kernel work to the frame identically (the frame flips to
    # self_timed either way once any sink-side _node_timed call lands).
    run_one = fn if timer is None else (lambda item: timer.run_timed(fn, item))
    # Serial runs keep the SAME ledger hook too: each morsel is charged at
    # production and released at hand-off, so cumulative charged bytes per
    # operator are identical at num_compute_threads=1 and =N (the
    # determinism property the cross-core attribution tests pin) — only
    # PEAK residency legitimately varies with concurrency.
    acct = _stage_account(ledger, name)

    def serial():
        try:
            for item in child_iter:
                out = run_one(item)
                if acct is not None:
                    acct.produced(out)
                    acct.consumed(out)
                yield out
        finally:
            if acct is not None:
                acct.drain()
            if owns_pool:
                pool.shutdown(wait=False, cancel_futures=True)

    return serial()


def ordered_prefetch_map(items: Iterator, fn: Callable, *, depth: int,
                         name: str = "prefetch-map") -> Iterator:
    """``run_stage`` over a DEDICATED pool: apply ``fn`` to up to ``depth``
    items concurrently, yielding results strictly in item order — the
    bounded-look-ahead fetch primitive (shuffle chunk prefetch). Order is a
    pure function of the item stream, never of completion time, so
    consumers keep the determinism contract; the pool dies with the
    iterator (exhaustion OR abandonment)."""
    from concurrent.futures import ThreadPoolExecutor

    depth = max(int(depth), 1)
    if depth == 1:
        # Serial look-ahead is no look-ahead: plain inline map, no pool to
        # build or tear down.
        return (fn(item) for item in items)
    pool = ThreadPoolExecutor(max_workers=depth,
                              thread_name_prefix=f"daft-{name}")
    return map_stage(items, fn, pool=pool, workers=depth, name=name,
                     ordered=True, owns_pool=True)


class Prefetch:
    """Pull an iterator eagerly on a dedicated thread into a bounded queue.

    The overlap primitive for blocking sinks with TWO inputs: a hash
    join's probe-side upstream (scan -> filter -> project stages) warms
    concurrently with the build-side materialization instead of sitting
    idle until the build finishes. A dedicated thread (never a pool
    worker) does the pulling, preserving the executor's only-feeders-wait
    deadlock-freedom rule; the bounded queue caps look-ahead memory.
    Exceptions surface to the consumer unwrapped at the morsel where they
    occurred. Callers MUST :meth:`close` (or exhaust) the prefetch — an
    error between construction and consumption would otherwise leave the
    puller thread spinning against a full queue.

    Whatever ``it`` computes runs on the one puller thread, in order, and at
    most ``capacity + 1`` items lie ahead of the one the consumer holds (the
    queue's and the one the puller waits to put): the UDF operator's ordered,
    bounded input transfer is a Prefetch over a generator that stages.
    """

    def __init__(self, it: Iterator, capacity: int = 4,
                 name: str = "prefetch"):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(capacity, 1))
        self._stop = threading.Event()
        ambient = contextvars.copy_context()

        def put_or_stop(item) -> bool:
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def pull_all():
            try:
                for item in it:
                    if not put_or_stop(item):
                        return
            except BaseException as e:  # noqa: BLE001 — delivered to consumer
                put_or_stop(e)
                return
            put_or_stop(_SENTINEL)

        self._thread = threading.Thread(
            target=ambient.copy().run, args=(pull_all,), daemon=True,
            name=f"daft-{name}")
        self._thread.start()

    def close(self, wait_s: float = 0.0) -> None:
        """Release the puller. With ``wait_s`` also wait that long for it to
        end and drop what it had queued, so that nothing prefetched outlives
        the consumer (a staged device batch holds HBM). The puller ends after
        the item it is computing, or when its source next yields."""
        self._stop.set()
        if wait_s > 0 and threading.current_thread() is not self._thread:
            self._thread.join(wait_s)
            while True:
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    return

    def ready(self) -> bool:
        """True when the consumer's next item (or the stream's end) is already
        in the queue: asking for it will not wait."""
        return not self._q.empty()

    def __iter__(self):
        try:
            while True:
                item = self._q.get()
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            self._stop.set()


def collect_parallel(items: List, fn: Callable, *, pool,
                     workers: int, timer=None) -> List:
    """Apply ``fn`` to every item concurrently and return results in item
    order — the barrier helper blocking sinks use to consume independent
    pieces (grace/partition buckets, aggregation chunks) in parallel.
    Items never pull the child iterator, so sharing the executor's compute
    pool stays deadlock-free."""
    run_one = fn if timer is None else (lambda item: timer.run_timed(fn, item))
    if workers <= 1 or len(items) <= 1:
        return [run_one(it) for it in items]
    ambient = contextvars.copy_context()
    futs = [pool.submit(ambient.copy().run, run_one, it) for it in items]
    out = []
    first_err: Optional[BaseException] = None
    for f in futs:
        try:
            out.append(f.result())
        except BaseException as e:  # noqa: BLE001 — re-raised after drain
            if first_err is None:
                first_err = e
            out.append(None)
    if first_err is not None:
        raise first_err
    return out
