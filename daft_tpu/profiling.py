"""Distributed query profiler: one coherent trace per query across the wire.

The metrics plane (daft_tpu/metrics.py) answers "how much"; this module
answers "where did the time go" at sub-task granularity. It builds on the
span model in ``tracing.py`` (OTel-shaped :class:`~daft_tpu.tracing.Span`,
monotonic epoch via :func:`~daft_tpu.tracing.span_clock_ns`) and adds the
three pieces the reference engine's Swordfish runtime stats + TensorFlow's
step-timeline profiler demonstrated a dataflow engine needs:

* **Cross-wire trace propagation** — the driver opens one trace per query
  (:class:`QueryProfile`); ``(trace_id, parent span_id)`` rides every
  :class:`~daft_tpu.distributed.task.Task` through the process/daemon wire
  (the same seam deadlines and metrics snapshots use). Workers open child
  spans locally (:class:`TaskProfiler`), buffer them, and piggyback the
  completed spans on task-reply frames — daemons additionally on heartbeat
  ping replies, so a worker killed mid-task has already shipped the spans
  of every operator that finished. Worker clock skew is corrected with a
  heartbeat RTT-midpoint offset estimate (:func:`record_worker_clock`).
* **Operator-level timing** — the executor wraps each physical operator's
  morsel loop in a span keyed by plan-node id, recording wall time per
  pull, CPU time (``time.thread_time_ns``), rows/bytes out, and — via the
  ambient frame stack (:func:`note_permit_wait` / :func:`note_spill` /
  :func:`note_device`) — memory-permit waits, spill volume, and the
  device-vs-numpy eval split. When no profiler is active every hook is a
  single int check (the ``DAFT_PROFILE=0`` fast path; ``bench.py
  --profile-overhead`` holds the enabled path under 2% on TPC-H).
* **Timeline export** — ``df.collect(profile="trace.json")`` /
  ``DAFT_PROFILE_FILE`` writes Chrome trace-event JSON (pid = worker,
  tid = operator lane) loadable in Perfetto / chrome://tracing, and the
  dashboard serves the same span store as a per-query Gantt timeline
  (``/api/queries/<id>/timeline``).
* **Device-path spans** — below the operator, the UDF path down to the
  jitted forward is recorded at device-batch granularity by
  :class:`device_span`: every finished span lands in a bounded
  process-wide ring (:func:`recent_device_spans`, the flight recorder of
  the device path, on whether or not a query is profiled) and, under an
  ambient :class:`TaskProfiler`, also in the query's trace as a child of
  the operator span.
* **Compile log** — every program JAX traces, lowers, compiles or loads
  from its persistent cache is logged beside the ring
  (:func:`recent_compiles`) with the device span open on that thread, and
  its seconds are counters of that span: why a first row took a minute,
  and which span compiled in steady state.

Spans are ALWAYS opened through context managers (daftlint DTL009): an
un-ended span silently drops from export and leaks the thread-local parent
stack. ``ExitStack.enter_context`` is the escape hatch for conditionals.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import random
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from daft_tpu.cancellation import current_token
from daft_tpu.tracing import Span, span_clock_ns

# Span ids: one urandom read per PROCESS, then a counter — secrets.token_hex
# per span costs a full urandom syscall (~100µs under sandboxed kernels),
# which alone would blow the 2% overhead budget. XOR with a random 64-bit
# base keeps ids unique within a process and collision-negligible across
# processes; trace ids (one per query) stay fully random.
_ID_BASE = int.from_bytes(os.urandom(8), "big")
_id_counter = itertools.count()


def _span_id_hex(n: int) -> str:
    return f"{(_ID_BASE ^ n) & 0xFFFFFFFFFFFFFFFF:016x}"


def new_span_id() -> str:
    return _span_id_hex(next(_id_counter))


# Trace ids (one per query) come from a PRNG seeded once from urandom —
# same per-query-syscall argument; 128 random bits keep cross-driver
# collisions negligible. Seeded explicitly (daftlint DTL003 discipline).
_TRACE_RNG = random.Random(int.from_bytes(os.urandom(16), "big"))


def new_trace_id() -> str:
    return f"{_TRACE_RNG.getrandbits(128):032x}"


# Thread-CPU clock with a perf_counter guard: CLOCK_THREAD_CPUTIME_ID is a
# real syscall (no vDSO — ~1µs normally, ~70µs under sandboxed kernels),
# while perf_counter is vDSO-cheap. Adjacent frame boundaries in a pull
# chain (parent.begin → child.begin, child.end → parent.end) are µs apart,
# so one syscall serves the whole cluster; boundaries of REAL work (pulls
# long enough to matter) always exceed the window and read fresh. The
# attribution fuzz this introduces is bounded by the window itself.
_CPU_CACHE_WINDOW_NS = 100_000
_cpu_cache = threading.local()


def _thread_cpu_ns() -> int:
    c = _cpu_cache
    pc = time.perf_counter_ns()
    if pc - getattr(c, "pc", -_CPU_CACHE_WINDOW_NS) < _CPU_CACHE_WINDOW_NS:
        return c.value
    v = time.thread_time_ns()
    c.value = v
    c.pc = time.perf_counter_ns()
    return v


# Per-PULL CPU sampling is self-calibrating: on normal kernels the thread
# clock costs ~1µs and every pull gets an exact CPU delta; under sandboxed
# kernels (gVisor-style) the same read costs 50µs+, which alone would blow
# the <2% overhead budget — there, per-pull sampling switches off and CPU
# is recorded at TASK granularity only (two reads per task). Override with
# DAFT_PROFILE_CPU=1 (force per-pull) / =0 (task-level only).
_CPU_CLOCK_BUDGET_NS = 5_000
_sample_cpu: Optional[bool] = None


def cpu_sampling_enabled() -> bool:
    global _sample_cpu
    if _sample_cpu is None:
        from daft_tpu.config import daft_env

        raw = (daft_env("DAFT_PROFILE_CPU") or "").strip().lower()
        if raw and raw != "auto":
            _sample_cpu = raw not in ("0", "false", "no", "off")
        else:
            t0 = time.perf_counter_ns()
            for _ in range(4):
                time.thread_time_ns()
            _sample_cpu = \
                (time.perf_counter_ns() - t0) / 4 < _CPU_CLOCK_BUDGET_NS
    return _sample_cpu

# --------------------------------------------------------------------- #
# Enablement                                                            #
# --------------------------------------------------------------------- #
#: Task profilers currently open in THIS process. The note_* hot-path hooks
#: gate on this plain int so the disabled path costs one comparison and
#: allocates nothing (the metrics plane's noop-child discipline).
_active_count = 0
_active_lock = threading.Lock()

#: Per-query profiling request set by ``df.collect(profile=...)`` — a
#: :class:`ProfileRequest` (export path + result handle), None when the
#: ambient scope requests no profiling.
_request: contextvars.ContextVar[Optional["ProfileRequest"]] = \
    contextvars.ContextVar("daft_profile_request", default=None)

#: The ambient (trace_id, parent span_id) pair Tasks capture at creation
#: (``Task.trace_ctx`` default_factory) — set by the distributed runner
#: around plan execution so the planner needs no profiler plumbing.
_trace_ctx: contextvars.ContextVar[Optional[Tuple[str, str]]] = \
    contextvars.ContextVar("daft_trace_ctx", default=None)

#: The ambient TaskProfiler: set by ``TaskProfiler.task_scope`` and COPIED
#: into executor pool threads (contextvars propagate through the executor's
#: ambient-context submission), so tallies from parallel morsel workers
#: still reach the task even when no operator frame is on their stack.
_current_profiler: contextvars.ContextVar[Optional["TaskProfiler"]] = \
    contextvars.ContextVar("daft_current_profiler", default=None)

_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_trace_ctx() -> Optional[Tuple[str, str]]:
    """The ambient trace context, or None outside a profiled query — the
    ``Task.trace_ctx`` default_factory."""
    return _trace_ctx.get()


@contextlib.contextmanager
def trace_scope(profile: Optional["QueryProfile"]):
    """Make ``profile``'s trace context ambient (Task creation scope)."""
    if profile is None:
        yield
        return
    token = _trace_ctx.set(profile.trace_ctx)
    try:
        yield
    finally:
        _trace_ctx.reset(token)


class ProfileRequest:
    """Handle yielded by :func:`collect_profile`: ``.profile`` is set to the
    scope's finished QueryProfile at end_query — a race-free alternative to
    the process-global :func:`last_profile` (a concurrently finishing
    profiled query can replace the global between collect and read)."""

    __slots__ = ("path", "profile")

    def __init__(self, path: Optional[str]):
        self.path = path
        self.profile: Optional["QueryProfile"] = None


@contextlib.contextmanager
def collect_profile(path: Optional[str] = None):
    """Mark queries materialized inside this scope for profiling; ``path``
    additionally writes the Chrome trace-event JSON there at query end.
    Yields a :class:`ProfileRequest` whose ``.profile`` holds the scope's
    own finished profile."""
    req = ProfileRequest(path)
    token = _request.set(req)
    try:
        yield req
    finally:
        _request.reset(token)


@contextlib.contextmanager
def _activation():
    global _active_count
    with _active_lock:
        _active_count += 1
    try:
        yield
    finally:
        with _active_lock:
            _active_count -= 1


# --------------------------------------------------------------------- #
# Span wire format                                                      #
# --------------------------------------------------------------------- #
def span_to_wire(span: Span) -> dict:
    """JSON/pickle-safe span for the task-reply / heartbeat wires."""
    return {"name": span.name, "trace_id": span.trace_id,
            "span_id": span.span_id, "parent_id": span.parent_id,
            "start_ns": span.start_ns, "end_ns": span.end_ns,
            "status": span.status, "attributes": dict(span.attributes)}


def span_from_wire(d: dict) -> Span:
    return Span(name=d.get("name", ""), trace_id=d.get("trace_id", ""),
                span_id=d.get("span_id", ""), parent_id=d.get("parent_id"),
                start_ns=int(d.get("start_ns", 0)),
                end_ns=int(d.get("end_ns", 0)),
                status=d.get("status", "OK"),
                attributes=dict(d.get("attributes") or {}))


# --------------------------------------------------------------------- #
# Worker clock skew (heartbeat RTT-midpoint estimate)                   #
# --------------------------------------------------------------------- #
_clock_lock = threading.Lock()
# worker_id -> (offset, rtt, consecutive_rejections)
_WORKER_CLOCKS: Dict[str, Tuple[int, int, int]] = {}
# After this many consecutive too-noisy samples, accept one anyway: the
# RTT increase is evidently the new normal (route change, lasting load),
# and a frozen offset lets perf_counter drift (tens of ppm) walk the
# worker's spans off the timeline for the daemon's remaining lifetime.
_CLOCK_REANCHOR_AFTER = 8


def record_worker_clock(worker_id: str, remote_now_ns: int,
                        t0_ns: int, t1_ns: int) -> None:
    """Fold one heartbeat's clock sample in: the worker read its span clock
    once while the driver's request was in flight, so the best estimate of
    the driver-time of that read is the RTT midpoint ``(t0+t1)/2``; the
    difference is the worker's span-clock offset. Lower-RTT samples are
    sharper estimates, so a much-noisier sample never replaces a crisp one
    (drift still tracks: samples within 1.5x of the stored RTT refresh it,
    and a run of rejections re-anchors so a PERMANENT RTT shift can't
    freeze the offset forever)."""
    offset = int(remote_now_ns) - (int(t0_ns) + int(t1_ns)) // 2
    rtt = max(int(t1_ns) - int(t0_ns), 0)
    with _clock_lock:
        prev = _WORKER_CLOCKS.get(worker_id)
        if prev is None or rtt <= prev[1] * 1.5 \
                or prev[2] + 1 >= _CLOCK_REANCHOR_AFTER:
            _WORKER_CLOCKS[worker_id] = (offset, rtt, 0)
        else:
            _WORKER_CLOCKS[worker_id] = (prev[0], prev[1], prev[2] + 1)


def worker_clock_offsets() -> Dict[str, int]:
    with _clock_lock:
        return {wid: rec[0] for wid, rec in _WORKER_CLOCKS.items()}


def reset_worker_clocks() -> None:
    with _clock_lock:
        _WORKER_CLOCKS.clear()


# --------------------------------------------------------------------- #
# Worker-side span buffer (daemon heartbeat piggyback)                  #
# --------------------------------------------------------------------- #
_buffer_lock = threading.Lock()
_WORKER_BUFFER: List[dict] = []
_MAX_BUFFERED = 10_000
_BUFFER_DROPPED: Dict[str, int] = {}  # query_id -> overflow-dropped spans

#: Synthetic wire entry accounting for spans the bounded worker buffer had
#: to discard (driver paused longer than the buffer's worth of work). The
#: driver folds it into the trace's ``dropped_spans`` tally instead of
#: rendering it — a silent gap would read as "those operators never ran".
DROP_MARKER = "daft.profile.dropped"


def buffer_spans(wires: List[dict]) -> None:
    """TaskProfiler sink inside daemon processes: completed spans land here
    the moment they finish, so the next ping OR task reply — whichever
    comes first — ships them. Bounded: a driver that never drains (died)
    must not grow the worker without limit; overflow is COUNTED per query
    and the tally ships with the next drain."""
    with _buffer_lock:
        room = _MAX_BUFFERED - len(_WORKER_BUFFER)
        if room > 0:
            _WORKER_BUFFER.extend(wires[:room])
        for w in wires[max(room, 0):]:
            qid = str((w.get("attributes") or {}).get("query_id") or "")
            _BUFFER_DROPPED[qid] = _BUFFER_DROPPED.get(qid, 0) + 1


def drain_worker_buffer() -> List[dict]:
    with _buffer_lock:
        out = list(_WORKER_BUFFER)
        _WORKER_BUFFER.clear()
        dropped = dict(_BUFFER_DROPPED)
        _BUFFER_DROPPED.clear()
    for qid, n in dropped.items():
        out.append({"name": DROP_MARKER,
                    "attributes": {"query_id": qid, "dropped_spans": n}})
    return out


def iter_with_profiler_scope(gen, profiler: Optional["TaskProfiler"]):
    """Drain ``gen`` with ``profiler`` ambient during each resumption only —
    same shape as ``context.iter_with_frozen_clock`` / cancellation's
    ``iter_with_cancel_scope``: set/reset around every ``next()`` so
    interleaved lazy queries on one thread can't clobber each other's
    profiler (the paired ``task_scope(ambient=False)`` keeps the span open
    for the generator's whole lifetime without touching the contextvar)."""
    if profiler is None:
        yield from gen
        return
    while True:
        token = _current_profiler.set(profiler)
        try:
            try:
                item = next(gen)
            finally:
                _current_profiler.reset(token)
        except StopIteration:
            return
        yield item


# --------------------------------------------------------------------- #
# Hot-path attribution hooks                                            #
# --------------------------------------------------------------------- #
def note_permit_wait(seconds: float) -> None:
    """Attribute a memory-permit wait to the operator whose pull is on this
    thread's frame stack (falling back to the ambient task profiler)."""
    if not _active_count:
        return
    st = getattr(_tls, "stack", None)
    if st:
        st[-1].permit_wait_ns += int(seconds * 1e9)
        return
    prof = _current_profiler.get()
    if prof is not None:
        prof.tally("permit_wait_ns", int(seconds * 1e9))


def note_spill(nbytes: int) -> None:
    if not _active_count:
        return
    st = getattr(_tls, "stack", None)
    if st:
        st[-1].spill_bytes += int(nbytes)
        return
    prof = _current_profiler.get()
    if prof is not None:
        prof.tally("spill_bytes", int(nbytes))


def note_device(rows: int, fused: bool) -> None:
    """Record the eval path taken (device XLA vs numpy fallback) for the
    ambient operator/task — pool threads resolve through the contextvar."""
    if not _active_count:
        return
    field = "device_rows" if fused else "fallback_rows"
    st = getattr(_tls, "stack", None)
    if st:
        setattr(st[-1], field, getattr(st[-1], field) + int(rows))
        return
    prof = _current_profiler.get()
    if prof is not None:
        prof.tally(field, int(rows))


# --------------------------------------------------------------------- #
# Operator frames + TaskProfiler (worker side)                          #
# --------------------------------------------------------------------- #
class _OpFrame:
    """Mutable per-operator accumulator behind one operator span.

    Two timing modes feed ONE frame (and so one span per plan node):

    * **pull timing** (serial operators, blocking sinks) — the executor's
      morsel loop brackets ``next(child)`` with begin_pull/end_pull on the
      consumer thread; busy/cpu measure the pull chain as before.
    * **worker timing** (pipeline stages) — every stage worker runs the
      morsel kernel through :meth:`run_timed`, which measures wall/CPU
      tight around the kernel on the worker thread and aggregates under
      the frame lock. Concurrent per-morsel walls SUM (they are work, and
      may legitimately exceed the span's open interval on multi-core);
      the consumer-side pull times degrade to queue-wait attribution and
      export separately as ``consumer_wait_ns``, so inclusive time is
      never double-counted between an operator's own span and its
      parent's (operator_table subtracts a stage child's *consumer-
      visible* wait from the parent, not its parallel work).
    """

    __slots__ = ("span", "busy_ns", "cpu_ns", "morsels", "rows_out",
                 "bytes_out", "spill_bytes", "permit_wait_ns",
                 "device_rows", "fallback_rows", "_t0", "_c0",
                 "_row_width", "_sample_cpu", "work_ns", "work_cpu_ns",
                 "work_morsels", "self_timed", "_lock")

    def __init__(self, span: Span):
        self.span = span
        self._sample_cpu = cpu_sampling_enabled()
        self.busy_ns = 0
        self.cpu_ns = 0
        self.morsels = 0
        self.rows_out = 0
        self.bytes_out = 0
        self.spill_bytes = 0
        self.permit_wait_ns = 0
        self.device_rows = 0
        self.fallback_rows = 0
        self.work_ns = 0
        self.work_cpu_ns = 0
        self.work_morsels = 0
        self.self_timed = False
        self._lock = threading.Lock()
        self._t0 = 0
        self._c0 = 0
        self._row_width = 0.0

    def begin_pull(self) -> None:
        _stack().append(self)
        self._t0 = time.perf_counter_ns()
        if self._sample_cpu:
            self._c0 = _thread_cpu_ns()

    def end_pull(self) -> None:
        self.busy_ns += time.perf_counter_ns() - self._t0
        if self._sample_cpu:
            self.cpu_ns += _thread_cpu_ns() - self._c0
        st = _stack()
        # Identity-checked pop: a frame whose pull raised may unwind through
        # several frames at once; never pop someone else's entry.
        if st and st[-1] is self:
            st.pop()

    def run_timed(self, fn, item):
        """Run one morsel kernel on a stage WORKER thread, attributing its
        wall + thread-CPU to this frame. Local clocks + a locked add keep
        concurrent workers race-free; the frame also rides this thread's
        attribution stack so note_spill/note_permit_wait/note_device land
        on the right operator from pool threads."""
        st = _stack()
        st.append(self)
        t0 = time.perf_counter_ns()
        c0 = _thread_cpu_ns() if self._sample_cpu else 0
        try:
            return fn(item)
        finally:
            dt = time.perf_counter_ns() - t0
            dc = (_thread_cpu_ns() - c0) if self._sample_cpu else 0
            with self._lock:
                self.work_ns += dt
                self.work_cpu_ns += dc
                self.work_morsels += 1
                self.self_timed = True
            if st and st[-1] is self:
                st.pop()

    @contextlib.contextmanager
    def attributing(self):
        """Ride this thread's attribution stack without being timed: for a
        stage's feeder thread, whose pull of the child is the child's time but
        whose own device span (``udf.pull``) names this operator."""
        st = _stack()
        st.append(self)
        try:
            yield
        finally:
            if st and st[-1] is self:
                st.pop()

    def add_worker_output(self, rows: int, mp) -> None:
        """Output accounting from a stage WORKER thread (fused-chain member
        operators record their per-node output inside the composed morsel
        fn): same bookkeeping as :meth:`add_output`, under the frame lock
        because concurrent workers race on the counters."""
        with self._lock:
            self.add_output(rows, mp)

    def add_output(self, rows: int, mp) -> None:
        """Per-morsel output accounting. ``size_bytes()`` walks every
        column buffer, so bytes are SAMPLED (first morsel, then every
        16th) and extrapolated by row width between samples — morsels of
        one operator are near-uniform, and exact-per-morsel byte walks
        would cost more than the rest of the frame combined."""
        self.morsels += 1
        self.rows_out += rows
        if (self.morsels & 0xF) == 1:
            nbytes = mp.size_bytes()
            if rows:
                self._row_width = nbytes / rows
            self.bytes_out += nbytes
        else:
            self.bytes_out += int(rows * self._row_width)


class TaskProfiler:
    """Per-task span collector on a worker (or the driver, for the native
    runner). Spans parent onto the shipped ``(trace_id, parent span_id)``
    context so the driver's exporter assembles ONE trace per query. Finished
    spans go to ``sink`` immediately (daemon buffer / driver store) or stay
    in a local buffer drained onto the task reply."""

    def __init__(self, trace_id: str, parent_span_id: Optional[str],
                 query_id: str, worker_id: str = "driver",
                 sink: Optional[Callable[[List[dict]], None]] = None):
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.query_id = query_id
        self.worker_id = worker_id
        self._sink = sink
        self._lock = threading.Lock()
        self._buffer: List[dict] = []
        self._root: Optional[Span] = None
        self._tallies: Dict[str, int] = {}

    # -- plumbing ---------------------------------------------------------
    def tally(self, key: str, value: int) -> None:
        """Task-level accumulator for attributions that could not reach an
        operator frame (pool threads); exported on the task root span."""
        with self._lock:
            self._tallies[key] = self._tallies.get(key, 0) + value

    def _finish(self, span: Span) -> None:
        span.attributes.setdefault("query_id", self.query_id)
        span.attributes.setdefault("worker_id", self.worker_id)
        wire = span_to_wire(span)
        if self._sink is not None:
            self._sink([wire])
            return
        with self._lock:
            self._buffer.append(wire)

    def drain(self) -> List[dict]:
        with self._lock:
            out, self._buffer = self._buffer, []
        return out

    def _new_span(self, name: str, parent_id: Optional[str],
                  attrs: Dict[str, Any]) -> Span:
        return Span(name=name, trace_id=self.trace_id,
                    span_id=new_span_id(), parent_id=parent_id,
                    start_ns=span_clock_ns(), attributes=attrs)

    def _parent_id(self) -> Optional[str]:
        st = getattr(_tls, "stack", None)
        if st:
            return st[-1].span.span_id
        if self._root is not None:
            return self._root.span_id
        return self.parent_span_id

    # -- span openers (context-manager API only: daftlint DTL009) ---------
    @contextlib.contextmanager
    def task_scope(self, task=None, name: str = "daft.task.run",
                   ambient: bool = True, **attrs):
        """Root span covering the whole task execution on this worker.

        ``ambient=False`` skips publishing this profiler on the ambient
        contextvar — required when the scope lives inside a GENERATOR
        (native runner): a set() executed during a resumption mutates the
        caller's shared context (generators own no Context of their own),
        so interleaved lazy queries would clobber each other and a close
        from a GC thread would reset a foreign token. Such callers pair
        this with :func:`iter_with_profiler_scope`, which set/resets
        around every ``next()`` instead."""
        if task is not None:
            attrs.setdefault("task_id", task.task_id)
            attrs.setdefault("partition_idx", task.partition_idx)
            attrs.setdefault("attempt", getattr(task, "attempt", 0))
        span = self._new_span(name, self.parent_span_id, attrs)
        self._root = span
        token = _current_profiler.set(self) if ambient else None
        # Task-level CPU is always recorded (two clock reads per task):
        # the per-pull sampling below it is what self-calibrates away on
        # expensive-clock kernels.
        cpu0 = time.thread_time_ns()
        try:
            with _activation():
                yield span
        except BaseException as e:  # noqa: BLE001 — annotate + re-raise
            if not isinstance(e, GeneratorExit):
                # GeneratorExit is normal early close (limit pushdown); a
                # real failure exports a PARTIAL span so a worker dying
                # mid-task still shows up on the timeline.
                span.status = "ERROR"
                span.attributes["error"] = repr(e)
                span.attributes["partial"] = True
            raise
        finally:
            if token is not None:
                _current_profiler.reset(token)
            span.end_ns = span_clock_ns()
            span.attributes["cpu_ns"] = time.thread_time_ns() - cpu0
            with self._lock:
                tallies = dict(self._tallies)
            for k, v in tallies.items():
                span.attributes[k] = v
            self._finish(span)

    @contextlib.contextmanager
    def operator_span(self, op: str, node_id: str):
        """One span per operator iterator; yields the mutable frame the
        executor's morsel loop accumulates into."""
        span = self._new_span(f"daft.op.{op}", self._parent_id(),
                              {"operator": op, "plan_node": node_id})
        frame = _OpFrame(span)
        try:
            yield frame
        except BaseException as e:  # noqa: BLE001 — annotate + re-raise
            if not isinstance(e, GeneratorExit):
                span.status = "ERROR"
                span.attributes["error"] = repr(e)
            raise
        finally:
            span.end_ns = span_clock_ns()
            a = span.attributes
            if frame.self_timed:
                # Stage-timed operator: busy/cpu are worker-side WORK
                # (summed across concurrent pulls — can exceed the span
                # interval); the consumer-side pull time is queue wait,
                # exported separately so parents subtract the wait they
                # actually saw instead of parallel work they never paid.
                a["busy_ns"] = frame.work_ns
                a["cpu_ns"] = frame.work_cpu_ns
                a["consumer_wait_ns"] = frame.busy_ns
                a["worker_morsels"] = frame.work_morsels
                a["self_timed"] = True
            else:
                a["busy_ns"] = frame.busy_ns
                a["cpu_ns"] = frame.cpu_ns
            a["morsels"] = frame.morsels
            a["rows_out"] = frame.rows_out
            a["bytes_out"] = frame.bytes_out
            for k in ("spill_bytes", "permit_wait_ns", "device_rows",
                      "fallback_rows"):
                v = getattr(frame, k)
                if v:
                    a[k] = v
            self._finish(span)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Generic child span (input binding, shuffle fetch, …)."""
        span = self._new_span(name, self._parent_id(), attrs)
        try:
            yield span
        except BaseException as e:  # noqa: BLE001 — annotate + re-raise
            if not isinstance(e, GeneratorExit):
                span.status = "ERROR"
                span.attributes["error"] = repr(e)
            raise
        finally:
            span.end_ns = span_clock_ns()
            self._finish(span)


    def _emit_device_span(self, sp: "device_span") -> None:
        """A finished :class:`device_span` as a span of this task's trace: the
        child of its enclosing device span, else of the operator whose pull is
        on this thread, and drawn on that operator's lane."""
        attrs = dict(sp.count, thread=sp.thread)
        frames = getattr(_tls, "stack", None)
        if frames:
            attrs["operator"] = frames[-1].span.attributes.get("operator")
        if sp.error:
            attrs["error"] = True
        parent_id = _span_id_hex(sp.parent) if sp.parent else self._parent_id()
        self._finish(Span(name=sp.name, trace_id=self.trace_id,
                          span_id=_span_id_hex(sp.span_id), parent_id=parent_id,
                          start_ns=sp.start_ns, end_ns=sp.end_ns, attributes=attrs,
                          status="ERROR" if sp.error else "OK"))


def task_profiler_for(trace_ctx, query_id: str, worker_id: str,
                      sink: Optional[Callable[[List[dict]], None]] = None
                      ) -> Optional[TaskProfiler]:
    """The worker-side profiler for a task's shipped trace context, or
    None when the task carries none (the query isn't profiled) — the one
    construction path for all three worker kinds."""
    if not trace_ctx:
        return None
    return TaskProfiler(trace_ctx[0], trace_ctx[1], query_id,
                        worker_id=worker_id, sink=sink)


def maybe_span(prof: Optional[TaskProfiler], name: str, **attrs):
    """Nullcontext when ``prof`` is None, else the named child span — for
    conditionally-profiled blocks at worker call sites."""
    if prof is None:
        return contextlib.nullcontext()
    # daftlint: disable=DTL009 -- returned into the caller's with-statement
    return prof.span(name, **attrs)


def profiled_task_scope(prof: Optional[TaskProfiler], task=None, **kw):
    """Nullcontext when ``prof`` is None, else the worker-side task root
    span — the ONE conditional-entry choreography every wire path
    (LocalWorker, process worker, daemon, native runner) shares, so a
    task-span change lands identically on all of them. ``kw`` passes
    through to :meth:`TaskProfiler.task_scope` (``name=``, ``ambient=``,
    span attributes)."""
    if prof is None:
        return contextlib.nullcontext()
    # daftlint: disable=DTL009 -- returned into the caller's with-statement
    return prof.task_scope(task, **kw)


# --------------------------------------------------------------------- #
# Device-path spans (flight recorder + children of the operator span)   #
# --------------------------------------------------------------------- #
#: The newest finished device-path spans of this process, oldest first.
#: At 8 to 16 spans a device batch that is the last 500 to 1,000 batches.
DEVICE_SPAN_RING = 8192
# No lock: a deque's append is atomic, and the one reader copies with a retry.
_device_ring: "deque[device_span]" = deque(maxlen=DEVICE_SPAN_RING)


class device_span:
    """``with device_span("provider.stage", bytes=n) as sp:`` records one
    span of the device path: the UDF operator's pull and call, image
    preprocessing, model set-up, and pad/stage/dispatch/fetch of each device
    batch. Batch granularity only (at most 16 a device batch); there is no
    switch, and a span costs about two microseconds.

    The finished span is appended to the process-wide ring
    (:func:`recent_device_spans`) always, and under an ambient
    :class:`TaskProfiler` it is also emitted into the query's trace, as a
    child of the enclosing device span or else of the operator span whose
    pull is on this thread. ``count`` holds the span's counters; the caller
    may add to it until the span closes (``sp.count["rows"] = n``). Times
    are on :func:`~daft_tpu.tracing.span_clock_ns`; ``span_id`` and
    ``parent`` are process-local integers (``parent`` is 0 for a span that
    no device span encloses on its thread); a span left by an exception has
    ``error`` true.
    """

    __slots__ = ("name", "count", "start_ns", "end_ns", "span_id", "parent",
                 "query_id", "thread", "error", "_open")

    def __init__(self, name: str, **counters):
        self.name = name
        self.count = counters
        self.end_ns = 0
        self.error = False

    def __enter__(self) -> "device_span":
        try:
            open_spans = _tls.device_spans
        except AttributeError:
            open_spans = _tls.device_spans = []
        self._open = open_spans
        self.parent = open_spans[-1].span_id if open_spans else 0
        self.span_id = next(_id_counter)
        self.thread = threading.get_ident()
        prof = _current_profiler.get()
        if prof is not None:
            self.query_id = prof.query_id
        else:
            token = current_token()
            self.query_id = token.query_id if token is not None else ""
        open_spans.append(self)
        self.start_ns = span_clock_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end_ns = span_clock_ns()
        self.error = exc_type is not None and exc_type is not GeneratorExit
        open_spans = self._open
        if open_spans and open_spans[-1] is self:
            open_spans.pop()
        _device_ring.append(self)
        prof = _current_profiler.get()
        if prof is not None:
            prof._emit_device_span(self)
        return False

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__[:-1]}


def _copied(ring: deque) -> list:
    while True:
        try:
            return list(ring)
        except RuntimeError:  # another thread appended during the copy
            continue


def recent_device_spans() -> List[device_span]:
    """A copy of the ring: the newest ``DEVICE_SPAN_RING`` finished device-path
    spans of this process, oldest first (by the time they closed, so a parent
    follows its children). Read it after a slow or stalled batch, or after a
    measured window, without having asked for a profile beforehand."""
    return _copied(_device_ring)


def newest_device_span(name: str) -> Optional[device_span]:
    """The span called ``name`` that closed last, or None: the counters of the
    last forward are ``newest_device_span("provider.forward").count``."""
    return next((sp for sp in reversed(recent_device_spans()) if sp.name == name), None)


def open_device_span(name: str) -> Optional[device_span]:
    """The innermost span called ``name`` that is open on this thread, or None:
    for code that runs below a span without being handed it (a model's choice
    of path while its forward traces is a counter of ``provider.forward``)."""
    open_spans = getattr(_tls, "device_spans", ())
    return next((sp for sp in reversed(open_spans) if sp.name == name), None)


# --------------------------------------------------------------------- #
# Compile log (which program was traced, lowered, compiled or loaded)   #
# --------------------------------------------------------------------- #
#: The newest entries of the compile log: three a program compiled and one a function traced outside any program
#: (every ``jnp`` call under ``eval_shape``): some 2,400 by a decoder's first steady row, most of them such traces.
COMPILE_LOG = 16384
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
#: ``jax.monitoring``'s duration events -> (the log's kind, the seconds' and the count's counter on the open span).
_COMPILE_EVENTS = {
    _TRACE_EVENT: ("trace", "trace_s", None),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower", "lower_s", None),
    "/jax/core/compile/backend_compile_duration": ("compile", "compile_s", "compiles"),
    "/jax/compilation_cache/cache_retrieval_time_sec": ("cache_load", "cache_load_s", "cache_loads"),
}
_compile_log: "deque[Tuple[int, str, float, str]]" = deque(maxlen=COMPILE_LOG)


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    """One stage of bringing a program to the device, as JAX reports it on the
    thread that asked for the program: logged, and added to the counters of the
    innermost device span open on that thread (``serve.dispatch`` for the decode
    program's first call, ``provider.forward`` for an embedder's)."""
    found = _COMPILE_EVENTS.get(event)
    if found is None:
        return
    kind, seconds_key, count_key = found
    if kind == "trace":
        _tls.tracing = depth = max(getattr(_tls, "tracing", 0) - 1, 0)
        if depth:
            return  # a jitted function traced inside another's trace, whose seconds hold this one's
    elif kind == "cache_load":
        _tls.loaded_from_cache = True
    elif kind == "compile" and getattr(_tls, "loaded_from_cache", False):
        _tls.loaded_from_cache = False
        return  # JAX times the backend around its cache too: this is the load just logged, not a compile
    seconds = float(seconds)
    open_spans = getattr(_tls, "device_spans", None)
    sp = open_spans[-1] if open_spans else None
    _compile_log.append((span_clock_ns(), kind, seconds, sp.name if sp is not None else ""))
    if sp is not None:
        sp.count[seconds_key] = sp.count.get(seconds_key, 0.0) + seconds
        if count_key:
            sp.count[count_key] = sp.count.get(count_key, 0) + 1


def _on_jax_scalar(event: str, _value, **_kw) -> None:
    """JAX raises a stage's event as a scalar when the stage begins: traces nest
    (every ``jnp`` function is jitted), and only the outermost is logged."""
    if event == _TRACE_EVENT:
        _tls.tracing = getattr(_tls, "tracing", 0) + 1


def recent_compiles() -> List[Tuple[int, str, float, str]]:
    """The compile log, oldest first: ``(span_clock_ns() when the stage ended,
    kind, seconds, name of the innermost device span open on that thread or
    "")`` for every program this process traced (``trace``), lowered
    (``lower``), compiled (``compile``) or loaded from the persistent compile
    cache (``cache_load``). Read it after a first row that took a minute, or
    after a steady state that should have compiled nothing; the same seconds
    are on the spans (``trace_s``, ``lower_s``, ``compile_s``, ``cache_load_s``,
    ``compiles``, ``cache_loads``), so ``collect(profile=...)`` shows them."""
    return _copied(_compile_log)


if not globals().get("_listening"):  # a reload keeps the module's dictionary, and JAX the listener
    import jax.monitoring

    # Looked up by name at each event, so that a reloaded module's functions and log are the ones written to.
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: _on_jax_duration(event, seconds, **kw))
    jax.monitoring.register_scalar_listener(lambda event, value, **kw: _on_jax_scalar(event, value, **kw))
    _listening = True


def span_clock_offset_ns() -> int:
    """``time.time_ns() - span_clock_ns()``, the two read back to back: add it
    to a device span's times to place them on the wall clock that
    ``jax.profiler`` stamps a device trace with."""
    return time.time_ns() - span_clock_ns()


# --------------------------------------------------------------------- #
# QueryProfile (driver side)                                            #
# --------------------------------------------------------------------- #
class QueryProfile:
    """The driver's per-query trace: root span, driver scheduling spans
    (from dispatcher events), and every worker-shipped span — assembled,
    skew-corrected, and exported as Chrome trace-event JSON."""

    MAX_SPANS = 50_000

    def __init__(self, query_id: str, export_path: Optional[str] = None):
        self.query_id = query_id
        self.export_path = export_path
        self.trace_id = new_trace_id()
        self.root = Span(name="daft.query", trace_id=self.trace_id,
                         span_id=new_span_id(),
                         start_ns=span_clock_ns(),
                         attributes={"query_id": query_id,
                                     "worker_id": "driver"})
        self.finished = False
        self.error: Optional[str] = None
        self.request: Optional[ProfileRequest] = None
        self._lock = threading.Lock()
        self._wires: List[dict] = []
        self._dropped = 0
        # (monotonic stamp | None-when-final, rows) — see timeline().
        self._timeline_cache: Optional[Tuple[Optional[float], dict]] = None
        # (task_id, worker_id) -> open driver dispatch spans, OLDEST first.
        # Speculative attempts normally land on a different worker (the
        # dispatcher excludes the original's), but with one live worker the
        # scheduler's never-strand fallback re-uses it — a LIST per key
        # keeps both attempts' spans instead of overwriting.
        self._open_tasks: Dict[Tuple[str, str], List[Span]] = {}

    @property
    def trace_ctx(self) -> Tuple[str, str]:
        """What rides the wire with every Task: (trace_id, parent span_id)."""
        return (self.trace_id, self.root.span_id)

    def local_task_profiler(self) -> TaskProfiler:
        """A driver-local TaskProfiler feeding this profile directly (the
        native runner's executor runs in-process)."""
        return TaskProfiler(self.trace_id, self.root.span_id, self.query_id,
                            worker_id="driver", sink=self.add_wires)

    # -- ingestion --------------------------------------------------------
    def add_wires(self, wires: Optional[List[dict]],
                  worker_id: Optional[str] = None) -> None:
        if not wires:
            return
        with self._lock:
            for w in wires:
                if w.get("name") == DROP_MARKER:
                    # Worker-side buffer overflow tally, not a span.
                    self._dropped += int(
                        (w.get("attributes") or {}).get("dropped_spans", 0))
                    continue
                if len(self._wires) >= self.MAX_SPANS:
                    self._dropped += 1
                    continue
                attrs = w.get("attributes") or {}
                if worker_id and not attrs.get("worker_id"):
                    w = dict(w, attributes=dict(attrs, worker_id=worker_id))
                self._wires.append(w)

    @contextlib.contextmanager
    def driver_span(self, name: str, **attrs):
        """Driver-side child span of the query root (plan/optimize etc.)."""
        span = Span(name=name, trace_id=self.trace_id,
                    span_id=new_span_id(),
                    parent_id=self.root.span_id, start_ns=span_clock_ns(),
                    attributes=dict(attrs, query_id=self.query_id,
                                    worker_id="driver"))
        try:
            yield span
        except BaseException as e:  # noqa: BLE001 — annotate + re-raise
            if not isinstance(e, GeneratorExit):
                span.status = "ERROR"
                span.attributes["error"] = repr(e)
            raise
        finally:
            span.end_ns = span_clock_ns()
            self.add_wires([span_to_wire(span)])

    # -- dispatcher events (ProfilingSubscriber) --------------------------
    def on_event(self, e) -> None:
        from daft_tpu.subscribers.events import (
            QueryCancelled,
            TaskCompleted,
            TaskScheduled,
        )

        now = span_clock_ns()
        if isinstance(e, TaskScheduled):
            span = Span(name="daft.task", trace_id=self.trace_id,
                        span_id=new_span_id(),
                        parent_id=self.root.span_id, start_ns=now,
                        attributes={"query_id": self.query_id,
                                    "worker_id": "driver",
                                    "task_id": e.task_id,
                                    "on_worker": e.worker_id,
                                    "attempt": getattr(e, "attempt", 0)})
            with self._lock:
                self._open_tasks.setdefault(
                    (e.task_id, e.worker_id), []).append(span)
        elif isinstance(e, TaskCompleted):
            with self._lock:
                stack = self._open_tasks.get((e.task_id, e.worker_id))
                span = None
                if stack:
                    # Match by attempt number, not FIFO order: a retry or
                    # speculative duplicate can land on the SAME worker as
                    # its original, and the later attempt may finish first —
                    # popping the oldest would crown attempt 0 the winner
                    # with attempt 1's completion.
                    want = getattr(e, "attempt", 0)
                    for i, s in enumerate(stack):
                        if s.attributes.get("attempt", 0) == want:
                            span = stack.pop(i)
                            break
                    else:
                        span = stack.pop(0)
                if stack is not None and not stack:
                    del self._open_tasks[(e.task_id, e.worker_id)]
            if span is None and e.error:
                # Already closed (worker-lost reaping beat the future) or
                # pre-profiling: a second ERROR bar would double-report the
                # same dead attempt.
                return
            if span is None:
                # Unmatched completion (scheduled before profiling began):
                # synthesize from the reported duration.
                span = Span(name="daft.task", trace_id=self.trace_id,
                            span_id=new_span_id(),
                            parent_id=self.root.span_id,
                            start_ns=now - int(e.duration_s * 1e9),
                            attributes={"query_id": self.query_id,
                                        "worker_id": "driver",
                                        "task_id": e.task_id,
                                        "on_worker": e.worker_id})
            span.end_ns = now
            if e.error:
                # The attempt died (worker kill, injected fault …): the span
                # still exports — partial, status=ERROR — so a worker lost
                # mid-task is visible on the timeline even though its own
                # in-flight spans never came back.
                span.status = "ERROR"
                span.attributes["error"] = str(e.error)[:200]
                span.attributes["partial"] = True
            else:
                # This attempt WON. Sibling attempts (speculation losers)
                # are cancelled without a TaskCompleted of their own — close
                # them as superseded, not ERROR: a healthy speculated query
                # must not render failure bars on the timeline.
                with self._lock:
                    loser_keys = [k for k in self._open_tasks
                                  if k[0] == e.task_id]
                    losers = [s for k in loser_keys
                              for s in self._open_tasks.pop(k)]
                for loser in losers:
                    loser.end_ns = now
                    loser.attributes["superseded"] = True
                    self.add_wires([span_to_wire(loser)])
            self.add_wires([span_to_wire(span)])
        elif isinstance(e, QueryCancelled):
            self.root.status = "ERROR"
            self.root.attributes["cancel_reason"] = e.reason

    def on_worker_lost(self, worker_id: str) -> None:
        """Close attempts open on a lost worker as ERROR/partial NOW: a
        heartbeat-reaped attempt never gets a TaskCompleted of its own, and
        a later retry's win must not relabel the dead attempt as a healthy
        speculation loser."""
        with self._lock:
            keys = [k for k in self._open_tasks if k[1] == worker_id]
            dead = [s for k in keys for s in self._open_tasks.pop(k)]
        now = span_clock_ns()
        for span in dead:
            span.end_ns = now
            span.status = "ERROR"
            span.attributes["partial"] = True
            span.attributes["error"] = f"worker {worker_id} lost"
            self.add_wires([span_to_wire(span)])

    # -- finalization -----------------------------------------------------
    def finish(self, error: Optional[str] = None) -> None:
        with self._lock:
            still_open = [s for stack in self._open_tasks.values()
                          for s in stack]
            self._open_tasks.clear()
        now = span_clock_ns()
        for span in still_open:
            span.end_ns = now
            span.status = "ERROR"
            span.attributes["partial"] = True
            self.add_wires([span_to_wire(span)])
        self.root.end_ns = now
        if error:
            self.root.status = "ERROR"
            self.root.attributes["error"] = str(error)[:200]
        self.error = error
        self.finished = True
        if self.export_path:
            self.write_chrome_trace(self.export_path)

    # -- assembly / export ------------------------------------------------
    def spans(self) -> List[Span]:
        """Every collected span plus the root, with per-worker clock-skew
        correction applied (heartbeat RTT-midpoint offsets)."""
        offsets = worker_clock_offsets()
        with self._lock:
            wires = list(self._wires)
        root = Span(name=self.root.name, trace_id=self.trace_id,
                    span_id=self.root.span_id, start_ns=self.root.start_ns,
                    end_ns=self.root.end_ns or span_clock_ns(),
                    status=self.root.status,
                    attributes=dict(self.root.attributes))
        out = [root]
        for w in wires:
            s = span_from_wire(w)
            off = offsets.get(str(s.attributes.get("worker_id") or ""), 0)
            if off:
                s.start_ns -= off
                if s.end_ns:
                    s.end_ns -= off
            out.append(s)
        return out

    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON (the Perfetto/chrome://tracing format):
        one process per worker, one thread lane per operator, complete
        ("X") events carrying span attributes as args."""
        spans = sorted(self.spans(), key=lambda s: s.start_ns)
        base = spans[0].start_ns if spans else 0
        pids: Dict[str, int] = {}
        tids: Dict[Tuple[int, str], int] = {}
        events: List[dict] = []
        for s in spans:
            wid = str(s.attributes.get("worker_id") or "driver")
            pid = pids.get(wid)
            if pid is None:
                pid = pids[wid] = len(pids) + 1
                events.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": wid}})
            lane = str(s.attributes.get("operator") or s.name)
            tid = tids.get((pid, lane))
            if tid is None:
                tid = tids[(pid, lane)] = \
                    sum(1 for k in tids if k[0] == pid) + 1
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": lane}})
            end = s.end_ns or s.start_ns
            events.append({
                "ph": "X", "cat": "daft", "name": s.name,
                "pid": pid, "tid": tid,
                "ts": (s.start_ns - base) / 1000.0,
                "dur": max(end - s.start_ns, 0) / 1000.0,
                "args": dict(s.attributes, status=s.status),
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"trace_id": self.trace_id,
                              "query_id": self.query_id,
                              "dropped_spans": self._dropped}}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def operator_table(self, by: str = "operator") -> List[dict]:
        """Per-operator aggregate over the trace's ``daft.op.*`` spans:
        rows/bytes out, inclusive wall, SELF wall/CPU (inclusive minus
        direct children — on a serial chain self sums ≈ query time), spill
        bytes, and memory-permit wait; sorted by self wall descending (the
        EXPLAIN ANALYZE table). ``by="plan_node"`` keys rows on the plan
        node id (``HashJoin#3``) instead of the operator name, so two
        instances of one operator stay attributable — the granularity the
        perf observatory's span-diff reports regress against."""
        ops = [s for s in self.spans() if s.name.startswith("daft.op.")]
        # Parent-child subtraction uses each child's CONSUMER-VISIBLE time:
        # a pull-timed child's busy IS what its parent's pull included, but
        # a stage-timed (self_timed) child's busy is parallel worker WORK
        # the parent never paid — the parent only saw the child's queue
        # wait (consumer_wait_ns). CPU of a stage child burns on pool
        # threads, never inside the parent's pull, so it subtracts as 0.
        child_busy: Dict[str, int] = {}
        child_cpu: Dict[str, int] = {}
        by_id = {s.span_id for s in ops}
        for s in ops:
            p = s.parent_id
            if p in by_id:
                a = s.attributes
                if a.get("self_timed"):
                    visible_busy = int(a.get("consumer_wait_ns", 0))
                    visible_cpu = 0
                else:
                    visible_busy = int(a.get("busy_ns", 0))
                    visible_cpu = int(a.get("cpu_ns", 0))
                child_busy[p] = child_busy.get(p, 0) + visible_busy
                child_cpu[p] = child_cpu.get(p, 0) + visible_cpu
        agg: Dict[str, dict] = {}
        for s in ops:
            a = s.attributes
            op = str(a.get("operator") or s.name)
            key = op if by != "plan_node" else str(a.get("plan_node") or op)
            busy = int(a.get("busy_ns", 0))
            cpu = int(a.get("cpu_ns", 0))
            r = agg.setdefault(key, {
                "operator": op, "rows": 0, "wall_ns": 0, "self_wall_ns": 0,
                "self_cpu_ns": 0, "bytes_out": 0, "spill_bytes": 0,
                "permit_wait_ns": 0, "morsels": 0, "device_rows": 0,
                "fallback_rows": 0})
            if by == "plan_node":
                r["plan_node"] = key
            r["rows"] += int(a.get("rows_out", 0))
            r["morsels"] += int(a.get("morsels", 0))
            r["wall_ns"] += busy
            if a.get("self_timed"):
                # Stage-timed: busy is already SELF work (the kernel never
                # pulls its child — the feeder does), aggregated into the
                # one span this plan node owns.
                r["self_wall_ns"] += busy
                r["self_cpu_ns"] += cpu
            else:
                r["self_wall_ns"] += max(busy - child_busy.get(s.span_id, 0), 0)
                r["self_cpu_ns"] += max(cpu - child_cpu.get(s.span_id, 0), 0)
            r["bytes_out"] += int(a.get("bytes_out", 0))
            r["spill_bytes"] += int(a.get("spill_bytes", 0))
            r["permit_wait_ns"] += int(a.get("permit_wait_ns", 0))
            r["device_rows"] += int(a.get("device_rows", 0))
            r["fallback_rows"] += int(a.get("fallback_rows", 0))
        return sorted(agg.values(), key=lambda r: -r["self_wall_ns"])

    #: The dashboard polls the timeline every second; more rows than this
    #: freezes the browser tab long before they are readable as a Gantt.
    #: Longest-duration spans win — the bottleneck bars are the point.
    MAX_TIMELINE_ROWS = 2_000
    #: While the query still runs, serve a snapshot at most this stale:
    #: rebuilding a near-MAX_SPANS store per 1s poll would monopolize the
    #: dashboard's single-threaded HTTP handler.
    TIMELINE_TTL_S = 0.9

    def timeline(self) -> dict:
        """Flat span rows for the dashboard's Gantt view (ms relative to
        the query root). A FINISHED profile never changes, so its rows are
        built once and cached; a RUNNING one is rebuilt at most once per
        TTL — the dashboard's 1s poll must not re-deserialize a 50k-span
        store on the single-threaded handler."""
        cached = self._timeline_cache
        if cached is not None:
            if self.finished and cached[0] is None:
                return cached[1]
            if cached[0] is not None \
                    and time.monotonic() - cached[0] < self.TIMELINE_TTL_S:
                return cached[1]
        spans = sorted(self.spans(), key=lambda s: s.start_ns)
        base = spans[0].start_ns if spans else 0
        if len(spans) > self.MAX_TIMELINE_ROWS:
            spans = sorted(
                spans,
                key=lambda s: (s.end_ns or s.start_ns) - s.start_ns,
                reverse=True)[:self.MAX_TIMELINE_ROWS]
            spans.sort(key=lambda s: s.start_ns)
        rows = []
        for s in spans:
            end = s.end_ns or s.start_ns
            rows.append({
                "name": s.name,
                "worker": str(s.attributes.get("worker_id") or "driver"),
                "lane": str(s.attributes.get("operator") or s.name),
                "start_ms": (s.start_ns - base) / 1e6,
                "dur_ms": max(end - s.start_ns, 0) / 1e6,
                "status": s.status,
                "rows": s.attributes.get("rows_out"),
            })
        out = {"query_id": self.query_id, "trace_id": self.trace_id,
               "finished": self.finished, "spans": rows}
        # (None, out) = immutable finished snapshot; (stamp, out) = TTL'd.
        self._timeline_cache = (None if self.finished else time.monotonic(),
                                out)
        return out


# --------------------------------------------------------------------- #
# Driver-side store + lifecycle                                         #
# --------------------------------------------------------------------- #
_profiles_lock = threading.Lock()
_PROFILES: Dict[str, QueryProfile] = {}
_FINISHED: "OrderedDict[str, QueryProfile]" = OrderedDict()
_MAX_FINISHED = 8
_LAST: Optional[QueryProfile] = None


class ProfilingSubscriber:
    """Routes dispatcher lifecycle events into the owning QueryProfile."""

    def on_event(self, e) -> None:
        from daft_tpu.subscribers.events import WorkerLost

        if isinstance(e, WorkerLost):
            # No query_id on the event: every active profile closes its
            # attempts open on that worker (ERROR/partial).
            with _profiles_lock:
                profs = list(_PROFILES.values())
            for prof in profs:
                prof.on_worker_lost(e.worker_id)
            return
        qid = getattr(e, "query_id", "")
        if not qid:
            return
        with _profiles_lock:
            prof = _PROFILES.get(qid)
        if prof is not None:
            prof.on_event(e)


_subscriber: Optional[ProfilingSubscriber] = None


def _ensure_subscriber() -> None:
    global _subscriber
    if _subscriber is not None:
        return
    from daft_tpu.context import get_context

    with _profiles_lock:
        if _subscriber is not None:  # double-checked: begin_query races
            return
        sub = ProfilingSubscriber()
        get_context().attach_subscriber(sub)
        _subscriber = sub


def begin_query(query_id: str, cfg=None) -> Optional[QueryProfile]:
    """Open a QueryProfile when profiling is requested — by the ambient
    ``collect(profile=...)`` scope, ``DAFT_PROFILE``, or the config knob.
    Returns None (and costs nothing downstream) otherwise."""
    req = _request.get()
    active = req is not None
    path = req.path if req is not None else None
    if not active:
        from daft_tpu.config import daft_env, daft_env_flag

        # An EXPLICITLY-set DAFT_PROFILE wins in both directions: the env
        # var is the documented live process-wide switch, so DAFT_PROFILE=0
        # must turn profiling off even when the context baked
        # profile_enabled=True at creation. Config decides only when the
        # env var is unset.
        if daft_env("DAFT_PROFILE") is not None:
            active = daft_env_flag("DAFT_PROFILE", False)
        else:
            active = bool(getattr(cfg, "profile_enabled", False))
        # The env/config export path applies only to env/config-triggered
        # profiling: an explicit collect(profile=True) scope asked for an
        # IN-MEMORY trace (and explain-analyze's internal scope must not
        # overwrite a file DAFT_PROFILE_FILE was set to keep).
        if active:
            path = daft_env("DAFT_PROFILE_FILE") \
                or getattr(cfg, "profile_export_path", None)
    if not active:
        return None
    prof = QueryProfile(query_id, export_path=path)
    prof.request = req
    _ensure_subscriber()
    with _profiles_lock:
        _PROFILES[query_id] = prof
    return prof


def force_begin_query(query_id: str,
                      export_path: Optional[str] = None
                      ) -> Optional[QueryProfile]:
    """Open a QueryProfile UNCONDITIONALLY for an already-started query —
    the tail-based auto-profiling entry point (daft_tpu/slo.py): the SLO
    plane decides post-planning that this query's plan fingerprint deserves
    a trace, after begin_query already said no. Idempotent per query id
    (returns the existing profile if one is open); the runner's normal
    end_query finalizes it like any other profile."""
    with _profiles_lock:
        existing = _PROFILES.get(query_id)
        if existing is not None:
            return existing
    prof = QueryProfile(query_id, export_path=export_path)
    _ensure_subscriber()
    with _profiles_lock:
        _PROFILES.setdefault(query_id, prof)
        return _PROFILES[query_id]


def end_query(query_id: str, error: Optional[str] = None) -> Optional[QueryProfile]:
    """Finalize + export the query's profile (root span closed, Chrome
    trace written when a path was configured)."""
    global _LAST
    with _profiles_lock:
        prof = _PROFILES.pop(query_id, None)
    if prof is None:
        return None
    prof.finish(error=error)
    if prof.request is not None:
        # Hand the finished profile back to ITS collect_profile scope —
        # last_profile() is a process-global that a concurrent query's
        # end_query can replace before the caller reads it.
        prof.request.profile = prof
    with _profiles_lock:
        _FINISHED[query_id] = prof
        while len(_FINISHED) > _MAX_FINISHED:
            _FINISHED.popitem(last=False)
        _LAST = prof
    return prof


def last_profile() -> Optional[QueryProfile]:
    """The most recently finished QueryProfile (collect(profile=True))."""
    return _LAST


def profile_for(query_id: str) -> Optional[QueryProfile]:
    with _profiles_lock:
        return _PROFILES.get(query_id) or _FINISHED.get(query_id)


def timeline_json(query_id: str) -> Optional[dict]:
    prof = profile_for(query_id)
    return prof.timeline() if prof is not None else None


def deliver_spans(wires: Optional[List[dict]],
                  worker_id: Optional[str] = None) -> None:
    """Driver-side ingestion of worker span wires (task replies, heartbeat
    piggybacks): routed by each span's ``query_id`` attribute; spans for
    unknown or already-exported queries drop silently."""
    if not wires:
        return
    by_query: Dict[str, List[dict]] = {}
    for w in wires:
        qid = str((w.get("attributes") or {}).get("query_id") or "")
        if qid:
            by_query.setdefault(qid, []).append(w)
    for qid, group in by_query.items():
        with _profiles_lock:
            prof = _PROFILES.get(qid)
        if prof is not None:
            prof.add_wires(group, worker_id=worker_id)
