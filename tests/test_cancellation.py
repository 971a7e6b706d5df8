"""Bounded-time execution: query deadlines, cooperative cancellation, and
IO circuit breakers (daft_tpu/cancellation.py, daft_tpu/io/circuit.py).

Covers the acceptance scenarios: ``df.collect(timeout=t)`` with a
delay-injected shuffle returns DaftTimeoutError within ``t + grace`` with
workers drained and byte-identical results on the no-fault control run; and
an endpoint failing repeatedly opens its circuit breaker (CircuitOpened
event) with queries failing fast — never hanging. Plus the cancellation
races: speculative-execution losers, heartbeat-marked-dead workers, and
deadline expiry during lineage recovery.
"""

import pickle
import threading
import time

import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.cancellation import (
    CancelToken,
    Deadline,
    cancel_scope,
    current_token,
)
from daft_tpu.distributed.faults import FaultInjected, fault_scope
from daft_tpu.distributed.partition_ref import LocalPartitionRef
from daft_tpu.distributed.scheduler import Dispatcher, Scheduler
from daft_tpu.distributed.task import BoundInput, Task
from daft_tpu.distributed.worker import LocalWorker, Worker, WorkerManager
from daft_tpu.errors import (
    DaftCancelledError,
    DaftCircuitOpenError,
    DaftError,
    DaftTimeoutError,
    DaftTransientError,
)
from daft_tpu.io.circuit import (
    CircuitBreaker,
    breaker_for,
    endpoint_of,
    reset_circuit_breakers,
    seed_circuit_jitter,
)
from daft_tpu.io.retry import RetryPolicy, with_retries
from daft_tpu.micropartition import MicroPartition
from daft_tpu.runners.distributed import DistributedRunner
from daft_tpu.subscribers.events import (
    CircuitClosed,
    CircuitOpened,
    QueryCancelled,
    QueryStart,
)

pytestmark = pytest.mark.chaos


class EventTap:
    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def on_event(self, event):
        with self._lock:
            self.events.append(event)

    def of(self, kind):
        with self._lock:
            return [e for e in self.events if isinstance(e, kind)]


@pytest.fixture
def tap():
    ctx = daft_tpu.get_context()
    t = EventTap()
    ctx.attach_subscriber(t)
    yield t
    ctx.detach_subscriber(t)


@pytest.fixture
def dist_runner():
    ctx = daft_tpu.get_context()
    old = ctx._runner
    runner = DistributedRunner(num_workers=3)
    ctx.set_runner(runner)
    yield runner
    runner.manager.shutdown()
    ctx.set_runner(old)


@pytest.fixture(autouse=True)
def _fresh_breakers():
    reset_circuit_breakers()
    yield
    reset_circuit_breakers()
    seed_circuit_jitter(None)


# ------------------------------------------------------------------ #
# Deadline / CancelToken primitives                                    #
# ------------------------------------------------------------------ #
def test_deadline_monotonic_and_wire_reanchor():
    d = Deadline.after(10.0)
    assert 9.0 < d.remaining() <= 10.0
    assert not d.expired()
    # The wire re-anchors remaining budget on the receiver's clock: the
    # monotonic instant itself is meaningless across processes.
    d2 = pickle.loads(pickle.dumps(d))
    assert 9.0 < d2.remaining() <= 10.0
    assert d2.timeout_s == 10.0
    assert Deadline.after(-1.0).expired()


def test_cancel_token_cancel_and_deadline_errors():
    tok = CancelToken(query_id="q1")
    assert tok.error() is None
    tok.check()  # live: no-op
    tok.cancel("user-cancel")
    assert tok.cancelled() and tok.reason == "user-cancel"
    with pytest.raises(DaftCancelledError, match="user-cancel"):
        tok.check("unit test")
    # Deadline-bearing token expires into DaftTimeoutError.
    tok2 = CancelToken(Deadline.after(-0.1), query_id="q2")
    with pytest.raises(DaftTimeoutError, match="deadline"):
        tok2.check()
    assert tok2.remaining() == 0.0


def test_cancel_token_listeners_and_interruptible_wait():
    tok = CancelToken()
    fired = []
    tok.add_listener(lambda: fired.append(1))
    t = threading.Timer(0.1, tok.cancel)
    t.start()
    t0 = time.monotonic()
    assert tok.wait(5.0)  # woken early by the cancel, not the timeout
    assert time.monotonic() - t0 < 2.0
    assert fired == [1]
    tok.add_listener(lambda: fired.append(2))  # late listener fires at once
    assert fired == [1, 2]


def test_cancel_scope_is_ambient():
    assert current_token() is None
    tok = CancelToken()
    with cancel_scope(tok):
        assert current_token() is tok
    assert current_token() is None


def test_maybe_inject_observes_ambient_token():
    """Every fault-injection point doubles as a cancellation checkpoint."""
    from daft_tpu.distributed.faults import maybe_inject

    tok = CancelToken(Deadline.after(-0.1))
    with cancel_scope(tok):
        with pytest.raises(DaftTimeoutError):
            maybe_inject("shuffle.fetch")


def test_injected_delay_is_interruptible():
    """A delay-injected stall wakes at the deadline instead of sleeping
    through it — injected chaos must not defeat bounded-time execution."""
    tok = CancelToken(Deadline.after(0.15))
    t0 = time.monotonic()
    with fault_scope("io.get_object:delay:*:30"):
        with cancel_scope(tok):
            with pytest.raises(DaftTimeoutError):
                from daft_tpu.distributed.faults import maybe_inject

                maybe_inject("io.get_object")
    assert time.monotonic() - t0 < 5.0  # nowhere near the 30s injected delay


# ------------------------------------------------------------------ #
# io/retry.py: budget-aware retries (satellite)                        #
# ------------------------------------------------------------------ #
def test_with_retries_never_sleeps_past_budget():
    """A backoff sleep that would overrun the remaining budget raises the
    LAST error immediately instead of sleeping into certain failure."""
    calls = []

    def boom():
        calls.append(1)
        raise DaftTransientError("blip")

    policy = RetryPolicy(max_retries=5, backoff_base_s=30.0)  # huge sleeps
    t0 = time.monotonic()
    with pytest.raises(DaftTransientError, match="blip"):
        with_retries(boom, policy, deadline=Deadline.after(0.5))
    assert time.monotonic() - t0 < 2.0  # did NOT sleep 30s
    assert len(calls) == 1  # the sleep-overrun raised before a retry


def test_with_retries_uses_ambient_token_deadline():
    def boom():
        raise DaftTransientError("blip")

    tok = CancelToken(Deadline.after(0.3))
    policy = RetryPolicy(max_retries=5, backoff_base_s=30.0)
    t0 = time.monotonic()
    with cancel_scope(tok):
        with pytest.raises(DaftTransientError):
            with_retries(boom, policy)
    assert time.monotonic() - t0 < 2.0


def test_with_retries_cancel_interrupts_sleep():
    tok = CancelToken()

    def boom():
        raise DaftTransientError("blip")

    policy = RetryPolicy(max_retries=3, backoff_base_s=20.0)
    threading.Timer(0.15, tok.cancel).start()
    t0 = time.monotonic()
    with cancel_scope(tok):
        with pytest.raises(DaftCancelledError):
            with_retries(boom, policy)
    assert time.monotonic() - t0 < 5.0  # woke from the 20s sleep on cancel


def test_with_retries_checks_token_before_attempts():
    calls = []
    tok = CancelToken()
    tok.cancel("pre-cancelled")
    with cancel_scope(tok):
        with pytest.raises(DaftCancelledError):
            with_retries(lambda: calls.append(1), RetryPolicy())
    assert not calls  # never even attempted


# ------------------------------------------------------------------ #
# MemoryManager: poison / cancel (satellite)                           #
# ------------------------------------------------------------------ #
def test_memory_manager_poison_wakes_unbounded_waiter():
    from daft_tpu.execution.resource_manager import MemoryManager

    mm = MemoryManager(limit_bytes=100)
    assert mm.acquire(100)
    errors, entered = [], threading.Event()

    def waiter():
        entered.set()
        try:
            mm.acquire(50, timeout=None)  # would block forever
        except DaftError as e:
            errors.append(e)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    entered.wait(2.0)
    time.sleep(0.1)  # let it reach the cond wait
    mm.poison(DaftTimeoutError("query died"))
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert len(errors) == 1 and isinstance(errors[0], DaftTimeoutError)
    # Poison is generation-scoped: the NEXT waiter is untouched.
    mm.release(100)
    assert mm.acquire(50, timeout=0.5)


def test_memory_manager_poison_is_query_scoped():
    """Poisoning query A must not fail query B's waiter: a waiter carrying
    a live token of a DIFFERENT query keeps waiting through the poison."""
    from daft_tpu.execution.resource_manager import MemoryManager

    mm = MemoryManager(limit_bytes=100)
    assert mm.acquire(100)
    tok_b = CancelToken(query_id="query-B")
    got = []

    def waiter_b():
        got.append(mm.acquire(50, timeout=None, token=tok_b))

    t = threading.Thread(target=waiter_b, daemon=True)
    t.start()
    time.sleep(0.1)
    mm.poison(DaftTimeoutError("query A died"), query_id="query-A")
    time.sleep(0.2)
    assert t.is_alive()  # B's waiter survived A's poison
    mm.release(100)  # capacity frees: B acquires normally
    t.join(timeout=5.0)
    assert not t.is_alive() and got == [True]


def test_memory_manager_token_cancel_wakes_waiter():
    from daft_tpu.execution.resource_manager import MemoryManager

    mm = MemoryManager(limit_bytes=100)
    assert mm.acquire(100)
    tok = CancelToken()
    out = []

    def waiter():
        out.append(mm.acquire(50, timeout=None, token=tok))

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.1)
    t0 = time.monotonic()
    tok.cancel()
    t.join(timeout=5.0)
    assert not t.is_alive() and out == [False]
    assert time.monotonic() - t0 < 2.0


def test_memory_manager_token_deadline_bounds_wait():
    from daft_tpu.execution.resource_manager import MemoryManager

    mm = MemoryManager(limit_bytes=100)
    assert mm.acquire(100)
    tok = CancelToken(Deadline.after(0.2))
    t0 = time.monotonic()
    assert mm.acquire(50, timeout=None, token=tok) is False
    assert time.monotonic() - t0 < 2.0


def test_executor_failure_poisons_blocked_sink_threads():
    """The executor's failure path poisons the memory manager so sink
    threads blocked in acquire() don't outlive the dead query."""
    from daft_tpu.execution.resource_manager import get_memory_manager, memory_limit

    with memory_limit(100) as mm:
        assert mm.acquire(100)
        try:
            errors, entered = [], threading.Event()

            def waiter():
                entered.set()
                try:
                    mm.acquire(60, timeout=None)
                except DaftError as e:
                    errors.append(e)

            t = threading.Thread(target=waiter, daemon=True)
            t.start()
            entered.wait(2.0)
            time.sleep(0.1)

            @daft_tpu.udf.func(return_dtype=daft_tpu.DataType.int64())
            def explode(s):
                raise ValueError("kaboom")

            with pytest.raises(DaftError):
                daft_tpu.from_pydict({"x": [1, 2, 3]}).select(
                    explode(col("x"))).to_pydict()
            t.join(timeout=5.0)
            assert not t.is_alive() and len(errors) == 1
        finally:
            mm.release(100)


# ------------------------------------------------------------------ #
# Dispatcher: event-driven wake (satellite) + cancellation             #
# ------------------------------------------------------------------ #
class ScriptedWorker(Worker):
    """Completes every task after a fixed delay (no real execution)."""

    def __init__(self, worker_id, delay):
        from concurrent.futures import Future

        self.worker_id = worker_id
        self.num_slots = 4
        self.delay = delay
        self._active = 0
        self._Future = Future

    def submit(self, task):
        fut = self._Future()
        mp = MicroPartition.from_pydict({"x": [1]})

        def run():
            time.sleep(self.delay)
            if not fut.cancelled():
                fut.set_result([LocalPartitionRef(mp, self.worker_id)])

        threading.Thread(target=run, daemon=True).start()
        return fut

    def active_tasks(self):
        return self._active


def test_dispatcher_wakes_on_async_death_not_poll():
    """A wedged worker marked dead asynchronously unwedges the dispatcher
    promptly via the death listener — not a 5s poll cadence."""
    stuck = ScriptedWorker("stuck", delay=600.0)
    backup = ScriptedWorker("backup", delay=0.02)
    manager = WorkerManager([stuck, backup])
    dispatcher = Dispatcher(Scheduler(manager),
                            cfg=daft_tpu.get_context().execution_config)
    mp = MicroPartition.from_pydict({"x": [0]})
    tasks = [Task(BoundInput(0, mp.schema), [[LocalPartitionRef(mp)]])
             for _ in range(4)]
    threading.Timer(0.3, manager.mark_dead, args=("stuck",),
                    kwargs={"reason": "heartbeat-timeout"}).start()
    t0 = time.monotonic()
    results = dispatcher.run_tasks(tasks)
    elapsed = time.monotonic() - t0
    assert len(results) == 4
    # Old behavior: up to a 5s poll before noticing the death. New: the
    # death listener wakes the wait immediately (~0.3s + rescheduling).
    assert elapsed < 4.0, f"death wake too slow: {elapsed:.2f}s"
    manager.shutdown()


def test_dispatcher_wake_listeners_unhooked_after_run():
    manager = WorkerManager([ScriptedWorker("w0", delay=0.01)])
    dispatcher = Dispatcher(Scheduler(manager),
                            cfg=daft_tpu.get_context().execution_config)
    mp = MicroPartition.from_pydict({"x": [0]})
    for _ in range(3):
        dispatcher.run_tasks([Task(BoundInput(0, mp.schema),
                                   [[LocalPartitionRef(mp)]])])
    # The manager outlives queries: listeners must not accumulate.
    assert manager._death_listeners == []
    manager.shutdown()


def test_dispatcher_deadline_with_wedged_worker_never_hangs():
    """Heartbeat-marked-dead races aside, even a future that NEVER completes
    cannot outlive the query deadline."""
    stuck = ScriptedWorker("stuck", delay=600.0)
    manager = WorkerManager([stuck])
    token = CancelToken(Deadline.after(0.5), query_id="qwedge")
    dispatcher = Dispatcher(Scheduler(manager),
                            cfg=daft_tpu.get_context().execution_config,
                            cancel_token=token)
    mp = MicroPartition.from_pydict({"x": [0]})
    t0 = time.monotonic()
    with pytest.raises(DaftTimeoutError) as ei:
        dispatcher.run_tasks([Task(BoundInput(0, mp.schema),
                                   [[LocalPartitionRef(mp)]],
                                   query_id="qwedge")])
    assert time.monotonic() - t0 < 5.0
    assert ei.value.progress.get("total") == 1
    manager.shutdown()


class RunningStuckWorker(Worker):
    """Future is RUNNING (uncancellable) and never completes — a wedged
    task on a partitioned worker."""

    def __init__(self, worker_id="rstuck"):
        from concurrent.futures import Future

        self.worker_id = worker_id
        self.num_slots = 4
        self._Future = Future

    def submit(self, task):
        fut = self._Future()
        fut.set_running_or_notify_cancel()  # cancel() will now fail
        return fut  # never resolved

    def active_tasks(self):
        return 0


def test_cancel_drain_is_grace_bounded_with_uncancellable_future():
    """The cancellation drain must not wait forever on a RUNNING future
    that never completes: collect(timeout=t) returns within t + grace."""
    manager = WorkerManager([RunningStuckWorker()])
    token = CancelToken(Deadline.after(0.5), query_id="qgrace")
    cfg = daft_tpu.get_context().execution_config.with_changes(
        cancel_drain_grace_s=1.0)
    dispatcher = Dispatcher(Scheduler(manager), cfg=cfg, cancel_token=token)
    mp = MicroPartition.from_pydict({"x": [0]})
    t0 = time.monotonic()
    with pytest.raises(DaftTimeoutError):
        dispatcher.run_tasks([Task(BoundInput(0, mp.schema),
                                   [[LocalPartitionRef(mp)]],
                                   query_id="qgrace")])
    # deadline (0.5) + grace (1.0) + slack — nowhere near a hang.
    assert time.monotonic() - t0 < 5.0
    manager.shutdown()


def test_user_cancel_aborts_dispatch(tap):
    slow = ScriptedWorker("slow", delay=30.0)
    manager = WorkerManager([slow])
    token = CancelToken(query_id="qcancel")
    dispatcher = Dispatcher(Scheduler(manager),
                            cfg=daft_tpu.get_context().execution_config,
                            cancel_token=token)
    mp = MicroPartition.from_pydict({"x": [0]})
    threading.Timer(0.2, token.cancel, args=("user-cancel",)).start()
    t0 = time.monotonic()
    with pytest.raises(DaftCancelledError, match="user-cancel"):
        dispatcher.run_tasks([Task(BoundInput(0, mp.schema),
                                   [[LocalPartitionRef(mp)]],
                                   query_id="qcancel")])
    assert time.monotonic() - t0 < 5.0
    cancelled = tap.of(QueryCancelled)
    assert cancelled and cancelled[0].reason == "user-cancel"
    manager.shutdown()


def test_speculation_losers_dont_block_deadline(tap):
    """Speculative-execution race: the winner finishes, the loser attempt is
    abandoned — and a query deadline longer than the fast path but shorter
    than the straggler still SUCCEEDS."""
    fast = ScriptedWorker("fast", delay=0.02)
    slow = ScriptedWorker("slow", delay=30.0)
    manager = WorkerManager([fast, slow])
    cfg = daft_tpu.get_context().execution_config.with_changes(
        speculative_execution=True, speculative_multiplier=2.0,
        speculative_min_completed=2)
    token = CancelToken(Deadline.after(10.0), query_id="qspecdl")
    dispatcher = Dispatcher(Scheduler(manager), cfg=cfg, cancel_token=token)
    mp = MicroPartition.from_pydict({"x": [0]})
    tasks = [Task(BoundInput(0, mp.schema), [[LocalPartitionRef(mp)]],
                  query_id="qspecdl") for _ in range(6)]
    t0 = time.monotonic()
    results = dispatcher.run_tasks(tasks)
    assert len(results) == 6 and all(r[0].num_rows() == 1 for r in results)
    assert time.monotonic() - t0 < 10.0  # losers never held the query
    manager.shutdown()


# ------------------------------------------------------------------ #
# Acceptance: collect(timeout=...) end to end                          #
# ------------------------------------------------------------------ #
def groupby_df():
    return daft_tpu.from_pydict({
        "a": list(range(60)),
        "b": [f"k{i % 5}" for i in range(60)],
        "c": [float(i) for i in range(60)],
    }).into_partitions(6)


def q(timeout=None):
    return groupby_df().groupby("b").agg(
        col("c").sum().alias("s"), col("a").count().alias("n"),
    ).sort("b").collect(timeout=timeout).to_pydict()


def test_collect_timeout_with_delayed_shuffle(dist_runner, tap):
    """df.collect(timeout=t) with a delay-injected shuffle fails with
    DaftTimeoutError within t + grace, workers drained, and the no-fault
    control run returns byte-identical results."""
    expected = q()
    t0 = time.monotonic()
    with fault_scope("shuffle.fetch:delay:*:30"):
        with pytest.raises(DaftTimeoutError) as ei:
            q(timeout=1.0)
    elapsed = time.monotonic() - t0
    assert elapsed < 6.0, f"timeout not honored: {elapsed:.2f}s"
    assert "deadline" in str(ei.value)
    assert ei.value.progress  # per-task progress rode along
    assert tap.of(QueryCancelled)
    # Workers drained: the pool accepts and completes new work immediately,
    # and the control run is byte-identical.
    assert q() == expected
    # No leaked memory-permit waiters: the global manager is idle.
    from daft_tpu.execution.resource_manager import get_memory_manager

    assert get_memory_manager().used() == 0


def test_collect_timeout_generous_budget_is_noop(dist_runner):
    assert q(timeout=300.0) == q()


def test_native_runner_timeout():
    @daft_tpu.udf.func(return_dtype=daft_tpu.DataType.int64())
    def slow(s):
        time.sleep(0.4)
        return s

    df = daft_tpu.from_pydict({"x": list(range(9))}).into_partitions(3) \
        .select(slow(col("x")))
    t0 = time.monotonic()
    with pytest.raises(DaftTimeoutError):
        df.collect(timeout=0.5)
    assert time.monotonic() - t0 < 5.0


def test_cancel_query_by_id(dist_runner, tap):
    """daft_tpu.cancel_query cancels a running query by id."""
    started = threading.Event()
    qids = []

    class Watcher:
        def on_event(self, e):
            if isinstance(e, QueryStart):
                qids.append(e.query_id)
                started.set()

    ctx = daft_tpu.get_context()
    w = Watcher()
    ctx.attach_subscriber(w)

    def cancel_soon():
        started.wait(10.0)
        time.sleep(0.2)
        daft_tpu.cancel_query(qids[-1], reason="operator-abort")

    try:
        threading.Thread(target=cancel_soon, daemon=True).start()
        with fault_scope("shuffle.fetch:delay:*:30"):
            with pytest.raises(DaftCancelledError, match="operator-abort"):
                q()
    finally:
        ctx.detach_subscriber(w)
    assert daft_tpu.cancel_query("no-such-query") is False


def test_deadline_during_lineage_recovery(dist_runner, tap):
    """Deadline expiry firing DURING lineage recovery: kill a worker so
    recovery starts, pin the recovery's fetches with an injected delay, and
    assert the query still times out cleanly instead of recovering forever."""
    expected = q()
    # Kill the worker hosting stage-1 outputs (hit 8 lands after the 6
    # stage-1 submissions) AND delay every shuffle fetch — recovery's
    # recompute + refetch path is pinned in-flight when the deadline hits.
    with fault_scope("worker.pre_submit:kill:8,shuffle.fetch:delay:*:30",
                     seed=0):
        t0 = time.monotonic()
        with pytest.raises((DaftTimeoutError, DaftCancelledError)):
            q(timeout=1.5)
        assert time.monotonic() - t0 < 8.0
    # Control: the same kill WITHOUT the delay recovers to identical results.
    with fault_scope("worker.pre_submit:kill:8", seed=0):
        assert q() == expected


# ------------------------------------------------------------------ #
# Circuit breaker                                                      #
# ------------------------------------------------------------------ #
def test_breaker_opens_after_threshold(tap):
    b = CircuitBreaker("test://host", failure_threshold=3, open_base_s=60.0,
                       open_cap_s=60.0, half_open_probes=1)
    for _ in range(2):
        b.record_failure()
    b.allow()  # still closed
    b.record_failure()  # third consecutive: trips
    assert b.state() == "open"
    with pytest.raises(DaftCircuitOpenError, match="circuit open"):
        b.allow()
    opened = tap.of(CircuitOpened)
    assert opened and opened[0].endpoint == "test://host" \
        and opened[0].failures == 3
    # DaftCircuitOpenError is transient: the dispatcher's retry owns it.
    assert isinstance(DaftCircuitOpenError("x"), DaftTransientError)


def test_breaker_half_open_probe_then_close(tap):
    b = CircuitBreaker("probe://host", failure_threshold=1,
                       open_base_s=0.05, open_cap_s=0.05, half_open_probes=1)
    b.record_failure()
    assert b.state() == "open"
    time.sleep(0.1)  # past the probe delay
    b.allow()  # admitted as the half-open probe
    assert b.state() == "half_open"
    # Only ONE probe is admitted — recovery is probed, not stampeded.
    with pytest.raises(DaftCircuitOpenError, match="probe quota"):
        b.allow()
    b.record_success()
    assert b.state() == "closed"
    assert [e.endpoint for e in tap.of(CircuitClosed)] == ["probe://host"]


def test_breaker_probe_failure_reopens_with_backoff():
    seed_circuit_jitter(7)
    b = CircuitBreaker("flap://host", failure_threshold=1,
                       open_base_s=0.05, open_cap_s=10.0, half_open_probes=1)
    b.record_failure()
    first_delay = b._probe_at - time.monotonic()
    time.sleep(0.1)
    b.allow()  # probe admitted
    b.record_failure()  # probe failed: reopen, doubled backoff
    assert b.state() == "open"
    second_delay = b._probe_at - time.monotonic()
    assert second_delay > first_delay


def test_breaker_jitter_is_seed_deterministic(monkeypatch):
    import types

    from daft_tpu.io import circuit

    now = 1000.0  # the breaker's clock stands still, so _probe_at - now is the seeded delay itself
    monkeypatch.setattr(circuit, "time", types.SimpleNamespace(monotonic=lambda: now))

    def delays(seed):
        seed_circuit_jitter(seed)
        b = CircuitBreaker(f"seed{seed}://h", failure_threshold=1,
                           open_base_s=1.0, open_cap_s=64.0,
                           half_open_probes=1)
        out = []
        for _ in range(4):
            b.record_failure()
            out.append(b._probe_at - now)
            b._state = "half_open"  # re-trip without waiting
        return out

    first = delays(11)
    assert first == delays(11)  # to the bit: nothing but the seed decides them
    assert all(0.5 * 2 ** i <= d <= 2 ** i for i, d in enumerate(first))  # base * 2^n * [0.5, 1]
    assert delays(12) != first


def test_breaker_registry_shared_and_reset():
    a = breaker_for("shared://ep")
    assert breaker_for("shared://ep") is a
    reset_circuit_breakers()
    assert breaker_for("shared://ep") is not a
    assert endpoint_of("/tmp/data.parquet") == "file://local"
    assert endpoint_of("s3://bucket/key") == "s3://bucket"
    assert endpoint_of("https://host:8443/x/y") == "https://host:8443"


def test_reset_also_heals_cached_breaker_objects():
    """Clients (S3Client/GCSClient) cache their breaker at construction:
    reset must heal those OBJECTS in place, not just clear the registry —
    else a chaos-tripped cached breaker keeps failing healthy queries while
    later lookups get a divergent fresh state machine."""
    cached = breaker_for("cached://ep", failure_threshold=1,
                         open_base_s=60.0, open_cap_s=60.0,
                         half_open_probes=1)
    cached.record_failure()
    assert cached.state() == "open"
    reset_circuit_breakers()
    assert cached.state() == "closed"
    cached.allow()  # admits again


def test_half_open_probe_slot_rearms_after_window():
    """A probe whose caller never reports an outcome (cancelled query,
    non-retryable error, abandoned stream) must not wedge the breaker
    half-open forever: the quota re-arms after the probe window."""
    b = CircuitBreaker("leak://host", failure_threshold=1,
                       open_base_s=0.1, open_cap_s=0.1, half_open_probes=1)
    b.record_failure()
    time.sleep(0.15)
    b.allow()  # probe admitted... and its caller vanishes (no outcome)
    with pytest.raises(DaftCircuitOpenError, match="probe quota"):
        b.allow()  # within the window: quota still held
    time.sleep(0.15)  # past the probe window
    b.allow()  # re-armed: a new probe is admitted
    b.record_success()
    assert b.state() == "closed"


def test_io_circuit_injection_point():
    """The new io.circuit FaultInjector point fires inside the breaker's
    admission check."""
    b = CircuitBreaker("inj://host", failure_threshold=99, open_base_s=1.0,
                       open_cap_s=1.0, half_open_probes=1)
    with fault_scope("io.circuit:raise:1") as inj:
        with pytest.raises(FaultInjected):
            b.allow()
    assert inj.fired("io.circuit") == 1


def test_with_retries_breaker_integration(tap):
    breaker = CircuitBreaker("wr://host", failure_threshold=2,
                             open_base_s=60.0, open_cap_s=60.0,
                             half_open_probes=1)
    calls = []

    def boom():
        calls.append(1)
        raise DaftTransientError("down")

    policy = RetryPolicy(max_retries=3, backoff_base_s=0.01)
    with pytest.raises(DaftError):
        with_retries(boom, policy, breaker=breaker)
    # Two failures tripped the breaker; the next attempt was refused by
    # allow() without calling fn again.
    assert breaker.state() == "open"
    assert len(calls) == 2
    assert tap.of(CircuitOpened)


def test_breaker_chaos_query_fails_fast_never_hangs(dist_runner, tap, tmp_path):
    """Acceptance: io.get_object failing repeatedly opens the breaker
    (CircuitOpened event) and queries fail fast — never hang; the healthy
    rerun outside the fault scope returns identical results."""
    daft_tpu.from_pydict({"v": list(range(50))}).write_parquet(str(tmp_path))
    expected = sorted(daft_tpu.read_parquet(str(tmp_path)).to_pydict()["v"])
    t0 = time.monotonic()
    # Result/scan cache off: the control read above would otherwise serve
    # this repeat from memory and the breaker would never see a failure.
    with daft_tpu.execution_config_ctx(task_transient_backoff_s=0.01,
                                       circuit_failure_threshold=3,
                                       result_cache_enabled=False):
        with fault_scope("io.get_object:raise_transient:*"):
            with pytest.raises(DaftError):
                daft_tpu.read_parquet(str(tmp_path)).to_pydict()
    assert time.monotonic() - t0 < 30.0  # failed fast, not hung
    opened = tap.of(CircuitOpened)
    assert opened and opened[0].endpoint == "file://local"
    # fault_scope exit reset breaker state: the healthy rerun succeeds.
    assert sorted(daft_tpu.read_parquet(str(tmp_path)).to_pydict()["v"]) == expected


def test_breaker_partial_outage_retries_on_other_paths(dist_runner, tap, tmp_path):
    """A breaker tripped by a burst of transient failures recovers through
    its half-open probe: the same query completes via retry once the
    endpoint heals — degraded, not dead."""
    daft_tpu.from_pydict({"v": list(range(30))}).write_parquet(str(tmp_path))
    expected = sorted(daft_tpu.read_parquet(str(tmp_path)).to_pydict()["v"])
    # The control run above created the endpoint's breaker with default
    # thresholds (first creation wins): reset so the tuned config applies.
    reset_circuit_breakers()
    # First 4 object gets fail: the breaker (threshold 3) opens mid-query,
    # in-flight tasks fail fast, and the dispatcher's backoff outlives the
    # short probe delay — the probe succeeds and the query completes.
    # result_cache off: the control read above must not serve this repeat.
    with daft_tpu.execution_config_ctx(task_transient_backoff_s=0.2,
                                       task_max_retries=6,
                                       circuit_failure_threshold=3,
                                       circuit_open_base_s=0.1,
                                       circuit_open_cap_s=0.1,
                                       result_cache_enabled=False):
        spec = ",".join(f"io.get_object:raise_transient:{n}"
                        for n in (1, 2, 3, 4))
        with fault_scope(spec):
            out = sorted(daft_tpu.read_parquet(str(tmp_path)).to_pydict()["v"])
    assert out == expected
    assert tap.of(CircuitOpened) and tap.of(CircuitClosed)
