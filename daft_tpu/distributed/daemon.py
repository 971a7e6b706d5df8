"""Multi-host worker daemon: TCP control plane + Arrow Flight data plane.

Reference: the reference runs one worker per node, reachable only over the
network — Ray actor RPC control plane (daft/runners/flotilla.py:139-290,
RaySwordfishActor per node) with an Arrow Flight shuffle data plane
(src/daft-shuffles/src/server/flight_server.rs); the scheduler talks to them
through the Worker/WorkerManager abstraction
(src/daft-distributed/src/scheduling/worker.rs:13-77).

Here the control plane is a framed-cloudpickle TCP protocol (the shape a
gRPC service would have, without codegen): a daemon process per host accepts
``run_task`` requests, executes plan fragments on the real streaming
Executor, keeps the outputs LOCAL in its shuffle cache, and answers with
FlightPartitionRefs. Downstream tasks running on other hosts fetch those
inputs directly from the owning daemon's Flight server — worker↔worker data
movement rides the data plane (DCN), never the driver.

SECURITY: the control protocol deserializes cloudpickle from any peer that
can reach the port — equivalent to remote code execution by design (tasks ARE
code). Run daemons only on a private cluster network (the reference's Ray
actors have the same trust model); bind --host to an internal interface.

Launch standalone:  ``python -m daft_tpu.distributed.daemon --port 9201``
Connect a driver:   ``DAFT_WORKER_ADDRESSES=hostA:9201,hostB:9201``
                    ``DAFT_RUNNER=distributed``
"""

from __future__ import annotations

import logging
import os
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import cloudpickle

from daft_tpu.distributed.partition_ref import (
    FlightPartitionRef,
    LocalPartitionRef,
    PartitionRef,
    deserialize_partition,
    serialize_partition,
)
from daft_tpu.distributed.task import Task
from daft_tpu.distributed.worker import (
    Worker,
    WorkerDiedError,
    bind_task_fragment,
    collect_task_outputs,
)

_log = logging.getLogger("daft_tpu.daemon")

_LEN = struct.Struct("<Q")


def _send_frame(sock: socket.socket, data: bytes) -> None:
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_frame(sock: socket.socket) -> bytes:
    buf = bytearray()
    while len(buf) < _LEN.size:
        chunk = sock.recv(_LEN.size - len(buf))
        if not chunk:
            raise EOFError("socket closed")
        buf += chunk
    (n,) = _LEN.unpack(bytes(buf))
    out = bytearray()
    while len(out) < n:
        chunk = sock.recv(min(n - len(out), 1 << 20))
        if not chunk:
            raise EOFError("socket closed")
        out += chunk
    return bytes(out)


# ------------------------------------------------------------------ #
# Ref wire format                                                      #
# ------------------------------------------------------------------ #
def encode_ref(ref: PartitionRef) -> dict:
    """Flight/shuffle refs travel as addresses (zero-copy); anything else
    ships its bytes inline (driver-resident partitions, e.g. from_pydict
    inputs)."""
    from daft_tpu.distributed.partition_ref import ShufflePartitionRef

    if isinstance(ref, ShufflePartitionRef):
        return {"kind": "shuffle", "address": ref.address, "ticket": ref.ticket,
                "rows": ref.rows, "bytes": ref.bytes_,
                "worker_id": ref.worker_id,
                "chunks": [c.to_wire() for c in ref.chunks]}
    if isinstance(ref, FlightPartitionRef):
        return {"kind": "flight", "address": ref.address, "ticket": ref.ticket,
                "rows": ref.rows, "bytes": ref.bytes_, "worker_id": ref.worker_id}
    return {"kind": "bytes", "data": serialize_partition(ref.fetch())}


def decode_ref(d: dict) -> PartitionRef:
    if d["kind"] == "shuffle":
        from daft_tpu.distributed.partition_ref import (
            ChunkRef,
            ShufflePartitionRef,
        )

        return ShufflePartitionRef(
            d["address"], d["ticket"], d["rows"], d["bytes"],
            d.get("worker_id"),
            [ChunkRef.from_wire(c) for c in d.get("chunks") or []])
    if d["kind"] == "flight":
        return FlightPartitionRef(d["address"], d["ticket"], d["rows"],
                                  d["bytes"], d.get("worker_id"))
    return LocalPartitionRef(deserialize_partition(d["data"]))


# ------------------------------------------------------------------ #
# Daemon (server side)                                                 #
# ------------------------------------------------------------------ #
class WorkerDaemon:
    """One per host. Executes task fragments; serves results over Flight."""

    def __init__(self, port: int = 0, slots: int = 2, data_dir: Optional[str] = None,
                 host: str = "0.0.0.0", advertise_host: Optional[str] = None):
        from daft_tpu.distributed.flight import ShuffleFlightServer
        from daft_tpu.distributed.shuffle import ShuffleCache

        self.worker_id = f"daemon-{uuid.uuid4().hex[:8]}"
        self.slots = slots
        # The cache nests (and cleans up) its own root inside the given
        # dir; a fresh mkdtemp here would strand the empty outer dir.
        self.cache = ShuffleCache(data_dir or tempfile.gettempdir())
        # Intra-host short-circuit: reduce tasks running ON this daemon
        # read their colocated chunks straight off disk instead of
        # round-tripping through their own Flight server.
        from daft_tpu.distributed.shuffle import register_local_cache

        register_local_cache(self.worker_id, self.cache)
        self.flight = ShuffleFlightServer(self.cache)
        from daft_tpu.config import daft_env

        self.advertise_host = advertise_host or daft_env(
            "DAFT_ADVERTISE_HOST") or socket.gethostname()
        self._pool = ThreadPoolExecutor(max_workers=slots,
                                        thread_name_prefix=f"{self.worker_id}-task")
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind((host, port))
        self._server.listen(64)
        self.port = self._server.getsockname()[1]
        self._active = 0
        self._lock = threading.Lock()
        self._shutdown = threading.Event()
        # In-flight task count per query: fragments of ONE query run
        # concurrently on the pool, and the memory-ledger drain must ship
        # with the query's LAST finishing fragment — a mid-flight pop
        # would report a sibling's live held bytes as leaked residue.
        self._query_tasks: Dict[str, int] = {}

    @property
    def flight_address(self) -> str:
        return f"grpc://{self.advertise_host}:{self.flight.port}"

    def serve_forever(self) -> None:
        while not self._shutdown.is_set():
            try:
                conn, _ = self._server.accept()
            except OSError:
                return
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while True:
                frame = _recv_frame(conn)
                try:
                    msg = cloudpickle.loads(frame)
                except BaseException as e:  # noqa: BLE001
                    # A payload referencing modules this host can't import
                    # must fail THIS request, not the whole connection.
                    _send_frame(conn, cloudpickle.dumps(
                        {"ok": False, "error": f"cannot decode request: {e}"}))
                    continue
                op = msg.get("op")
                if op == "ping":
                    # Doubles as the heartbeat channel: drivers probe with a
                    # short deadline and count silence as a missed beat. The
                    # registry snapshot piggybacks on the same frame, so
                    # worker metrics reach the driver at heartbeat cadence
                    # with zero extra connections. Buffered profiler spans
                    # and this host's span clock ride along too: spans of
                    # operators that finished BEFORE a crash have already
                    # shipped, and the clock sample feeds the driver's
                    # RTT-midpoint skew estimate (profiling.py).
                    from daft_tpu import profiling
                    from daft_tpu.metrics import get_registry
                    from daft_tpu.tracing import span_clock_ns

                    spans = profiling.drain_worker_buffer()
                    try:
                        _send_frame(conn, cloudpickle.dumps(
                            {"ok": True, "worker_id": self.worker_id,
                             "slots": self.slots,
                             "flight": self.flight_address,
                             "metrics": get_registry().to_wire(),
                             "spans": spans,
                             "now_ns": span_clock_ns()}))
                    except OSError:
                        # The driver timed out / hung up mid-reply: put the
                        # drained spans back so the next beat ships them —
                        # crash durability must survive a missed heartbeat.
                        profiling.buffer_spans(spans)
                        raise
                elif op == "run_task":
                    # The pool caps concurrent executions at `slots` even
                    # with many connections (per-chip ownership on TPU hosts).
                    fut = self._pool.submit(self._run_task, msg)
                    reply = fut.result()
                    try:
                        _send_frame(conn, cloudpickle.dumps(reply))
                    except OSError:
                        # Driver hung up mid-reply: re-buffer the drained
                        # spans so the next heartbeat ships them (same
                        # crash-durability contract as the ping path).
                        if reply.get("spans"):
                            from daft_tpu import profiling

                            profiling.buffer_spans(reply["spans"])
                        raise
                elif op == "release_query":
                    # Query teardown: delete this query's shuffle chunk
                    # files NOW (same driver finally as admission-ticket
                    # release) instead of letting them sit until daemon
                    # shutdown — the zero-leak lifecycle contract.
                    removed = self.cache.release_query(
                        msg.get("query_id", ""))
                    _send_frame(conn, cloudpickle.dumps(
                        {"ok": True, "removed": removed}))
                elif op == "die":
                    # Fault injection (tests only): refuse unless explicitly
                    # enabled — an unauthenticated kill switch otherwise.
                    from daft_tpu.config import daft_env

                    if daft_env("DAFT_DAEMON_ALLOW_FAULT_INJECTION"):
                        os._exit(17)
                    _send_frame(conn, cloudpickle.dumps(
                        {"ok": False, "error": "fault injection disabled"}))
                elif op == "shutdown":
                    _send_frame(conn, cloudpickle.dumps({"ok": True}))
                    self.stop()
                    return
                else:
                    _send_frame(conn, cloudpickle.dumps(
                        {"ok": False, "error": f"unknown op {op!r}"}))
        except (EOFError, OSError, ConnectionError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _finish_query_task_mem(self, query_id: str):
        """Decrement the query's in-flight task count; the LAST finishing
        fragment (count reaches zero, decided atomically under the lock)
        drains and ships the query's worker-side ledger profile. Earlier
        fragments ship None — their attribution rides out with the last
        one instead of popping a sibling's live bytes as phantom residue."""
        with self._lock:
            n = self._query_tasks.get(query_id, 1) - 1
            if n <= 0:
                self._query_tasks.pop(query_id, None)
            else:
                self._query_tasks[query_id] = n
        if n > 0:
            return None
        from daft_tpu.execution.memledger import get_ledger

        return get_ledger().drain_query_wire(query_id)

    def _run_task(self, msg: dict) -> dict:
        with self._lock:
            self._active += 1
            qid = msg.get("query_id", "")
            self._query_tasks[qid] = self._query_tasks.get(qid, 0) + 1
        prof = None
        try:
            from daft_tpu.execution.executor import Executor

            from daft_tpu.execution.resource_manager import RuntimeStats

            fragment = msg["fragment"]
            inputs = [[decode_ref(d) for d in slot] for slot in msg["inputs"]]
            stats = RuntimeStats(msg.get("query_id", ""))
            stats.local_flush = False  # shipped back in the reply instead
            # Wire deadline, re-anchored on this host's monotonic clock
            # (Deadline.__reduce__): the daemon bounds its own execution.
            from daft_tpu.cancellation import cancel_scope, token_for_task

            token = token_for_task(msg.get("query_id", ""),
                                   msg.get("deadline"))
            # Trace context (profiling.py): spans sink into the process-wide
            # buffer as they finish, so completed-operator spans reach the
            # driver on the NEXT heartbeat even if this task never replies
            # (daemon killed mid-task).
            from daft_tpu import profiling

            prof = profiling.task_profiler_for(
                msg.get("trace_ctx"), msg.get("query_id", ""),
                self.worker_id, sink=profiling.buffer_spans)
            executor = Executor(msg["cfg"], partition_offset=msg["partition_idx"],
                                stats=stats, cancel_token=token, profiler=prof)
            from daft_tpu.context import frozen_clock_scope

            with cancel_scope(token), \
                    frozen_clock_scope(msg.get("frozen_clock")), \
                    profiling.profiled_task_scope(
                        prof,
                        task_id=msg.get("task_id", ""),
                        partition_idx=msg["partition_idx"],
                        attempt=msg.get("attempt", 0)):
                with profiling.maybe_span(prof, "daft.task.bind"):
                    bound = bind_task_fragment(fragment, inputs,
                                               cfg=msg["cfg"])
                out = list(executor.run(bound))
            parts = collect_task_outputs(out, msg["expect_outputs"], fragment.schema)
            # Outputs land in the chunked shuffle plane: compressed chunk
            # files + chunk-granular tickets, so downstream reduce tasks
            # stream them with pipelined prefetch (and colocated ones read
            # the files directly). query_id-tracked for teardown.
            shuffle_id = f"task-{uuid.uuid4().hex[:12]}"
            writer = self.cache.writer(shuffle_id, len(parts),
                                       query_id=msg.get("query_id", ""),
                                       cfg=msg["cfg"], profiler=prof)
            for i, p in enumerate(parts):
                writer.write_bucket(i, p)
            metas = writer.finish()
            refs = []
            for i, p in enumerate(parts):
                m = metas[i]
                refs.append({"kind": "shuffle",
                             "address": self.flight_address,
                             "ticket": m.ticket, "rows": m.rows,
                             "bytes": m.bytes_, "worker_id": self.worker_id,
                             "chunks": [[c.ticket, c.rows, c.bytes_, c.digest]
                                        for c in m.chunks]})
            from daft_tpu.metrics import get_registry

            return {"ok": True, "refs": refs, "stats": stats.to_wire(),
                    "metrics": get_registry().to_wire(),
                    "mem": self._finish_query_task_mem(
                        msg.get("query_id", "")),
                    "spans": profiling.drain_worker_buffer()
                    if prof is not None else None}
        except BaseException as e:  # noqa: BLE001
            import traceback

            # Classify so the driver can keep its typed failure handling
            # (transient retry / lineage recovery / cancellation) across the
            # wire, where exceptions travel as strings.
            from daft_tpu.distributed.scheduler import (
                find_fetch_failure,
                find_in_chain,
                is_transient_failure,
            )
            from daft_tpu.errors import DaftCancelledError, DaftCorruptionError

            reply = {"ok": False, "error": f"{e}\n{traceback.format_exc()}"}
            try:
                # Per-query ledger state still drains on failure (last
                # fragment only) and ships whatever was attributed before
                # the death.
                reply["mem"] = self._finish_query_task_mem(
                    msg.get("query_id", ""))
            # daftlint: disable=DTL002 -- the error reply (which carries the REAL failure) must reach the driver even if the ledger drain breaks
            except Exception:  # noqa: BLE001 — reply must still go out
                pass
            if prof is not None:
                # Partial ERROR spans (task_scope unwound) still ship: the
                # driver's trace shows how far the task got before failing.
                reply["spans"] = profiling.drain_worker_buffer()
            fetch = find_fetch_failure(e)
            corruption = find_in_chain(e, DaftCorruptionError)
            if find_in_chain(e, DaftCancelledError) is not None:
                reply["kind"] = "cancelled"
            elif fetch is not None:
                # Chunk corruption wrapped into a fetch failure keeps the
                # fetch classification: the lost descriptors (flagged
                # corruption=True) are what drive lineage recovery.
                reply["kind"] = "fetch"
                reply["lost"] = fetch.lost
            elif corruption is not None:
                # Bare corruption (spill / checkpoint artifact, no lineage
                # descriptor): typed re-raise on the driver so the
                # dispatcher keeps its deliberately-NOT-transient handling.
                reply["kind"] = "corruption"
                reply["artifact"] = corruption.artifact
                reply["path"] = corruption.path
                reply["ticket"] = corruption.ticket
            elif is_transient_failure(e):
                reply["kind"] = "transient"
            return reply
        finally:
            with self._lock:
                self._active -= 1

    def stop(self) -> None:
        self._shutdown.set()
        self._pool.shutdown(wait=False, cancel_futures=True)
        try:
            self._server.close()
        except OSError:
            pass
        self.flight.shutdown()
        from daft_tpu.distributed.shuffle import unregister_local_cache

        unregister_local_cache(self.worker_id)
        self.cache.cleanup()


# ------------------------------------------------------------------ #
# RemoteWorker (driver side)                                           #
# ------------------------------------------------------------------ #
class RemoteWorker(Worker):
    """Driver-side handle to a WorkerDaemon, speaking the TCP protocol.
    Implements the same Worker interface the scheduler/dispatcher already
    use, so WorkerDied rescheduling and autoscale work unchanged."""

    def __init__(self, address: str, cfg=None, connect_timeout: float = 10.0):
        from daft_tpu.context import get_context

        self.address = address
        host, port = address.rsplit(":", 1)
        self._host, self._port = host, int(port)
        self.cfg = cfg or get_context().execution_config
        self._active = 0
        self._lock = threading.Lock()
        info = self._ping(timeout=connect_timeout)
        self.worker_id = info["worker_id"]
        self.num_slots = info["slots"]
        self.flight_address = info["flight"]

    def _ping(self, timeout: Optional[float] = None) -> dict:
        """One ping round-trip, folding the piggybacked profiler payloads
        in: the daemon's span-clock sample becomes an RTT-midpoint skew
        estimate, and buffered worker spans reach the driver's span store."""
        from daft_tpu import profiling
        from daft_tpu.tracing import span_clock_ns

        t0 = span_clock_ns()
        info = self._request({"op": "ping"}, timeout=timeout)
        t1 = span_clock_ns()
        wid = info.get("worker_id", "")
        if info.get("now_ns") and wid:
            profiling.record_worker_clock(wid, info["now_ns"], t0, t1)
        profiling.deliver_spans(info.get("spans"), worker_id=wid)
        return info

    def _request(self, msg: dict, timeout: Optional[float] = None) -> dict:
        try:
            with socket.create_connection((self._host, self._port),
                                          timeout=timeout) as sock:
                # run_task legitimately waits unbounded; control ops
                # (ping/shutdown/die) keep the caller's timeout on recv too.
                if msg.get("op") == "run_task":
                    sock.settimeout(None)
                _send_frame(sock, cloudpickle.dumps(msg))
                reply = cloudpickle.loads(_recv_frame(sock))
        except (OSError, EOFError, ConnectionError) as e:
            raise WorkerDiedError(
                f"worker at {self.address} unreachable: {e}") from e
        if not reply.get("ok"):
            # A failed task's partial ERROR spans piggyback the error reply;
            # deliver them before the raise discards the frame — and the
            # worker's shipped ledger profile merges the same way (the
            # daemon already drained its side, so dropping it here would
            # make a dying task's attributed bytes vanish entirely).
            from daft_tpu import profiling
            from daft_tpu.execution.memledger import get_ledger

            profiling.deliver_spans(reply.get("spans"),
                                    worker_id=getattr(self, "worker_id", None))
            get_ledger().merge_worker_profile(msg.get("query_id", ""),
                                              reply.get("mem"))
            err = reply.get("error", "unknown daemon error")
            kind = reply.get("kind")
            if kind == "fetch":
                from daft_tpu.distributed.partition_ref import PartitionFetchError

                raise PartitionFetchError(err, reply.get("lost") or [])
            if kind == "cancelled":
                from daft_tpu.errors import DaftCancelledError

                raise DaftCancelledError(err)
            if kind == "corruption":
                from daft_tpu.errors import DaftCorruptionError

                raise DaftCorruptionError(
                    err, artifact=reply.get("artifact", ""),
                    path=reply.get("path", ""),
                    ticket=reply.get("ticket", ""))
            if kind == "transient":
                from daft_tpu.errors import DaftTransientError

                raise DaftTransientError(err)
            raise RuntimeError(err)
        return reply

    def submit(self, task: Task) -> "Future[List[PartitionRef]]":
        fut: "Future[List[PartitionRef]]" = Future()
        with self._lock:
            self._active += 1

        def run() -> List[PartitionRef]:
            try:
                payload = {
                    "op": "run_task",
                    "cfg": task.cfg or self.cfg,
                    "fragment": task.fragment,
                    "inputs": [[encode_ref(r) for r in slot] for slot in task.inputs],
                    "partition_idx": task.partition_idx,
                    "expect_outputs": task.expect_outputs,
                    "query_id": task.query_id,
                    "frozen_clock": task.frozen_clock,
                    "deadline": task.deadline,
                    "task_id": task.task_id,
                    "attempt": task.attempt,
                    "trace_ctx": task.trace_ctx,
                }
                reply = self._request(payload)
                # Worker-side operator stats stream back with the reply and
                # re-emit on the driver (reference: the remote event-log sink
                # forwarding worker events, daft/runners/flotilla.py:171-176).
                from daft_tpu import profiling
                from daft_tpu.execution.resource_manager import emit_operator_stats
                from daft_tpu.metrics import get_registry

                profiling.deliver_spans(reply.get("spans"),
                                        worker_id=self.worker_id)
                from daft_tpu.execution.memledger import get_ledger

                get_ledger().merge_worker_profile(task.query_id,
                                                  reply.get("mem"))
                emit_operator_stats(task.query_id, reply.get("stats"))
                # revive=False: a reply racing this worker's death on a
                # still-open connection must not un-stale it.
                get_registry().merge_worker_wire(self.worker_id,
                                                 reply.get("metrics"),
                                                 revive=False)
                return [decode_ref(d) for d in reply["refs"]]
            finally:
                with self._lock:
                    self._active -= 1

        def runner():
            # Honor a cancel() that lands before execution starts (dispatcher
            # abort): the task is skipped entirely. Once running, cancel()
            # fails and the abort path drains us instead.
            if not fut.set_running_or_notify_cancel():
                with self._lock:
                    self._active -= 1
                return
            try:
                fut.set_result(run())
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=runner, daemon=True,
                         name=f"submit-{self.worker_id}").start()
        return fut

    def active_tasks(self) -> int:
        return self._active

    def heartbeat(self) -> bool:
        """Liveness probe: a quick ping with a short deadline. A daemon that
        cannot answer within 2s counts as a missed beat (the monitor marks it
        dead only after ``heartbeat_miss_threshold`` consecutive misses)."""
        try:
            # _ping also folds in the piggybacked profiler payloads: the
            # span-clock sample (RTT-midpoint skew estimate) and any worker
            # spans buffered since the last beat.
            info = self._ping(timeout=2.0)
            # The worker's cumulative registry snapshot rides the heartbeat
            # (ISSUE 5): merge under this worker's id so driver-side scrapes
            # see per-worker series without a second wire.
            from daft_tpu.metrics import get_registry

            get_registry().merge_worker_wire(self.worker_id,
                                             info.get("metrics"))
            return True
        except Exception:
            # False IS the classification here: the heartbeat monitor counts
            # the miss. Log so a systematic cause (bad pickle, auth) shows.
            _log.debug("daemon ping %s:%s failed", self._host, self._port,
                       exc_info=True)
            return False

    def release_query(self, query_id: str) -> int:
        """Best-effort shuffle teardown on the remote daemon: a dead or
        unreachable daemon just means its files die with its tempdir —
        never a teardown failure on the driver."""
        try:
            reply = self._request({"op": "release_query",
                                   "query_id": query_id}, timeout=5.0)
            return int(reply.get("removed", 0))
        except Exception:
            _log.debug("release_query(%s) on %s failed", query_id,
                       self.address, exc_info=True)
            return 0

    def kill(self) -> None:
        """Fault injection: crash the remote daemon process."""
        try:
            with socket.create_connection((self._host, self._port), timeout=5) as sock:
                _send_frame(sock, cloudpickle.dumps({"op": "die"}))
        except OSError:
            pass

    def shutdown(self) -> None:
        try:
            self._request({"op": "shutdown"}, timeout=2)
        except Exception:
            _log.debug("daemon shutdown frame failed (already dead?)",
                       exc_info=True)


# ------------------------------------------------------------------ #
# Spawning helpers (single-machine clusters for tests / dev)           #
# ------------------------------------------------------------------ #
def spawn_local_daemon(port: int = 0, slots: int = 2,
                       device_index: Optional[int] = None,
                       fault_injection: bool = False,
                       advertise_host: str = "localhost") -> "subprocess.Popen":
    """Launch a daemon subprocess on localhost; returns the Popen. The port
    is written to stdout line 1 (`PORT <n>`) when 0 is requested. The child
    is given chip ``device_index`` of this host, or CPU, before it imports
    JAX (device.child_device_env)."""
    from daft_tpu.device import child_device_env

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # daftlint: disable=DTL007 -- constructs the child process environment, not a config read
    env = dict(os.environ)
    # Same-host spawn: propagate the driver's full sys.path so task payloads
    # referencing driver-importable modules (plugins, test fixtures) resolve.
    extra = [p for p in sys.path if p and os.path.isdir(p)]
    env["PYTHONPATH"] = os.pathsep.join([repo_root, *extra,
                                         env.get("PYTHONPATH", "")])
    env.update(child_device_env(device_index))
    if fault_injection:
        env["DAFT_DAEMON_ALLOW_FAULT_INJECTION"] = "1"
    return subprocess.Popen(
        [sys.executable, "-m", "daft_tpu.distributed.daemon",
         "--port", str(port), "--slots", str(slots),
         "--advertise-host", advertise_host],
        env=env, stdout=subprocess.PIPE, text=True,
    )


def wait_for_daemon(proc: "subprocess.Popen", timeout: float = 60.0,
                    host: str = "localhost") -> str:
    """Block until the daemon prints its PORT line; returns '<host>:port'.
    Fails fast if the process dies, and respects the deadline even if the
    daemon stays alive but silent."""
    import select

    deadline = time.monotonic() + timeout
    buf = ""
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise DaftDaemonError(
                f"daemon exited rc={proc.returncode} before reporting a port")
        ready, _, _ = select.select([proc.stdout], [], [], 0.2)
        if not ready:
            continue
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.1)
            continue
        if line.startswith("PORT "):
            return f"{host}:{line.split()[1]}"
    raise DaftDaemonError("daemon did not report a port in time")


class DaftDaemonError(RuntimeError):
    pass


def main(argv: Optional[Sequence[str]] = None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description="daft_tpu worker daemon")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--slots", type=int, default=2)
    parser.add_argument("--data-dir", default=None)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--advertise-host", default=None,
                        help="hostname other workers use to fetch this "
                             "daemon's partitions over Flight (default: "
                             "$DAFT_ADVERTISE_HOST or gethostname())")
    args = parser.parse_args(argv)

    from daft_tpu.device import enter_child

    enter_child()

    daemon = WorkerDaemon(port=args.port, slots=args.slots, data_dir=args.data_dir,
                          host=args.host, advertise_host=args.advertise_host)
    print(f"PORT {daemon.port}", flush=True)
    # Re-point stdout at stderr: the spawner reads only the PORT line from
    # the stdout pipe, and unread task print()s would fill it and deadlock.
    try:
        os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    except OSError:
        pass
    daemon.serve_forever()


if __name__ == "__main__":
    main()
