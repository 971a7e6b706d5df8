"""Process-isolated workers: the reference's per-node Ray actor analogue.

Reference: daft/runners/flotilla.py — ``RaySwordfishActor`` hosts a
NativeExecutor per node; tasks arrive as serialized plans, partitions move as
object-store refs. Here each ProcessWorker is a subprocess running the real
streaming Executor; tasks ship as cloudpickle'd plan fragments with
Arrow-IPC-serialized input partitions over a socketpair (length-prefixed
frames), results return as IPC bytes. A dead process surfaces as
WorkerDiedError, which the dispatcher handles by marking the worker dead and
rescheduling elsewhere.

The subprocess is launched with plain ``subprocess`` + an inherited socket fd
(not multiprocessing.spawn, which re-executes __main__ and breaks under
notebooks/REPLs). This is also what the libtpu single-owner constraint demands
for TPU UDFs: one process per chip owns the device (SURVEY.md §7 hard part (e)).
"""

from __future__ import annotations

import os
import socket
import struct
import subprocess
import sys
import threading
import uuid
from concurrent.futures import Future
from typing import List, Optional

import cloudpickle

from daft_tpu.distributed.partition_ref import (
    LocalPartitionRef,
    PartitionRef,
    deserialize_partition,
    serialize_partition,
)
from daft_tpu.distributed.task import Task
from daft_tpu.distributed.worker import Worker, WorkerDiedError, fetch_task_input

_LEN = struct.Struct("<Q")


def _send_frame(sock: socket.socket, data: bytes) -> None:
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_frame(sock: socket.socket) -> bytes:
    header = _recv_exact(sock, _LEN.size)
    (n,) = _LEN.unpack(header)
    return _recv_exact(sock, n)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise EOFError("socket closed")
        buf += chunk
    return bytes(buf)


def _worker_entry(fd: int) -> None:
    """Subprocess loop (invoked via `python -c`)."""
    from daft_tpu.device import enter_child

    enter_child()
    sock = socket.socket(fileno=fd)
    from daft_tpu.distributed.worker import bind_task_fragment, collect_task_outputs
    from daft_tpu.execution.executor import Executor

    while True:
        try:
            msg = _recv_frame(sock)
        except (EOFError, OSError):
            return
        if msg == b"__shutdown__":
            return
        prof = None
        try:
            payload = cloudpickle.loads(msg)
            cfg = payload["cfg"]
            fragment = payload["fragment"]
            inputs = [
                [LocalPartitionRef(deserialize_partition(blob)) for blob in slot]
                for slot in payload["inputs"]
            ]
            expect = payload["expect_outputs"]
            from daft_tpu.execution.resource_manager import RuntimeStats

            stats = RuntimeStats(payload.get("query_id", ""))
            stats.local_flush = False  # shipped back in the reply instead
            # The wire deadline re-anchored against THIS process's clock
            # (Deadline.__reduce__): the child enforces the query bound
            # locally at morsel boundaries and injection points.
            from daft_tpu.cancellation import cancel_scope, token_for_task

            token = token_for_task(payload.get("query_id", ""),
                                   payload.get("deadline"))
            # Trace context shipped with the task (profiling.py): child
            # spans buffer locally and ride the reply frame back.
            from daft_tpu import profiling

            prof = profiling.task_profiler_for(
                payload.get("trace_ctx"), payload.get("query_id", ""),
                payload.get("worker_id", ""))
            executor = Executor(cfg, partition_offset=payload["partition_idx"],
                                stats=stats, cancel_token=token, profiler=prof)
            from daft_tpu.context import frozen_clock_scope

            with cancel_scope(token), \
                    frozen_clock_scope(payload.get("frozen_clock")), \
                    profiling.profiled_task_scope(
                        prof,
                        task_id=payload.get("task_id", ""),
                        partition_idx=payload["partition_idx"],
                        attempt=payload.get("attempt", 0)):
                with profiling.maybe_span(prof, "daft.task.bind"):
                    bound = bind_task_fragment(fragment, inputs)
                out = list(executor.run(bound))
            parts = collect_task_outputs(out, expect, fragment.schema)
            blobs = [serialize_partition(p) for p in parts]
            from daft_tpu.metrics import get_registry

            # The child's cumulative registry snapshot rides the task reply
            # (this wire IS the heartbeat surface for process workers —
            # liveness is proc.poll(), which carries no payload). Completed
            # profiler spans piggyback the same frame, and the memory
            # ledger's per-query byte profile ships (and drains worker-
            # side) like the spill/token tallies before it.
            from daft_tpu.execution.memledger import get_ledger

            _send_frame(sock, cloudpickle.dumps(
                {"ok": True, "parts": blobs, "stats": stats.to_wire(),
                 "metrics": get_registry().to_wire(),
                 "mem": get_ledger().drain_query_wire(
                     payload.get("query_id", "")),
                 "spans": prof.drain() if prof is not None else None}))
        except BaseException as e:  # noqa: BLE001
            import traceback

            from daft_tpu.distributed.scheduler import find_in_chain, is_transient_failure
            from daft_tpu.errors import DaftCancelledError, DaftCorruptionError

            reply = {"ok": False, "error": f"{e}\n{traceback.format_exc()}"}
            try:
                # Drain the child ledger even on failure (the worker must
                # not accumulate per-query state past the task) and ship
                # whatever was attributed before the death.
                from daft_tpu.execution.memledger import get_ledger

                reply["mem"] = get_ledger().drain_query_wire(
                    payload.get("query_id", ""))
            # daftlint: disable=DTL002 -- the error reply (which carries the REAL failure) must reach the driver even if the ledger drain breaks
            except Exception:  # noqa: BLE001 — reply must still go out
                pass
            if prof is not None:
                # The task span closed ERROR/partial in task_scope's unwind:
                # ship whatever finished so the driver's trace shows how far
                # the task got before dying.
                reply["spans"] = prof.drain()
            corruption = find_in_chain(e, DaftCorruptionError)
            if find_in_chain(e, DaftCancelledError) is not None:
                # Keep the cancellation type across the wire so the driver
                # never retries cancelled work.
                reply["kind"] = "cancelled"
            elif corruption is not None:
                # Keep the corruption type (deliberately NOT transient)
                # across the wire: a spill/checkpoint artifact that failed
                # verification inside the child must not be retried as if
                # the failure were load.
                reply["kind"] = "corruption"
                reply["artifact"] = corruption.artifact
                reply["path"] = corruption.path
                reply["ticket"] = corruption.ticket
            elif is_transient_failure(e):
                # Keep the driver's typed transient-retry handling across the
                # process boundary, where exceptions travel as strings.
                reply["kind"] = "transient"
            try:
                _send_frame(sock, cloudpickle.dumps(reply))
            except OSError:
                return  # parent closed the socket: nobody to reply to


class ProcessWorker(Worker):
    """One worker = one subprocess executing tasks serially (num_slots=1 —
    the per-chip ownership model)."""

    def __init__(self, worker_id: Optional[str] = None, cfg=None,
                 device_index: Optional[int] = None):
        from daft_tpu.context import get_context
        from daft_tpu.device import child_device_env

        self.worker_id = worker_id or f"proc-{uuid.uuid4().hex[:8]}"
        self.num_slots = 1
        self.cfg = cfg or get_context().execution_config
        parent_sock, child_sock = socket.socketpair()
        # daftlint: disable=DTL007 -- constructs the child process environment, not a config read
        env = dict(os.environ)
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        # The child's device is decided here, before it imports JAX: chip
        # ``device_index`` of this host, or CPU.
        env.update(child_device_env(device_index))
        self._proc = subprocess.Popen(
            [sys.executable, "-c",
             "import sys; from daft_tpu.distributed.process_worker import _worker_entry; "
             f"_worker_entry({child_sock.fileno()})"],
            pass_fds=(child_sock.fileno(),), env=env,
        )
        child_sock.close()
        self._sock = parent_sock
        self._active = 0
        self._active_lock = threading.Lock()
        self._lock = threading.Lock()  # serializes socket use

    def kill(self) -> None:
        """Hard-kill the subprocess (fault injection / retire)."""
        self._proc.kill()

    def heartbeat(self) -> bool:
        return self._proc.poll() is None

    def submit(self, task: Task) -> "Future[List[PartitionRef]]":
        fut: "Future[List[PartitionRef]]" = Future()
        # Count queued work synchronously (before the thread even starts) so
        # the dispatcher's next least-loaded pick sees this worker's backlog.
        with self._active_lock:
            self._active += 1

        def run() -> List[PartitionRef]:
            try:
                with self._lock:
                    if self._proc.poll() is not None:
                        raise WorkerDiedError(f"worker {self.worker_id} process is dead")
                    payload = {
                        "cfg": task.cfg or self.cfg,
                        "fragment": task.fragment,
                        # fetch_task_input: fetch failures surface as
                        # PartitionFetchError -> lineage recovery, not a
                        # query-fatal error.
                        "inputs": [
                            [serialize_partition(fetch_task_input(r, si, pi))
                             for pi, r in enumerate(slot)]
                            for si, slot in enumerate(task.inputs)
                        ],
                        "partition_idx": task.partition_idx,
                        "expect_outputs": task.expect_outputs,
                        "query_id": task.query_id,
                        "frozen_clock": task.frozen_clock,
                        "deadline": task.deadline,
                        "task_id": task.task_id,
                        "attempt": task.attempt,
                        "trace_ctx": task.trace_ctx,
                        "worker_id": self.worker_id,
                    }
                    try:
                        _send_frame(self._sock, cloudpickle.dumps(payload))
                        msg = _recv_frame(self._sock)
                    except (EOFError, OSError, BrokenPipeError) as e:
                        raise WorkerDiedError(
                            f"worker {self.worker_id} died mid-task: {e}"
                        ) from e
                    result = cloudpickle.loads(msg)
                    from daft_tpu import profiling

                    # Spans piggyback BOTH reply shapes: a failed task still
                    # delivers its partial ERROR spans before the raise —
                    # and the memory ledger's shipped profile merges the
                    # same way (a dying task's attributed bytes still count).
                    profiling.deliver_spans(result.get("spans"),
                                            worker_id=self.worker_id)
                    from daft_tpu.execution.memledger import get_ledger

                    get_ledger().merge_worker_profile(task.query_id,
                                                      result.get("mem"))
                    if not result["ok"]:
                        if result.get("kind") == "cancelled":
                            from daft_tpu.errors import DaftCancelledError

                            raise DaftCancelledError(result["error"])
                        if result.get("kind") == "corruption":
                            from daft_tpu.errors import DaftCorruptionError

                            raise DaftCorruptionError(
                                result["error"],
                                artifact=result.get("artifact", ""),
                                path=result.get("path", ""),
                                ticket=result.get("ticket", ""))
                        if result.get("kind") == "transient":
                            from daft_tpu.errors import DaftTransientError

                            raise DaftTransientError(result["error"])
                        raise RuntimeError(result["error"])
                    from daft_tpu.execution.resource_manager import (
                        emit_operator_stats,
                    )
                    from daft_tpu.metrics import get_registry

                    emit_operator_stats(task.query_id, result.get("stats"))
                    get_registry().merge_worker_wire(self.worker_id,
                                                     result.get("metrics"),
                                                     revive=False)
                    return [
                        LocalPartitionRef(deserialize_partition(blob), self.worker_id)
                        for blob in result["parts"]
                    ]
            finally:
                with self._active_lock:
                    self._active -= 1

        def runner():
            # A cancel() before execution starts (dispatcher abort) skips the
            # task; once running, cancel() fails and the abort path drains.
            if not fut.set_running_or_notify_cancel():
                with self._active_lock:
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

    def shutdown(self) -> None:
        # Never block behind an in-flight (possibly hung) task: try the lock
        # briefly for a graceful shutdown frame, otherwise go straight to kill.
        got = self._lock.acquire(timeout=0.5)
        try:
            if got:
                try:
                    _send_frame(self._sock, b"__shutdown__")
                except OSError:
                    pass  # socket already dead: the kill below still runs
        finally:
            if got:
                self._lock.release()
        try:
            self._proc.wait(timeout=2)
        except subprocess.TimeoutExpired:
            self._proc.kill()
        self._sock.close()
