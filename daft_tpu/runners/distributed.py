"""DistributedRunner: partition-parallel execution over a worker pool.

Reference: daft/runners/flotilla.py (FlotillaRunner / RaySwordfishActor).
The control plane here is the in-process scheduler + LocalWorkers (the
reference's LocalSwordfishWorker CI pattern); remote gRPC/Flight workers plug
in behind the same Worker interface.
"""

from __future__ import annotations

import logging
import os
import time
import uuid
from typing import Iterator, List, Optional

from daft_tpu.context import get_context
from daft_tpu.distributed.planner import DistributedExecutor
from daft_tpu.distributed.worker import LocalWorker, WorkerManager
from daft_tpu.micropartition import MicroPartition
from daft_tpu.runners.runner import Runner
from daft_tpu.subscribers.events import QueryEnd, QueryStart


class DistributedRunner(Runner):
    name = "distributed"

    def __init__(self, num_workers: Optional[int] = None, slots_per_worker: int = 2,
                 manager: Optional[WorkerManager] = None, backend: Optional[str] = None):
        cfg = get_context().execution_config
        if manager is not None:
            self.manager = manager
            return
        from daft_tpu.config import daft_env

        backend = backend or daft_env("DAFT_WORKER_BACKEND", "thread")
        addresses = daft_env("DAFT_WORKER_ADDRESSES")
        n = num_workers or cfg.num_workers or int(daft_env("DAFT_NUM_WORKERS", "2"))
        if addresses or backend == "daemon":
            # Multi-host daemons reachable over TCP + Flight (reference: the
            # Ray-actor control plane in daft/runners/flotilla.py:139-290).
            from daft_tpu.distributed.daemon import (
                RemoteWorker,
                spawn_local_daemon,
                wait_for_daemon,
            )

            addrs = [a.strip() for a in (addresses or "").split(",") if a.strip()]
            self._daemon_procs = []
            try:
                if not addrs:
                    # No cluster given: spawn a local one (dev/CI convenience).
                    self._daemon_procs = [
                        spawn_local_daemon(slots=slots_per_worker,
                                           device_index=i)
                        for i in range(n)]
                    addrs = [wait_for_daemon(p) for p in self._daemon_procs]
                workers = [RemoteWorker(a) for a in addrs]
            except BaseException:
                for p in self._daemon_procs:  # don't leak half-started daemons
                    try:
                        p.kill()
                    except OSError:
                        pass  # already exited
                raise
            procs = self._daemon_procs

            class _DaemonManager(WorkerManager):
                def shutdown(self) -> None:
                    super().shutdown()
                    for p in procs:
                        try:
                            p.kill()
                        except OSError:
                            pass  # already exited

            def _daemon_factory():
                # Fleet scale-up for locally-spawned daemon clusters: mint a
                # fresh daemon process; _DaemonManager.shutdown reaps it with
                # the rest (procs is shared by closure).
                p = spawn_local_daemon(slots=slots_per_worker)
                procs.append(p)
                return RemoteWorker(wait_for_daemon(p))

            self.manager = _DaemonManager(
                workers, factory=_daemon_factory if not addresses else None)
            self._start_heartbeat(cfg)
            self._maybe_start_fleet(cfg)
            return
        if backend == "process":
            # True process isolation (reference: per-node Ray actors). A
            # chip belongs to one process: worker i is started on chip i of
            # this host while chips remain, every further worker (and every
            # one the factory mints later) on CPU — device.child_device_env.
            from daft_tpu.distributed.process_worker import ProcessWorker

            workers = [ProcessWorker(f"proc-{i}", device_index=i)
                       for i in range(n)]
            self.manager = WorkerManager(workers, factory=lambda: ProcessWorker())
            self._start_heartbeat(cfg)
        else:
            workers = [LocalWorker(f"worker-{i}", num_slots=slots_per_worker) for i in range(n)]
            self.manager = WorkerManager(
                workers, factory=lambda: LocalWorker(num_slots=slots_per_worker)
            )
        self._maybe_start_fleet(cfg)

    def _maybe_start_fleet(self, cfg) -> None:
        """Elastic fleet (DAFT_FLEET=1 / fleet_enabled): a FleetController
        watching the telemetry planes drives this manager's worker set
        between fleet_min_workers and fleet_max_workers. Factory-bearing
        backends only — the controller must be able to mint workers. The
        manager owns the controller's lifetime (stopped first in its
        shutdown)."""
        if not getattr(cfg, "fleet_enabled", False):
            return
        if getattr(self.manager, "_factory", None) is None:
            return
        from daft_tpu.distributed.fleet import FleetController

        FleetController(self.manager, cfg).start()

    def _start_heartbeat(self, cfg) -> None:
        # Out-of-process workers can die silently; probe them so the
        # scheduler stops routing to a dead host before a task has to fail.
        if cfg.heartbeat_interval_s > 0:
            self.manager.start_heartbeat_monitor(
                cfg.heartbeat_interval_s, cfg.heartbeat_miss_threshold)

    def run_iter(self, builder, timeout: Optional[float] = None) -> Iterator[MicroPartition]:
        from daft_tpu import profiling

        ctx = get_context()
        cfg = ctx.execution_config
        query_id = uuid.uuid4().hex[:16]
        # Profiling (opt-in: collect(profile=...) / DAFT_PROFILE): the
        # QueryProfile's (trace_id, root span_id) becomes ambient inside
        # trace_scope below, so every Task created by the planner captures
        # it (Task.trace_ctx default_factory) and ships it to its worker.
        prof = profiling.begin_query(query_id, cfg)
        from daft_tpu import querylog
        from daft_tpu.cancellation import (
            cancel_scope,
            register_query_token,
            unregister_query_token,
        )
        from daft_tpu.runners.runner import enter_front_door

        # Feedback-sized admission (see native.py): the pre-optimize query
        # key is computed before the front door so the reservation can be
        # hinted from the store's observed peak for this fingerprint.
        pre_key = None
        mem_hint = None
        from daft_tpu import feedback

        if feedback.corrections_enabled(cfg):
            try:
                from daft_tpu import plancache

                pre_key = plancache.compute_query_key(builder.plan, cfg)
                mem_hint = feedback.get_store(cfg).mem_hint(pre_key.fp)
            except Exception:  # daftlint: disable=DTL002 -- feedback is never a gate
                pre_key = None
                mem_hint = None

        # One token per query, created on the driver by the shared
        # prologue (flight-recorder entry + explicit timeout > config
        # default > unbounded), then the admission front door BEFORE
        # planning/dispatch. A shed-ladder thread cap lands on cfg, which
        # ships with every Task, so worker-side executors inherit it (see
        # runner.py).
        token, ticket, cfg, fentry = enter_front_door(query_id, cfg, timeout,
                                                      runner=self.name,
                                                      mem_hint=mem_hint)
        from daft_tpu.execution import memledger
        from daft_tpu.runners.runner import plan_with_caches

        # Memory observatory: LocalWorkers charge this process ledger
        # directly (same query id); process/daemon workers ship their
        # per-task ledger profiles on the reply wire, merged in the worker
        # glue — the finish_query below reconciles the combined picture.
        ledger = memledger.get_ledger()
        if not getattr(cfg, "memory_ledger_enabled", True) and ledger.enabled:
            # Like the metrics plane, config can only DISABLE, process-
            # wide — and disabling drops all in-flight attribution so no
            # balance strands behind the kill switch.
            ledger.enabled = False
            ledger.reset()
        ledger.ensure_sampler(cfg)

        def _finish_mem():
            mem = ledger.finish_query(query_id,
                                      reserved_bytes=ticket.mem_reserved,
                                      tenant=ticket.tenant)
            if fentry is not None:
                fentry.note_memory(mem)

        build = None
        try:
            # Result cache → plan cache → real optimize+translate (the
            # shared plan_with_caches helper; see runner.py). A result-
            # cache hit never dispatches a single task.
            physical, plan_repr, cached_parts, build = plan_with_caches(
                builder, cfg, prof, fentry, token, ticket.tenant,
                key=pre_key)
            if fentry is not None and cached_parts is None:
                # First moment the plan fingerprint exists: the tail
                # sampler may recognize an armed slow shape and open a
                # full profile for this run (daft_tpu/slo.py).
                fentry.observe_plan(plan_repr)
                if prof is None:
                    prof = querylog.maybe_autoprofile(query_id, fentry)
                fentry.profiled = prof is not None
        except BaseException as e:  # noqa: BLE001
            # The execution try/finally below hasn't started: close the
            # profile HERE or a planning failure leaks it in the process-
            # global registry forever (and collect_profile gets no trace) —
            # and release the admission slot + flight record the same way.
            if build is not None:
                build.abort()
            ticket.release()
            _finish_mem()
            profiling.end_query(query_id, error=str(e))
            querylog.finish_entry(fentry, error=e)
            raise
        if cached_parts is not None:
            # Result-cache hit: stream the materialized partitions under
            # the same event/record/token/finally discipline as a real run
            # (registered token: cancel_query(id) must work on a cached
            # stream exactly as the native runner's hit path does).
            ctx.notify(QueryStart(query_id=query_id, plan=plan_repr))
            start = time.perf_counter()
            error = None
            error_obj = None
            register_query_token(query_id, token)
            try:
                for mp in cached_parts:
                    token.check("cached-result")
                    if fentry is not None:
                        fentry.count(mp)
                    yield mp
            except BaseException as e:  # noqa: BLE001
                error = str(e)
                error_obj = e
                raise
            finally:
                ticket.release()
                _finish_mem()
                unregister_query_token(query_id)
                ctx.notify(QueryEnd(query_id=query_id,
                                    duration_s=time.perf_counter() - start,
                                    error=error))
                prof_fin = profiling.end_query(query_id, error=error)
                querylog.finish_entry(fentry, error=error_obj,
                                      profile=prof_fin)
            return
        ctx.notify(QueryStart(query_id=query_id, plan=plan_repr))
        start = time.perf_counter()
        error = None
        error_obj = None
        from daft_tpu.execution.resource_manager import (
            RuntimeStats,
            register_query_stats,
            unregister_query_stats,
        )

        stats = RuntimeStats(query_id)
        stats.local_flush = False  # workers already emit OperatorStats events
        ctx.last_query_stats = stats  # DataFrame.metrics() surface
        register_query_stats(query_id, stats)
        from daft_tpu.context import frozen_clock_scope

        from daft_tpu.distributed.faults import config_fault_scope

        register_query_token(query_id, token)
        try:
            executor = DistributedExecutor(self.manager, cfg, query_id=query_id,
                                           cancel_token=token)
            # A cfg-armed fault spec is scoped to the SYNCHRONOUS execution
            # of this query only (explicit fault_scope / DAFT_FAULT_SPEC env
            # injectors take precedence) — it must not stay armed across the
            # generator's yields, where a concurrent query would inherit it.
            with config_fault_scope(cfg):
                # Freeze only around the synchronous plan execution: every
                # Task created inside captures this one instant
                # (Task.frozen_clock default_factory) and ships it with it —
                # the trace context follows the same capture discipline.
                with cancel_scope(token), frozen_clock_scope(), \
                        profiling.trace_scope(prof):
                    refs = executor.execute(physical)
            for ref in refs:
                # Recovery-aware: an output hosted on a since-dead worker
                # is recomputed from lineage instead of failing collect.
                # Still deadline-bounded: fetch/recovery checks the token.
                mp = executor.fetch_output(ref)
                if len(mp):
                    if fentry is not None:
                        fentry.count(mp)
                    if build is not None:
                        build.add(mp)
                    yield mp
            if build is not None:
                # Full drain only — a partial iteration aborts in the
                # finally instead (no partially-built cache entries).
                build.commit()
        except BaseException as e:  # noqa: BLE001
            error = str(e)
            error_obj = e
            raise
        finally:
            # Exception-safe on EVERY exit: success, timeout, cancel,
            # worker loss mid-query, chaos, and generator close all pass
            # here — admission slots/reservations can never leak, and the
            # query's ONE flight record lands whatever the outcome.
            if build is not None:
                build.abort()
            # Shuffle chunk files released in the SAME finally as the
            # admission ticket: cancel/timeout/worker-death teardown frees
            # disk exactly like success (zero-leak lifecycle contract;
            # audit_shuffle_leaks() is the assertion surface).
            try:
                self.manager.release_query(query_id)
            except Exception:
                # Best-effort: the audit hook catches anything a broken
                # release leaves behind; teardown must not mask the
                # query's own outcome.
                logging.getLogger("daft_tpu.runner").debug(
                    "shuffle release for query %s failed", query_id,
                    exc_info=True)
            ticket.release()
            # Reservation-vs-actual reconciliation (memory observatory):
            # worker-shipped ledger profiles have merged by now — the mem
            # block lands on the flight record, residue force-drains, and
            # the over/under counters move.
            _finish_mem()
            unregister_query_token(query_id)
            unregister_query_stats(query_id)
            ctx.notify(QueryEnd(query_id=query_id,
                                duration_s=time.perf_counter() - start, error=error))
            prof_fin = profiling.end_query(query_id, error=error)
            querylog.finish_entry(fentry, error=error_obj, profile=prof_fin)
