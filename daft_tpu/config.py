"""Planning and execution configuration.

Reference: ``DaftPlanningConfig`` / ``DaftExecutionConfig``
(src/common/daft-config/src/lib.rs:120-200, ~35 flags). Frozen dataclasses
threaded through the context; TPU-specific knobs (device_eval, batch-shape
bucketing to avoid XLA recompiles) extend the reference's set.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Optional, Tuple


def daft_env(name: str, default: Optional[str] = None) -> Optional[str]:
    """The single audited choke point for environment reads (daftlint
    DTL007). Every engine knob consulted from the environment goes through
    here so tests can monkeypatch ONE function, scattered reads can't drift
    from the config snapshot, and the set of honored variables stays
    greppable. Cloud-SDK credential conventions (AWS_*, GOOGLE_*) are the
    deliberate exception — they follow provider chains, not engine config."""
    return os.environ.get(name, default)


def daft_env_flag(name: str, default: bool = False) -> bool:
    """Boolean form of :func:`daft_env`: '0'/'false'/'no'/'off' (any case)
    are false, unset means ``default``, anything else is true."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("0", "false", "no", "off", "")


@dataclass(frozen=True)
class PlanningConfig:
    default_io_config: Optional[object] = None

    def with_changes(self, **kwargs) -> "PlanningConfig":
        return dataclasses.replace(self, **kwargs)


@dataclass(frozen=True)
class ExecutionConfig:
    # Scan task sizing (reference defaults: lib.rs:165-200)
    scan_tasks_min_size_bytes: int = 96 * 1024 * 1024
    scan_tasks_max_size_bytes: int = 384 * 1024 * 1024
    max_sources_per_scan_task: int = 10
    # Join strategy
    broadcast_join_size_bytes_threshold: int = 10 * 1024 * 1024
    sort_merge_join_sort_with_aligned_boundaries: bool = False
    # Partitioning
    hash_join_partition_size_leniency: float = 0.5
    num_preview_rows: int = 8
    # Morsel rows for pipeline stages. 2x the reference's 128k: this
    # engine's per-morsel cost has a Python component (stage dispatch,
    # expression eval setup) that 256k-row kernels amortize measurably —
    # TPC-H q18/q21 run ~15% faster at 256k than 128k on 4 threads.
    default_morsel_size: int = 256 * 1024
    target_batch_size_bytes: int = 64 * 1024 * 1024
    shuffle_algorithm: str = "auto"  # "auto" | "flight" | "in_memory"
    flight_shuffle_dirs: Tuple[str, ...] = ("/tmp",)
    # Shuffle data plane (distributed/shuffle.py): chunk files are
    # compressed Arrow IPC ("auto" negotiates lz4 -> zstd -> raw against
    # the local Arrow build), cut at shuffle_chunk_bytes; reduce readers
    # prefetch up to shuffle_prefetch_depth refs ahead on the wire path
    # (pipelined fetch overlapping downstream compute; <=1 fetches inline
    # with no look-ahead). shuffle_pipelined_fetch=False restores the
    # legacy eager whole-partition bind path entirely.
    shuffle_compression: str = "auto"  # "auto" | "lz4" | "zstd" | "none"
    shuffle_chunk_bytes: int = 4 * 1024 * 1024
    shuffle_prefetch_depth: int = 4
    shuffle_pipelined_fetch: bool = True
    partial_aggregation_threshold: int = 10_000
    # First-chunk group-reduction ratio above which the pipelined
    # aggregation hash-partitions instead of merging chunk partials: a
    # partial pass keeping > 30% of its rows feeds a serial merge nearly
    # the size of the input (q18's clustered l_orderkey measures ~25%
    # locally but 4x that globally — 0.3 routes it to the partitioned
    # path, ~1.7x faster there at 4 threads).
    high_cardinality_aggregation_threshold: float = 0.3
    # Reader/writer
    parquet_target_filesize: int = 512 * 1024 * 1024
    parquet_target_row_group_size: int = 128 * 1024 * 1024
    parquet_inflation_factor: float = 3.0
    csv_target_filesize: int = 512 * 1024 * 1024
    csv_inflation_factor: float = 0.5
    json_target_filesize: int = 512 * 1024 * 1024
    read_sql_partition_size_bytes: int = 512 * 1024 * 1024
    # Execution
    enable_aqe: bool = False
    default_maintain_order: bool = True
    # Worker-pool width for the pipelined executor (project / filter /
    # join-probe / parallel aggregation stages share ONE pool this wide).
    # 0 = one worker per visible CPU core; DAFT_COMPUTE_THREADS overrides
    # (reference: per-operator max_concurrency in
    # src/daft-local-execution/src/intermediate_ops/intermediate_op.rs:41).
    num_compute_threads: int = 0
    # Stage-input coalescing floor (rows): morsels smaller than this merge
    # before entering a pipeline stage so per-morsel queue + span overhead
    # can't dominate small-row queries. Must stay a pure config value —
    # morsel boundaries are part of the parallel-vs-serial determinism
    # contract (executor docstring).
    min_morsel_size: int = 16 * 1024
    enable_strict_filter_pushdown: bool = True
    min_cpu_per_task: float = 0.5
    memory_limit_bytes: Optional[int] = None
    # Host-UDF dynamic batching (reference: dynamic_batching/
    # latency_constrained_strategy.rs). Device UDFs keep static XLA buckets.
    udf_dynamic_batching: bool = True
    udf_target_batch_latency_s: float = 0.2
    # TPU-specific
    device_eval: bool = True
    device_eval_min_rows: int = 1024
    device_batch_buckets: Tuple[int, ...] = (1024, 4096, 16384, 65536, 131072)
    # Whole-chain compiled evaluation (ops/compiled_eval.py): filter →
    # project → agg chains trace into ONE jitted XLA program per
    # micropartition, cache-keyed on schema + canonicalized plan
    # fingerprint. DAFT_COMPILED_EVAL=0 disables; the module also carries a
    # process-level self-disable flipped by the fused-vs-interpreted ABBA
    # guard (perf_observatory.py --ab-fusion) when the compiled path loses.
    compiled_eval_enabled: bool = True
    # Stage fusion (execution/executor.py): adjacent Project/Filter
    # pipeline stages collapse into ONE composed morsel stage so a chain
    # costs one queue hop instead of N. Pure plan+config decision — never
    # thread-count — so the determinism contract holds. DAFT_STAGE_FUSION=0
    # disables.
    stage_fusion_enabled: bool = True
    # Distributed
    num_workers: int = 0  # 0 = autodetect / local
    autoscaling_threshold: float = 1.25
    # Fault tolerance (distributed/faults.py, distributed/scheduler.py)
    task_max_retries: int = 3           # per-task attempt budget (all causes)
    task_transient_backoff_s: float = 0.05   # base backoff for transient retries
    task_transient_backoff_cap_s: float = 2.0
    max_partition_recoveries: int = 32  # per-query lineage-recompute budget
    speculative_execution: bool = False  # duplicate straggler tasks
    speculative_multiplier: float = 3.0  # straggler = > mult x median duration
    speculative_min_completed: int = 3   # need this many samples for a median
    heartbeat_interval_s: float = 5.0    # worker liveness probe period
    heartbeat_miss_threshold: int = 3    # consecutive misses -> mark dead
    fault_spec: Optional[str] = None     # DAFT_FAULT_SPEC (see faults.py)
    fault_seed: int = 0
    # Elastic fleet (distributed/fleet.py): SLO-driven autoscaling between
    # fleet_min_workers and fleet_max_workers with hysteresis + cooldown.
    # DAFT_FLEET=1 enables; scale-up fires on admission queue pressure /
    # shed level / SLO burn / inflight saturation, scale-down drains ONE
    # idle worker after fleet_idle_ticks consecutive calm control ticks.
    # A drain that cannot pass the leak audits re-activates the worker;
    # one still running tasks past fleet_drain_timeout_s is killed into
    # the normal lineage-recovery path.
    fleet_enabled: bool = False          # DAFT_FLEET
    fleet_min_workers: int = 1           # DAFT_FLEET_MIN_WORKERS
    fleet_max_workers: int = 8           # DAFT_FLEET_MAX_WORKERS
    fleet_cooldown_s: float = 5.0        # DAFT_FLEET_COOLDOWN_S (between scale events)
    fleet_tick_interval_s: float = 0.5   # controller decision cadence
    fleet_idle_ticks: int = 3            # calm ticks before a drain (hysteresis)
    fleet_drain_timeout_s: float = 30.0  # running-task grace before kill-to-recovery
    fleet_up_queue_frac: float = 0.25    # queued/capacity fraction that scales up
    fleet_up_burn_rate: float = 1.0      # fast SLO burn rate that scales up
    fleet_up_inflight_frac: float = 0.9  # inflight/slots fraction that scales up
    fleet_up_memory_frac: float = 0.85   # ledger-held/limit fraction that scales up
    # Bounded-time execution (cancellation.py, io/circuit.py)
    query_timeout_s: Optional[float] = None  # DAFT_QUERY_TIMEOUT_S; None = unbounded
    # On deadline/cancel abort, how long the dispatcher waits for running
    # tasks to observe the token before abandoning them (a wedged worker
    # must not hang collect(timeout=...) past t + grace).
    cancel_drain_grace_s: float = 5.0
    # Per-endpoint IO circuit breaker (io/circuit.py): consecutive transient
    # failures to open; base/cap of the open->half-open probe delay
    # (seeded-jitter exponential); probes allowed while half-open.
    circuit_failure_threshold: int = 5
    circuit_open_base_s: float = 1.0
    circuit_open_cap_s: float = 30.0
    circuit_half_open_probes: int = 1
    # Metrics plane (daft_tpu/metrics.py). The registry gates itself on
    # DAFT_METRICS at first use; metrics_enabled=False on the ACTIVE config
    # additionally disables it process-wide at the first event notify (one
    # plane per process, not per query). metrics_export_path is the config
    # spelling of DAFT_METRICS_FILE (OTLP-JSON resourceMetrics lines).
    metrics_enabled: bool = True
    metrics_export_path: Optional[str] = None
    # Multi-tenant admission control (execution/admission.py). Enabled by
    # default — with the default unlimited per-tenant concurrency the
    # uncontended path is one lock acquisition per query (<2% guarded in
    # CI). Per-tenant defaults: admission_max_concurrent_queries (0 =
    # unlimited), admission_queue_depth (bounded wait queue; full = fast
    # DaftAdmissionError), admission_max_memory_fraction (reservation quota
    # vs DAFT_MEMORY_LIMIT; 1.0 = ungated). admission_policies is a JSON
    # map {tenant: {max_concurrent_queries, max_memory_fraction,
    # queue_depth, priority}} (DAFT_ADMISSION_POLICIES). Overload ladder:
    # queue pressure above admission_overload_queue_fraction of capacity or
    # MemoryManager permit-wait p95 above admission_permit_wait_p95_s sheds
    # in steps (see admission.py docstring); levels decay one step per
    # admission_shed_cooldown_s without overload.
    admission_enabled: bool = True
    admission_max_concurrent_queries: int = 0
    admission_queue_depth: int = 32
    admission_max_memory_fraction: float = 1.0
    admission_policies: Optional[str] = None
    admission_overload_queue_fraction: float = 0.8
    admission_permit_wait_p95_s: float = 1.0
    admission_shed_cooldown_s: float = 2.0
    # Query profiler (daft_tpu/profiling.py). Default OFF: profiling is
    # opt-in per query via df.collect(profile=...) or process-wide via
    # DAFT_PROFILE=1; profile_export_path (DAFT_PROFILE_FILE) writes the
    # Chrome trace-event JSON there at query end.
    profile_enabled: bool = False
    profile_export_path: Optional[str] = None
    # Query flight recorder (daft_tpu/querylog.py). Default ON — one
    # structured record per query (every outcome) into a bounded ring
    # (daft_tpu.recent_queries()); DAFT_QUERY_RECORDER=0 is the live kill
    # switch (and the overhead guard's A/B lever). query_log_path
    # (DAFT_QUERY_LOG) additionally appends schema-versioned JSONL with a
    # size-capped rotation (DAFT_QUERY_LOG_MAX_BYTES).
    query_recorder_enabled: bool = True
    query_log_path: Optional[str] = None
    # SLO plane (daft_tpu/slo.py). Per-tenant objectives — overridable per
    # tenant via the admission policy JSON (slo_latency_p99_s /
    # slo_error_rate keys) — and the multiwindow burn-rate alerting knobs:
    # an alert fires when the bad-query fraction burns the error budget
    # faster than slo_fast_burn x over slo_fast_window_s AND slo_slow_burn
    # x over slo_slow_window_s. slo_autoprofile_count is the tail sampler's
    # capture budget per armed plan fingerprint; slo_slow_query_s (> 0) is
    # a global slow-query arming threshold below the tenant objective.
    slo_latency_p99_s: float = 30.0
    slo_error_rate: float = 0.05
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 300.0
    slo_fast_burn: float = 14.0
    slo_slow_burn: float = 6.0
    slo_autoprofile_count: int = 3
    slo_slow_query_s: float = 0.0
    # Query-as-a-service caching (daft_tpu/plancache.py). Plan cache: a
    # bounded LRU keyed on the canonical PRE-optimize logical-plan
    # fingerprint + planning-config digest; a hit skips optimize+translate
    # (DAFT_PLAN_CACHE=0 disables, plan_cache_size bounds entries).
    # Result/scan cache: bounded byte-accounted cache of materialized
    # results and hot scan outputs (memoized size_bytes is the unit),
    # charged against the tenant's admission memory quota, invalidated by
    # every engine write and validated against source-file mtime/size at
    # hit time (DAFT_RESULT_CACHE=0 / DAFT_RESULT_CACHE_BYTES override;
    # result_cache_max_entry_bytes drops results too big to be worth
    # keeping; result_cache_scan_outputs gates the scan-output tier).
    plan_cache_enabled: bool = True
    plan_cache_size: int = 256
    # A cached plan over in-memory frames keeps those frames resident
    # (the plan references its InMemorySource partitions): the plan cache
    # is byte-bounded on that pinned total, not just entry count.
    plan_cache_max_pinned_bytes: int = 256 << 20
    result_cache_enabled: bool = True
    result_cache_max_bytes: int = 1 << 30
    result_cache_max_entry_bytes: int = 256 << 20
    result_cache_scan_outputs: bool = True
    # Memory observatory (execution/memledger.py). Default ON — the
    # per-query byte ledger every byte-holding subsystem reports into
    # (permits, stage queues, spill files, shuffle fetch buffers), with
    # reservation-vs-actual reconciliation at query end and a v3 ``mem``
    # block on every flight record. DAFT_MEMLEDGER=0 is the kill switch
    # (and the <2% overhead guard's A/B lever). The RSS sampler thread
    # correlates process truth against the ledger while queries are in
    # flight; DAFT_MEM_SAMPLER=0 / mem_sampler_enabled=False disables it
    # independently, mem_sampler_interval_s paces it.
    memory_ledger_enabled: bool = True
    mem_sampler_enabled: bool = True
    mem_sampler_interval_s: float = 0.25
    # Streaming ingestion & incremental materialized views
    # (daft_tpu/streaming/). Tailing sources emit bounded micro-batches —
    # at most streaming_max_batch_files files / streaming_max_batch_bytes
    # listed bytes per poll — so one refresh query through the front door
    # stays admission-sized; leftovers stay pending and surface as the
    # view's delta backlog. streaming_poll_interval_s paces the refresh
    # driver loop; streaming_checkpoint_dir (DAFT_STREAMING_CHECKPOINT)
    # persists per-view refresh state (consumed-delta keys + merged
    # partial state) so a process restart resumes without re-absorbing or
    # losing deltas. slo_staleness_p99_s is the freshness objective the
    # staleness burn-rate alerting (slo.py FreshnessTracker) evaluates —
    # overridable per tenant via the admission policy JSON, like the
    # latency objectives.
    streaming_max_batch_files: int = 64
    streaming_max_batch_bytes: int = 256 << 20
    streaming_poll_interval_s: float = 1.0
    streaming_checkpoint_dir: Optional[str] = None
    slo_staleness_p99_s: float = 60.0
    # Data-integrity plane (daft_tpu/integrity.py). Default ON: every
    # persisted / wire-crossing artifact (shuffle chunk files, spill files,
    # streaming checkpoint state) carries a digest minted at write and
    # verified at read; a mismatch quarantines the file and routes into
    # lineage recovery instead of serving corrupt bytes. Digests are always
    # MINTED (one streaming pass over bytes already in cache) so an
    # artifact written while verification was off still verifies later;
    # integrity_enabled gates only the read-side checks (DAFT_INTEGRITY=0
    # is the kill switch and the <2% ABBA overhead guard's A/B lever).
    # integrity_verify_on_write additionally re-reads each artifact
    # immediately after flush — a paranoid write-path knob for chaos runs.
    integrity_enabled: bool = True
    integrity_verify_on_write: bool = False
    # Feedback-driven planning (daft_tpu/feedback.py). The observation
    # plane is ON by default: the optimizer stamps its per-node row/byte
    # estimates into the physical plan, the executor counts what each
    # node actually produced, and every completed flight record (schema
    # v6 ``estimates`` block) feeds the per-fingerprint statistics store
    # (EWMA of observed cardinalities + peak memory). The CORRECTION
    # plane — approx_stats/ReorderJoins overridden by observed
    # cardinalities, admission reservations sized from observed peaks,
    # estimate-driven mid-query strategy switches — is opt-in via
    # feedback_correct_plans (plan-cache entries for corrected plans key
    # on the store's stats epoch, so a feedback update re-plans instead
    # of serving the stale plan). DAFT_FEEDBACK wins both directions:
    # =1 enables observation AND corrections, =0 byte-identically
    # restores today's planning (and is the <2% ABBA overhead guard's
    # A/B lever). feedback_path (DAFT_FEEDBACK_PATH) persists the store
    # as torn-line-safe JSONL; feedback_probe_factor is the observed-vs-
    # estimated contradiction ratio that triggers a mid-query strategy
    # switch (PlanCorrected event).
    feedback_enabled: bool = True
    feedback_correct_plans: bool = False
    feedback_path: Optional[str] = None
    feedback_ewma_alpha: float = 0.4
    feedback_max_fingerprints: int = 512
    feedback_probe_factor: float = 8.0

    def with_changes(self, **kwargs) -> "ExecutionConfig":
        return dataclasses.replace(self, **kwargs)

    @staticmethod
    def from_env() -> "ExecutionConfig":
        cfg = ExecutionConfig()
        env_memory = os.environ.get("DAFT_MEMORY_LIMIT")
        changes = {}
        if env_memory:
            changes["memory_limit_bytes"] = int(env_memory)
        if os.environ.get("DAFT_TPU_DEVICE_EVAL") in ("0", "false"):
            changes["device_eval"] = False
        if not daft_env_flag("DAFT_COMPILED_EVAL", True):
            changes["compiled_eval_enabled"] = False
        if not daft_env_flag("DAFT_STAGE_FUSION", True):
            changes["stage_fusion_enabled"] = False
        if os.environ.get("DAFT_SHUFFLE_ALGORITHM"):
            changes["shuffle_algorithm"] = os.environ["DAFT_SHUFFLE_ALGORITHM"]
        if os.environ.get("DAFT_SHUFFLE_COMPRESSION"):
            changes["shuffle_compression"] = \
                os.environ["DAFT_SHUFFLE_COMPRESSION"]
        if os.environ.get("DAFT_SHUFFLE_CHUNK_BYTES"):
            changes["shuffle_chunk_bytes"] = int(
                os.environ["DAFT_SHUFFLE_CHUNK_BYTES"])
        if os.environ.get("DAFT_SHUFFLE_PREFETCH_DEPTH"):
            changes["shuffle_prefetch_depth"] = int(
                os.environ["DAFT_SHUFFLE_PREFETCH_DEPTH"])
        if not daft_env_flag("DAFT_SHUFFLE_PIPELINED", True):
            changes["shuffle_pipelined_fetch"] = False
        if os.environ.get("DAFT_FAULT_SPEC"):
            changes["fault_spec"] = os.environ["DAFT_FAULT_SPEC"]
        if os.environ.get("DAFT_FAULT_SEED"):
            changes["fault_seed"] = int(os.environ["DAFT_FAULT_SEED"])
        if daft_env_flag("DAFT_FLEET", False):
            changes["fleet_enabled"] = True
        if os.environ.get("DAFT_FLEET_MIN_WORKERS"):
            changes["fleet_min_workers"] = int(
                os.environ["DAFT_FLEET_MIN_WORKERS"])
        if os.environ.get("DAFT_FLEET_MAX_WORKERS"):
            changes["fleet_max_workers"] = int(
                os.environ["DAFT_FLEET_MAX_WORKERS"])
        if os.environ.get("DAFT_FLEET_COOLDOWN_S"):
            changes["fleet_cooldown_s"] = float(
                os.environ["DAFT_FLEET_COOLDOWN_S"])
        if os.environ.get("DAFT_SPECULATION") in ("1", "true"):
            changes["speculative_execution"] = True
        if os.environ.get("DAFT_COMPUTE_THREADS"):
            changes["num_compute_threads"] = int(
                os.environ["DAFT_COMPUTE_THREADS"])
        if os.environ.get("DAFT_QUERY_TIMEOUT_S"):
            changes["query_timeout_s"] = float(os.environ["DAFT_QUERY_TIMEOUT_S"])
        if not daft_env_flag("DAFT_METRICS", True):
            changes["metrics_enabled"] = False
        if os.environ.get("DAFT_METRICS_FILE"):
            changes["metrics_export_path"] = os.environ["DAFT_METRICS_FILE"]
        if not daft_env_flag("DAFT_ADMISSION", True):
            changes["admission_enabled"] = False
        if os.environ.get("DAFT_ADMISSION_MAX_CONCURRENT"):
            changes["admission_max_concurrent_queries"] = int(
                os.environ["DAFT_ADMISSION_MAX_CONCURRENT"])
        if os.environ.get("DAFT_ADMISSION_QUEUE_DEPTH"):
            changes["admission_queue_depth"] = int(
                os.environ["DAFT_ADMISSION_QUEUE_DEPTH"])
        if os.environ.get("DAFT_ADMISSION_POLICIES"):
            changes["admission_policies"] = \
                os.environ["DAFT_ADMISSION_POLICIES"]
        if daft_env_flag("DAFT_PROFILE", False):
            changes["profile_enabled"] = True
        if os.environ.get("DAFT_PROFILE_FILE"):
            changes["profile_export_path"] = os.environ["DAFT_PROFILE_FILE"]
        if not daft_env_flag("DAFT_QUERY_RECORDER", True):
            changes["query_recorder_enabled"] = False
        if os.environ.get("DAFT_QUERY_LOG"):
            changes["query_log_path"] = os.environ["DAFT_QUERY_LOG"]
        if os.environ.get("DAFT_SLO_LATENCY_P99_S"):
            changes["slo_latency_p99_s"] = float(
                os.environ["DAFT_SLO_LATENCY_P99_S"])
        if os.environ.get("DAFT_SLO_ERROR_RATE"):
            changes["slo_error_rate"] = float(
                os.environ["DAFT_SLO_ERROR_RATE"])
        if os.environ.get("DAFT_SLO_AUTOPROFILE"):
            changes["slo_autoprofile_count"] = int(
                os.environ["DAFT_SLO_AUTOPROFILE"])
        if not daft_env_flag("DAFT_PLAN_CACHE", True):
            changes["plan_cache_enabled"] = False
        if os.environ.get("DAFT_PLAN_CACHE_SIZE"):
            changes["plan_cache_size"] = int(
                os.environ["DAFT_PLAN_CACHE_SIZE"])
        if not daft_env_flag("DAFT_RESULT_CACHE", True):
            changes["result_cache_enabled"] = False
        if not daft_env_flag("DAFT_MEMLEDGER", True):
            changes["memory_ledger_enabled"] = False
        if not daft_env_flag("DAFT_MEM_SAMPLER", True):
            changes["mem_sampler_enabled"] = False
        if os.environ.get("DAFT_RESULT_CACHE_BYTES"):
            changes["result_cache_max_bytes"] = int(
                os.environ["DAFT_RESULT_CACHE_BYTES"])
        if os.environ.get("DAFT_STREAMING_BATCH_FILES"):
            changes["streaming_max_batch_files"] = int(
                os.environ["DAFT_STREAMING_BATCH_FILES"])
        if os.environ.get("DAFT_STREAMING_BATCH_BYTES"):
            changes["streaming_max_batch_bytes"] = int(
                os.environ["DAFT_STREAMING_BATCH_BYTES"])
        if os.environ.get("DAFT_STREAMING_CHECKPOINT"):
            changes["streaming_checkpoint_dir"] = \
                os.environ["DAFT_STREAMING_CHECKPOINT"]
        if os.environ.get("DAFT_SLO_STALENESS_P99_S"):
            changes["slo_staleness_p99_s"] = float(
                os.environ["DAFT_SLO_STALENESS_P99_S"])
        if not daft_env_flag("DAFT_INTEGRITY", True):
            changes["integrity_enabled"] = False
        if daft_env_flag("DAFT_INTEGRITY_VERIFY_ON_WRITE", False):
            changes["integrity_verify_on_write"] = True
        if os.environ.get("DAFT_FEEDBACK") is not None:
            on = daft_env_flag("DAFT_FEEDBACK", True)
            changes["feedback_enabled"] = on
            changes["feedback_correct_plans"] = on
        if os.environ.get("DAFT_FEEDBACK_PATH"):
            changes["feedback_path"] = os.environ["DAFT_FEEDBACK_PATH"]
        if os.environ.get("DAFT_FEEDBACK_PROBE_FACTOR"):
            changes["feedback_probe_factor"] = float(
                os.environ["DAFT_FEEDBACK_PROBE_FACTOR"])
        return cfg.with_changes(**changes) if changes else cfg
