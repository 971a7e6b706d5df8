"""Headline benchmark: CLIP ViT-L/14 embed_image throughput on TPU.

North star (BASELINE.json): `df.with_column(embed_image(...))` over a
LAION-like image corpus, measured as embeddings/sec/chip, matching
RayRunner-on-A100 rows/sec. The comparison point is CLIP ViT-L/14 batch
inference on one A100 (fp16, batched) ≈ 340 images/sec — the published
ballpark for the reference's GPU path.

Runs the REAL engine path: FixedShapeImage column -> UDFProject actor ->
uint8 HBM staging -> jitted bf16 Flax CLIP forward, once, in this process.
Prints exactly one JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "n_devices", ...}. Without a TPU it fails: no
cached number is replayed and nothing runs on the CPU in its place.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

A100_BASELINE_IMGS_PER_SEC = 340.0

IMAGE_SIZE = 224
NUM_IMAGES = 4096
BATCH_SIZE = 256

# MFU estimate inputs: CLIP ViT-L/14 forward ~160 GFLOP/image at 224px, over
# the chip's published bf16 peak. A device kind that is not in the table is
# an error, not a default.
VIT_L14_GFLOP_PER_IMG = 160.0
PEAK_TFLOPS_BF16 = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": 197.0,
}


def _bench_engine(num_images: int, batch_size: int) -> dict:
    """The real measurement: engine-path embed_image over an image column."""
    from daft_tpu.device import require_tpu, setup_compile_cache

    setup_compile_cache()
    device = require_tpu()
    if device["kind"] not in PEAK_TFLOPS_BF16:
        raise RuntimeError(
            f"no bf16 peak on record for device kind {device['kind']!r}; "
            f"add it to PEAK_TFLOPS_BF16 with its source")
    import numpy as np

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.datatype import DataType
    from daft_tpu.functions.ai import embed_image
    from daft_tpu.profiling import newest_device_span

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 255, (num_images, IMAGE_SIZE, IMAGE_SIZE, 3),
                        dtype=np.uint8)
    img_dtype = DataType.image("RGB", IMAGE_SIZE, IMAGE_SIZE)
    series = daft_tpu.Series.from_numpy(
        imgs.reshape(num_images, -1), "img", img_dtype)

    df = daft_tpu.from_pydict({"img": series})
    expr = embed_image(col("img"), provider="flax_random", model="ViT-L/14",
                       batch_size=batch_size)

    with daft_tpu.execution_config_ctx(default_morsel_size=num_images):
        # Warmup: compile the forward for the batch bucket.
        warm = df.limit(batch_size).with_column("emb", expr)
        warm.collect()

        start = time.perf_counter()
        out = df.with_column("emb", expr).select("emb")
        total = 0
        for part in out.iter_partitions():
            total += len(part)
        elapsed = time.perf_counter() - start

    assert total == num_images, f"expected {num_images} rows, got {total}"
    # Publish the last forward's counters (rows, chunks, devices), so
    # results are attributable.
    stats = newest_device_span("provider.forward").count
    sys.stderr.write(f"last forward: {stats}, engine wall {elapsed:.2f}s\n")
    from daft_tpu.perf_report import resolved_compute_threads

    # Per chip = per device the model's parameters occupy, not per device
    # the host happens to have.
    per_chip = num_images / elapsed / stats["n_devices"]
    return {
        "metric": "embed_image_clip_vit_l14_throughput_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / A100_BASELINE_IMGS_PER_SEC, 3),
        "platform": device["platform"],
        "device_kind": device["kind"],
        "n_devices": device["count"],
        "n_devices_used": stats["n_devices"],
        "cpu_cores": os.cpu_count(),
        "num_compute_threads": resolved_compute_threads(),
        "phases": stats,
        "mfu_est": round(per_chip * VIT_L14_GFLOP_PER_IMG
                         / (PEAK_TFLOPS_BF16[device["kind"]] * 1e3), 3),
    }


# ------------------------------------------------------------------ #
# Metrics-plane overhead guard (ISSUE 5 satellite)                     #
# ------------------------------------------------------------------ #
# A TPC-H-style relational loop (scan -> filter -> join -> groupby ->
# sort), timed with the metrics plane enabled vs DAFT_METRICS=0. The
# instrumented hot paths (morsel counters, permit gates, IO counters,
# dispatcher gauges) must cost < 2% — otherwise the measurement plane is
# eating the goodput it exists to protect.
METRICS_OVERHEAD_LIMIT_PCT = float(
    os.environ.get("DAFT_METRICS_OVERHEAD_LIMIT_PCT", "2.0"))
_TPCH_CHILD = r"""
import json, sys, time
import numpy as np
import daft_tpu
from daft_tpu import col

n = int(sys.argv[1]); reps = int(sys.argv[2])
rng = np.random.default_rng(0)
orders = daft_tpu.from_pydict({
    "o_key": np.arange(n, dtype=np.int64).tolist(),
    "o_cust": rng.integers(0, n // 8, n).tolist(),
    "o_total": rng.random(n).tolist()})
cust = daft_tpu.from_pydict({
    "c_key": np.arange(n // 8, dtype=np.int64).tolist(),
    "c_seg": rng.integers(0, 5, n // 8).tolist()})

def loop():
    q = (orders.where(col("o_total") > 0.2)
         .join(cust, left_on="o_cust", right_on="c_key")
         .groupby("c_seg").agg(col("o_total").sum().alias("rev"))
         .sort("rev", desc=True))
    return q.to_pydict()

loop()  # warm caches/JIT before timing
times = []
for _ in range(reps):
    t0 = time.perf_counter(); loop(); times.append(time.perf_counter() - t0)
print(json.dumps({"best_s": min(times)}))
"""


def _ab_overhead_check(env_var: str, metric: str, limit_pct: float,
                       n: int, reps: int, rounds: int) -> dict:
    """Compare best-of-N loop times with ``env_var`` on vs off, each config
    in fresh subprocesses (both planes read the env once per process).
    Single runs on a shared box vary 2x process-to-process, so the configs
    run INTERLEAVED over several rounds and the best time per config wins —
    the minimum is the only estimator whose noise shrinks with samples."""

    def run(enabled: bool) -> float:
        # DAFT_RESULT_CACHE=0: the loop repeats ONE query shape, and a
        # result-cache hit would replace the measured execution with a
        # sub-ms lookup — the guard's fixed per-query cost would then read
        # as a huge percentage of nothing.
        env = dict(os.environ, JAX_PLATFORMS="cpu", DAFT_RESULT_CACHE="0",
                   **{env_var: "1" if enabled else "0"})
        proc = subprocess.run(
            [sys.executable, "-c", _TPCH_CHILD, str(n), str(reps)],
            capture_output=True, text=True, env=env, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        if proc.returncode != 0:
            raise RuntimeError(f"overhead child failed:\n{proc.stderr[-2000:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])["best_s"]

    offs, ons = [], []
    for _ in range(rounds):  # alternate so load/thermal drift hits both
        offs.append(run(False))
        ons.append(run(True))
    from daft_tpu.perf_report import resolved_compute_threads

    off, on = min(offs), min(ons)
    pct = (on - off) / off * 100.0 if off > 0 else 0.0
    return {"metric": metric, "value": round(pct, 3),
            "unit": f"% vs {env_var}=0", "enabled_s": round(on, 4),
            "disabled_s": round(off, 4), "limit_pct": limit_pct,
            "cpu_cores": os.cpu_count(),
            "num_compute_threads": resolved_compute_threads(),
            "ok": pct < limit_pct}


def metrics_overhead_check(n: int = 400_000, reps: int = 7,
                           rounds: int = 3) -> dict:
    return _ab_overhead_check("DAFT_METRICS", "metrics_overhead_pct",
                              METRICS_OVERHEAD_LIMIT_PCT, n, reps, rounds)


# The profiler's enabled path (operator spans + per-pull clocks + span
# buffering) must ALSO stay under 2%: it is the instrument every perf PR is
# judged with, so it cannot eat the goodput it measures. Unlike the metrics
# registry (env read once per process), the profiler consults DAFT_PROFILE
# at every begin_query — so the A/B can alternate profiled and unprofiled
# reps INSIDE one process. That pairing is what makes the verdict stable on
# shared boxes: machine drift between two separate child processes swings
# 10x the 2% budget, but hits interleaved same-process reps symmetrically.
PROFILE_OVERHEAD_LIMIT_PCT = float(
    os.environ.get("DAFT_PROFILE_OVERHEAD_LIMIT_PCT", "2.0"))

# The flight recorder (daft_tpu/querylog.py) is ALWAYS on — unlike the
# opt-in profiler, its cost lands on every production query — so it gets
# the same paired guard with the same budget, toggling
# DAFT_QUERY_RECORDER per rep (the recorder consults the env at every
# begin, exactly so this A/B can alternate inside one process).
QUERYLOG_OVERHEAD_LIMIT_PCT = float(
    os.environ.get("DAFT_QUERYLOG_OVERHEAD_LIMIT_PCT", "2.0"))

_PROFILE_AB_CHILD = r"""
import gc, json, os, sys, time
import numpy as np
import daft_tpu
from daft_tpu import col

n = int(sys.argv[1]); blocks = int(sys.argv[2])
# Which plane's live switch this child A/Bs (DAFT_PROFILE for the
# profiler guard, DAFT_QUERY_RECORDER for the flight-recorder guard —
# both consult the env per query, which is what makes in-process
# alternation valid).
var = sys.argv[3] if len(sys.argv) > 3 else "DAFT_PROFILE"
rng = np.random.default_rng(0)
# numpy arrays go to from_pydict as-is: .tolist() on three 6M-element
# columns costs ~45s of untimed child setup per round, which alone eats
# most of the CI lane's timeout budget.
orders = daft_tpu.from_pydict({
    "o_key": np.arange(n, dtype=np.int64),
    "o_cust": rng.integers(0, n // 8, n),
    "o_total": rng.random(n)})
cust = daft_tpu.from_pydict({
    "c_key": np.arange(n // 8, dtype=np.int64),
    "c_seg": rng.integers(0, 5, n // 8)})

def loop():
    q = (orders.where(col("o_total") > 0.2)
         .join(cust, left_on="o_cust", right_on="c_key")
         .groupby("c_seg").agg(col("o_total").sum().alias("rev"))
         .sort("rev", desc=True))
    return q.to_pydict()

os.environ[var] = "1"
loop()  # warm caches/JIT + plane module state before timing
os.environ[var] = "0"
loop()
# ABBA blocks (phase alternates so a period-2 systematic — allocator
# oscillation, cache state — can't masquerade as config cost) with a
# gc.collect() before every timed rep (collector bursts land on whichever
# rep they please, 10x the signal).
on, off = [], []
for b in range(blocks):
    order = ("0", "1") if b % 2 == 0 else ("1", "0")
    ts = {}
    for m in order:
        os.environ[var] = m
        gc.collect()
        t0 = time.perf_counter(); loop(); ts[m] = time.perf_counter() - t0
    on.append(ts["1"]); off.append(ts["0"])
print(json.dumps({"on_s": on, "off_s": off}))
"""


def _paired_overhead_check(env_var: str, metric: str, limit_pct: float,
                           n: int, reps: int, rounds: int,
                           drop_env: tuple = ()) -> dict:
    # n matches TPC-H SF1 lineitem scale (6M rows): these planes' residual
    # cost is FIXED per query (a handful of spans / one ring append), and
    # the budget is "<2% TPC-H overhead" — queries there run hundreds of
    # ms to seconds, so the guard's loop must be query-sized, not
    # microbenchmark-sized, or a ~1ms fixed cost reads as inflated per-row
    # cost. ``reps`` counts ABBA pair-blocks per child.
    # Estimator: each pair shares one instant of machine weather; the
    # MEDIAN of paired deltas (pooled across children) rejects both slow
    # outliers and drift, where min-vs-min re-introduces each config's
    # independent luck. Shared-box drift is 10x the 2% budget; pairing is
    # what makes the verdict reproducible. Even so, the pooled median of
    # ~30 pairs still wanders ±2% when the box spends a whole round in a
    # storm, so a failing verdict ESCALATES once: double the sample with
    # fresh rounds and re-judge the pooled set. A real regression holds
    # its level through twice the data; weather does not.
    deltas, offs = [], []

    def collect(num_rounds: int) -> None:
        for _ in range(num_rounds):
            # DAFT_RESULT_CACHE=0: the child repeats one query shape —
            # served from the result cache it would measure the plane's
            # fixed tax against a sub-ms lookup instead of a real query.
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       DAFT_RESULT_CACHE="0")
            env.pop(env_var, None)  # the child drives the toggle
            for k in drop_env:      # measure collection, not file IO
                env.pop(k, None)
            proc = subprocess.run(
                [sys.executable, "-c", _PROFILE_AB_CHILD, str(n), str(reps),
                 env_var],
                capture_output=True, text=True, env=env, timeout=600,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            if proc.returncode != 0:
                raise RuntimeError(
                    f"overhead child failed:\n{proc.stderr[-2000:]}")
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
            deltas.extend(o - f for o, f in zip(rec["on_s"], rec["off_s"]))
            offs.extend(rec["off_s"])

    import statistics

    def verdict() -> tuple:
        off = statistics.median(offs)
        delta = statistics.median(deltas)
        pct = delta / off * 100.0 if off > 0 else 0.0
        return pct, off, delta

    collect(rounds)
    pct, off, delta = verdict()
    escalated = False
    if pct >= limit_pct:
        escalated = True
        collect(rounds)
        pct, off, delta = verdict()
    return {"metric": metric, "value": round(pct, 3),
            "unit": f"% vs {env_var}=0", "pairs": len(deltas),
            "escalated": escalated,
            "enabled_s": round(off + delta, 4), "disabled_s": round(off, 4),
            "limit_pct": limit_pct, "ok": pct < limit_pct}


def profile_overhead_check(n: int = 6_000_000, reps: int = 10,
                           rounds: int = 3) -> dict:
    return _paired_overhead_check(
        "DAFT_PROFILE", "profile_overhead_pct", PROFILE_OVERHEAD_LIMIT_PCT,
        n, reps, rounds, drop_env=("DAFT_PROFILE_FILE",))


def querylog_overhead_check(n: int = 6_000_000, reps: int = 10,
                            rounds: int = 3) -> dict:
    # Always-on recording must be invisible: same pairing, same budget,
    # DAFT_QUERY_LOG dropped so the guard measures the ring + SLO feed,
    # not an operator-configured sink's disk.
    return _paired_overhead_check(
        "DAFT_QUERY_RECORDER", "querylog_overhead_pct",
        QUERYLOG_OVERHEAD_LIMIT_PCT, n, reps, rounds,
        drop_env=("DAFT_QUERY_LOG",))


FEEDBACK_OVERHEAD_LIMIT_PCT = float(
    os.environ.get("DAFT_FEEDBACK_OVERHEAD_LIMIT_PCT", "2.0"))


def feedback_overhead_check(n: int = 6_000_000, reps: int = 10,
                            rounds: int = 3) -> dict:
    # Feedback plane (daft_tpu/feedback.py): estimate stamping at
    # translate, per-node actual counting in the executor's batch path,
    # the v6 estimates block, and the statistics-store feed — all keyed
    # off DAFT_FEEDBACK, consulted per query, so the same in-process
    # ABBA alternation holds. DAFT_FEEDBACK_PATH dropped so the guard
    # measures observation, not JSONL persistence.
    return _paired_overhead_check(
        "DAFT_FEEDBACK", "feedback_overhead_pct",
        FEEDBACK_OVERHEAD_LIMIT_PCT, n, reps, rounds,
        drop_env=("DAFT_FEEDBACK_PATH",))


# The integrity plane (daft_tpu/integrity.py) hashes every shuffle chunk
# at write AND verifies at read — a per-byte cost, unlike the fixed-per-
# query planes above, so its guard runs a genuinely shuffle-heavy query on
# a small flight-shuffle cluster and toggles ``integrity_enabled`` via the
# config (consulted at every verify site, so in-process ABBA alternation
# is valid the same way the profiler's env toggle is).
INTEGRITY_OVERHEAD_LIMIT_PCT = float(
    os.environ.get("DAFT_INTEGRITY_OVERHEAD_LIMIT_PCT", "2.0"))

_INTEGRITY_AB_CHILD = r"""
import gc, json, sys, time
import numpy as np
import daft_tpu
from daft_tpu import col
from daft_tpu.runners.distributed import DistributedRunner

n = int(sys.argv[1]); blocks = int(sys.argv[2])
rng = np.random.default_rng(0)
orders = daft_tpu.from_pydict({
    "o_key": np.arange(n, dtype=np.int64),
    "o_cust": rng.integers(0, n // 8, n),
    "o_total": rng.random(n)})
cust = daft_tpu.from_pydict({
    "c_key": np.arange(n // 8, dtype=np.int64),
    "c_seg": rng.integers(0, 5, n // 8)})

ctx = daft_tpu.get_context()
runner = DistributedRunner(num_workers=2)
ctx.set_runner(runner)

def loop(enabled):
    with daft_tpu.execution_config_ctx(
            shuffle_algorithm="flight", shuffle_chunk_bytes=64 * 1024,
            result_cache_enabled=False, integrity_enabled=enabled):
        q = (orders.join(cust, left_on="o_cust", right_on="c_key")
             .groupby("c_seg").agg(col("o_total").sum().alias("rev"))
             .sort("rev", desc=True))
        return q.to_pydict()

try:
    loop(True)   # warm workers/JIT/plane module state before timing
    loop(False)
    on, off = [], []
    for b in range(blocks):
        order = (False, True) if b % 2 == 0 else (True, False)
        ts = {}
        for m in order:
            gc.collect()
            t0 = time.perf_counter(); loop(m)
            ts[m] = time.perf_counter() - t0
        on.append(ts[True]); off.append(ts[False])
finally:
    runner.manager.shutdown()
print(json.dumps({"on_s": on, "off_s": off}))
"""


def integrity_overhead_check(n: int = 600_000, reps: int = 8,
                             rounds: int = 3) -> dict:
    import statistics

    deltas, offs = [], []

    def collect(num_rounds: int) -> None:
        for _ in range(num_rounds):
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("DAFT_INTEGRITY", None)  # the child drives the toggle
            proc = subprocess.run(
                [sys.executable, "-c", _INTEGRITY_AB_CHILD, str(n),
                 str(reps)],
                capture_output=True, text=True, env=env, timeout=600,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            if proc.returncode != 0:
                raise RuntimeError(
                    f"overhead child failed:\n{proc.stderr[-2000:]}")
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
            deltas.extend(o - f for o, f in zip(rec["on_s"], rec["off_s"]))
            offs.extend(rec["off_s"])

    def verdict() -> tuple:
        off = statistics.median(offs)
        delta = statistics.median(deltas)
        pct = delta / off * 100.0 if off > 0 else 0.0
        return pct, off, delta

    collect(rounds)
    pct, off, delta = verdict()
    escalated = False
    if pct >= INTEGRITY_OVERHEAD_LIMIT_PCT:
        # Same weather-vs-regression escalation as the paired guards:
        # double the sample before believing a failure.
        escalated = True
        collect(rounds)
        pct, off, delta = verdict()
    return {"metric": "integrity_overhead_pct", "value": round(pct, 3),
            "unit": "% vs integrity_enabled=False", "pairs": len(deltas),
            "escalated": escalated,
            "enabled_s": round(off + delta, 4), "disabled_s": round(off, 4),
            "limit_pct": INTEGRITY_OVERHEAD_LIMIT_PCT,
            "ok": pct < INTEGRITY_OVERHEAD_LIMIT_PCT}


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--metrics-overhead":
        rec = metrics_overhead_check()
        print(json.dumps(rec))
        if not rec["ok"]:
            sys.stderr.write(
                f"metrics plane overhead {rec['value']}% exceeds "
                f"{rec['limit_pct']}% budget\n")
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--profile-overhead":
        rec = profile_overhead_check()
        print(json.dumps(rec))
        if not rec["ok"]:
            sys.stderr.write(
                f"profiler overhead {rec['value']}% exceeds "
                f"{rec['limit_pct']}% budget\n")
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--querylog-overhead":
        rec = querylog_overhead_check()
        print(json.dumps(rec))
        if not rec["ok"]:
            sys.stderr.write(
                f"flight-recorder overhead {rec['value']}% exceeds "
                f"{rec['limit_pct']}% budget\n")
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--feedback-overhead":
        rec = feedback_overhead_check()
        print(json.dumps(rec))
        if not rec["ok"]:
            sys.stderr.write(
                f"feedback plane overhead {rec['value']}% exceeds "
                f"{rec['limit_pct']}% budget\n")
            sys.exit(1)
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--integrity-overhead":
        rec = integrity_overhead_check()
        print(json.dumps(rec))
        if not rec["ok"]:
            sys.stderr.write(
                f"integrity plane overhead {rec['value']}% exceeds "
                f"{rec['limit_pct']}% budget\n")
            sys.exit(1)
        return
    print(json.dumps(_bench_engine(NUM_IMAGES, BATCH_SIZE)))


if __name__ == "__main__":
    main()
