"""Engine-overhead watchdog: the SAME CLIP forward run (a) standalone
through FlaxCLIPImageEmbedder and (b) through the full engine path
``read -> UDFProject(embed_image) -> collect`` at MATCHED batch size and
staging mode, on whatever backend is available. The engine may cost at most
15% over the bare forward. On this sandbox's CPU that is a host-side fence,
not a device metric.

Statistical discipline (the PR 6 profiler-guard machinery): standalone and
engine runs alternate in ABBA blocks inside ONE process, so shared-box
weather hits both sides of each pair symmetrically; the verdict is the
median of per-block ratios, and a failing verdict escalates once with 3x
the blocks before it is believed. A CONFIRMED failure does not just report
a ratio — it re-runs the engine side under the profiler and fails with a
per-operator gap breakdown (morsel re-batching vs UDF dispatch vs fetch),
so the offending layer is named.
"""

from __future__ import annotations

import statistics
import time

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.datatype import DataType
from daft_tpu.functions.ai import embed_image
from daft_tpu.perf_report import gap_breakdown

#: Engine wall / standalone wall must stay under this.
OVERHEAD_LIMIT = 1.15
#: Corpus size: 12 chunks at B=1024, 24 at B=512 — big enough that the
#: forward dominates the engine's per-QUERY fixed cost (plan/optimize ≈
#: 10-15 ms, which is amortized noise in any real workload but reads as
#: inflated per-row tax on a tiny corpus), small enough for tier-1
#: (tiny CLIP, 32x32 images: ~0.15 s per pass on one CPU core).
N = 12288
MODEL = "tiny"
BLOCKS = 3
ESCALATED_BLOCKS = 9


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    return rng.integers(0, 255, (N, 32, 32, 3), dtype=np.uint8)


def _engine_frame(imgs):
    series = daft_tpu.Series.from_numpy(
        imgs.reshape(N, -1), "img", DataType.image("RGB", 32, 32))
    return daft_tpu.from_pydict({"img": series})


def _measure_pairs(imgs, batch: int, blocks: int,
                   staging_mode: str) -> tuple:
    """(ratios, standalone_s, engine_s): per-ABBA-block engine/standalone
    wall ratios plus the median wall of each side."""
    from daft_tpu.ai.flax_provider import FlaxCLIPImageEmbedder

    emb = FlaxCLIPImageEmbedder(MODEL, batch_size=batch,
                                staging_mode=staging_mode)
    df = _engine_frame(imgs)
    expr = embed_image(col("img"), provider="flax_random", model=MODEL,
                       batch_size=batch, staging_mode=staging_mode)

    def standalone_once() -> float:
        t0 = time.perf_counter()
        out = emb.embed_image(imgs)
        assert out.shape[0] == N
        return time.perf_counter() - t0

    def engine_once(profile=None) -> float:
        with daft_tpu.execution_config_ctx(default_morsel_size=N):
            t0 = time.perf_counter()
            q = df.with_column("emb", expr).select("emb")
            q.collect(profile=profile)
            wall = time.perf_counter() - t0
        assert len(q.to_pydict()["emb"]) == N
        return wall

    # Warm both sides (jit compile for the batch bucket + plan caches)
    # before anything is timed.
    emb.embed_image(imgs[:batch])
    engine_once()

    ratios, st_walls, en_walls = [], [], []
    for b in range(blocks):
        order = (standalone_once, engine_once) if b % 2 == 0 else \
            (engine_once, standalone_once)
        ts = [fn() for fn in order]
        st, en = (ts if b % 2 == 0 else (ts[1], ts[0]))
        st_walls.append(st)
        en_walls.append(en)
        ratios.append(en / st)
    return ratios, statistics.median(st_walls), statistics.median(en_walls)


def _profiled_breakdown(imgs, batch: int, staging_mode: str,
                        standalone_s: float, engine_s: float) -> str:
    """One profiled engine pass -> per-operator gap attribution."""
    df = _engine_frame(imgs)
    expr = embed_image(col("img"), provider="flax_random", model=MODEL,
                       batch_size=batch, staging_mode=staging_mode)
    with daft_tpu.execution_config_ctx(default_morsel_size=N):
        q = df.with_column("emb", expr).select("emb")
        q.collect(profile=True)
    return gap_breakdown(q.query_profile, standalone_s, engine_s)


@pytest.mark.parametrize("batch", [512, 1024])
def test_engine_overhead_within_budget(corpus, batch):
    staging_mode = "overlap"  # matched on both sides
    ratios, st, en = _measure_pairs(corpus, batch, BLOCKS, staging_mode)
    verdict = statistics.median(ratios)
    if verdict >= OVERHEAD_LIMIT:
        # Escalate once: weather rarely survives 3x the paired sample, a
        # real engine tax does.
        ratios, st, en = _measure_pairs(corpus, batch, ESCALATED_BLOCKS,
                                        staging_mode)
        verdict = statistics.median(ratios)
    if verdict >= OVERHEAD_LIMIT:
        breakdown = _profiled_breakdown(corpus, batch, staging_mode, st, en)
        pytest.fail(
            f"engine path costs x{verdict:.3f} over the standalone forward "
            f"at B={batch} (budget x{OVERHEAD_LIMIT}); attribution:\n"
            f"{breakdown}")
    # Throughput context on the record (visible with -rP / -v).
    print(f"B={batch} staging={staging_mode}: engine x{verdict:.3f} "
          f"standalone ({N / en:.0f} vs {N / st:.0f} img/s)")


def test_gap_breakdown_names_operators(corpus):
    """The failure path's attribution names the engine's operators with
    their self-times — a watchdog that fails must say WHERE."""
    df = _engine_frame(corpus)
    expr = embed_image(col("img"), provider="flax_random", model=MODEL,
                       batch_size=512)
    with daft_tpu.execution_config_ctx(default_morsel_size=N):
        q = df.with_column("emb", expr).select("emb")
        q.collect(profile=True)
    text = gap_breakdown(q.query_profile, 0.10, 0.15)
    assert "UDFProject" in text
    assert "gap +0.050s" in text
    assert "<unattributed (plan/dispatch)>" in text
