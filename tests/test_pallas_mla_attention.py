"""The prefill attention kernel of LongCat-Flash (``ops/pallas_mla_attention.py``):
its arithmetic in interpret mode on the CPU against ``longcat_flash.
mla_core_expanded`` over the same cache, the walk it is handed, the rule that
selects it, and ``LongcatFlashLM.prefill`` on either path. Its compile for a
described v5e at the benchmark's shapes is in ``tests/test_pallas.py``, with the
fixture that describes the chip."""

import dataclasses
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.models import longcat_flash as lc
from daft_tpu.ops import pallas_attention as pa
from daft_tpu.ops import pallas_mla_attention as pm

T = 128                     # one lane tile of queries a chunk
NOPE, ROPE, DV = 128, 64, 128

#: name -> (heads, latent, starts, lengths): one row of the call each, at slot (rows - 1 - row) of a cache of three
#: blocks and five positions more. A row attends ``starts // T + 1`` blocks, and none where its length is 0.
LAYOUTS = {
    "one_block": (4, 128, [0, 0], [T, T]),
    "two_blocks": (4, 128, [T, T], [T, T]),
    "three_blocks": (4, 256, [2 * T, 2 * T], [T, T]),
    "a_row_without_a_query": (4, 128, [2 * T, 2 * T, 2 * T], [T, 0, T]),
    "only_rows_without_a_query": (1, 128, [T, T], [0, 0]),
    "a_partial_last_chunk": (4, 128, [T, T, T], [T, 77, 1]),
    "rows_at_unlike_depths": (4, 128, [2 * T, 0, T], [T, T, 50]),
    "one_head": (1, 512, [T, T], [T, 9]),
}


def _cfg(lat, dtype):
    """What ``mla_core_expanded`` reads of a configuration."""
    return SimpleNamespace(qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, qk_head_dim=NOPE + ROPE, v_head_dim=DV,
                           kv_lora_rank=lat, dtype=dtype)


def _operands(heads, lat, rows, dtype, seed=0, positions=3 * T + 5):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((rows, T, heads, NOPE + ROPE)), dtype)
    kv = jnp.asarray(rng.standard_normal((rows + 1, lat + ROPE, positions)), dtype)
    w = jnp.asarray(rng.standard_normal((lat, heads, NOPE + DV)) * lat ** -0.5, dtype)
    return q, kv, w, jnp.arange(rows - 1, -1, -1, dtype=jnp.int32)


def _expanded(cfg, q, kv, w, slots, starts):
    """XLA's path as ``_mla_prefill`` takes it: every row over the blocks the deepest row attends."""
    return np.asarray(lc.mla_expanded_over_slots(cfg, w, q, kv, slots, jnp.asarray(starts, jnp.int32)), np.float32)


def _fused(q, kv, w, slots, starts, lengths):
    return pm.mla_prefill_attention(q, kv, w, slots, jnp.asarray(starts, jnp.int32), jnp.asarray(lengths, jnp.int32),
                                    nope=NOPE, interpret=True)


# -- arithmetic ------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernel_matches_the_expanded_attention_on_the_rows_that_hold_a_query(layout, dtype, tol):
    """What the kernel is handed holds NaN wherever it must not look: in every
    block behind the one a row's chunk lies in, in the whole slot of a row
    without a query, and in the slot no row names. None reaches a result, and a
    row without a query comes back as zeros."""
    heads, lat, starts, lengths = LAYOUTS[layout]
    rows = len(starts)
    q, kv, w, slots = _operands(heads, lat, rows, dtype, seed=len(layout))
    ref = _expanded(_cfg(lat, dtype), q, kv, w, slots, starts)
    poisoned = kv.at[rows].set(jnp.nan)
    for b in range(rows):
        behind = (starts[b] // T + 1) * T if lengths[b] else 0
        poisoned = poisoned.at[slots[b], :, behind:].set(jnp.nan)
    out = _fused(q, poisoned, w, slots, starts, lengths)
    assert out.shape == (rows, T, heads, DV) and out.dtype == dtype
    out = np.asarray(out, np.float32)
    held = np.asarray(lengths) > 0
    assert np.isfinite(out).all() and not out[~held].any()
    np.testing.assert_allclose(out[held], ref[held], atol=tol, rtol=tol)


def test_large_scores_do_not_overflow():
    q, kv, w, slots = _operands(2, 128, 2, jnp.float32, seed=5)
    out = np.asarray(_fused(q * 30.0, kv, w, slots, [2 * T, T], [T, T]))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _expanded(_cfg(128, jnp.float32), q * 30.0, kv, w, slots, [2 * T, T]),
                               atol=1e-4, rtol=1e-4)


def test_rows_and_heads_do_not_mix():
    """Another slot's cache rows change that row's result alone; one head's
    slice of ``W_kvb`` changes that head's alone, in every row."""
    q, kv, w, slots = _operands(4, 128, 3, jnp.float32, seed=3)
    starts, lengths = [T, T, T], [T, T, T]
    base = np.asarray(_fused(q, kv, w, slots, starts, lengths))
    other_slot = np.abs(np.asarray(_fused(q, kv.at[slots[1]].add(1.0), w, slots, starts, lengths)) - base) > 1e-6
    assert other_slot[1].all()
    other_slot[1] = False
    assert not other_slot.any()
    other_head = np.abs(np.asarray(_fused(q, kv, w.at[:, 2, NOPE:].add(0.5), slots, starts, lengths)) - base) > 1e-6
    assert other_head[:, :, 2].all()
    other_head[:, :, 2] = False
    assert not other_head.any()


def test_a_query_sees_its_own_position_and_none_behind_it():
    """Causal by position inside the chunk's own block: query t's result does not
    move with the cache rows behind position ``starts + t``."""
    q, kv, w, slots = _operands(2, 128, 1, jnp.float32, seed=8)
    base = np.asarray(_fused(q, kv, w, slots, [T], [T]))
    moved = np.asarray(_fused(q, kv.at[0, :, T + 40:].add(3.0), w, slots, [T], [T]))
    assert np.array_equal(moved[0, :40], base[0, :40]) and (np.abs(moved[0, 40:] - base[0, 40:]) > 1e-6).any(axis=(1, 2)).all()


# -- the walk -------------------------------------------------------------------------
@pytest.mark.parametrize("starts,lengths,counts,walk", [
    ([0, 0, 0, 0], [5, 128, 1, 77], [1, 1, 1, 1], [(0, 0), (1, 0), (2, 0), (3, 0)]),
    ([256, 256, 256, 256], [128, 0, 0, 3], [3, 0, 0, 3], [(0, 0), (0, 1), (0, 2), (1, 0), (2, 0), (3, 0), (3, 1), (3, 2)]),
    ([384, 384, 384, 384], [0, 0, 0, 0], [0, 0, 0, 0], [(0, 0), (1, 0), (2, 0), (3, 0)]),
    ([128, 0, 384, 256], [9, 9, 9, 9], [2, 1, 4, 3], [(0, 0), (0, 1), (1, 0), (2, 0), (2, 1), (2, 2), (2, 3), (3, 0), (3, 1), (3, 2)]),
], ids=["first_chunk", "two_rows_ended", "nothing_held", "unlike_depths"])
def test_the_walk_visits_the_blocks_a_query_reaches_and_an_empty_row_once(starts, lengths, counts, walk):
    got_counts, row, block, visits = pm.row_visits(jnp.asarray(starts, jnp.int32), jnp.asarray(lengths, jnp.int32), T, 4)
    assert got_counts.tolist() == counts and int(visits) == len(walk)
    assert list(zip(row[:len(walk)].tolist(), block[:len(walk)].tolist())) == walk
    assert row.shape == block.shape == (16,)  # what the grid may reach at most: every row at every block
    assert 0 <= int(row.min()) and int(row.max()) <= 3 and 0 <= int(block.min()) and int(block.max()) <= 3


# -- the rule that selects it ---------------------------------------------------------
PUBLISHED = ((4, 512, 64, 192), jnp.bfloat16, 512, 128, 64, 128)   # LongCat-Flash-Chat's chunk: q, dtype, latent, nope, rope, v


def test_cpu_backend_takes_the_expanded_path():
    assert not pa.backend_is_tpu()
    assert not pm.mla_prefill_applies(*PUBLISHED)


def test_on_a_tpu_the_published_widths_take_the_kernel_and_narrow_ones_do_not(monkeypatch):
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    assert pm.mla_prefill_applies(*PUBLISHED)
    assert pm.mla_prefill_applies((2, 128, 2, 144), jnp.bfloat16, 128, 128, 16, 128)   # this file's model
    tiny = lc.LongcatFlashConfig.from_name("longcat-flash-tiny")
    assert not pm.mla_prefill_applies((4, 8, tiny.num_attention_heads, tiny.qk_head_dim), jnp.bfloat16, tiny.kv_lora_rank,
                                      tiny.qk_nope_head_dim, tiny.qk_rope_head_dim, tiny.v_head_dim)
    assert not pm.mla_prefill_applies((4, 512, 64, 192), jnp.float32, 512, 128, 64, 128)    # not bfloat16
    assert not pm.mla_prefill_applies((4, 500, 64, 192), jnp.bfloat16, 512, 128, 64, 128)   # the chunk is no whole tile
    assert not pm.mla_prefill_applies((4, 512, 64, 160), jnp.bfloat16, 512, 96, 64, 128)    # nor is the head's width
    assert not pm.mla_prefill_applies((4, 512, 64, 192), jnp.bfloat16, 500, 128, 64, 128)   # nor the latent
    assert not pm.mla_prefill_applies((4, 512, 64, 136), jnp.bfloat16, 512, 128, 8, 128)    # the rotary key splits a sublane tile
    assert not pm.mla_prefill_applies((4, 4096, 64, 192), jnp.bfloat16, 512, 128, 64, 128)  # beyond the budget
    heads = pm._heads_a_step(512, 512, 128, 64, 128, 64, 2)
    assert 0 < heads <= pm.MAX_HEADS and 64 % heads == 0
    assert pm._step_bytes(512, 512, 128, 64, 128, heads, 2) <= pm.VMEM_BUDGET


# -- the model on either path ---------------------------------------------------------
def lane_tile_model(seed: int = 0, **sizes):
    """The tiny decoder with an attention one lane tile wide (two heads of 128 +
    16 | 128 over a latent of 128): what the kernel serves."""
    cfg = dataclasses.replace(lc.LongcatFlashConfig.from_name("longcat-flash-tiny"), num_attention_heads=2,
                              kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=16, v_head_dim=128, **sizes)
    return lc.init_longcat_params(cfg, seed)


@pytest.fixture
def on_tpu(monkeypatch):
    """The backend rule answers as on a TPU, and the kernel it then selects runs
    interpreted. ``calls`` keeps the shape of q at each call traced."""
    calls = []
    real = pm.mla_prefill_attention

    def interpreted(q, kv, w_kvb, slots, starts, lengths, nope):
        calls.append(q.shape)
        return real(q, kv, w_kvb, slots, starts, lengths, nope=nope, interpret=True)

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pm, "mla_prefill_attention", interpreted)
    return calls


def _two_chunks(model, params, lengths):
    """Rows of ``lengths`` tokens as two calls of one chunk; -> (logits after the second, the caches)."""
    rng = np.random.default_rng(1)
    rows = len(lengths)
    tokens = rng.integers(2, 128, (rows, 2 * T)).astype(np.int32)
    state = model.init_state(rows + 1, 2 * T + 8)
    slots = jnp.arange(rows, 0, -1, dtype=jnp.int32)
    prefill = jax.jit(model.prefill)
    for c in range(2):
        here = np.clip(np.asarray(lengths) - c * T, 0, T).astype(np.int32)
        state, logits, _ = prefill(params, state, tokens[:, c * T:(c + 1) * T], slots,
                                   jnp.full((rows,), c * T, jnp.int32), here)
    return np.asarray(logits), [np.asarray(s["kv"], np.float32) for s in state]


def test_prefill_with_the_kernel_equals_prefill_on_xlas_path(monkeypatch, on_tpu):
    """Two chunks of three rows, of which one ended in the first chunk: the
    logits of the rows that hold a query in the second call and every slot's
    cache rows agree across the two paths; the ended row's slot is as its chunk
    left it."""
    model, params = lane_tile_model()
    lengths = [2 * T, 60, T + 31]
    fused, fused_kv = _two_chunks(model, params, lengths)
    assert on_tpu == [(3, T, 2, 144)] * 4 and not pm.mla_prefill_applies((3, 8, 4, 24), jnp.bfloat16, 8, 16, 8, 16)
    on_tpu.clear()
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: False)
    xla, xla_kv = _two_chunks(model, params, lengths)
    assert on_tpu == []
    assert np.max(np.abs(fused[[0, 2]] - xla[[0, 2]])) <= 2e-2  # bfloat16 rounding of the attention's result; read 0.004
    assert np.std(xla) > 0.3
    for a, b in zip(fused_kv, xla_kv):
        assert np.max(np.abs(a - b)) <= 0.13  # one bfloat16 step of rows that spread ~3.5 (the latent at kv_scale)


def test_kernel_failure_propagates(monkeypatch):
    """A kernel that raises when the prefill traces: the error leaves the model,
    and XLA's result is not substituted."""
    model, params = lane_tile_model()

    def broken_kernel(*args, **kw):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pm, "mla_prefill_attention", broken_kernel)
    args = (params, model.init_state(2, 2 * T), jnp.zeros((2, T), jnp.int32), jnp.arange(2, dtype=jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.full((2,), T, jnp.int32))
    with pytest.raises(RuntimeError, match="mosaic refused"):
        model.prefill(*args)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        jax.jit(model.prefill)(*args)
    # the decode step never takes it
    state, logits, _ = model.decode(params, args[1], jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool))
    assert np.isfinite(np.asarray(logits)).all()
