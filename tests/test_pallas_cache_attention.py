"""The attention kernel over a cache of per-head key/value rows
(``ops/pallas_cache_attention.py``): its arithmetic in interpret mode on the CPU
against ``decoders.attention_chunk`` (a prefill's chunk), ``attention_step``
(a decode step) and ``decoders.attention_block`` (a block of a diffusion decoder,
grouped queries) over the same rows, the walk it is handed, the slot's end, the
rule that selects it and the one function that asks it (``decoders.attend_cache``),
and a failing kernel. The models on either path and the
compile for a described v5e at the cells' sizes are in ``tests/test_olmo_hybrid.py``
and ``tests/test_sdar_moe.py``."""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.models import decoders, olmo_hybrid as oh, sdar_moe as sd
from daft_tpu.models.serving import prefill_schedule
from daft_tpu.ops import pallas_attention as pa
from daft_tpu.ops import pallas_cache_attention as pca
from daft_tpu.ops import pallas_cache_blocks as pcb

T = 128                     # one lane tile of queries a chunk
HD = 128
SCALE = HD ** -0.5
BLOCK = pca.BLOCK           # key positions a visit of a decode step fetches
TOL = {"f32": (jnp.float32, 2e-5), "bf16": (jnp.bfloat16, 3e-2)}

#: name -> (key/value heads, starts, lengths) of a chunk's call: row b at slot (rows - 1 - b) of a cache of three
#: blocks and a sublane tile more. A row attends ``starts // T + 1`` blocks, and none where its length is 0.
CHUNKS = {
    "one_block": (3, [0, 0], [T, T]),
    "three_blocks": (3, [2 * T, 2 * T], [T, T]),
    "a_row_without_a_query": (3, [2 * T, 2 * T, 2 * T], [T, 0, T]),
    "only_rows_without_a_query": (1, [T, T], [0, 0]),
    "a_partial_last_chunk": (3, [T, T, T], [T, 77, 1]),
    "rows_at_unlike_depths": (3, [2 * T, 0, T], [T, T, 50]),
    "two_head_groups": (2 * pca.MAX_HEADS_CHUNK, [T, 0], [T, 9]),
}
#: name -> (positions, active) of a decode step over four slots of two blocks and 80 positions.
STEPS = {
    "before_a_block_boundary": ([BLOCK - 1, 3, 700, 0], [1, 1, 1, 1]),
    "on_a_block_boundary": ([BLOCK, 2 * BLOCK, 1, 40], [1, 1, 1, 1]),
    "in_the_last_partial_block": ([2 * BLOCK + 79, 2 * BLOCK, 2 * BLOCK - 1, 5], [1, 1, 1, 1]),
    "inactive_slots": ([300, 900, 2 * BLOCK + 3, 7], [0, 1, 0, 1]),
    "nobody_active": ([5, 6, 7, 8], [0, 0, 0, 0]),
}
#: A block of a diffusion decoder: L positions a slot, R queries a key/value head (L x R = 16, a sublane tile).
L, R = 4, 4
BLOCK_S = 2 * BLOCK + 80                                            # a slot's last key block is partial
#: name -> (the blocks' first positions, active) of a block call over four slots of ``BLOCK_S`` positions.
BLOCKS = {
    "at_zero_on_and_inside_a_block_and_at_the_end": ([0, BLOCK, 700, BLOCK_S - L], [1, 1, 1, 1]),
    "in_the_first_block": ([4, 16, BLOCK - 4, 1], [1, 1, 1, 1]),
    "inactive_slots": ([300, 2 * BLOCK, BLOCK_S - L, 0], [0, 1, 0, 1]),
    "nobody_active": ([5, 6, 7, 8], [0, 0, 0, 0]),
}


def _rows(slots_n, KV, S, dtype, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((slots_n, KV, S, HD)), dtype),
            jnp.asarray(rng.standard_normal((slots_n, KV, S, HD)), dtype))


def _chunk_operands(KV, rows, dtype, seed=0):
    rng = np.random.default_rng(seed)
    ck, cv = _rows(rows + 1, KV, 3 * T + pca.STEP_ROWS, dtype, seed + 1)
    return jnp.asarray(rng.standard_normal((rows, T, KV, 1, HD)), dtype), ck, cv, jnp.arange(rows - 1, -1, -1, dtype=jnp.int32)


def _xla_chunk(q, ck, cv, slots, starts, dtype):
    """XLA's path as ``decoders.attend_cache`` takes it on the CPU: every row over the blocks the deepest attends."""
    starts = jnp.asarray(starts, jnp.int32)
    block = lambda c, j: pcb.read_blocks_xla(c, slots, j * T, T).astype(jnp.float32)  # noqa: E731
    out = decoders.attention_chunk(q, lambda j: (block(ck, j), block(cv, j)), starts[:, None] + jnp.arange(T)[None, :],
                                   jnp.max(starts) // T + 1, SCALE, dtype)
    return np.asarray(out, np.float32)


def _xla_step(q, ck, cv, positions, dtype):
    """``decoders.attention_core`` for one token a slot: ``attention_step`` over every position a slot holds."""
    out = decoders.attention_core(q, jnp.swapaxes(ck, 1, 2).astype(jnp.float32), jnp.swapaxes(cv, 1, 2).astype(jnp.float32),
                                  jnp.asarray(positions, jnp.int32)[:, None], SCALE, dtype)
    return np.asarray(out, np.float32)


def _block_operands(KV, dtype, seed, rows=4, S=BLOCK_S):
    """A block call's q (rows, L, KV, R, hd), the caches, and the blocks' own keys and values (rows, L, KV, hd)."""
    rng = np.random.default_rng(seed)
    ck, cv = _rows(rows, KV, S, dtype, seed + 1)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype) for shape in
               ((rows, L, KV, R, HD), (rows, L, KV, HD), (rows, L, KV, HD)))
    return q, ck, cv, (k, v)


def _xla_block(q, ck, cv, own, starts, dtype):
    """``decoders.attention_block``: every slot over every position it holds and its block's own keys."""
    return np.asarray(decoders.attention_block(q, ck, cv, *own, jnp.asarray(starts, jnp.int32), SCALE, dtype), np.float32)


def _fused(q, ck, cv, slots, starts, lengths, own=None):
    return pca.cache_attention(q, ck, cv, jnp.asarray(slots, jnp.int32), jnp.asarray(starts, jnp.int32),
                               jnp.asarray(lengths, jnp.int32), scale=SCALE, interpret=True, own=own)


def _poisoned(ck, cv, slots, starts, lengths, block, spare=()):
    """NaN wherever the kernel must not look: every block behind the one a row's last query lies in, the whole slot
    of a row without a query, and the slots no row names."""
    for s in spare:
        ck, cv = ck.at[s].set(jnp.nan), cv.at[s].set(jnp.nan)
    for b, slot in enumerate(np.asarray(slots)):
        behind = (starts[b] // block + 1) * block if lengths[b] else 0
        ck, cv = ck.at[slot, :, behind:].set(jnp.nan), cv.at[slot, :, behind:].set(jnp.nan)
    return ck, cv


# -- arithmetic ------------------------------------------------------------------
@pytest.mark.parametrize("precision", list(TOL))
@pytest.mark.parametrize("layout", list(CHUNKS))
def test_a_chunk_matches_xlas_loop_on_the_rows_that_hold_a_query(layout, precision):
    """Rows of one call at unlike depths and slots. None of the NaN reaches a
    result, and a row without a query comes back as zeros. (A chunk lies whole
    inside a slot: the batcher holds every prompt chunk's positions.)"""
    dtype, tol = TOL[precision]
    KV, starts, lengths = CHUNKS[layout]
    rows = len(starts)
    q, ck, cv, slots = _chunk_operands(KV, rows, dtype, seed=len(layout))
    ref = _xla_chunk(q, ck, cv, slots, starts, dtype)
    out = _fused(q, *_poisoned(ck, cv, slots, starts, lengths, T, spare=[rows]), slots, starts, lengths)
    assert out.shape == (rows, T, KV, 1, HD) and out.dtype == dtype
    out = np.asarray(out, np.float32)
    held = np.asarray(lengths) > 0
    assert np.isfinite(out).all() and not out[~held].any()
    np.testing.assert_allclose(out[held], ref[held], atol=tol, rtol=tol)


@pytest.mark.parametrize("precision", list(TOL))
@pytest.mark.parametrize("layout", list(STEPS))
def test_a_decode_step_matches_attention_over_every_position(layout, precision):
    """One query a slot at its own position: on each side of a block boundary and
    in the last block, which holds 80 positions of 512. NaN stands in every block
    behind a slot's own and in the whole slot of an inactive one; past the slot's
    end the interpreter's own."""
    dtype, tol = TOL[precision]
    positions, active = STEPS[layout]
    KV, S = 3, 2 * BLOCK + 80
    ck, cv = _rows(4, KV, S, dtype, seed=len(layout))
    q = jnp.asarray(np.random.default_rng(7).standard_normal((4, 1, KV, 1, HD)), dtype)
    ref = _xla_step(q, ck, cv, positions, dtype)
    out = _fused(q, *_poisoned(ck, cv, np.arange(4), positions, active, BLOCK), np.arange(4), positions, active)
    assert out.shape == (4, 1, KV, 1, HD) and out.dtype == dtype
    out = np.asarray(out, np.float32)
    held = np.asarray(active) > 0
    assert np.isfinite(out).all() and not out[~held].any()
    np.testing.assert_allclose(out[held], ref[held], atol=tol, rtol=tol)


@pytest.mark.parametrize("precision", list(TOL))
@pytest.mark.parametrize("layout", list(BLOCKS))
def test_a_block_matches_attend_block_over_every_position(layout, precision):
    """A block's 4 x 4 queries a key/value head over the committed positions
    before its start and its own 4 keys, in one softmax: at position 0 (no
    committed row), on and inside a block, in the partial last block, with
    inactive slots. NaN stands in every key block at or past the one that holds
    the start and in the whole slot of an inactive one."""
    dtype, tol = TOL[precision]
    starts, active = BLOCKS[layout]
    q, ck, cv, own = _block_operands(2, dtype, seed=len(layout))
    ref = _xla_block(q, ck, cv, own, starts, dtype)
    out = _fused(q, *_poisoned(ck, cv, np.arange(4), np.asarray(starts) - 1, active, BLOCK), np.arange(4), starts, active, own)
    assert out.shape == q.shape and out.dtype == dtype
    out = np.asarray(out, np.float32)
    held = np.asarray(active) > 0
    assert np.isfinite(out).all() and not out[~held].any()
    np.testing.assert_allclose(out[held], ref[held], atol=tol, rtol=tol)


def test_the_interpreter_pads_with_nan():
    """What the decode step's test leans on: a block that reaches past its array comes
    to the kernel with NaN behind the end (on the chip: whatever the buffer held)."""
    from jax.experimental import pallas as pl

    def copy(src, dst):
        dst[...] = src[...]

    x = jnp.ones((1, 1, 24, HD), jnp.float32)
    out = pl.pallas_call(copy, grid=(2,), in_specs=[pl.BlockSpec((1, 1, 16, HD), lambda i: (0, 0, i, 0))],
                         out_specs=pl.BlockSpec((1, 1, 16, HD), lambda i: (i, 0, 0, 0)),
                         out_shape=jax.ShapeDtypeStruct((2, 1, 16, HD), jnp.float32), interpret=True)(x)
    assert np.isnan(np.asarray(out[1, 0, 8:])).all() and np.isfinite(np.asarray(out[1, 0, :8])).all()


@pytest.mark.parametrize("call", ["chunk", "step", "block"])
def test_large_scores_do_not_overflow(call):
    own = None
    if call == "chunk":
        q, ck, cv, slots = _chunk_operands(2, 2, jnp.float32, seed=5)
        starts, lengths = [2 * T, T], [T, T]
        ref = _xla_chunk(q * 30.0, ck, cv, slots, starts, jnp.float32)
    elif call == "step":
        ck, cv = _rows(2, 2, BLOCK + 80, jnp.float32, seed=5)
        q, slots = jnp.asarray(np.random.default_rng(5).standard_normal((2, 1, 2, 1, HD)), jnp.float32), np.arange(2)
        starts, lengths = [BLOCK + 40, 17], [1, 1]
        ref = _xla_step(q * 30.0, ck, cv, starts, jnp.float32)
    else:
        q, ck, cv, own = _block_operands(2, jnp.float32, seed=5, rows=3)
        slots, starts, lengths = np.arange(3), [BLOCK + 40, 17, 0], [1, 1, 1]
        ref = _xla_block(q * 30.0, ck, cv, own, starts, jnp.float32)
    out = np.asarray(_fused(q * 30.0, ck, cv, slots, starts, lengths, own))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("call", ["chunk", "step", "block"])
def test_rows_and_heads_do_not_mix(call):
    """Another slot's cache rows change that row's result alone; one head's keys
    change that head's alone, in every row (six heads are two steps of the grid
    for a chunk, one for a step or a block)."""
    KV, own = 6, None
    if call == "chunk":
        q, ck, cv, slots = _chunk_operands(KV, 3, jnp.float32, seed=3)
        starts, lengths = [T, T, T], [T, T, T]
    elif call == "step":
        ck, cv = _rows(3, KV, BLOCK + 80, jnp.float32, seed=3)
        q, slots = jnp.asarray(np.random.default_rng(3).standard_normal((3, 1, KV, 1, HD)), jnp.float32), np.arange(3)
        starts, lengths = [BLOCK + 9, 100, BLOCK - 1], [1, 1, 1]
    else:
        q, ck, cv, own = _block_operands(KV, jnp.float32, seed=3, rows=3)
        slots, starts, lengths = np.arange(3), [BLOCK + 9, 100, BLOCK - 1], [1, 1, 1]
    base = np.asarray(_fused(q, ck, cv, slots, starts, lengths, own))
    other_slot = np.abs(np.asarray(_fused(q, ck, cv.at[slots[1]].add(1.0), slots, starts, lengths, own)) - base) > 1e-6
    assert other_slot[1].all()
    other_slot[1] = False
    assert not other_slot.any()
    other_head = np.abs(np.asarray(_fused(q, ck.at[:, 4].multiply(-1.0), cv, slots, starts, lengths, own)) - base) > 1e-6
    assert other_head[:, :, 4].any(axis=(1, 2, 3)).all()
    other_head[:, :, 4] = False
    assert not other_head.any()
    if own is not None:  # a block's own keys of one slot and head move that slot's and head's results alone
        moved = np.abs(np.asarray(_fused(q, ck, cv, slots, starts, lengths, (own[0], own[1].at[2, :, 1].add(1.0)))) - base) > 1e-6
        assert moved[2, :, 1].all()
        moved[2, :, 1] = False
        assert not moved.any()


@pytest.mark.parametrize("call", ["chunk", "step", "block"])
def test_a_query_sees_its_own_position_and_none_behind_it(call):
    """Causal by position: a result moves with the cache row at its query's own
    position and not with the rows behind it. A block's queries see the position
    before the block and none from its start on (the block's own keys stand
    there)."""
    if call == "chunk":
        q, ck, cv, slots = _chunk_operands(2, 1, jnp.float32, seed=8)
        base = np.asarray(_fused(q, ck, cv, slots, [T], [T]))
        moved = np.asarray(_fused(q, ck, cv.at[0, :, T + 40:].add(3.0), slots, [T], [T]))
        assert np.array_equal(moved[0, :40], base[0, :40])
        assert (np.abs(moved[0, 40:] - base[0, 40:]) > 1e-6).any(axis=(1, 2, 3)).all()
    elif call == "block":
        q, ck, cv, own = _block_operands(2, jnp.float32, seed=8, rows=1)
        at = [BLOCK + 4]
        base = np.asarray(_fused(q, ck, cv, [0], at, [1], own))
        assert np.array_equal(np.asarray(_fused(q, ck, cv.at[0, :, BLOCK + 4:].add(3.0), [0], at, [1], own)), base)
        assert (np.abs(np.asarray(_fused(q, ck, cv.at[0, :, BLOCK + 3].add(3.0), [0], at, [1], own)) - base) > 1e-6).all()
    else:
        ck, cv = _rows(1, 2, BLOCK + 80, jnp.float32, seed=8)
        q = jnp.asarray(np.random.default_rng(8).standard_normal((1, 1, 2, 1, HD)), jnp.float32)
        base = np.asarray(_fused(q, ck, cv, [0], [BLOCK + 3], [1]))
        assert np.array_equal(np.asarray(_fused(q, ck, cv.at[0, :, BLOCK + 4:].add(3.0), [0], [BLOCK + 3], [1])), base)
        assert (np.abs(np.asarray(_fused(q, ck, cv.at[0, :, BLOCK + 3].add(3.0), [0], [BLOCK + 3], [1])) - base) > 1e-6).any()


# -- the walk -------------------------------------------------------------------------
@pytest.mark.parametrize("positions,active", [
    ([0, 511, 512, 16463], [1, 1, 1, 1]), ([1112, 3877, 4327, 15088], [1, 0, 1, 0]), ([9, 9, 9, 9], [0, 0, 0, 0]),
], ids=["block_edges", "half_active", "nobody"])
def test_a_decode_step_visits_the_blocks_up_to_each_slots_position(positions, active):
    """A slot at position p costs ``p // block + 1`` fetched blocks, an inactive
    one a single empty visit, whatever the 33 blocks a slot could hold."""
    counts, row, block, visits = pca.row_visits(jnp.asarray(positions, jnp.int32), jnp.asarray(active, jnp.int32), BLOCK, 33)
    want = [p // BLOCK + 1 if a else 0 for p, a in zip(positions, active)]
    assert counts.tolist() == want and int(visits) == sum(max(c, 1) for c in want)
    walk = list(zip(row[:int(visits)].tolist(), block[:int(visits)].tolist()))
    assert walk == [(b, j) for b, c in enumerate(want) for j in range(max(c, 1))]


@pytest.mark.parametrize("starts,active", [
    ([0, 1, 512, 513, 2316], [1, 1, 1, 1, 1]), ([700, 1100, 0, 2304, 64], [1, 0, 1, 0, 1]), ([9, 9, 9, 9, 9], [0, 0, 0, 0, 0]),
], ids=["block_edges", "half_active", "nobody"])
def test_a_block_visits_the_key_blocks_below_each_slots_start(starts, active):
    """A block at start s reads ``ceil(s / block)`` key blocks of the 5 that a
    slot of 2,320 positions holds (``row_visits`` of ``s - 1``); a slot at 0 and
    an inactive one cost a single visit that reads none."""
    counts, row, block, visits = pca.row_visits(jnp.asarray(starts, jnp.int32) - 1, jnp.asarray(active, jnp.int32), BLOCK, 5)
    want = [-(-s // BLOCK) if a else 0 for s, a in zip(starts, active)]
    assert counts.tolist() == want and int(visits) == sum(max(c, 1) for c in want)
    walk = list(zip(row[:int(visits)].tolist(), block[:int(visits)].tolist()))
    assert walk == [(b, j) for b, c in enumerate(want) for j in range(max(c, 1))]


@pytest.mark.parametrize("chunks", [[8, 4, 1], [30, 3, 3, 2, 2, 2, 1, 1], [1, 1, 1, 1, 1]], ids=["three", "a_wave", "short"])
def test_a_rounds_visits_are_the_block_rows_its_span_counts(chunks):
    """Over the calls ``prefill_schedule`` packs a round into, the visits that
    hold a query sum to ``serve.prefill``'s ``block_rows`` (a row of c chunks:
    c (c + 1) / 2), and a row of a call that carries no prompt adds one empty visit."""
    held = empty = 0
    for call in prefill_schedule(chunks, 4):
        starts = [c * T for _, c in call] + [0] * (4 - len(call))
        lengths = [T] * len(call) + [0] * (4 - len(call))
        counts, _, _, visits = pca.row_visits(jnp.asarray(starts, jnp.int32), jnp.asarray(lengths, jnp.int32), T, 64)
        held += int(counts.sum())
        empty += int(visits) - int(counts.sum())
        assert int(counts.sum()) == sum(c + 1 for _, c in call) and int(visits) - int(counts.sum()) == 4 - len(call)
    assert held == sum(c * (c + 1) // 2 for c in chunks)


# -- the rule that selects it ---------------------------------------------------------
CELL = (8, 30, 16464, 128)                                       # Olmo-Hybrid's cell: slots, heads, rows, head size
CHUNK_Q, STEP_Q = (4, 512, 30, 1, 128), (8, 1, 30, 1, 128)


SDAR_CELL, BLOCK_Q = (64, 4, 2320, 128), (64, 4, 4, 8, 128)     # SDAR's cell: 64 slots, blocks of 4, 8 queries a head


def test_cpu_backend_takes_xlas_path():
    assert not pa.backend_is_tpu()
    assert not pca.cache_attention_applies(CHUNK_Q, CELL, jnp.bfloat16) and not pca.cache_attention_applies(STEP_Q, CELL, jnp.bfloat16)
    assert not pca.cache_attention_applies(BLOCK_Q, SDAR_CELL, jnp.bfloat16, own=True)


def test_on_a_tpu_a_block_of_grouped_queries_takes_the_kernel_and_others_do_not(monkeypatch):
    """With a block's own keys the rule asks for a head's L x R queries in whole
    sublane tiles (4 x 8 = 32 at SDAR's widths): grouped queries without them
    stay refused, and so do the tiny decoder's head of 16, float32 and a slot
    shorter than a key block."""
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    assert pca.cache_attention_applies(BLOCK_Q, SDAR_CELL, jnp.bfloat16, own=True)
    assert pca.cache_attention_applies((3, L, 2, R, HD), (4, 2, BLOCK_S, HD), jnp.bfloat16, own=True)     # this file's blocks
    assert not pca.cache_attention_applies(BLOCK_Q, SDAR_CELL, jnp.bfloat16)                             # grouped, no own keys
    tiny = sd.SdarMoeConfig.from_name("sdar-moe-tiny")
    R_tiny = tiny.num_attention_heads // tiny.num_key_value_heads
    assert not pca.cache_attention_applies((3, 4, tiny.num_key_value_heads, R_tiny, tiny.head_dim), (3, 2, 96, 16), jnp.bfloat16, own=True)
    assert not pca.cache_attention_applies(BLOCK_Q, SDAR_CELL, jnp.float32, own=True)                    # not bfloat16
    assert not pca.cache_attention_applies((64, 4, 4, 2, 128), SDAR_CELL, jnp.bfloat16, own=True)        # 8 rows: no whole tile
    assert not pca.cache_attention_applies(BLOCK_Q, (64, 4, 496, 128), jnp.bfloat16, own=True)          # less than a key block
    heads = pca._heads_a_step(4, 128, 4, 2, 8, True)
    assert heads == 4 and pca._step_bytes(32, pca.BLOCK, 128, heads, 2, 4) <= pca.VMEM_BUDGET            # one grid step a slot
    assert pca._call_shape(4, 8, True) == (32, pca.BLOCK, pca.MAX_HEADS_STEP)


#: What ``decoders.attend_cache`` is handed at each call site: name -> (q, the cache rows as kept, the mode).
SITES = {
    "olmo_chunk": (CHUNK_Q, CELL, "chunk"),
    "olmo_step": (STEP_Q, CELL, "token"),
    "sdar_block": (BLOCK_Q, SDAR_CELL, "block"),
    "sdar_prefill": ((4, 512, 4, 8, 128), SDAR_CELL, "block_causal"),
    "olmo_tiny_chunk": ((4, 8, 4, 1, 16), (4, 4, 64, 16), "chunk"),
    "olmo_tiny_step": ((4, 1, 4, 1, 16), (4, 4, 64, 16), "token"),
    "sdar_tiny_block": ((3, 4, 2, 2, 16), (3, 2, 96, 16), "block"),
    "sdar_tiny_prefill": ((3, 16, 2, 2, 16), (3, 2, 96, 16), "block_causal"),
}
#: (site, a TPU's backend rule) -> the ``attn`` the function notes (None: none) and whether it calls the kernel.
ON_A_TPU = {"olmo_chunk": "fused", "olmo_step": "fused", "sdar_block": "fused", "sdar_prefill": None,
            "olmo_tiny_chunk": "xla", "olmo_tiny_step": "xla", "sdar_tiny_block": "xla", "sdar_tiny_prefill": None}


@pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "cpu"])
@pytest.mark.parametrize("site", list(SITES))
def test_attend_cache_alone_chooses_the_kernel_or_xla_and_notes_it(site, tpu, monkeypatch):
    """The one function that asks ``cache_attention_applies``, traced at each site's shapes (shapes only): on a TPU
    Olmo-Hybrid's chunk and step and SDAR's block at the cells' widths take the kernel; SDAR's block-causal prefill
    (no such mode in the kernel) and the tiny widths take XLA's forms; on the CPU everything does. It notes ``attn``
    where there was a choice, and SDAR's prefill notes nothing."""
    q_shape, cache_shape, mode = SITES[site]
    noted, calls = {}, []
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: tpu)
    monkeypatch.setattr(decoders, "note_on_serving_span", lambda key, value: noted.update({key: value}))
    monkeypatch.setattr(pca, "cache_attention", lambda q, *a, **kw: calls.append(sorted(kw)) or jnp.zeros(q.shape, q.dtype))
    B, T, KV = q_shape[:3]
    of = jax.ShapeDtypeStruct
    q, cache, ints = of(q_shape, jnp.bfloat16), of(cache_shape, jnp.bfloat16), of((B,), jnp.int32)
    own = (of((B, T, KV, q_shape[-1]), jnp.bfloat16),) * 2
    view = of((cache_shape[0], cache_shape[2], cache_shape[1], cache_shape[3]), jnp.bfloat16)
    kw = {"chunk": lambda: dict(slots=ints, lengths=ints),
          "block_causal": lambda: dict(slots=ints, lengths=ints, sees=of((B, T), jnp.int32)),
          "token": lambda: dict(active=of((B,), bool), view=(view, view)),
          "block": lambda: dict(active=of((B,), bool), own=own)}[mode]()
    out = jax.eval_shape(lambda q, c, s, kw: decoders.attend_cache(q, c, c, s, SCALE, jnp.bfloat16, **kw), q, cache, ints, kw)
    assert out.shape == q_shape
    want = ON_A_TPU[site] if tpu else (None if mode == "block_causal" else "xla")
    assert noted == ({} if want is None else {"attn": want})
    assert calls == ([["own", "scale"] if mode == "block" else ["scale"]] if want == "fused" else [])


def test_on_a_tpu_the_published_widths_take_the_kernel_and_others_do_not(monkeypatch):
    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    assert pca.cache_attention_applies(CHUNK_Q, CELL, jnp.bfloat16) and pca.cache_attention_applies(STEP_Q, CELL, jnp.bfloat16)
    assert pca.cache_attention_applies((3, T, 2, 1, HD), (4, 2, 608, HD), jnp.bfloat16)      # tests/test_olmo_hybrid.py's model
    tiny = oh.OlmoHybridConfig.from_name("olmo-hybrid-tiny")
    for Tq in (8, 1):  # the tiny decoder's head fills no lane tile
        assert not pca.cache_attention_applies((4, Tq, tiny.num_key_value_heads, 1, tiny.head_dim), (4, 4, 64, tiny.head_dim), jnp.bfloat16)
    assert not pca.cache_attention_applies(CHUNK_Q, CELL, jnp.float32)                        # not bfloat16
    assert not pca.cache_attention_applies((4, 500, 30, 1, 128), CELL, jnp.bfloat16)          # the chunk is no whole tile
    assert not pca.cache_attention_applies((4, 512, 30, 1, 96), (8, 30, 16464, 96), jnp.bfloat16)   # nor is the head's size
    assert not pca.cache_attention_applies((4, 512, 8, 4, 128), (8, 8, 16464, 128), jnp.bfloat16)   # grouped queries (granite's)
    assert not pca.cache_attention_applies((8, 1, 8, 4, 128), (8, 8, 16464, 128), jnp.bfloat16)
    assert not pca.cache_attention_applies(STEP_Q, (8, 30, 496, 128), jnp.bfloat16)           # a slot holds less than a block
    assert not pca.cache_attention_applies(STEP_Q, (8, 30, 16449, 128), jnp.bfloat16)         # a slot ends inside a sublane tile
    assert not pca.cache_attention_applies((4, 4096, 30, 1, 128), CELL, jnp.bfloat16)         # beyond the budget
    for Tq, most in ((512, pca.MAX_HEADS_CHUNK), (1, pca.MAX_HEADS_STEP)):
        heads = pca._heads_a_step(Tq, 128, 30, 2)
        rows, block, _ = pca._call_shape(Tq)
        assert 0 < heads <= most and 30 % heads == 0 and pca._step_bytes(rows, block, 128, heads, 2) <= pca.VMEM_BUDGET
    with pytest.raises(ValueError, match="queries a key/value head"):                        # who calls it unasked is told
        pca.cache_attention(jnp.zeros((1, 1, 2, 4, HD), jnp.bfloat16), *_rows(1, 2, 608, jnp.bfloat16, 0), jnp.zeros((1,), jnp.int32),
                            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32), scale=SCALE, interpret=True)


# -- what importing it costs ----------------------------------------------------------
def test_importing_the_module_builds_nothing():
    """``ai/flax_provider.py`` imports every decoder in every process, the embed
    cells' too: the module brings in no module that the two attention kernels
    beside it do not import already, creates no array and wakes no backend."""
    code = """
import sys
from daft_tpu.ops import pallas_attention, pallas_mla_attention
before = set(sys.modules)
from daft_tpu.ops import pallas_cache_attention
new = set(sys.modules) - before
assert new == {"daft_tpu.ops.pallas_cache_attention"}, new
import jax
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "importing the kernel's module initialised a backend"
held = [k for k, v in vars(pallas_cache_attention).items() if isinstance(v, jax.Array) or type(v).__module__.startswith("numpy")]
assert not held, held
print("imported-and-built-nothing")
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "imported-and-built-nothing" in proc.stdout


# -- a failing kernel -----------------------------------------------------------------
def lane_tile_model(seed: int = 0):
    """The tiny decoder with an attention of two heads one lane tile wide: the narrowest the kernel serves."""
    cfg = dataclasses.replace(oh.OlmoHybridConfig.from_name("olmo-hybrid-tiny"), num_attention_heads=2,
                              num_key_value_heads=2, head_dim=HD)
    return oh.init_olmo_params(cfg, seed)


def test_kernel_failure_propagates(monkeypatch):
    """A kernel that raises when a program traces: the error leaves the model,
    in the prefill and in the decode step, and XLA's result is not substituted."""
    model, params = lane_tile_model()

    def broken_kernel(*args, **kw):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(pa, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pcb, "write_blocks_kernel", lambda *a: pcb.write_blocks_xla(*a))
    monkeypatch.setattr(pca, "cache_attention", broken_kernel)
    state = model.init_state(2, 600)
    prefill = (params, state, jnp.zeros((2, T), jnp.int32), jnp.arange(2, dtype=jnp.int32), jnp.zeros((2,), jnp.int32),
               jnp.full((2,), T, jnp.int32))
    decode = (params, state, jnp.zeros((2,), jnp.int32), jnp.full((2,), 5, jnp.int32), jnp.ones((2,), bool))
    for fn, args in ((model.prefill, prefill), (model.decode, decode)):
        with pytest.raises(RuntimeError, match="mosaic refused"):
            fn(*args)
        with pytest.raises(RuntimeError, match="mosaic refused"):
            jax.jit(fn)(*args)
