"""Full attention over a cache of per-head key/value rows as one Pallas TPU
kernel: a call's queries against the blocks of cache rows their slots hold so
far, read where the rows lie.

``q (B, Tq, KV, 1, hd)``, the caches ``cache_k``, ``cache_v (slots, KV, S, hd)``
as a model's ``init_state`` holds them (row-major: the operand of the call in
the layout the device keeps, no copy and no view); row ``b`` of the call is slot
``slots[b]``, its query ``t`` stands at position ``starts[b] + t`` and sees the
positions ``<=`` its own, and ``lengths[b] == 0`` says that the row carries no
query. Two shapes of call, one body:

- **A prefill's chunk** (``Tq`` = the chunk, key blocks of ``Tq`` positions,
  ``starts`` multiples of it), after the chunk's rows were written
  (``ops/pallas_cache_blocks.write_blocks``).
- **A decode step** (``Tq`` = 1: one query a slot, ``starts`` the slots'
  positions, ``lengths`` who is active), after the new token's row was written.
  The one query is padded to a sublane tile of ``STEP_ROWS`` rows that all
  stand at the query's position; key blocks of ``BLOCK`` positions.

What XLA's path (``models/decoders.attention_chunk`` / ``attention_step``)
streams through HBM stays in VMEM here, and what it reads without need is not
read:

- **The scores.** A grid step holds one row's queries of ``heads`` heads and one
  block of cache positions; head by head it computes the float32 scores, the
  running max, the exponentials, the row sum and the rescaled accumulator in
  VMEM. ``q``, the cache rows and the result are what crosses HBM.
- **The walk.** A *visit* is one (row, block) pair in which the row holds a
  query: row ``b`` attends ``starts[b] // block + 1`` blocks, whatever the
  deepest row of the call attends and however many positions a slot could
  hold. The visits are ``pallas_mla_attention.row_visits``' few integer arrays,
  prefetched into scalar memory where the index maps read them, and their count
  bounds the grid: a block beyond a row's own depth is never fetched and never
  multiplied. A row without a query gets one visit that only writes zeros.
- **The slot's end.** A slot's rows need not be whole blocks (16,464 = 32 x 512
  + 80), and what the fetch pads the last block with is not the program's. A
  masked score weighs ``exp(LOW - m) = 0``, but ``0 x NaN`` in the second
  product is ``NaN``: the value rows past the end are zeroed in VMEM before the
  block is used, so they weigh exactly nothing whatever bits stand there.

The arithmetic is ``attention_chunk``'s: operands enter the MXU in bfloat16 as
they arrive, scores, max, exp, sum and the accumulator are float32, the scale
multiplies the float32 scores, the mask is by position, the probabilities are
cast to bfloat16 for the second product, one division at the end.

The grid is (head groups, visits), visits innermost; a row's result block is
written back once, after its last visit. Which path a program takes is decided
when it traces, from what can be seen (``cache_attention_applies``): there is
no switch. A kernel that fails to lower fails the program. Forward only. Tests
run the kernel in interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.ops import pallas_attention
from daft_tpu.ops.pallas_mla_attention import row_visits

_LANES = pallas_attention._LANES
#: What one grid step may hold in VMEM, by ``_step_bytes``' reckoning, and the limit handed to the compiler.
VMEM_BUDGET = pallas_attention.VMEM_BUDGET
#: Key positions a visit of a decode step fetches: 128 KB a head and cache in bfloat16 at a head size of 128.
BLOCK = 512
#: Rows the one query of a decode step is padded to: a sublane tile of bfloat16.
STEP_ROWS = 16
#: Heads a grid step takes at most, for a chunk and for a decode step: the body is unrolled over them, and a block
#: of cache rows is fetched once a step. A chunk's visit is bound by its products and exponentials (a head is
#: 2 x 512 x 512 x 128 multiply-adds), so more heads a step only lengthen the compile; a decode step's visit is bound
#: by the fetch, which wants large blocks and few steps.
MAX_HEADS_CHUNK = 5
MAX_HEADS_STEP = 30
_LOW = float(np.finfo(np.float32).min)


def _step_bytes(rows: int, block: int, hd: int, heads: int, itemsize: int) -> int:
    """VMEM of one grid step over ``heads`` heads: the q, result, key and value
    blocks, double-buffered; the running max, sum (a lane tile wide each) and
    accumulator of every head; and the float32 temporaries of two heads in flight
    (scores, exponentials and their cast)."""
    blocks = 2 * itemsize * heads * hd * 2 * (rows + block)
    scratch = heads * rows * (2 * _LANES + hd) * 4
    flight = 2 * rows * block * (4 + 4 + itemsize)
    return blocks + scratch + flight


def _call_shape(Tq: int):
    """-> (query rows a step, key positions a block, the most heads a step)."""
    return (STEP_ROWS, BLOCK, MAX_HEADS_STEP) if Tq == 1 else (Tq, Tq, MAX_HEADS_CHUNK)


def _heads_a_step(Tq: int, hd: int, KV: int, itemsize: int) -> int:
    """The most heads (a divisor of ``KV``) whose step fits the budget; 0 when one head's does not."""
    rows, block, most = _call_shape(Tq)
    for heads in range(min(KV, most), 0, -1):
        if KV % heads == 0 and _step_bytes(rows, block, hd, heads, itemsize) <= VMEM_BUDGET:
            return heads
    return 0


def cache_attention_applies(q_shape, cache_shape, dtype) -> bool:
    """Whether ``cache_attention`` serves this call: a TPU backend, bfloat16, one
    query a key/value head, a head size in whole lane tiles, a chunk in whole lane
    tiles (or one token), a slot that holds a block at least and ends on a sublane
    tile, and one head's step inside ``VMEM_BUDGET``. Otherwise the caller takes
    ``decoders.attention_chunk`` / ``attention_core``."""
    _, Tq, KV, R, hd = q_shape
    S = cache_shape[2]
    return (pallas_attention.backend_is_tpu()
            and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and R == 1 and hd % _LANES == 0 and (Tq == 1 or Tq % _LANES == 0)
            and S >= _call_shape(Tq)[1] and S % STEP_ROWS == 0
            and _heads_a_step(Tq, hd, KV, 2) > 0)


def _kernel(slots, starts, counts, row, block, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            heads: int, queries: int, held: int, scale: float):
    """One visit: blocks are q and the result ``(1, rows, heads * hd)``, keys and
    values ``(1, heads, T, hd)``; scratch is the running max and sum ``(heads,
    rows, 128)`` and the accumulator ``(heads, rows, hd)``. ``queries`` of the
    ``rows`` are the call's (the rest stand where the last one does), ``held``
    the positions a slot holds."""
    from jax.experimental import pallas as pl

    rows, T, hd = q_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    dtype = q_ref.dtype
    visit = pl.program_id(1)
    b, j = row[visit], block[visit]
    blocks = counts[b]
    start = starts[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _LOW, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    if held % T:  # the slot ends inside its last block: what the fetch left behind the end weighs nothing
        @pl.when(j == held // T)
        def _():
            v_ref[0, :, held % T:, :] = jnp.zeros((heads, T - held % T, hd), dtype)

    @pl.when(blocks > 0)
    def _():
        key = j * T + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        at = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 0)
        seen = key <= start + (at if queries == rows else jnp.minimum(at, queries - 1))
        for h in range(heads):
            s = jax.lax.dot_general(q_ref[0, :, h * hd:(h + 1) * hd], k_ref[0, h], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _LOW)
            m_old = m_ref[h]                                                     # (rows, 128), lanes alike
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            shrink = jnp.exp(m_old - m_new)
            l_ref[h] = l_ref[h] * shrink + jnp.sum(p, axis=-1, keepdims=True)
            pv = jnp.dot(p.astype(dtype), v_ref[0, h], preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * shrink[:, :1] + pv
            m_ref[h] = m_new

    @pl.when((blocks > 0) & (j == blocks - 1))
    def _():
        for h in range(heads):
            o_ref[0, :, h * hd:(h + 1) * hd] = (acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)

    @pl.when(blocks == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


# Jitted so that the attention layers of a program share one trace and one lowering of the kernel (as ``fused_attention``).
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def cache_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array, slots: jax.Array, starts: jax.Array,
                    lengths: jax.Array, scale: float, interpret: bool = False) -> jax.Array:
    """``q (B, Tq, KV, 1, hd)``, ``cache_k``, ``cache_v (slots, KV, S, hd)``, ``slots``,
    ``starts`` and ``lengths`` ``(B,)`` integers (``starts`` multiples of ``Tq``
    for a chunk, any position for ``Tq`` = 1). Returns ``(B, Tq, KV, 1, hd)`` in
    ``q``'s dtype: query ``t`` of row ``b`` over positions ``<= starts[b] + t`` of
    slot ``slots[b]``, zeros for a row with ``lengths[b] == 0``. The caller has
    asked ``cache_attention_applies``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, KV, R, hd = q.shape
    S = cache_k.shape[2]
    rows, T, _ = _call_shape(Tq)
    heads = _heads_a_step(Tq, hd, KV, q.dtype.itemsize)
    if R != 1 or heads == 0:
        raise ValueError(f"cache_attention: {R} queries a key/value head, or one head's step over {rows} x {T} "
                         f"exceeds the VMEM budget of {VMEM_BUDGET} bytes")
    counts, row, block, visits = row_visits(starts, lengths, T, -(-S // T))
    q = jnp.pad(q.reshape(B, Tq, KV * hd), ((0, 0), (0, rows - Tq), (0, 0)))
    here = lambda g, v, slots, starts, counts, row, block: (row[v], 0, g)  # noqa: E731
    rows_of = lambda g, v, slots, starts, counts, row, block: (slots[row[v]], g, block[v], 0)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, queries=Tq, held=S, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(KV // heads, visits),
            in_specs=[pl.BlockSpec((1, rows, heads * hd), here),
                      pl.BlockSpec((1, heads, T, hd), rows_of),
                      pl.BlockSpec((1, heads, T, hd), rows_of)],
            out_specs=pl.BlockSpec((1, rows, heads * hd), here),
            scratch_shapes=[pltpu.VMEM((heads, rows, _LANES), jnp.float32), pltpu.VMEM((heads, rows, _LANES), jnp.float32),
                            pltpu.VMEM((heads, rows, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, rows, KV * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(slots.astype(jnp.int32), starts.astype(jnp.int32), counts, row, block, q, cache_k, cache_v)
    return out[:, :Tq].reshape(B, Tq, KV, R, hd)
