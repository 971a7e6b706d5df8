"""DeepSeek sparse attention's index scores of one prefill chunk as one Pallas TPU
kernel: ``I(t, s) = sum_h w_h(t) relu(q_h(t) . k(s))`` for the ``T`` queries of
every row of a call against the blocks of indexer keys its slot holds so far.

``q (B, T, Hi * Di)`` (the rotary part turned), weights ``w (B, T, Hi)`` float32,
the indexer's keys ``ik (slots, Di, S)`` with positions minor; row ``b`` of the
call is slot ``slots[b]``, its query ``t`` stands at position ``starts[b] + t``,
``lengths[b] == 0`` says the row carries no query. XLA's path
(``models/deepseek_v32.index_scores_expanded``) writes every head's ``(T, T)``
scores of a block to HBM before it weighs and sums them (64 heads: 67 MB a row
and block, where the block's result is 1 MB); here a grid step holds one row's
queries of all heads and one block of ``T`` keys, and per head computes the
``(T, T)`` float32 product, the ReLU, the weight and the running sum in VMEM.
``q``, the keys and the ``(T, T)`` result are what crosses HBM, and a row's
queries stay in VMEM while the walk stays in that row.

The walk is ``ops/pallas_mla_attention.row_visits``': one visit a (row, block)
pair in which the row holds a query, the visits prefetched into scalar memory
and their count bounding the grid. A block no query of its row can see is never
written: what the result holds there is undefined, and the reader
(``deepseek_v32.kth_threshold``, the attention's causal mask) never looks
beyond a query's own position. A row without a query gets one visit that
writes zeros.

Operands enter the MXU in the dtype they arrive in (bfloat16), products
accumulate in float32, ReLU, weight and sum are float32. Which path a prefill
takes is decided when it traces, from what can be seen
(``index_scores_applies``): there is no switch. A kernel that fails to lower
fails the program. Forward only. Tests run the kernel in interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from daft_tpu.ops import pallas_attention
from daft_tpu.ops.pallas_mla_attention import row_visits

_LANES = pallas_attention._LANES
VMEM_BUDGET = pallas_attention.VMEM_BUDGET


def _step_bytes(T: int, Hi: int, Di: int, itemsize: int) -> int:
    """VMEM of one grid step: the q, weight, key and result blocks, double-buffered,
    and the float32 temporaries of two heads in flight beside the running sum."""
    blocks = 2 * (T * Hi * Di * itemsize + T * max(Hi, _LANES) * 4 + Di * T * itemsize + T * T * 4)
    return blocks + 3 * T * T * 4


def index_scores_applies(q_shape, dtype) -> bool:
    """Whether ``index_scores`` serves this chunk: a TPU backend, bfloat16, the
    chunk and the head width in whole 128-lane tiles, and one step inside
    ``VMEM_BUDGET``. Otherwise the caller takes ``index_scores_expanded``."""
    _, T, Hi, Di = q_shape
    return (pallas_attention.backend_is_tpu()
            and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and T % _LANES == 0 and Di % _LANES == 0
            and _step_bytes(T, Hi, Di, 2) <= VMEM_BUDGET)


def _kernel(slots, counts, row, block, q_ref, w_ref, k_ref, o_ref, *, heads: int, width: int):
    """One visit: blocks are q ``(1, T, heads * width)``, the weights ``(1, T, heads)``,
    the keys ``(1, width, T)`` (positions are lanes) and the result ``(1, T, T)``."""
    from jax.experimental import pallas as pl

    visit = pl.program_id(0)
    holds_query = counts[row[visit]] > 0

    @pl.when(holds_query)
    def _():
        k = k_ref[0]
        w = w_ref[0]
        acc = None
        for h in range(heads):
            s = jnp.dot(q_ref[0, :, h * width:(h + 1) * width], k, preferred_element_type=jnp.float32)   # (T, T)
            part = jnp.maximum(s, 0.0) * w[:, h:h + 1]
            acc = part if acc is None else acc + part
        o_ref[0] = acc

    @pl.when(jnp.logical_not(holds_query))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores(q: jax.Array, w: jax.Array, ik: jax.Array, slots: jax.Array, starts: jax.Array,
                 lengths: jax.Array, interpret: bool = False) -> jax.Array:
    """``q (B, T, Hi, Di)``, ``w (B, T, Hi)`` float32, ``ik (slots, Di, S)``,
    ``slots``, ``starts`` (multiples of ``T``) and ``lengths`` ``(B,)`` integers.
    Returns ``(B, T, blocks * T)`` float32, ``blocks = ceil(S / T)``: query ``t``
    of row ``b`` against the positions of slot ``slots[b]``, defined over the
    blocks up to the one that holds ``starts[b]`` (a row with ``lengths[b] == 0``:
    zeros in block 0). The caller has asked ``index_scores_applies``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, Hi, Di = q.shape
    max_blocks = -(-ik.shape[2] // T)
    counts, row, block, visits = row_visits(starts, lengths, T, max_blocks)
    return pl.pallas_call(
        functools.partial(_kernel, heads=Hi, width=Di),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits,),
            in_specs=[pl.BlockSpec((1, T, Hi * Di), lambda v, slots, counts, row, block: (row[v], 0, 0)),
                      pl.BlockSpec((1, T, Hi), lambda v, slots, counts, row, block: (row[v], 0, 0)),
                      pl.BlockSpec((1, Di, T), lambda v, slots, counts, row, block: (slots[row[v]], 0, block[v]))],
            out_specs=pl.BlockSpec((1, T, T), lambda v, slots, counts, row, block: (row[v], 0, block[v]))),
        out_shape=jax.ShapeDtypeStruct((B, T, max_blocks * T), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(slots.astype(jnp.int32), counts, row, block, q.reshape(B, T, Hi * Di), w.astype(jnp.float32), ik)
