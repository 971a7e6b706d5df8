"""The latent attention's prefill as one Pallas TPU kernel (``models/latent_attention``:
LongCat-Flash's, and DeepSeek-V3.2's with a selection as a further input): one chunk of
``T`` queries a row against the blocks of latent cache rows its slot holds so far.

``q (B, T, H, nope + rope)`` (the rotary part turned), the cache ``kv (slots,
lat + rope, S)`` with positions minor (a token's row is ``[c | RoPE(k_r)]``),
``w_kvb (lat, H, nope + v)``; row ``b`` of the call is slot ``slots[b]``, its
query ``t`` stands at position ``starts[b] + t``, and ``lengths[b] == 0`` says
that the row carries no query. What XLA's path (``models/latent_attention.
core_expanded``) streams through HBM stays in VMEM here:

- **The scores.** A grid step holds one row's queries of ``heads`` heads and one
  block of ``T`` cache positions; per head it computes the ``(T, T)`` float32
  scores, the running max, the exponentials, the row sum and the rescaled
  accumulator, all in VMEM. ``q``, the cache rows, ``W_kvb`` and the ``(B, T, H,
  v)`` result are what crosses HBM.
- **The expansion.** The block's latent rows (``kv[slot, :lat, jT:(j+1)T]``, as
  the cache is laid out) times the head's slice of ``W_kvb`` give that head's
  keys and values, transposed, in VMEM; the rotary key ``kv[slot, lat:, ...]`` is
  shared by all heads. No per-head key or value exists in HBM.
- **The walk.** A *visit* is one (row, block) pair in which the row holds a
  query: row ``b`` attends ``starts[b] // T + 1`` blocks, a row without a query
  none. The visits (each one's row and block) are a few small integer arrays
  computed before the kernel and prefetched into scalar memory, where the index
  maps read them, and their count bounds the grid (as in
  ``ops/pallas_grouped_matmul``): a block no query of its row can see is never
  fetched, expanded or multiplied. A row without a query gets one visit that
  only writes zeros: the layers after the attention still run over that row.

The arithmetic is ``core_expanded``'s: operands enter the MXU in the dtype
they arrive in (the expanded keys and values cast to it, the probabilities cast
to it for the second product), scores, max, exp, sum and the accumulator are
float32, the scale multiplies the float32 scores, the causal mask is by
position, one division at the end. ``[nope | rope]``
is contracted in one product: ``q`` arrives with each head padded to whole lane
tiles (zeros: the pad is written by the fusion that turns the rotary part), and
the keys' tile is ``[k_nope | k_r | 0]``.

The grid is (head groups, visits), visits innermost: while the walk stays in
one head group its slice of ``W_kvb`` stays in VMEM, and a row's result block is
written back once, after its last visit. Which path a prefill takes is decided
when it traces, from what can be seen (``mla_prefill_applies``): there is no
switch. A kernel that fails to lower fails the program. Forward only. Tests run
the kernel in interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.ops import pallas_attention

_LANES = pallas_attention._LANES
#: What one grid step may hold in VMEM, by ``_step_bytes``' reckoning, and the
#: limit handed to the compiler: a quarter of a v5e core's 128 MiB.
VMEM_BUDGET = pallas_attention.VMEM_BUDGET
#: Heads a grid step takes at most: the step's body is unrolled over them, and a
#: block of cache rows is fetched once a step, so once for this many heads. A
#: visit of all 64 heads at LongCat-Flash-Chat's widths takes 145 us at 8 heads a
#: step, 148 at 4 and 147 at 2, and the prefill program's eight attentions
#: compile in 14 s more at 8 than at 2 (my chip run and my compile for a
#: described v5e, PR 34).
MAX_HEADS = 2
_LOW = float(np.finfo(np.float32).min)


def _step_bytes(T: int, lat: int, nope: int, rope: int, dv: int, heads: int, itemsize: int, selects: bool = False) -> int:
    """VMEM of one grid step over ``heads`` heads: the q, weight, cache and result
    blocks (with a selection the block's float32 index scores and the thresholds,
    a lane tile wide), double-buffered; the running max, sum (a lane tile wide
    each) and accumulator of every head and the keys' tile; and the float32
    temporaries of two heads in flight (the expanded block, scores, exponentials
    and their cast)."""
    qk = pallas_attention._round_up(nope + rope, _LANES)
    blocks = 2 * itemsize * (T * heads * qk + heads * (nope + dv) * lat + (lat + rope) * T + T * heads * dv)
    blocks += 2 * 4 * (T * T + T * _LANES) if selects else 0
    scratch = heads * T * (2 * _LANES + dv) * 4 + qk * T * itemsize
    flight = 2 * ((nope + dv) * T * 4 + T * T * (4 + 4 + itemsize))
    return blocks + scratch + flight


def _heads_a_step(T: int, lat: int, nope: int, rope: int, dv: int, H: int, itemsize: int,
                  max_heads: int = MAX_HEADS, selects: bool = False) -> int:
    """The most heads (a divisor of ``H``, at most ``max_heads``) whose step fits
    the budget; 0 when one head's does not."""
    for heads in range(min(H, max_heads), 0, -1):
        if H % heads == 0 and _step_bytes(T, lat, nope, rope, dv, heads, itemsize, selects) <= VMEM_BUDGET:
            return heads
    return 0


def mla_prefill_applies(q_shape, dtype, lat: int, nope: int, rope: int, dv: int) -> bool:
    """Whether ``mla_prefill_attention`` serves this chunk: a TPU backend,
    bfloat16, the latent, both head widths and the chunk in whole 128-lane tiles
    (the rotary width in whole sublane tiles), and one head's step inside
    ``VMEM_BUDGET``. Otherwise the caller takes ``latent_attention.core_expanded``."""
    _, T, H, _ = q_shape
    return (pallas_attention.backend_is_tpu()
            and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and all(n % _LANES == 0 for n in (T, lat, nope, dv)) and rope % 16 == 0
            and _heads_a_step(T, lat, nope, rope, dv, H, 2) > 0)


def row_visits(starts: jax.Array, lengths: jax.Array, T: int, max_blocks: int):
    """The walk, as int32 arrays: ``counts (B,)`` the blocks each row attends
    (``starts // T + 1`` where it holds a query, else 0); ``row`` and ``block``
    ``(B * max_blocks,)`` of every visit in order (a row's visits are adjacent,
    blocks ascending; a row without a query has one, block 0); and the count of
    visits."""
    B = starts.shape[0]
    counts = jnp.where(lengths > 0, jnp.minimum(starts // T + 1, max_blocks), 0).astype(jnp.int32)
    walk = jnp.maximum(counts, 1)
    ends = jnp.cumsum(walk, dtype=jnp.int32)
    v = jnp.arange(B * max_blocks, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32), B - 1)
    block = jnp.clip(v - (ends - walk)[row], 0, max_blocks - 1)
    return counts, row, block, ends[-1]


def _kernel(slots, starts, counts, row, block, q_ref, w_ref, kv_ref, *rest,
            lat: int, nope: int, rope: int, dv: int, heads: int, scale: float, selects: bool):
    """One visit: blocks are q ``(1, T, heads * qk)``, the weights ``(heads, nope
    + v, lat)``, the cache rows ``(1, lat + rope, T)``, with a selection the
    block's index scores ``(1, T, T)`` and the queries' thresholds ``(1, T, 1)``,
    and the result ``(1, T, heads * v)``; scratch is the running max and sum
    ``(heads, T, 128)``, the accumulator ``(heads, T, v)`` and the keys' tile
    ``(qk, T)``."""
    from jax.experimental import pallas as pl

    if selects:
        idx_ref, thr_ref, o_ref, m_ref, l_ref, acc_ref, k_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, k_ref = rest

    T = q_ref.shape[1]
    qk = k_ref.shape[0]
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

    @pl.when(blocks > 0)
    def _():
        c = kv_ref[0, :lat, :]                                           # (lat, T): positions are lanes
        k_ref[nope:nope + rope, :] = kv_ref[0, lat:, :]
        if qk > nope + rope:
            k_ref[nope + rope:, :] = jnp.zeros((qk - nope - rope, T), dtype)
        key = j * T + jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        seen = key <= start + jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        if selects:  # a key enters a query's softmax where its index score reaches the query's threshold
            seen = seen & (idx_ref[0] >= thr_ref[0])
        for h in range(heads):
            kv_t = jnp.dot(w_ref[h], c, preferred_element_type=jnp.float32)     # (nope + v, T)
            k_ref[:nope, :] = kv_t[:nope].astype(dtype)
            v_t = kv_t[nope:].astype(dtype)
            s = jnp.dot(q_ref[0, :, h * qk:(h + 1) * qk], k_ref[...], preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _LOW)
            m_old = m_ref[h]                                                     # (T, 128), lanes alike
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            shrink = jnp.exp(m_old - m_new)
            l_ref[h] = l_ref[h] * shrink + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(dtype), v_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * shrink[:, :1] + pv
            m_ref[h] = m_new

    @pl.when((blocks > 0) & (j == blocks - 1))
    def _():
        for h in range(heads):
            o_ref[0, :, h * dv:(h + 1) * dv] = (acc_ref[h] / l_ref[h][:, :1]).astype(o_ref.dtype)

    @pl.when(blocks == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


# Jitted so that the attentions of a model share one trace and one lowering of the kernel (as ``fused_attention``).
@functools.partial(jax.jit, static_argnames=("nope", "interpret", "scale", "max_heads"))
def mla_prefill_attention(q: jax.Array, kv: jax.Array, w_kvb: jax.Array, slots: jax.Array, starts: jax.Array,
                          lengths: jax.Array, nope: int, interpret: bool = False, scale: float = None,
                          index: jax.Array = None, threshold: jax.Array = None,
                          max_heads: int = MAX_HEADS) -> jax.Array:
    """``q (B, T, H, nope + rope)``, ``kv (slots, lat + rope, S)``, ``w_kvb (lat, H,
    nope + v)``, ``slots``, ``starts`` (multiples of ``T``) and ``lengths`` ``(B,)``
    integers. Returns ``(B, T, H, v)`` in ``q``'s dtype: query ``t`` of row ``b``
    over positions ``<= starts[b] + t`` of slot ``slots[b]``, zeros for a row
    with ``lengths[b] == 0``. The caller has asked ``mla_prefill_applies``.

    ``scale`` multiplies the scores (``None``: ``(nope + rope) ** -0.5``).
    A selection (``models/deepseek_v32``) is ``index (B, T, blocks * T)`` float32,
    query ``t``'s index score of each position of its slot, and ``threshold (B,
    T)``: position ``s`` enters the softmax where it is causal and ``index[b, t,
    s] >= threshold[b, t]``. The walk is the same (the masked form: a block of
    which no query keeps a key is still expanded); what the visits of one block
    read more is its ``(T, T)`` scores, once a head group, which is why such a
    caller takes more heads a step (``max_heads``). Without ``index`` the call
    lowers to the kernel it was before a selection existed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, qk_dim = q.shape
    lat, _, kv_dim = w_kvb.shape
    rope, dv = qk_dim - nope, kv_dim - nope
    heads = _heads_a_step(T, lat, nope, rope, dv, H, q.dtype.itemsize, max_heads, index is not None)
    if heads == 0:
        raise ValueError(f"mla_prefill_attention: one head's step over T={T}, latent {lat} exceeds "
                         f"the VMEM budget of {VMEM_BUDGET} bytes")
    qk = pallas_attention._round_up(qk_dim, _LANES)
    max_blocks = -(-kv.shape[2] // T)
    counts, row, block, visits = row_visits(starts, lengths, T, max_blocks)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, qk - qk_dim))).reshape(B, T, H * qk)
    w_t = jnp.transpose(w_kvb, (1, 2, 0))                                   # (H, nope + v, lat)
    selects = index is not None
    selection, selection_specs = (), []
    if selects:
        selection = (index, threshold.reshape(B, T, 1).astype(index.dtype))
        selection_specs = [pl.BlockSpec((1, T, T), lambda g, v, slots, starts, counts, row, block: (row[v], 0, block[v])),
                           pl.BlockSpec((1, T, 1), lambda g, v, slots, starts, counts, row, block: (row[v], 0, 0))]
    out = pl.pallas_call(
        functools.partial(_kernel, lat=lat, nope=nope, rope=rope, dv=dv, heads=heads,
                          scale=qk_dim ** -0.5 if scale is None else scale, selects=selects),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(H // heads, visits),
            in_specs=[pl.BlockSpec((1, T, heads * qk), lambda g, v, slots, starts, counts, row, block: (row[v], 0, g)),
                      pl.BlockSpec((heads, nope + dv, lat), lambda g, v, *_: (g, 0, 0)),
                      pl.BlockSpec((1, lat + rope, T),
                                   lambda g, v, slots, starts, counts, row, block: (slots[row[v]], 0, block[v]))]
            + selection_specs,
            out_specs=pl.BlockSpec((1, T, heads * dv), lambda g, v, slots, starts, counts, row, block: (row[v], 0, g)),
            scratch_shapes=[pltpu.VMEM((heads, T, _LANES), jnp.float32), pltpu.VMEM((heads, T, _LANES), jnp.float32),
                            pltpu.VMEM((heads, T, dv), jnp.float32), pltpu.VMEM((qk, T), q.dtype)]),
        out_shape=jax.ShapeDtypeStruct((B, T, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(slots.astype(jnp.int32), starts.astype(jnp.int32), counts, row, block, q, w_t, kv, *selection)
    return out.reshape(B, T, H, dv)
