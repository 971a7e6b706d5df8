"""Fused attention for the unmasked towers (the ViT of CLIP): one Pallas TPU
kernel between the ``qkv`` projection and the ``out`` projection.

``fused_attention`` reads q, k and v where the projection wrote them, as
column blocks of its ``[B, T, 3d]`` output, and writes its result as columns
of ``[B, T, d]``, heads in the order the projection laid them out. The
compiled forward therefore holds no split, head reshape, transpose or copy
around attention, and no ``[B, H, T, T]`` tensor exists in HBM: one grid step
holds one batch row's columns in VMEM and computes, head by head, the whole
``[T, T]`` score tile, one max, one exp, one sum and the product with v.
There is no online softmax: the sequences this serves (197 and 257 tokens)
fit VMEM many times over, and ``fused_attention_applies`` sends anything
that does not to XLA.

Arithmetic is that of ``jax.nn.dot_product_attention``: operands enter the
MXU in the dtype they arrive in, scores and softmax are float32, the
probabilities are cast to v's dtype for the second product. The row sum
divides the ``[T, head_dim]`` result and not the ``[T, T]`` tile.

Heads narrower than a 128-lane tile are handled without a lane shuffle. A
128-column slice of q, k or v holds ``128 // head_dim`` adjacent heads. For
head h the other heads' lanes of q are zeroed and the scores contract over
all 128 lanes (the MXU is 128 deep whether 64 are used or not); the
probabilities multiply the whole 128-wide v slice and head h's lanes are
selected from the result.

Which path a forward takes is decided when it is traced, from what the code
can see (``fused_attention_applies`` and, in ``models/layers.py``, the mask
and whether the forward is partitioned over a mesh): there is no switch. A
kernel that fails to lower fails the forward. Forward only: no VJP is
defined. Tests run the kernel in interpret mode on the CPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_LANES = 128
#: What one grid step may hold in VMEM, by ``_step_bytes``' reckoning: a quarter
#: of a v5e core's 128 MiB, and the limit handed to the compiler. ViT-L/14's
#: step (T 257, d 1024) is reckoned at 6.6 MiB; at T 1024 a step takes half a
#: row of d 1024, and past T 1.1k the score tiles alone exceed it.
VMEM_BUDGET = 32 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _step_bytes(T: int, width: int, itemsize: int) -> int:
    """VMEM of one grid step over ``width`` columns of one batch row: the q, k, v
    and result blocks, double-buffered, and the float32 score tiles (scores,
    exponentials and their cast copy, of two heads in flight)."""
    blocks = 2 * 4 * _round_up(T, 16) * width * itemsize
    scores = 2 * 3 * _round_up(T, 8) * _round_up(T, _LANES) * 4
    return blocks + scores


def _block_width(T: int, d: int, itemsize: int) -> int:
    """The widest column block that divides ``d`` into whole 128-lane tiles and
    fits the budget, the whole row first (its DMAs are contiguous and the grid is
    shortest); 0 when not even one tile fits."""
    tiles = d // _LANES
    for n in range(1, tiles + 1):
        if tiles % n == 0 and _step_bytes(T, d // n, itemsize) <= VMEM_BUDGET:
            return d // n
    return 0


def backend_is_tpu() -> bool:
    """The kernel lowers for a TPU only, and is baked into the jaxpr when the
    forward traces: the rule is the backend the process computes on."""
    return jax.default_backend() == "tpu"


def fused_attention_applies(qkv_shape, dtype, num_heads: int) -> bool:
    """Whether ``fused_attention`` serves this projection output: a TPU backend,
    bf16 or f32 operands, whole heads filling 128-lane tiles, and a step's
    working set inside ``VMEM_BUDGET``. Otherwise the caller takes XLA's path."""
    _, T, d3 = qkv_shape
    d = d3 // 3
    dtype = jnp.dtype(dtype)
    return (backend_is_tpu()
            and dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and d % num_heads == 0 and _LANES % (d // num_heads) == 0
            and d % _LANES == 0
            and _block_width(T, d, dtype.itemsize) > 0)


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, *, head_dim: int):
    """Blocks are ``(1, T, width)``: one batch row, ``width // head_dim`` heads."""
    width = q_ref.shape[-1]
    scale = head_dim ** -0.5
    # A power of two (head_dim 64) scales q exactly in any float dtype;
    # otherwise the float32 scores are scaled, as XLA's path does.
    scale_q = math.frexp(scale)[0] == 0.5
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    contract_lanes = (((1,), (1,)), ((), ()))
    for c in range(width // _LANES):
        cols = slice(c * _LANES, (c + 1) * _LANES)
        q, k, v = q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, cols]
        if scale_q:
            q = q * jnp.asarray(scale, q.dtype)
        out = None
        for h in range(_LANES // head_dim):
            in_head = (lane >= h * head_dim) & (lane < (h + 1) * head_dim)
            q_h = q if head_dim == _LANES else jnp.where(in_head, q, jnp.zeros_like(q))
            s = jax.lax.dot_general(q_h, k, contract_lanes,
                                    preferred_element_type=jnp.float32)  # [T, T]
            if not scale_q:
                s = s * scale
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            row_sum = jnp.sum(p, axis=-1, keepdims=True)
            o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            o = o * (1.0 / row_sum)  # [T, 128]; head h's lanes are its result
            out = o if out is None else jnp.where(in_head, o, out)
        o_ref[0, :, cols] = out.astype(o_ref.dtype)


# Jitted so that the blocks of a tower share one trace and one lowering of the
# kernel: traced bare, 24 blocks cost a process 2 to 4 s more set-up each time
# a forward traces, whatever the compile cache holds (my chip runs, PR 27).
@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def fused_attention(qkv: jax.Array, num_heads: int, interpret: bool = False) -> jax.Array:
    """Unmasked attention over the ``qkv`` projection's output.

    ``qkv``: ``[B, T, 3d]``, columns ``[0, d)`` q, ``[d, 2d)`` k, ``[2d, 3d)`` v,
    each head ``d // num_heads`` adjacent columns. Returns ``[B, T, d]`` in the
    same head order: what ``MultiHeadAttention``'s ``out`` projection reads.
    The caller has asked ``fused_attention_applies``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, d3 = qkv.shape
    d = d3 // 3
    width = _block_width(T, d, qkv.dtype.itemsize)
    if width == 0:
        raise ValueError(f"fused_attention: one step over T={T}, d={d} exceeds "
                         f"the VMEM budget of {VMEM_BUDGET} bytes")
    n = d // width  # column blocks of each of q, k and v
    # T is the array's full extent, which a block dimension may be.
    column_block = lambda part: pl.BlockSpec(
        (1, T, width), lambda b, j: (b, 0, part * n + j))
    return pl.pallas_call(
        functools.partial(_attention_kernel, head_dim=d // num_heads),
        grid=(B, n),
        in_specs=[column_block(0), column_block(1), column_block(2)],
        out_specs=column_block(0),
        out_shape=jax.ShapeDtypeStruct((B, T, d), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(qkv, qkv, qkv)
