"""A prefill's write into a cache of per-head key/value rows, ``cache (slots,
heads, positions, head size)``, as one small Pallas TPU kernel: ``write_blocks``
puts a chunk's valid rows into the slots' rows in place. Row ``b`` of the call
is slot ``slots[b]``. Beside it, XLA's slices for the same write and for the
read of one block of every row of a call, which the CPU backend and the tiny
test sizes take (``models/olmo_hybrid._attn_prefill``'s loop over
``decoders.attention_chunk``).

As XLA the write is a few dynamic slices, and at long caches that is what does
not work. XLA chooses the layout of whatever an update or a loop's products
touch by what they like best, so it lays the *whole* cache out anew, once a
call: around the chunk's ``dynamic_update_slice``, which it prefers to do with
positions minor, and before a loop over blocks, whichever axis the rows keep
minor (compiled for a described v5e at 30 heads x 128, PR 35: at 8 or 9 slots x
16,464 rows two copies of 1 GB a call, 2.2 to 2.5 GB of temporaries where the
kernels leave 0.49; four to six where the device itself kept the rows
slots-minor, which ``models/olmo_hybrid.ROW_TILE`` now prevents). A custom
call's operands keep the layout they arrive in: on a TPU the cache is an operand
of this kernel and of ``ops/pallas_cache_attention.py``'s alone, in the layout
the device keeps it in (heads, positions, head size: row-major, no padding), and
the write aliases its input.

The write takes the kernel on a TPU where the shapes let it (``kernels_apply``:
a head size in whole lane tiles, blocks in whole steps of ``STEP_POSITIONS``)
and XLA's slices elsewhere, in the cache's dtype either way; the choice is made
when the program traces. Tests run the kernel in interpret mode on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from daft_tpu.ops import pallas_attention

#: Positions one grid step moves: 30 heads x 128 positions x 128 is 1 MB in bfloat16.
STEP_POSITIONS = 128


def kernels_apply(cache_shape, T: int) -> bool:
    """A TPU backend, a cache (slots, heads, positions, head size) with a head size
    in whole lane tiles, and a block that is whole steps."""
    return (pallas_attention.backend_is_tpu() and len(cache_shape) == 4
            and cache_shape[3] % pallas_attention._LANES == 0 and T % STEP_POSITIONS == 0)


# -- XLA's slices: the CPU backend and the tiny sizes ---------------------------------------------
def read_blocks_xla(cache, slots, first, T: int):
    """``cache[slots[b], :, first : first + T]`` for every row b of the call, as (B, T, heads, head size)."""
    KV, hd = cache.shape[1], cache.shape[3]
    rows = [jax.lax.dynamic_slice(cache, (slots[b], 0, first, 0), (1, KV, T, hd)) for b in range(slots.shape[0])]
    return jnp.moveaxis(jnp.concatenate(rows), 1, 2)


def write_blocks_xla(cache, new, slots, starts, lengths):
    B, T, KV, hd = new.shape
    new = jnp.moveaxis(new, 1, 2)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    for b in range(B):
        at = (slots[b], 0, starts[b], 0)
        old = jax.lax.dynamic_slice(cache, at, (1, KV, T, hd))
        cache = jax.lax.dynamic_update_slice(cache, jnp.where(valid[b][None, None, :, None], new[b][None], old), at)
    return cache


# -- the kernel -------------------------------------------------------------------------------------
def write_blocks_kernel(cache, new, slots, starts, lengths, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, KV, hd = new.shape
    step = STEP_POSITIONS

    def put(slots_ref, starts_ref, lengths_ref, old, new, out):
        b, t = pl.program_id(0), pl.program_id(1)
        at = t * step + jax.lax.broadcasted_iota(jnp.int32, (1, KV, step, hd), 2)
        out[...] = jnp.where(at < lengths_ref[b], new[...], old[...])

    here = lambda b, t, slots, starts, lengths: (slots[b], 0, starts[b] // step + t, 0)  # noqa: E731
    return pl.pallas_call(
        put,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, T // step),
            in_specs=[pl.BlockSpec((1, KV, step, hd), here),
                      pl.BlockSpec((1, KV, step, hd), lambda b, t, *_: (b, 0, t, 0))],
            out_specs=pl.BlockSpec((1, KV, step, hd), here)),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={3: 0},  # the cache, after the three prefetched arrays: steps not visited stay as they are
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(slots.astype(jnp.int32), starts.astype(jnp.int32), lengths.astype(jnp.int32), cache, jnp.moveaxis(new, 1, 2))


# -- what a model calls -----------------------------------------------------------------------------
def write_blocks(cache, new, slots, starts, lengths):
    """The chunk ``new (B, T, heads, head size)`` into the slots' rows from
    ``starts`` (B,; multiples of ``T``), valid tokens only (``lengths`` of each
    row): padding, and a row that carries no prompt, leave the slot as it was."""
    if kernels_apply(cache.shape, new.shape[1]):  # a kernel that fails to trace or lower fails the program
        return write_blocks_kernel(cache, new, slots, starts, lengths)
    return write_blocks_xla(cache, new, slots, starts, lengths)
