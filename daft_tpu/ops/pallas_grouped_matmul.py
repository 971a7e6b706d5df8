"""The routed experts' grouped product as one Pallas TPU kernel: ``x (m, k)``
sorted by group, ``w (G, k, n)``, ``sizes (G,)`` with ``sum(sizes) <= m``;
row ``r`` of group ``g`` gives ``x[r] @ w[g]``.

``lax.ragged_dot`` is handed all ``m`` rows whatever ``sizes`` covers. Here the
rows are walked in tiles of ``tm`` by group: a *visit* is one (group, row tile)
pair in which the group owns at least one row, and the grid runs the visits
and nothing else. The walk (each visit's group and row tile, the groups' row
offsets, the count of visits) is a few small integer arrays computed from
``sizes`` before the kernel and prefetched into scalar memory, where the index
maps read them; the count bounds the grid. So a tile that lies wholly behind
the last group is never read, multiplied or written, nor is an empty group's
weight. What the result holds in rows no group owns is undefined (whatever the
buffers held): the caller drops those rows.

A visit multiplies the tile, ``ROW_PART`` rows at a time and only the parts in
which its group owns a row, by the group's weights over the whole of ``k``
(bfloat16 or float32 operands as they arrive, float32 accumulation in the MXU)
and stores the rows the group owns; the rows of a neighbouring group in the
same tile keep what that group's visit stored, visits to one tile being
consecutive. The grid is (column blocks, visits), visits innermost: while the
walk stays inside one group the block of its weights stays where it is in VMEM,
so a group's weights are read once a column block however many tiles it spans.

``gated=True`` is the first product of a gated MLP. ``w`` is ``(G, k, 2f)``
with the ``a`` half in columns ``[0, f)`` and the ``b`` half in ``[f, 2f)``:
a visit reads a column block of each (two block specs over the one array),
applies ``silu(a) * b`` to the float32 accumulators and writes ``(m, f)`` in
``x``'s dtype. No ``(m, 2f)`` float32 array exists in HBM.

Tile sizes follow from the shapes (``_tiles``); which path a caller takes is
decided when its program traces, from what can be seen (``grouped_matmul_applies``):
there is no switch. A kernel that fails to lower fails the program. Forward
only. Tests run the kernel in interpret mode on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from daft_tpu.ops import pallas_attention

_LANES = pallas_attention._LANES
#: What one grid step may hold in VMEM, by ``_step_bytes``' reckoning, and the
#: limit handed to the compiler: a quarter of a v5e core's 128 MiB.
VMEM_BUDGET = 32 << 20
#: The tallest row tile. A group of r rows costs about r / tm + 1 visits of tm
#: rows each, so the MXU multiplies up to tm rows a group that are masked away;
#: a taller tile feeds the MXU longer between two loads of its weights.
MAX_ROW_TILE = 256
#: Rows of a tile multiplied at once: a part of a tile in which the visit's group
#: owns no row is skipped, so a visit multiplies what its group owns of the tile
#: to this many rows. One prefill call's two products at granite-4.0-h-small's
#: widths (7,478 of 20,480 rows held, 36 groups): 1.89 ms whole tiles of 256,
#: 1.67 in parts of 128, 1.57 in parts of 64 (my chip run, PR 32).
ROW_PART = 64


def _sublanes(itemsize: int) -> int:
    """Rows of one (sublane, lane) tile: 8 of float32, 16 of bfloat16."""
    return 32 // itemsize


def _step_bytes(tm: int, k: int, tn: int, itemsize: int, gated: bool) -> int:
    """VMEM of one grid step: the x, weight and result blocks, double-buffered,
    and the float32 accumulators with the copy the epilogue makes of them."""
    halves = 2 if gated else 1
    blocks = 2 * itemsize * (tm * k + halves * k * tn + tm * tn)
    return blocks + (halves + 1) * tm * tn * 4


def _tiles(m: int, k: int, n_out: int, groups: int, itemsize: int, gated: bool):
    """(tm, tn): the row tile is the rows a group gets on average (``m / groups``)
    rounded up to a power of two, between one sublane tile and ``MAX_ROW_TILE``;
    the column block is the widest that divides ``n_out`` into whole lane tiles
    and fits the budget (the whole width first: x is then read once). tn is 0
    where not even one lane tile fits."""
    sub = _sublanes(itemsize)
    tm = sub
    while tm < min(MAX_ROW_TILE, -(-m // groups)):
        tm *= 2
    tm = min(tm, pallas_attention._round_up(m, sub))
    lanes = n_out // _LANES
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and _step_bytes(tm, k, n_out // parts, itemsize, gated) <= VMEM_BUDGET:
            return tm, n_out // parts
    return tm, 0


def grouped_matmul_applies(x_shape, w_shape, dtype, gated: bool = False) -> bool:
    """Whether ``grouped_matmul`` serves this product: a TPU backend, bf16 or f32
    operands, ``k`` and the result's width in whole 128-lane tiles, and a step
    that fits ``VMEM_BUDGET``. Otherwise the caller takes ``lax.ragged_dot``."""
    m, k = x_shape
    groups, _, n = w_shape
    n_out = n // 2 if gated else n
    dtype = jnp.dtype(dtype)
    return (pallas_attention.backend_is_tpu()
            and dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and k % _LANES == 0 and n_out % _LANES == 0
            and _tiles(m, k, n_out, groups, dtype.itemsize, gated)[1] > 0)


def group_visits(sizes: jax.Array, row_tiles: int, tm: int):
    """The walk over ``row_tiles`` tiles of ``tm`` rows, as int32 arrays:
    ``offsets (G + 1,)`` the row each group starts at; ``group`` and ``tile``
    ``(row_tiles + G - 1,)`` of every visit, in order (a tile's visits are
    adjacent, and so are a group's); and the count of visits. A tile is visited
    once and once more for each further group that owns rows of it, so the
    arrays' length bounds the count; an empty group is not visited."""
    groups = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(tiles, dtype=jnp.int32)
    v = jnp.arange(row_tiles + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(visit_end[None, :] <= v[:, None], axis=1, dtype=jnp.int32), groups - 1)
    tile = jnp.clip(first[group] + v - (visit_end - tiles)[group], 0, row_tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, visit_end[-1]


def _kernel(offsets, group, tile, x_ref, *refs, tm: int, sub: int, gated: bool):
    """One visit: blocks are x ``(tm, k)``, weights ``(k, tn)`` (two of them when
    gated) and the result ``(tm, tn)``. The tile is multiplied ``sub`` rows at a
    time, and only the parts in which the group owns a row."""
    from jax.experimental import pallas as pl

    *w_refs, o_ref = refs
    visit = pl.program_id(1)
    start, end = offsets[group[visit]], offsets[group[visit] + 1]
    for part in range(tm // sub):
        first = tile[visit] * tm + part * sub

        @pl.when((first < end) & (first + sub > start))
        def _():
            rows = slice(part * sub, (part + 1) * sub)
            row = first + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            mine = (row >= start) & (row < end)
            x = x_ref[rows, :]
            acc = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
            if gated:
                acc = jax.nn.silu(acc) * jnp.dot(x, w_refs[1][...], preferred_element_type=jnp.float32)
            o_ref[rows, :] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[rows, :])


# Jitted so that the layers of a model share one trace and one lowering of each
# product's kernel (as ``fused_attention``).
@functools.partial(jax.jit, static_argnames=("gated", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array, gated: bool = False,
                   interpret: bool = False) -> jax.Array:
    """``x (m, k)`` with group g's rows at ``[sum(sizes[:g]), sum(sizes[:g + 1]))``,
    ``w (G, k, n)``, ``sizes (G,)`` integers with ``sum(sizes) <= m``. Returns
    ``(m, n)`` in ``x``'s dtype, or ``(m, n // 2)`` holding ``silu(a) * b`` when
    ``gated``; rows no group owns are undefined. The caller has asked
    ``grouped_matmul_applies``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    groups, _, n = w.shape
    n_out = n // 2 if gated else n
    tm, tn = _tiles(m, k, n_out, groups, x.dtype.itemsize, gated)
    if tn == 0:
        raise ValueError(f"grouped_matmul: one step over k={k} exceeds the VMEM budget of {VMEM_BUDGET} bytes")
    row_tiles, col_blocks = -(-m // tm), n_out // tn
    offsets, group, tile, visits = group_visits(sizes, row_tiles, tm)

    def weights(half):
        return pl.BlockSpec((None, k, tn), lambda j, v, offsets, group, tile: (group[v], 0, half * col_blocks + j))

    w_specs = [weights(0), weights(1)] if gated else [weights(0)]
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, sub=min(tm, ROW_PART), gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(col_blocks, visits),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, offsets, group, tile: (tile[v], 0))] + w_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, offsets, group, tile: (tile[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(offsets, group, tile, x, *([w] * len(w_specs)))
