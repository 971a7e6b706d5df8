"""The chunked gated delta rule of a prefill call as one Pallas TPU kernel: a
chunk's solve, its products and the carried state stay in VMEM.

``models/olmo_hybrid.py``'s head has the algebra (``A``, ``T = (I + A)^-1``,
``W``, ``U'``, ``U``, ``O``, ``S_C``); ``gated_delta_chunked`` there is the same
algebra as XLA runs it, the form this kernel is checked against on the chip and
the only form a CPU runs. The two share no code, on purpose.

**What crosses HBM** is what ``gated_delta_chunked`` takes and gives: q, k
``(B, T, H, dk)`` and v ``(B, T, H, dv)`` in bfloat16, g and beta ``(B, T, H)``
float32 (0 at padding), ``s0 (B, H, dv, dk)`` float32 -> o ``(B, T, H, dv)``
float32 and the state after the last step. ``K K^T``, ``Q K^T``, the decays,
``A``, ``T``, ``W``, ``U'``, ``U`` live and die in VMEM. At the kernel's edges,
in XLA and under the caller's ``delta_rule`` scope: q and k get each head's
``dk`` padded to whole lane tiles (96 -> 128: a head's columns then start on a
tile), the chunk-wise running sum of g is taken, and the two small vectors
(``cumsum(g)``, beta) are laid out once with time on sublanes and once with time
on lanes (two heads side by side), since the kernel scales rows by them and
builds ``Gamma_ij`` and its transpose from both. v and o are read and written
where they lie (a head's ``dv`` columns, 192, start on a tile for every second
head; heads go in even groups).

**The grid** is (rows, head groups, chunks): rows and groups in any order, a
call's chunks in order (``"arbitrary"``). A step holds one chunk of ``heads``
heads; their state is a VMEM scratch ``(heads, dv, dk padded)`` float32, loaded
from ``s0`` at a row's first chunk, carried from chunk to chunk, written out
after the last. ``heads`` is the most that divides ``H``, is even, keeps v's
and o's column blocks in whole lane tiles and fits ``VMEM_BUDGET`` by
``_step_bytes``' reckoning, up to ``MAX_HEADS``. The body is written over
stacks of heads, stage by stage: a head's stages are one chain of dependent
steps and products, and issued head after head they ran one after another
(2.57 ms a call of 4 x 512 x 30 heads on a v5e against 1.80 stage by stage; my
chip runs, PR 41).

**Two heads side by side.** A chunk is 64 steps, so what is ``C x C`` a head
(``K K^T``, ``Q K^T``, the decays, ``A``, the solve) fills half the 128 lanes.
It is computed for a pair of heads at once, the first in the lanes below 64:
one product of the pair's stacked rows gives both heads' ``K K^T`` on its
diagonal blocks; a pair's merge product ``x_h @ y_h`` is one product against
``y`` laid block-diagonally, at the matrix unit's full depth. The pair comes
apart (a lane slice) before the products with k, v and the state.

**The solve.** ``T = (I + A)^-1`` with ``A`` strictly lower triangular. Row by
row (the XLA form's ``_unit_lower_inverse``) is 63 dependent vector steps a
head. Here: forward substitution inside the diagonal blocks of ``SOLVE_BLOCK``
(16) steps, every block of every head of the step at once: a pair's eight
blocks are folded onto 16 sublanes x 128 lanes (two registers), a step's
weights ``n_ij`` are fetched from the transpose, folded the same way, by a
gather within each block's 16 lanes, and a step is a product, a sum over 16
sublanes and a row's update: 15 dependent steps of about a dozen register
operations a pair (the transpose is computed as ``A`` is, from the symmetric
``K K^T`` and the vectors' other layout: no transposition in the kernel). Then
the blocks are merged pairwise into the whole inverse, ``[[T11, 0], [-T22 A21
T11, T22]]`` written as ``T_d - T_d A_off T_d`` over the whole chunk (two
float32 products a level and pair, two levels). The merge is the block form of
the same substitution, so it stands where the row form stands when beta nears 2
and entries of ``A`` near 2 in magnitude; the nilpotent product ``(I - A)(I +
A^2)...(I + A^32)`` forms powers of ``A`` that grow before they cancel and
fails there (``PERF.md`` section 6, PR 41, has what each read).

**Precision** is the XLA form's: ``K K^T`` and ``Q K^T`` take the bfloat16
operands as they arrive and accumulate in float32; the decays, the solve, the
state and every product that reads the solve's result or the state take float32
operands at ``Precision.HIGHEST`` (the state is a sum over the whole prefix:
rounding it at a chunk's edge doubled the gap to the reference, PR 35).

Which form a program takes is decided while it traces, from what can be seen
(``delta_rule_applies``): there is no switch. A kernel that fails to lower
fails the program. Forward only. Tests run the kernel in interpret mode on the
CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from daft_tpu.ops import pallas_attention

_LANES = pallas_attention._LANES
#: What one grid step may hold in VMEM, by ``_step_bytes``' reckoning, and the limit handed to the compiler.
VMEM_BUDGET = pallas_attention.VMEM_BUDGET
#: Steps of a diagonal block that the solve substitutes row by row; blocks merge pairwise up to the chunk.
SOLVE_BLOCK = 16
#: Heads a grid step takes at most: the body is unrolled over them.
MAX_HEADS = 6
_HI = jax.lax.Precision.HIGHEST
_round_up = pallas_attention._round_up


def _step_bytes(chunk: int, dk: int, dv: int, heads: int) -> int:
    """VMEM of one grid step over ``heads`` heads: the q, k, v and o blocks and
    the state's two, double-buffered; the carried state; and the float32
    temporaries of two heads in flight (six ``chunk x chunk``, four of a key's
    width, four of a value's)."""
    dkp, dvp = _round_up(dk, _LANES), _round_up(dv, _LANES)
    blocks = 2 * chunk * heads * (2 * dkp * 2 + dv * 2 + dv * 4) + 2 * 2 * heads * dv * dkp * 4
    state = heads * dv * dkp * 4
    flight = 2 * 4 * chunk * (6 * _round_up(chunk, _LANES) + 4 * dkp + 4 * dvp)
    return blocks + state + flight


def _heads_a_step(chunk: int, H: int, dk: int, dv: int) -> int:
    """The most heads (an even divisor of ``H``: they go in pairs; whose ``dv``
    columns are whole lane tiles, or all of them) whose step fits the budget; 0
    when none does."""
    for heads in range(min(H, MAX_HEADS), 0, -1):
        if (H % heads == 0 and heads % 2 == 0 and ((heads * dv) % _LANES == 0 or heads == H)
                and _step_bytes(chunk, dk, dv, heads) <= VMEM_BUDGET):
            return heads
    return 0


def _merges(chunk: int) -> bool:
    """Whether ``SOLVE_BLOCK`` doubles up to the chunk."""
    blocks = chunk // SOLVE_BLOCK
    return chunk % SOLVE_BLOCK == 0 and blocks & (blocks - 1) == 0


def delta_rule_applies(q_shape, v_shape, dtype, chunk: int) -> bool:
    """Whether ``gated_delta_fused`` serves this call: a TPU backend, bfloat16 q /
    k / v, ``T`` a whole number of chunks, a chunk of which two fill the lanes
    (64 steps: two heads' ``C x C`` stand side by side) and whose diagonal blocks
    merge pairwise, and an even head group inside ``VMEM_BUDGET``. Otherwise the
    caller takes ``gated_delta_chunked``."""
    _, T, H, dk = q_shape
    return (pallas_attention.backend_is_tpu()
            and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and T % chunk == 0 and 2 * chunk == _LANES and _merges(chunk)
            and _heads_a_step(chunk, H, dk, v_shape[-1]) > 0)


def _bdot(x, y, dims):
    """A product a head (or a pair), float32 operands at full precision: ``x``, ``y`` (n, ., .), ``dims`` the axes contracted."""
    return jax.lax.dot_general(x, y, (dims, ((0,), (0,))), precision=_HI, preferred_element_type=jnp.float32)


_NN = ((2,), (1,))      # x y
_NT = ((2,), (2,))      # x y^T: both operands contract their lanes
_TN = ((1,), (1,))      # x^T y: both operands contract their sublanes (a chunk's steps)


def _side_by_side(x, C: int):
    """``x (pairs, 2C, 2C)``, a product of two heads' rows stacked -> ``(pairs, C, 2C)``: the first head's own block in
    the lanes below ``C``, the second's in those from ``C`` on."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    return jnp.where(lane < C, x[:, :C], x[:, C:])


def _block_diagonal(x, C: int):
    """``x (pairs, C, 2C)``, two heads side by side -> ``(pairs, 2C, 2C)`` with each head's block on the diagonal: a
    pair's ``y @ _block_diagonal(x)`` is the two heads' products ``y_h @ x_h`` side by side."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    return jnp.concatenate([jnp.where(lane < C, x, 0.0), jnp.where(lane >= C, x, 0.0)], axis=1)


def _unit_lower_inverse(a, nt, block: int):
    """``(I + a_h)^-1`` for pairs of heads side by side: ``a (pairs, C, 2C)``
    strictly lower triangular a head, float32, and ``nt`` its negated transpose a
    head, laid out the same way. The diagonal blocks of ``block`` steps by forward
    substitution, every block of every head at once, then merged pairwise."""
    P, C = a.shape[0], a.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1) % C           # a head's own column
    own = row // block == col // block
    # Row i of a block's inverse less the identity is m_i = n_i + sum_{j<i} n_ij m_j, n = -a. A block's rows are folded
    # onto ``block`` sublanes (lanes: head, block, column), so a step is two registers a pair; the weights n_ij stand
    # down the sublanes j beside the rows they weigh and are the same along a block's lanes: lane i of the block in
    # the transpose folded the same way, fetched by a gather within the block's lanes.
    fold = lambda x: sum(x[:, b * block:(b + 1) * block] for b in range(C // block))   # noqa: E731  (pairs, block, 2C)
    m = fold(jnp.where(own, -a, 0.0)).reshape(P * block, 2 * C)
    weights = fold(jnp.where(own, nt, 0.0)).reshape(P * block, 2 * C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (P * block, 2 * C), 1)
    step = jax.lax.broadcasted_iota(jnp.int32, (P, block, 2 * C), 1)
    for i in range(1, block):   # rows j >= i of a block weigh 0: n is strictly lower
        new = jnp.sum((jnp.take_along_axis(weights, lane // block * block + i, axis=1) * m).reshape(P, block, 2 * C),
                      axis=1, keepdims=True)
        m = (m.reshape(P, block, 2 * C) + jnp.where(step == i, new, 0.0)).reshape(P * block, 2 * C)
    m = m.reshape(P, block, 2 * C)
    t = jnp.where(own, jnp.concatenate([m] * (C // block), axis=1), 0.0) + jnp.where(row == col, 1.0, 0.0)
    size = block
    while size < C:   # [[T11, 0], [-T22 A21 T11, T22]] for every pair of neighbours: T_d - T_d A_off T_d
        off = (row // (2 * size) == col // (2 * size)) & (row // size != col // size)
        t = t - _bdot(t, _block_diagonal(_bdot(jnp.where(off, a, 0.0), _block_diagonal(t, C), _NN), C), _NN)
        size *= 2
    return t


def _kernel(q_ref, k_ref, v_ref, cs_col_ref, beta_col_ref, cs_row_ref, beta_row_ref, s0_ref, o_ref, s_ref, state, *,
            heads: int, dk: int, dv: int, block: int):
    """One chunk of ``heads`` heads of one row. Blocks: q, k ``(1, C, heads *
    dkp)`` and v ``(1, C, heads * dv)`` bfloat16; the running sum of g and beta
    with time on sublanes ``(1, 1, C, heads)`` and with time on lanes, two heads
    side by side, ``(1, heads / 2, chunks, 2C)``; the state in and out ``(1,
    heads, dv, dk)``; o ``(1, C, heads * dv)`` float32. Scratch: the state
    ``(heads, dv, dkp)``.

    Every value is a stack over the heads (or over pairs of them), so that each
    stage is issued for all of them before the next one starts: a head's stages
    wait for one another (the solve's steps and the products that follow it are
    one chain), the heads' do not. What is ``C x C`` a head (``K K^T``, ``Q
    K^T``, the decays, ``A``, the solve) is computed for two heads side by side
    in the 128 lanes, which halves its vector work and gives a pair's merge
    products the matrix unit's full depth."""
    from jax.experimental import pallas as pl

    C = q_ref.shape[1]
    dkp = q_ref.shape[2] // heads
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        if dkp > dk:
            state[...] = jnp.zeros(state.shape, jnp.float32)
        state[:, :, :dk] = s0_ref[0]

    over = lambda f, n=heads: jnp.stack([f(h) for h in range(n)])          # noqa: E731
    q, k = over(lambda h: q_ref[0, :, h * dkp:(h + 1) * dkp]), over(lambda h: k_ref[0, :, h * dkp:(h + 1) * dkp])
    v = over(lambda h: v_ref[0, :, h * dv:(h + 1) * dv])
    cs_cols, beta_cols = cs_col_ref[0, 0], beta_col_ref[0, 0]              # (C, heads)
    cs, beta = over(lambda h: cs_cols[:, h:h + 1]), over(lambda h: beta_cols[:, h:h + 1])   # (heads, C, 1): log gamma_i, beta_i
    # -- two heads side by side: lanes below C the pair's first head, from C on its second --------
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    col = lane % C
    both = lambda x: jnp.where(lane < C, over(lambda p: x[2 * p], heads // 2), over(lambda p: x[2 * p + 1], heads // 2))  # noqa: E731
    cs2, beta2 = both(cs), both(beta)
    cs_row, beta_row = cs_row_ref[0, :, pl.ds(c, 1), :], beta_row_ref[0, :, pl.ds(c, 1), :]   # (pairs, 1, 2C)
    rows2 = lambda x: x.reshape(heads // 2, 2 * C, dkp)                    # noqa: E731  a pair's rows stacked
    bf16_rows = functools.partial(jax.lax.dot_general, dimension_numbers=(_NT, ((0,), (0,))), preferred_element_type=jnp.float32)
    kk = _side_by_side(bf16_rows(rows2(k), rows2(k)), C)                    # K K^T a head, symmetric
    decay = jnp.exp(jnp.where(row >= col, cs2 - cs_row, -jnp.inf))          # Gamma_ij, j <= i; else 0
    a = jnp.where(row > col, beta2 * kk * decay, 0.0)
    nt = jnp.where(col > row, -beta_row * kk * jnp.exp(jnp.where(col > row, cs_row - cs2, -jnp.inf)), 0.0)   # -A^T
    qk2 = _side_by_side(bf16_rows(rows2(q), rows2(k)), C) * decay           # tril(Q K^T * Gamma)
    gamma = jnp.exp(cs)
    last = cs[:, C - 1:C, :]                                                # log gamma_C
    q32, k32, v32 = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    s = state[...]                                                          # (heads, dv, dkp)
    # What does not wait for the solve is issued before it: the matrix unit has nothing else to do during its steps.
    o = _bdot(q32 * gamma, s, _NT)                                          # (Q * gamma) S_0^T
    t2 = _unit_lower_inverse(a, nt, block)
    apart = lambda x: jnp.stack([x[h // 2][:, (h % 2) * C:(h % 2 + 1) * C] for h in range(heads)])   # noqa: E731
    t, qk = apart(t2), apart(qk2)                                           # (heads, C, C)
    wu = _bdot(t, jnp.concatenate([(beta * gamma) * k32, beta * v32], axis=2), _NN)   # W | U' (heads, C, dkp + dv)
    u = wu[:, :, dkp:] - _bdot(wu[:, :, :dkp], s, _NT)                      # U = U' - W S_0^T
    o = o + _bdot(qk, u, _NN)
    for h in range(heads):
        o_ref[0, :, h * dv:(h + 1) * dv] = o[h]
    whole = jnp.exp(jnp.broadcast_to(last, (heads, 1, dkp)))                # along the lanes first: one broadcast an axis
    state[...] = whole * s + _bdot(u, k32 * jnp.exp(last - cs), _TN)        # gamma_C S_0 + U^T (K gamma_C / gamma)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_ref[0] = state[:, :, :dk]


# Jitted so that the linear layers of a program share one trace and one lowering of the kernel (as ``cache_attention``).
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gated_delta_fused(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, s0: jax.Array,
                      chunk: int, interpret: bool = False):
    """``gated_delta_chunked``'s arguments and results: q, k ``(B, T, H, dk)``
    normalised, v ``(B, T, H, dv)``; g ``(B, T, H)`` float32 <= 0 and beta ``(B,
    T, H)`` float32, both 0 at padding; ``s0 (B, H, dv, dk)`` float32. -> (o
    ``(B, T, H, dv)`` float32, the state after the last step). The caller has
    asked ``delta_rule_applies``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    heads = _heads_a_step(C, H, dk, dv)
    if T % C or not _merges(C) or heads == 0:
        raise ValueError(f"gated_delta_fused: {T} steps are no whole chunks of {C}, the chunk's blocks of {SOLVE_BLOCK} "
                         f"do not merge pairwise, or one head's step exceeds the VMEM budget of {VMEM_BUDGET} bytes")
    nc, G, dkp = T // C, H // heads, _round_up(dk, _LANES)
    wide = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dkp - dk))).reshape(B, T, H * dkp)  # noqa: E731
    cs = jnp.cumsum(g.reshape(B, nc, C, H), axis=2).reshape(B, T, H)       # log gamma, from each chunk's start
    by_group = lambda x: jnp.moveaxis(x.reshape(B, T, G, heads), 2, 1)     # noqa: E731  (B, G, T, heads): time on sublanes
    by_pair = lambda x: jnp.transpose(x.reshape(B, nc, C, H // 2, 2), (0, 3, 1, 4, 2)).reshape(B, H // 2, nc, 2 * C)  # noqa: E731
    here = lambda b, g, c: (b, c, g)                                        # noqa: E731
    whole = lambda b, g, c: (b, g, 0, 0)                                    # noqa: E731
    o, s = pl.pallas_call(
        functools.partial(_kernel, heads=heads, dk=dk, dv=dv, block=SOLVE_BLOCK),
        grid=(B, G, nc),
        in_specs=[pl.BlockSpec((1, C, heads * dkp), here), pl.BlockSpec((1, C, heads * dkp), here),
                  pl.BlockSpec((1, C, heads * dv), here),
                  pl.BlockSpec((1, 1, C, heads), lambda b, g, c: (b, g, c, 0)),
                  pl.BlockSpec((1, 1, C, heads), lambda b, g, c: (b, g, c, 0)),
                  pl.BlockSpec((1, heads // 2, nc, 2 * C), whole), pl.BlockSpec((1, heads // 2, nc, 2 * C), whole),
                  pl.BlockSpec((1, heads, dv, dk), whole)],
        out_specs=[pl.BlockSpec((1, C, heads * dv), here), pl.BlockSpec((1, heads, dv, dk), whole)],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * dv), jnp.float32), jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dkp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(wide(q), wide(k), v.reshape(B, T, H * dv), by_group(cs), by_group(beta), by_pair(cs), by_pair(beta), s0)
    return o.reshape(B, T, H, dv), s
