"""What the decoders behind ``prompt`` share: the record of them by exact name,
the rules their random parameters are drawn by, the attention over a slot's
cache of per-head keys and values, the causal conv with a carried tail, and the
expert layer's dispatch after the router.

**The record.** A decoder module enters its names here (``register``) with how
a name becomes a configuration, how parameters are drawn, the model class that
speaks the serving protocol (``models/serving.ServedModel``), and the options of
``prompt`` that cut it to one chip's share. ``MODULES`` is the one list of
those modules: importing this module imports them, so the record is full for
whoever reads it (``ai/flax_provider.FlaxPrompter``, the benchmark's entries),
and none of them names a decoder module. A decoder whose model declares
``block_length`` generates a block of tokens a step (``Decoder.blocks``), and
``prompt``'s answers then carry the pass that fixed each token. Adding a
decoder is its module, one entry in ``MODULES``, its tests and its benchmark
files.

**The attention over a slot's cache** (``attend_cache``): grouped queries over
the rows a slot holds as ``(slots, KV, S, hd)``, for a causal chunk, a chunk
whose queries see up to other positions than their own (a block-causal mask), a
token, or a block with its own keys. It alone chooses between
``ops/pallas_cache_attention.py`` (on a TPU, from
``cache_attention_applies``) and XLA's forms of the same arithmetic, which stay
the path on the CPU and at the tiny sizes and the tests' references:
``attention_chunk`` (a chunk over the blocks of cache rows that reach its last
position, a running softmax between them), ``attention_core`` /
``attention_step`` (every row a slot holds, laid ``(slots, S, KV, hd)``) and
``attention_block`` (a block over the committed rows and its own keys, in one
softmax). It notes the choice on the batcher's open span as ``attn``.
Projections, norms, positions and the cache's layout are each model's own; a
model whose rows are laid otherwise calls ``attention_core`` itself.

**The conv** (``conv_with_tail``): the causal depthwise conv of a recurrent
mixer over [carried tail | this chunk] with its SiLU, and the tail after each
row's last valid step.

**The dispatch** (``held_experts_part``): each model routes in its own way
(which scores, which weights, which experts cost nothing); what follows is the
same work: mark the assignments that reach an expert this chip holds, sort them
by held expert (absent experts and padded tokens behind every held group), the
two grouped products of the gated MLPs, and one gated gather back for each of
the top-k choices. On a TPU at widths that fill lane tiles the two products are
``ops/pallas_grouped_matmul`` (it visits the held rows alone); otherwise
``lax.ragged_dot``. The choice is made when the program traces, from backend
and shapes, and noted on the batcher's open span as ``moe``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Tuple

import importlib

import jax
import jax.numpy as jnp

from daft_tpu.errors import DaftValueError
from daft_tpu.ops import pallas_attention, pallas_cache_attention, pallas_cache_blocks
from daft_tpu.ops import pallas_grouped_matmul as gmm
from daft_tpu.profiling import open_device_span


# ---------------------------------------------------------------------- #
# The record of decoders                                                  #
# ---------------------------------------------------------------------- #
@dataclass(frozen=True)
class Decoder:
    #: ``from_name(name, **cut) -> config``.
    from_name: Callable[..., Any]
    #: ``init(config, seed) -> (model, params)``.
    init: Callable[..., Any]
    #: ``model(config)``: the serving protocol over a parameter tree.
    model: Callable[[Any], Any]
    #: Options of ``prompt`` that cut the published model to one chip's share.
    cut_options: Tuple[str, ...]

    @property
    def blocks(self) -> bool:
        """The model generates a block a step (it declares ``block_length`` and has a ``block_step``), not a token."""
        return self.model.block_length is not None

    @property
    def drafts(self) -> bool:
        """The model drafts tokens a step and verifies them (it declares ``draft_length``)."""
        return bool(self.model.draft_length)


DECODERS: Dict[str, Decoder] = {}


def register(names, **fields) -> None:
    """Enter ``names`` (published sizes and test sizes alike) under one ``Decoder``."""
    DECODERS.update(dict.fromkeys(names, Decoder(**fields)))


def cut_options() -> Tuple[str, ...]:
    """Every cut option some decoder on record takes, in a stable order."""
    return tuple(dict.fromkeys(k for d in DECODERS.values() for k in d.cut_options))


def check_shards(shards) -> None:
    """``shards``: (option, (rank, size), the whole it has to divide)."""
    for what, (rank, size), whole in shards:
        if not 0 <= rank < size or whole % size:
            raise DaftValueError(f"{what}={[rank, size]} does not divide {whole} evenly")


# ---------------------------------------------------------------------- #
# Numerics and the drawing rules                                          #
# ---------------------------------------------------------------------- #
def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def mm(x, w):
    """bfloat16 operands, float32 accumulation."""
    return jnp.einsum("...k,kn->...n", x, w, preferred_element_type=jnp.float32)


def as_drawn(x):
    """A draw as the generator gave it: inside a jitted program XLA would fold
    the scale that follows into the generator's own last product, and round
    otherwise than the same two steps taken one by one."""
    return jax.lax.optimization_barrier(x)


def draw(key, shape, rule: str, gain: float = 1.0):
    """One tensor in float32, by rule. ``matrix``: normal, std fan_in ** -0.5
    (each product keeps its input's scale; times ``gain`` where the input does
    not arrive at that scale); ``norm``: 1 + 0.1 normal; ``bias``:
    0.1 normal; ``router_bias``: 1e-4 normal (small and not zero: it moves a
    choice between near ties and no weight); ``A_log``: log U(1, 16);
    ``dt_bias``: the inverse softplus of a delta log-uniform in [1e-3, 1e-1]
    (as Mamba-2 initialises both)."""
    if rule in ("matrix", "norm", "bias", "router_bias"):
        n = as_drawn(jax.random.normal(key, shape, jnp.float32))
        if rule == "matrix":
            return n * (shape[0] ** -0.5 * gain)
        return 1e-4 * n if rule == "router_bias" else 0.1 * n + (rule == "norm")
    if rule == "A_log":
        return jnp.log(as_drawn(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)))
    if rule == "dt_bias":
        dt = jnp.exp(as_drawn(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(rule)


def draw_row_blocks(key, first_block: int, blocks: int, block_rows: int, width: int):
    """``blocks`` blocks of ``block_rows`` standard-normal rows, block b from the
    key folded with its global index: a slice's rows are the whole table's
    whatever the split. -> (blocks * block_rows, width) float32, as drawn."""
    ids = first_block + jnp.arange(blocks)
    rows = as_drawn(jax.vmap(lambda b: jax.random.normal(
        jax.random.fold_in(key, b), (block_rows, width), jnp.float32))(ids))
    return rows.reshape(blocks * block_rows, width)


# ---------------------------------------------------------------------- #
# What the serving protocol asks of every decoder alike                   #
# ---------------------------------------------------------------------- #
def note_on_serving_span(key: str, value: str) -> None:
    """A choice of path made while a program traces, as a counter of the batcher's
    open span (``ContinuousBatcher._repeat_noted`` repeats it on later calls)."""
    for name in ("serve.prefill", "serve.decode_step"):
        span = open_device_span(name)
        if span is not None:
            span.count[key] = value


def add_counts(a, b):
    """A model's counts of its expert layers so far and one layer's more: sums, but the fullest expert's load, a maximum."""
    if a is None:
        return b
    return {k: jnp.maximum(a[k], b[k]) if k == "max_expert_load" else a[k] + b[k] for k in a}


# ---------------------------------------------------------------------- #
# Attention over a cache of per-head keys and values                      #
# ---------------------------------------------------------------------- #
_LOW = jnp.finfo(jnp.float32).min


def attention_step(q, rk, rv, pos, scale: float, dtype):
    """One row, one token: q (1, KV, R, hd) over all its cache rows rk, rv (S, KV, hd), causal by ``pos`` (1,)."""
    scores = jnp.einsum("tgrd,sgd->grts", q, rk, preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(rk.shape[0])[None, :] <= pos[:, None]         # (1, S): causal over the cache
    probs = jax.nn.softmax(jnp.where(seen[None, None], scores, _LOW), axis=-1).astype(dtype)
    return jnp.einsum("grts,sgd->tgrd", probs, rv, preferred_element_type=jnp.float32)


def attention_chunk(q, block_of, positions, blocks, scale: float, dtype):
    """A chunk of T queries a row, q (B, T, KV, R, hd) at ``positions`` (B, T),
    over the ``blocks`` blocks of T cache rows that reach the deepest row's last
    position (``block_of(j)`` -> that block's keys and values of every row, (B,
    T, KV, hd) each), a running softmax between them: the work follows the
    prefix held, not the positions a slot could hold. -> (B, T, KV, R, hd) float32."""
    B, T, KV, R, hd = q.shape

    def body(j, carry):
        m, l, acc = carry                                           # (B, KV, R, T), (B, KV, R, T), (B, T, KV, R, hd)
        kb, vb = block_of(j)
        sc = jnp.einsum("btgrd,bsgd->bgrts", q, kb, preferred_element_type=jnp.float32) * scale
        seen = (j * T + jnp.arange(T))[None, None, :] <= positions[:, :, None]      # (B, T, S)
        sc = jnp.where(seen[:, None, None], sc, _LOW)
        m_new = jnp.maximum(m, sc.max(-1))
        w = jnp.exp(sc - m_new[..., None])
        rescale = jnp.exp(m - m_new)
        acc = acc * jnp.moveaxis(rescale, 3, 1)[..., None] + jnp.einsum(
            "bgrts,bsgd->btgrd", w.astype(dtype), vb, preferred_element_type=jnp.float32)
        return m_new, l * rescale + w.sum(-1), acc

    init = (jnp.full((B, KV, R, T), _LOW), jnp.zeros((B, KV, R, T), jnp.float32),
            jnp.zeros((B, T, KV, R, hd), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
    return acc / jnp.moveaxis(l, 3, 1)[..., None]


def attention_core(q, rows_k, rows_v, positions, scale: float, dtype):
    """q (B, T, KV, R, hd) over these rows' cache rows_k, rows_v (B, S, KV, hd),
    the new tokens' rows already written; positions (B, T). One token a row
    attends every row held; a chunk attends block by block (each row of a call at
    its own depth: blocks beyond a row's own weigh 0). -> (B, T, KV, R, hd) float32."""
    T = q.shape[1]
    if T == 1:
        return jax.vmap(lambda q, rk, rv, pos: attention_step(q, rk, rv, pos, scale, dtype))(
            q, rows_k, rows_v, positions)
    rows = lambda j: (jax.lax.dynamic_slice_in_dim(rows_k, j * T, T, axis=1),  # noqa: E731
                      jax.lax.dynamic_slice_in_dim(rows_v, j * T, T, axis=1))
    return attention_chunk(q, rows, positions, jnp.max(positions[:, 0]) // T + 1, scale, dtype)


def attention_block(q, ck, cv, k, v, starts, scale: float, dtype):
    """A block of T queries a slot, q (B, T, KV, R, hd) from ``starts`` (B,), over the slot's committed rows ck, cv
    (B, KV, S, hd; rows s < start seen) and the block's own keys and values k, v (B, T, KV, hd; all seen), in one
    softmax: the block-causal mask. -> (B, T, KV, R, hd) float32."""
    if not pallas_attention.backend_is_tpu():  # the CPU's backend has no bfloat16 product of T > 1 rows
        ck, cv, k, v = (x.astype(jnp.float32) for x in (ck, cv, k, v))
    S = ck.shape[2]
    held = jnp.einsum("btgrd,bgsd->bgrts", q, ck, preferred_element_type=jnp.float32) * scale
    held = jnp.where((jnp.arange(S)[None, :] < starts[:, None])[:, None, None, None], held, jnp.finfo(jnp.float32).min)
    own = jnp.einsum("btgrd,bugd->bgrtu", q, k, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.concatenate([held, own], axis=-1), axis=-1).astype(dtype)
    return (jnp.einsum("bgrts,bgsd->btgrd", probs[..., :S], cv, preferred_element_type=jnp.float32)
            + jnp.einsum("bgrtu,bugd->btgrd", probs[..., S:], v, preferred_element_type=jnp.float32))


def attend_cache(q, cache_k, cache_v, starts, scale: float, dtype, *, slots=None, lengths=None, sees=None,
                 active=None, view=None, own=None):
    """Grouped queries q (B, T, KV, R, hd) over cache rows ``(slots, KV, S, hd)`` as the state keeps them (a chunk's
    or a token's new rows already written). What is attended:

    - **a chunk** (``slots``, ``starts``, ``lengths`` of each row of the call): query t of row b at ``starts[b] + t``
      over the blocks of T rows up to it, causal; with ``sees`` (B, T) each query sees up to that position instead
      (a block-causal chunk: its block's last), which the kernel has no form of;
    - **a token** a slot (``starts`` the slots' positions, ``active``): every row a slot holds, which XLA reads
      through ``view``, the rows laid (slots, S, KV, hd) as the caller wrote them;
    - **a block** a slot with its ``own`` keys and values (B, T, KV, hd) (``starts`` the blocks' first positions,
      ``active``): the committed rows below the start and the block's own keys, in one softmax.

    On a TPU the kernel takes what ``cache_attention_applies`` accepts (an inactive slot's queries come back as
    zeros), else XLA's form; a kernel that fails to trace or lower fails the program. On the CPU a chunk's blocks
    are widened to float32, as its backend has no bfloat16 product for them (the same numbers: a bfloat16 value is
    its float32 value, and the products accumulate in float32 either way). The choice is noted on the batcher's
    open span as ``attn`` = ``fused`` | ``xla``, where there was one. -> (B, T, KV, R, hd)."""
    fused = sees is None and pallas_cache_attention.cache_attention_applies(q.shape, cache_k.shape, dtype,
                                                                             own=own is not None)
    if sees is None:
        note_on_serving_span("attn", "fused" if fused else "xla")
    if fused:
        if slots is None:  # a token or a block: every slot in order, the active ones with a query
            slots, lengths = jnp.arange(q.shape[0]), active.astype(jnp.int32)
        extra = {} if own is None else {"own": own}
        return pallas_cache_attention.cache_attention(q, cache_k, cache_v, slots, starts, lengths, scale=scale, **extra)
    if own is not None:  # every slot over every position it holds and its block
        return attention_block(q, cache_k, cache_v, *own, starts, scale, dtype)
    if slots is None:  # every slot over every position it could hold
        return attention_core(q, *view, starts[:, None], scale, dtype)
    # XLA's loop walks the blocks that the call's deepest row attends, for every row (rows of a call stand at unlike
    # depths: blocks beyond a row's own weigh 0).
    T = q.shape[1]

    def block(cache, j):
        rows = pallas_cache_blocks.read_blocks_xla(cache, slots, j * T, T)
        return rows if pallas_attention.backend_is_tpu() else rows.astype(jnp.float32)

    if sees is None:
        sees = starts[:, None] + jnp.arange(T)[None, :]
    return attention_chunk(q, lambda j: (block(cache_k, j), block(cache_v, j)), sees, jnp.max(starts) // T + 1,
                           scale, dtype)


# ---------------------------------------------------------------------- #
# The causal conv of a recurrent mixer                                    #
# ---------------------------------------------------------------------- #
def conv_with_tail(tail, x, w, bias, lengths, dtype):
    """Causal depthwise conv over [carried tail | this chunk], then SiLU: tail
    (B, K - 1, C) the last inputs before the chunk, x (B, T, C), w (K, C), bias
    (C,) or None, lengths (B,) valid steps of each row. -> (silu(conv) (B, T, C)
    as ``dtype``, the tail after each row's last *valid* step: rows length ..
    length + K - 2 of the padded input, so a row without a valid step keeps its
    tail)."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.concatenate([tail, x], axis=1)                     # (B, K-1+T, C)
    w = w.astype(jnp.float32)
    conv = sum(padded[:, k:k + T].astype(jnp.float32) * w[k] for k in range(K))
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    out = jax.nn.silu(conv).astype(dtype)
    tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, K - 1, axis=0))(padded, lengths)
    return out, tail


# ---------------------------------------------------------------------- #
# Experts                                                                 #
# ---------------------------------------------------------------------- #
def gated_mlp(x, w_in, w_out, dtype):
    """x times ``w_in``, ``silu(a) * b`` in float32, one cast to ``dtype``, times
    ``w_out``. The gated activation is written once as its own buffer: left to
    fuse, XLA recomputed it inside the down projection, tile by tile, from the
    first product's float32 output, and LongCat-Flash's down projections ran at
    ≈ 20-65% of the MXU in prefill on a TPU v5e."""
    a, b = jnp.split(mm(x, w_in), 2, axis=-1)
    return mm(jax.lax.optimization_barrier((jax.nn.silu(a) * b).astype(dtype)), w_out)


def grouped_mlp(x, w_in, w_out, sizes, dtype):
    """The held experts' gated MLPs over x (rows, d) sorted by expert: group e's
    rows times ``w_in[e]``, ``silu(a) * b`` in float32, one cast to ``dtype``,
    times ``w_out[e]``. On a TPU at widths that fill lane tiles both products are
    ``ops/pallas_grouped_matmul`` (it visits only the rows ``sizes`` covers, and
    its first product applies the gate before it writes); otherwise XLA's
    ``ragged_dot`` over every row. Rows behind the groups come back undefined.
    The choice is made here, when the program traces, and noted on the batcher's
    open span as ``moe``."""
    f = w_out.shape[1]
    grouped = (gmm.grouped_matmul_applies(x.shape, w_in.shape, x.dtype, gated=True)
               and gmm.grouped_matmul_applies((x.shape[0], f), w_out.shape, dtype))
    note_on_serving_span("moe", "grouped" if grouped else "xla")
    if grouped:  # a kernel that fails to trace or lower fails the program
        return gmm.grouped_matmul(gmm.grouped_matmul(x, w_in, sizes, gated=True).astype(dtype), w_out, sizes)
    a, b = jnp.split(jax.lax.ragged_dot(x, w_in, sizes, preferred_element_type=jnp.float32), 2, axis=-1)
    return jax.lax.ragged_dot((jax.nn.silu(a) * b).astype(dtype), w_out, sizes, preferred_element_type=dtype)


def held_experts_part(v, idx, gates, valid, first_expert: int, w_in, w_out, dtype):
    """What the experts held here add for the router's choices: v (n, d) normed,
    idx (n, k) the chosen experts' global ids, gates (n, k) float32 their
    weights, valid (n,), ``w_in`` / ``w_out`` stacked over the held experts
    ``first_expert ..``. -> (sum over held choices of gate x expert(v) (n, d)
    float32, held (n, k): the choices that reached a held expert, sizes: rows of
    each held expert)."""
    n, d = v.shape
    k, held_n = idx.shape[1], w_in.shape[0]
    local = idx - first_expert
    held = (local >= 0) & (local < held_n) & valid[:, None]
    group = jnp.where(held, local, held_n).reshape(-1)          # absent: behind every held group
    order = jnp.argsort(group, stable=True)
    token = order // k
    sizes = jnp.bincount(group, length=held_n + 1)[:held_n].astype(jnp.int32)
    x = v[token]                                                # (n k, d), sorted by held expert
    y = grouped_mlp(x, w_in, w_out, sizes, dtype)
    # Back to the tokens: one gather of n rows for each of the k choices, gated and summed as it goes
    # (a scatter-add of rows is serial on the chip, and an (n, k, d) block pads k to a tile: 8.6 and 7.7 ms
    # a 2,048-token layer against 5.9 this way; my chip run, PR 29). Rows behind the held groups hold
    # whatever the product left there and are dropped by their gate of 0.
    back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k)).reshape(n, k)
    g = jnp.where(held, gates, 0.0)
    routed = jnp.zeros((n, d), jnp.float32)
    for j in range(k):
        gj = g[:, j, None]
        routed = routed + jnp.where(gj > 0, y[back[:, j]].astype(jnp.float32) * gj, 0.0)
    return routed, held, sizes


# ---------------------------------------------------------------------- #
# The decoders on record                                                  #
# ---------------------------------------------------------------------- #
#: The modules that enter their names in ``DECODERS`` (each calls ``register`` when imported).
MODULES = ("granite_hybrid", "longcat_flash", "olmo_hybrid", "deepseek_v32", "sdar_moe", "pangu_ultra_moe")
for _module in MODULES:
    importlib.import_module(f"daft_tpu.models.{_module}")
