"""Latent attention (MLA) as two decoders share it: ``models/longcat_flash`` (two
attentions a double layer, LoRA scales, plain rotary positions, every causal key)
and ``models/deepseek_v32`` (one a layer, no LoRA scales, YaRN's frequencies and a
softmax scale times ``mscale ** 2``, and only the keys an indexer selected).

    q         = (norm(x W_qa) * s_q) W_qb  -> H x [q_nope | q_r]
    [c | k_r] = x W_kva;   c = norm(c) * s_kv
    [k_nope | v] = c W_kvb -> H x [nope | v];   q_r, k_r = RoPE(q_r), RoPE(k_r)   (k_r one vector a token,
    shared by all heads; pairs interleaved (2i, 2i+1))
    score = (q_nope . k_nope + q_r . k_r) * scale, causal, and where a caller selects, over the selected keys alone
    out = softmax(score) v

**What a caller states**: the two LoRA scales (1 where the model has none), the
rotary frequencies (``frequencies(theta, n)``, or a scaled set), the softmax's
``scale`` (``None``: ``(nope + rope) ** -0.5``), and a selection (``None``: every
causal key). ``cfg`` is either decoder's configuration: what is read of it is
``kv_lora_rank``, the three head widths, ``qk_head_dim``, ``cache_row``,
``num_attention_heads``, ``rms_norm_eps`` and ``dtype``; the parameters are
``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``, ``kv_b`` and ``o`` with
the caller's suffix.

**The cache row** of a token is ``[c | RoPE(k_r)]`` (bfloat16); a slot's rows are
``(slots, cache_row, positions)`` with positions minor, which is how both
programs' products read them (with rows minor XLA copied every attention's whole
cache once a call: my chip run, PR 33). ``write_chunk`` and ``write_token`` put a
prefill's chunk and a decode step's token into any leaf so laid out (the
indexer's keys of ``deepseek_v32`` too).

**Two forms of one arithmetic.** Prefill *expands* a block of cache rows at a
time (``c W_kvb`` -> per-head keys and values, a running softmax between blocks):
on a TPU at widths that fill lane tiles one Pallas kernel
(``ops/pallas_mla_attention.py``), elsewhere ``core_expanded``, XLA's loop over
the blocks the call's deepest row attends. Decode *absorbs* ``W_kvb`` into the
query and the output (``core_absorbed``) and never expands a key.
``attend_chunk`` decides between kernel and loop when the program traces, from
backend, dtype and shapes, and notes it on the batcher's open span as ``mla``
= ``fused`` | ``expanded``; ``attend_token`` notes ``absorbed``.

**A selection** is what ``deepseek_v32`` brings: for a chunk, the index scores
``(B, T, blocks * T)`` float32 of each query against the positions of its slot
and each query's threshold ``(B, T)``: key ``s`` enters query ``t``'s softmax
where it is causal and ``index[t, s] >= threshold[t]`` (the masked form: every
block a row holds is still expanded and scored). For a token, a mask over the
slot's positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.models import decoders
from daft_tpu.models.decoders import mm, rms
from daft_tpu.ops import pallas_mla_attention

_LOW = float(np.finfo(np.float32).min)
#: Positions a decode step's write takes in and puts back around the one it sets: one lane tile.
_WRITE_POSITIONS = 128


def frequencies(theta: float, n: int):
    """Rotary frequencies of ``n`` dimensions: ``theta ** (-2i / n)``, i = 0 .. n / 2 - 1."""
    return theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)


def rope(x, positions, inv, interleaved: bool = True):
    """Rotary positions over the last axis by the frequencies ``inv`` (n / 2,):
    the pair (x[2i], x[2i+1]) (``interleaved``) or (x[i], x[i + n / 2]) turns by
    ``positions * inv[i]``. x (B, T, ..., n), positions (B, T). float32 in and out."""
    n = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * inv                   # (B, T, n / 2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if not interleaved:
        a, b = x[..., :n // 2], x[..., n // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)
    pairs = x.reshape(x.shape[:-1] + (n // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def project(cfg, p, s: str, x, positions, inv, q_scale: float = 1.0, kv_scale: float = 1.0):
    """x (B, T, d) normed -> (q (B, T, H, nope + rope) with its rotary part turned,
    the tokens' cache rows (B, T, cache_row), the normed low-rank query (B, T,
    q_lora_rank)), all bfloat16."""
    B, T, _ = x.shape
    eps = cfg.rms_norm_eps
    cq = (rms(mm(x, p["q_a" + s]), p["q_a_norm" + s], eps) * q_scale).astype(cfg.dtype)
    q = mm(cq, p["q_b" + s]).reshape(B, T, cfg.num_attention_heads, cfg.qk_head_dim)
    q = jnp.concatenate([q[..., :cfg.qk_nope_head_dim], rope(q[..., cfg.qk_nope_head_dim:], positions, inv)], -1)
    ckr = mm(x, p["kv_a" + s])
    c = rms(ckr[..., :cfg.kv_lora_rank], p["kv_a_norm" + s], eps) * kv_scale
    rows = jnp.concatenate([c, rope(ckr[..., cfg.kv_lora_rank:], positions, inv)], -1)
    return q.astype(cfg.dtype), rows.astype(cfg.dtype), cq


def kv_b(cfg, p, s: str):
    """``W_kvb`` as (latent, heads, nope + v)."""
    return p["kv_b" + s].reshape(cfg.kv_lora_rank, cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)


# ---------------------------------------------------------------------- #
# Writes into a leaf of (slots, values, positions)                        #
# ---------------------------------------------------------------------- #
def write_chunk(cache, rows, slots, starts, valid):
    """The chunk's valid rows (B, T, values) into the slots' positions ``starts ..``:
    padding, and a row that carries no prompt, leave the slot as it was."""
    B, T, width = rows.shape
    cols = jnp.swapaxes(rows, 1, 2)                                     # (B, values, T): positions are minor
    for b in range(B):
        at = (slots[b], 0, starts[b])
        old = jax.lax.dynamic_slice(cache, at, (1, width, T))
        cache = jax.lax.dynamic_update_slice(cache, jnp.where(valid[b][None, None, :], cols[b][None], old), at)
    return cache


def write_token(cache, rows, positions, active):
    """One token's row (slots, values) into every active slot at ``positions``.
    Slot by slot, in place, a lane tile of positions at a time: a window one
    position wide (as a gather, a scatter or a slice) makes XLA lay the whole
    cache out rows-minor for it, a copy of every row an attention a step (8 ms a
    step at 16 x 16,449 positions). An inactive slot keeps what it held."""
    B, width = rows.shape
    W = min(_WRITE_POSITIONS, cache.shape[2])
    for b in range(B):
        first = jnp.minimum(positions[b] // W * W, cache.shape[2] - W)
        old = jax.lax.dynamic_slice(cache, (b, 0, first), (1, width, W))
        here = (jnp.arange(W) == positions[b] - first) & active[b]
        cache = jax.lax.dynamic_update_slice(cache, jnp.where(here, rows[b][None, :, None], old), (b, 0, first))
    return cache


# ---------------------------------------------------------------------- #
# The core                                                                #
# ---------------------------------------------------------------------- #
def core_absorbed(cfg, w_kvb, q, cache, positions, scale: float = None, keep=None):
    """``W_kvb`` absorbed into the query and the output: q (B, T, H, nope + rope)
    against every position of ``cache`` (B, cache_row, S), causal by ``positions``
    (B, T) and, with ``keep`` (B, T, S), over the kept positions alone; no key or
    value is expanded. -> (B, T, H, v) float32."""
    nope, lat = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_lat = jnp.einsum("bthd,chd->bthc", q[..., :nope], w_kvb[..., :nope], preferred_element_type=jnp.float32)
    q_abs = jnp.concatenate([q_lat.astype(cfg.dtype), q[..., nope:]], -1)          # (B, T, H, cache_row)
    scores = jnp.einsum("bhtc,bcs->bhts", jnp.swapaxes(q_abs, 1, 2), cache, preferred_element_type=jnp.float32) \
        * (cfg.qk_head_dim ** -0.5 if scale is None else scale)
    seen = jnp.arange(cache.shape[2])[None, None, :] <= positions[:, :, None]       # (B, T, S)
    if keep is not None:
        seen = seen & keep
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, _LOW), axis=-1).astype(cfg.dtype)
    # over whole rows (the rotary part's columns are dropped after): no copy of the cache's latent columns
    o_lat = jnp.einsum("bhts,bcs->bhtc", probs, cache, preferred_element_type=jnp.float32)[..., :lat]
    return jnp.einsum("bhtc,chd->bthd", o_lat.astype(cfg.dtype), w_kvb[..., nope:], preferred_element_type=jnp.float32)


def core_expanded(cfg, w_kvb, q, block_of, blocks, positions, scale: float = None, keep_of=None):
    """One chunk of T queries a row over the ``blocks`` blocks of cache rows that
    reach its last position: each block's rows (``block_of(j)`` -> (B,
    cache_row, S), positions ``j S ..``) are expanded to per-head keys and values, a
    running softmax between blocks. q (B, T, H, nope + rope), positions (B, T);
    ``keep_of(j)`` -> (B, T, S): the block's keys each query keeps (while a query
    has kept no key yet it carries the mean of the values passed, which the first
    kept key's rescaling drops). -> (B, T, H, v) float32."""
    B, T, H, _ = q.shape
    nope, lat, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    scale = cfg.qk_head_dim ** -0.5 if scale is None else scale

    def body(j, carry):
        m, l, acc = carry                                               # (B, H, T), (B, H, T), (B, T, H, v)
        rows = block_of(j)
        S = rows.shape[2]
        kv = jnp.einsum("bcs,chd->bshd", rows[:, :lat], w_kvb, preferred_element_type=jnp.float32).astype(cfg.dtype)
        k_r = jnp.broadcast_to(jnp.swapaxes(rows[:, lat:], 1, 2)[:, :, None, :], (B, S, H, cfg.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :nope], k_r], -1)
        sc = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32) * scale
        seen = (j * S + jnp.arange(S))[None, None, :] <= positions[:, :, None]      # (B, T, S)
        if keep_of is not None:
            seen = seen & keep_of(j)
        sc = jnp.where(seen[:, None], sc, _LOW)
        m_new = jnp.maximum(m, sc.max(-1))
        w = jnp.exp(sc - m_new[..., None])
        shrink = jnp.exp(m - m_new)
        acc = acc * jnp.moveaxis(shrink, 1, 2)[..., None] + jnp.einsum(
            "bhts,bshd->bthd", w.astype(cfg.dtype), kv[..., nope:], preferred_element_type=jnp.float32)
        return m_new, l * shrink + w.sum(-1), acc

    init = (jnp.full((B, H, T), _LOW), jnp.zeros((B, H, T), jnp.float32), jnp.zeros((B, T, H, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
    return acc / jnp.moveaxis(l, 1, 2)[..., None]


def expanded_over_slots(cfg, w_kvb, q, kv, slots, starts, scale: float = None, keep=None):
    """``core_expanded`` for the rows ``slots`` of ``kv`` (slots, cache_row, S),
    each at the chunk that begins at ``starts``: every row over the blocks of T
    positions that the call's deepest row attends (each row of a call at its own
    depth: the blocks beyond a row's own positions are masked and weigh 0).
    ``keep`` (B, T, blocks * T): the positions each query keeps."""
    B, T = q.shape[:2]

    def block_of(j):
        return jnp.concatenate([jax.lax.dynamic_slice(kv, (slots[b], 0, j * T), (1, kv.shape[1], T)) for b in range(B)])

    keep_of = None if keep is None else (lambda j: jax.lax.dynamic_slice_in_dim(keep, j * T, T, axis=2))
    return core_expanded(cfg, w_kvb, q, block_of, jnp.max(starts) // T + 1, starts[:, None] + jnp.arange(T)[None, :],
                         scale, keep_of)


def attend_chunk(cfg, w_kvb, q, kv, slots, starts, lengths, scale: float = None, select=None, max_heads: int = None):
    """One chunk's queries q (B, T, H, nope + rope) over the rows ``slots`` of
    ``kv`` (the chunk's own rows already written). ``select``: ``None``, or (index
    scores (B, T, blocks * T) float32, thresholds (B, T)). -> (B, T, H, v)."""
    if pallas_mla_attention.mla_prefill_applies(q.shape, q.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                                                cfg.qk_rope_head_dim, cfg.v_head_dim):
        extra = {} if scale is None else {"scale": float(scale)}
        if select is not None:
            extra.update(index=select[0], threshold=select[1])
        if max_heads is not None:
            extra.update(max_heads=max_heads)
        out = pallas_mla_attention.mla_prefill_attention(q, kv, w_kvb, slots, starts, lengths,
                                                         nope=cfg.qk_nope_head_dim, **extra)
        decoders.note_on_serving_span("mla", "fused")
        return out
    keep = None if select is None else select[0] >= select[1][..., None]
    decoders.note_on_serving_span("mla", "expanded")
    return expanded_over_slots(cfg, w_kvb, q, kv, slots, starts, scale, keep)


def attend_token(cfg, w_kvb, q, kv, positions, scale: float = None, keep=None):
    """One token a slot: q (slots, 1, H, nope + rope) over each slot's own rows
    (its token's row already written); ``keep`` (slots, 1, S). -> (slots, 1, H, v)."""
    decoders.note_on_serving_span("mla", "absorbed")
    return core_absorbed(cfg, w_kvb, q, kv, positions[:, None], scale, keep)
