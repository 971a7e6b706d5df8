"""SDAR-30B-A3B-Chat decoder (``sdar_moe``): Qwen3-MoE layers (grouped-query
attention whose queries and keys are RMS-normed a head at a time, RoPE on
halves, 128 experts with top-8 gates renormalised) that **generate by diffusion
over blocks**. RMSNorm eps 1e-6, no bias, untied embedding and head. ``h`` is
the residual stream (float32 here; products take bfloat16 operands and
accumulate in float32):

    h = E[tok]
    h += W_o Attn(RoPE(RMSNorm_hd(W_q u)), RoPE(RMSNorm_hd(W_k u)), W_v u),   u = RMSNorm(h)
    h += sum_{e in top 8} g_e W2_e (silu(W1_e v) * W3_e v),   v = RMSNorm(h),
         g = softmax(v W_g) over 128 (float32), the chosen 8 divided by their sum (``norm_topk_prob``)
    logits = RMSNorm(h) @ W_head

    Attn: 32 query heads in groups of 8 over 4 key/value heads of 128, scale 128 ** -0.5, RoPE with theta 1e6 at
          true positions, the pair (x[i], x[i + 64]) turned by position * theta ** (-2i / 128); **block-causal**:
          query t sees key s iff floor(s / L) <= floor(t / L), L = ``block_length``

**Generation** (``block_step``; the batcher's decode program for a model with a
``block_length``, ``models/serving.py``). A slot holds its committed rows and a
block of L positions: the prompt's tail (P mod L tokens, fixed) and the mask id
elsewhere. A step runs ``denoising_steps`` forwards of every slot's block over
its committed rows; in each, x = argmax of a position's logits and c = its
softmax probability (the float32 log-softmax's largest value), and of the
positions still masked the L / steps with the largest c are fixed to x (static
low-confidence remasking; ties to the earlier position). One more forward of the
finished block writes its rows: the commit. Every query of a block attends its
slot's committed rows (the positions before the block) and the block's own L
keys, in one softmax (``decoders.attend_cache``); a pass writes nothing, so the cache is
no part of the passes' loop. The step returns every block's tokens, each fixed
token's log c and the pass that fixed it.

**Prefill** writes only whole blocks (the batcher hands ``P - P mod L`` tokens
in chunks of whole blocks) through ``decoders.attend_cache``, each query seeing
up to its block's last position (``block_ends``), on XLA's loop on every backend:
the kernel has no block-causal chunk. Positions stay true for RoPE.

**The cut.** ``num_layers`` keeps the first layers (the period is one layer);
every width, all 128 experts and the whole vocabulary are as published.

**Serving protocol** (``models/serving.ServedModel``): ``init_state``,
``prefill``, ``block_step`` (in place of ``decode``), ``prefill_counts``
(``tail_tokens``); ``block_length`` (a prefill chunk is whole blocks),
``denoising_steps`` and ``mask_id`` are the configuration's. Slot state is
``{"k", "v" (slots, heads, positions in whole tiles of ``ROW_TILE``,
head_dim)}`` a layer (the layout of ``models/olmo_hybrid.py``: no axis padded to
a tile). On a TPU the block step reads, in that layout, only the blocks of rows
below each slot's position (``ops/pallas_cache_attention.py``, its block mode: a
key/value head's 4 x 8 queries one tile); elsewhere it attends every position a
slot holds, densely (``decoders.attention_block``). The prefill writes its chunk
with ``ops/pallas_cache_blocks``.

Plain functions over a parameter tree, drawn tensor by tensor on the device in
bfloat16; ``jax.named_scope`` names the parts (``attn_proj``, ``attn_core``,
``router``, ``experts``, ``unmask``, ``head``) for the device trace. While the
block program traces, the model notes ``unmask`` = ``static`` and ``attn`` =
``fused`` | ``xla`` (the block step's core) on the batcher's open span.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders
from daft_tpu.models.decoders import draw, mm, rms
from daft_tpu.models.serving import ServedModel
from daft_tpu.ops import pallas_cache_blocks

#: Published sizes by exact model name (``config.json`` of the source). Kept as data: no substring rule.
#: ``block_length``, ``denoising_steps`` and ``mask_id`` are not in the published file (the catalog lists block
#: length and noise schedule as not given): SDAR's generation settings as assumed (the benchmark configuration's
#: ``assumed``).
PUBLISHED: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
    "SDAR-30B-A3B-Chat": dict(
        vocab_size=151936, hidden_size=2048, num_hidden_layers=48, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=1e6, block_length=4, denoising_steps=4, mask_id=151669),
}
#: Not published: the same mechanisms at a width the CPU tests can afford.
TEST_SIZES: Dict[str, Dict[str, Any]] = {
    "sdar-moe-tiny": dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=1e6, block_length=4, denoising_steps=4, mask_id=511),
}
#: Every name ``from_name`` resolves.
SIZES = {**PUBLISHED, **TEST_SIZES}
#: Options of ``prompt`` that cut a published model to one chip's share.
CUT_OPTIONS = ("num_layers",)
#: Embedding and head are drawn in blocks of this many rows (the drawing rule the decoders share).
EMBED_BLOCK_ROWS = 64
#: Standard deviation of the embedding's rows (the head is untied and drawn at fan-in scale: logits spread ~1).
EMBED_STD = 1.0
#: Not published: a drawing rule. Each expert's W2 is drawn at fan-in scale times this. A choice of the router weighs
#: ~1/8 (~1/2 at the tiny size), and the 8th and 9th of 128 softmax outputs flip on bfloat16 rounding in a few
#: token-layers in a hundred; at fan-in scale a flipped expert moves a token's log-probabilities by up to ~0.25, as far
#: as the fp8 control moves them (a CPU run at hidden 512 with this router, 3 layers: the program's largest gap
#: 0.22-0.26 against the control's 0.29-0.46 on four seeds; at 0.1 the program's 0.010-0.027 against 0.13-0.16; PR 43).
EXPERT_GAIN = 0.1
#: A slot's key/value rows are held in whole multiples of this many positions (``olmo_hybrid.ROW_TILE`` has why).
ROW_TILE = 16


@dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    block_length: int
    denoising_steps: int
    mask_id: int
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_name(name: str, num_layers: int = None) -> "SdarMoeConfig":
        if name not in SIZES:
            raise DaftValueError(
                f"unknown SDAR decoder {name!r}; the published sizes on record are {sorted(PUBLISHED)}")
        cfg = SdarMoeConfig(**SIZES[name])
        layers = int(num_layers or cfg.num_hidden_layers)
        if not 0 < layers <= cfg.num_hidden_layers:
            raise DaftValueError(f"num_layers={layers} is outside the published {name!r} ({cfg.num_hidden_layers})")
        return replace(cfg, num_hidden_layers=layers)


# ---------------------------------------------------------------------- #
# Parameters: drawn tensor by tensor on the device, bfloat16              #
# ---------------------------------------------------------------------- #
def tensor_specs(cfg: SdarMoeConfig) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape of one, rule).
    ``benchmark/reference/sdar_moe.py`` states the same rules."""
    d, H, KV, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    f, E = cfg.moe_intermediate_size, cfg.num_experts
    return [("attn_norm", (d,), "norm"), ("q", (d, H * hd), "matrix"), ("k", (d, KV * hd), "matrix"),
            ("v", (d, KV * hd), "matrix"), ("q_norm", (hd,), "norm"), ("k_norm", (hd,), "norm"),
            ("o", (H * hd, d), "matrix"), ("moe_norm", (d,), "norm"), ("router", (d, E), "matrix"),
            ("w_in", (d, 2 * f), "experts"), ("w_out", (f, d), "experts_out")]


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_tensor(cfg: SdarMoeConfig, key, shape: tuple, rule: str):
    """One tensor (or one stack of every expert's, each from its own id, one expert in float32 at a time)."""
    if rule in ("experts", "experts_out"):
        gain = EXPERT_GAIN if rule == "experts_out" else 1.0
        return jax.lax.map(lambda e: draw(jax.random.fold_in(key, e), shape, "matrix", gain).astype(cfg.dtype),
                           jnp.arange(cfg.num_experts))
    return draw(key, shape, rule).astype(cfg.dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_rows(cfg: SdarMoeConfig, key, std: float):
    """The embedding or the head, a few blocks at a time: drawn whole, the float32 rows would be 1.2 GB of
    temporaries that a TPU keeps reserved while the program stays loaded (``olmo_hybrid._init_rows``)."""
    blocks = cfg.vocab_size // EMBED_BLOCK_ROWS
    per = max(n for n in range(1, 33) if blocks % n == 0)

    def group(g):
        rows = decoders.draw_row_blocks(key, g * per, per, EMBED_BLOCK_ROWS, cfg.hidden_size)
        return (rows * std).astype(cfg.dtype)

    return jax.lax.map(group, jnp.arange(blocks // per)).reshape(cfg.vocab_size, cfg.hidden_size)


def _init_layer(cfg: SdarMoeConfig, key):
    """A layer's tensors as drawn, then the three projections of one input side by side (``qkv``)."""
    p = {name: _init_tensor(cfg, jax.random.fold_in(key, j), shape, rule)
         for j, (name, shape, rule) in enumerate(tensor_specs(cfg))}
    p["qkv"] = jnp.concatenate([p.pop("q"), p.pop("k"), p.pop("v")], axis=1)
    return p


def init_sdar_params(cfg: SdarMoeConfig, seed: int = 0):
    """-> (model, params). Key 0 of the seed draws the embedding (0), the final
    norm (1) and the head (2); key i + 1 layer i, tensor j of ``tensor_specs``
    from the layer's key folded with j, expert e's from that folded with e."""
    root = jax.random.PRNGKey(seed)
    k0 = jax.random.fold_in(root, 0)
    params = {"embed": _init_rows(cfg, jax.random.fold_in(k0, 0), EMBED_STD),
              "final_norm": _init_tensor(cfg, jax.random.fold_in(k0, 1), (cfg.hidden_size,), "norm"),
              "head": _init_rows(cfg, jax.random.fold_in(k0, 2), cfg.hidden_size ** -0.5),
              "layers": [_init_layer(cfg, jax.random.fold_in(root, i + 1)) for i in range(cfg.num_hidden_layers)]}
    return SdarMoeLM(cfg), params


# ---------------------------------------------------------------------- #
# Attention: QK-norm a head, RoPE on halves, block-causal                 #
# ---------------------------------------------------------------------- #
def inverse_frequencies(cfg: SdarMoeConfig) -> np.ndarray:
    """theta ** (-2i / head_dim), i < head_dim / 2: computed in float64, rounded to float32."""
    return (cfg.rope_theta ** (-np.arange(0, cfg.head_dim, 2, dtype=np.float64) / cfg.head_dim)).astype(np.float32)


def rope(x, positions, inv):
    """x (B, T, heads, hd) float32 at positions (B, T): the pair (x[i], x[i + hd / 2]) turned by position * inv[i]."""
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _project(cfg: SdarMoeConfig, p, u, positions):
    """u (B, T, d) -> q (B, T, KV, R, hd), k, v (B, T, KV, hd) as ``cfg.dtype``: q and k normed a head, then turned."""
    B, T, _ = u.shape
    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = jnp.split(mm(u, p["qkv"]), [H * hd, (H + KV) * hd], axis=-1)
    inv = inverse_frequencies(cfg)
    q = rope(rms(q.reshape(B, T, H, hd), p["q_norm"], cfg.rms_norm_eps), positions, inv)
    k = rope(rms(k.reshape(B, T, KV, hd), p["k_norm"], cfg.rms_norm_eps), positions, inv)
    return (q.astype(cfg.dtype).reshape(B, T, KV, H // KV, hd), k.astype(cfg.dtype),
            v.astype(cfg.dtype).reshape(B, T, KV, hd))


def _attn_out(cfg: SdarMoeConfig, p, out):
    B, T = out.shape[:2]
    return mm(out.astype(cfg.dtype).reshape(B, T, -1), p["o"])


def block_ends(positions, L: int):
    """Each position's block's last position: a query there sees every key up to it (the block-causal mask)."""
    return positions // L * L + L - 1


def _attn_prefill(cfg: SdarMoeConfig, p, u, st, slots, starts, lengths):
    """One chunk of whole blocks for the rows ``slots`` of the cache st {"k", "v" (slots, KV, S, hd)}: write the
    chunk's valid rows in place, then attend over the blocks held, block-causally. -> (out (B, T, d) float32, st)."""
    T, hd = u.shape[1], cfg.head_dim
    positions = starts[:, None] + jnp.arange(T)[None, :]
    with jax.named_scope("attn_proj"):
        q, k, v = _project(cfg, p, u, positions)
    with jax.named_scope("attn_core"):
        cache_k = pallas_cache_blocks.write_blocks(st["k"], k, slots, starts, lengths)
        cache_v = pallas_cache_blocks.write_blocks(st["v"], v, slots, starts, lengths)
        out = decoders.attend_cache(q, cache_k, cache_v, starts, hd ** -0.5, cfg.dtype, slots=slots, lengths=lengths,
                                    sees=block_ends(positions, cfg.block_length))
    with jax.named_scope("attn_proj"):
        return _attn_out(cfg, p, out), {"k": cache_k, "v": cache_v}


def _attn_block(cfg: SdarMoeConfig, p, u, st, positions, active, commit: bool):
    """One block a slot: u (slots, L, d) at ``positions`` (slots,: the block's first) -> (out, st). Every query
    attends its slot's committed rows and the block (``decoders.attend_cache``); the ``commit`` then writes the
    block's rows where the block lies (an inactive slot keeps what it held), a pass writes nothing."""
    B, T = u.shape[:2]
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    at = positions[:, None] + jnp.arange(T)[None, :]
    with jax.named_scope("attn_proj"):
        q, k, v = _project(cfg, p, u, at)
    with jax.named_scope("attn_core"):
        out = decoders.attend_cache(q, st["k"], st["v"], positions, hd ** -0.5, cfg.dtype, active=active, own=(k, v))
        if commit:
            S = st["k"].shape[2]
            where = jnp.where(active[:, None], at, S)[:, None, :]          # (B, 1, T); out of range: dropped
            rows, heads = jnp.arange(B)[:, None, None], jnp.arange(KV)[None, :, None]
            st = {name: st[name].at[rows, heads, where].set(jnp.swapaxes(new, 1, 2), mode="drop")
                  for name, new in (("k", k), ("v", v))}
    with jax.named_scope("attn_proj"):
        return _attn_out(cfg, p, out), st


# ---------------------------------------------------------------------- #
# Experts and the choice of a pass                                        #
# ---------------------------------------------------------------------- #
def _moe(cfg: SdarMoeConfig, p, v, valid):
    """v (n, d) normed; valid (n,). -> (every chosen expert's part (n, d) float32, counts)."""
    k = cfg.num_experts_per_tok
    with jax.named_scope("router"):
        top, idx = jax.lax.top_k(jax.nn.softmax(mm(v, p["router"]), axis=-1), k)     # float32 over all experts
        gates = top / jnp.sum(top, axis=-1, keepdims=True) if cfg.norm_topk_prob else top
    with jax.named_scope("experts"):
        y, held, sizes = decoders.held_experts_part(v, idx, gates, valid, 0, p["w_in"], p["w_out"], cfg.dtype)
    return y, {"assignments": jnp.sum(valid) * k, "held_assignments": jnp.sum(held), "max_expert_load": jnp.max(sizes),
               "experts_reached": jnp.sum(sizes > 0)}


def unmask(logits, tokens, masked, logprob, step, p, k: int):
    """One pass's choice, float32: each position's guess x = argmax and log c = its log-softmax value; of the
    positions still ``masked`` (B, L) the ``k`` of largest c (ties to the earlier; fewer where fewer are left) are
    fixed to x, with log c and the pass ``p``. -> (tokens, masked, logprob, step)."""
    m = jnp.max(logits, axis=-1)
    guess = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    conf = -jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))              # the log-softmax's largest value
    top, idx = jax.lax.top_k(jnp.where(masked, conf, -jnp.inf), k)
    chosen = jnp.zeros_like(masked).at[jnp.arange(masked.shape[0])[:, None], idx].set(top > -jnp.inf)
    return (jnp.where(chosen, guess, tokens), masked & ~chosen, jnp.where(chosen, conf, logprob),
            jnp.where(chosen, p, step))


# ---------------------------------------------------------------------- #
# The model                                                               #
# ---------------------------------------------------------------------- #
class SdarMoeLM(ServedModel):
    """The decoder over a parameter tree, as the serving protocol sees it."""

    def __init__(self, cfg: SdarMoeConfig):
        self.cfg = cfg

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    @property
    def block_length(self) -> int:
        return self.cfg.block_length

    @property
    def denoising_steps(self) -> int:
        return self.cfg.denoising_steps

    @property
    def mask_id(self) -> int:
        return self.cfg.mask_id

    @property
    def prefill_multiple(self) -> int:
        """A prefill chunk is whole blocks."""
        return self.cfg.block_length

    def init_state(self, slots: int, positions: int):
        cfg = self.cfg
        kv = (slots, cfg.num_key_value_heads, -(-positions // ROW_TILE) * ROW_TILE, cfg.head_dim)
        return [{"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype)} for _ in range(cfg.num_hidden_layers)]

    def prefill_counts(self, lengths) -> Dict[str, int]:
        """``tail_tokens``: the prompt tokens left to each slot's first block, beside the whole blocks prefilled."""
        return {"tail_tokens": int((np.asarray(lengths, np.int64) % self.cfg.block_length).sum())}

    def _forward(self, params, state, tokens, valid, attend):
        """The layers; ``attend(p, u, st)`` -> (out, st) is this program's attention. -> (h (B, T, d) float32,
        state, counts summed over the layers)."""
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.rms_norm_eps
        h = params["embed"][tokens].astype(jnp.float32)
        new_state, counts = [], None
        for i, (p, st) in enumerate(zip(params["layers"], state)):
            with jax.named_scope(f"layer_{i}"):
                out, st = attend(p, rms(h, p["attn_norm"], eps).astype(cfg.dtype), st)
                new_state.append(st)
                h = h + out
                y, c = _moe(cfg, p, rms(h, p["moe_norm"], eps).astype(cfg.dtype).reshape(B * T, -1), valid.reshape(-1))
                counts = decoders.add_counts(counts, c)
                h = h + y.reshape(B, T, -1)
        return h, new_state, counts

    def _head(self, params, h):
        with jax.named_scope("head"):
            h = rms(h, params["final_norm"], self.cfg.rms_norm_eps).astype(self.cfg.dtype)
            return jnp.einsum("...d,vd->...v", h, params["head"], preferred_element_type=jnp.float32)

    def prefill(self, params, state, tokens, slots, starts, lengths):
        """Advance ``slots`` (B,) over one chunk of whole blocks: tokens (B, T) right-padded, the chunk's first
        position ``starts`` (B,) and its valid length ``lengths`` (B,; 0 leaves the slot as it was). -> (state,
        None: no token follows a prefill, the prompt's tail waits in the slot's first block, counts)."""
        valid = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
        _, state, counts = self._forward(params, state, tokens, valid,
                                         lambda p, u, st: _attn_prefill(self.cfg, p, u, st, slots, starts, lengths))
        return state, None, counts

    def block_forward(self, params, state, tokens, positions, active, commit: bool = False):
        """One forward of every slot's block: tokens (slots, L) from ``positions`` (slots,). A pass (-> (state as it
        was, logits (slots, L, V) float32, counts)) writes nothing; the ``commit`` (-> (state, None, counts)) writes
        the active slots' rows and runs no head."""
        valid = jnp.broadcast_to(active[:, None], tokens.shape)
        h, state, counts = self._forward(params, state, tokens, valid,
                                         lambda p, u, st: _attn_block(self.cfg, p, u, st, positions, active, commit))
        return state, None if commit else self._head(params, h), counts

    def block_step(self, params, state, tokens, tail, positions, active):
        """One block for every slot: tokens (slots, L), the first ``tail`` (slots,) of them the prompt's and fixed,
        the rest the mask id; the block's first position ``positions`` (slots,). ``denoising_steps`` passes, each a
        forward and ``unmask``, then the commit. -> (state, {"tok" (slots, L), "logprob": log c where a pass fixed
        the token, else 0, "step": that pass, else -1}, counts summed over the forwards)."""
        cfg = self.cfg
        L = cfg.block_length
        decoders.note_on_serving_span("unmask", "static")
        masked = jnp.arange(L)[None, :] >= tail[:, None]

        def one_pass(p, carry):
            tokens, masked, logprob, step, counts = carry
            _, logits, c = self.block_forward(params, state, tokens, positions, active)
            with jax.named_scope("unmask"):
                tokens, masked, logprob, step = unmask(logits, tokens, masked, logprob, step, p, L // cfg.denoising_steps)
            return tokens, masked, logprob, step, decoders.add_counts(counts, c)

        zero = dict.fromkeys(("assignments", "held_assignments", "max_expert_load", "experts_reached"), jnp.int32(0))
        carry = (tokens, masked, jnp.zeros(tokens.shape, jnp.float32), jnp.full(tokens.shape, -1, jnp.int32), zero)
        tokens, _, logprob, step, counts = jax.lax.fori_loop(0, cfg.denoising_steps, one_pass, carry)
        state, _, c = self.block_forward(params, state, tokens, positions, active, commit=True)
        return state, {"tok": tokens, "logprob": logprob, "step": step}, decoders.add_counts(counts, c)


decoders.register(SIZES, from_name=SdarMoeConfig.from_name, init=init_sdar_params,
                  model=SdarMoeLM, cut_options=CUT_OPTIONS)
