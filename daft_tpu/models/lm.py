"""Decoder-only LM with KV-cache greedy/temperature decoding.

TPU-native analogue of the reference's vLLM integration surface
(daft/execution/vllm.py, src/daft-local-execution/src/streaming_sink/vllm.rs):
``llm_generate``/``prompt`` expressions run batched generation through this
model. Decode is a ``lax.scan`` over a static max_new_tokens with a
preallocated KV cache — no data-dependent Python control flow.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from daft_tpu.models.layers import MLP, causal_mask, init_params
from daft_tpu.models.serving import ServedModel


@dataclass(frozen=True)
class DecoderLMConfig:
    vocab_size: int = 32000
    hidden: int = 2048
    layers: int = 16
    heads: int = 16
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16

    @staticmethod
    def tiny() -> "DecoderLMConfig":
        return DecoderLMConfig(vocab_size=512, hidden=64, layers=2, heads=2, max_seq_len=64)

    @staticmethod
    def from_name(name: str) -> "DecoderLMConfig":
        # Substring rules, kept for the names in use (ROADMAP D4); a new model
        # is matched exactly, from data (models/decoders.DECODERS).
        n = name.lower()
        if "tiny" in n:
            return DecoderLMConfig.tiny()
        if "8b" in n:
            return DecoderLMConfig(vocab_size=128256, hidden=4096, layers=32, heads=32)
        return DecoderLMConfig()


class CachedSelfAttention(nn.Module):
    """Self-attention with an explicit KV cache passed in/out (decode path)."""

    num_heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, cache_k, cache_v, positions):
        """x: (B, T, D); cache_{k,v}: (B, S, H, hd); positions: (B, T) int32.

        Returns (out, new_cache_k, new_cache_v). Works for both prefill
        (T = prompt length) and decode (T = 1).
        """
        d = x.shape[-1]
        head_dim = d // self.num_heads
        qkv = nn.Dense(3 * d, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        B, T = x.shape[0], x.shape[1]
        S = cache_k.shape[1]

        def heads(t):
            return t.reshape(B, T, self.num_heads, head_dim)

        q, k, v = heads(q), heads(k), heads(v)
        # Scatter new K/V into the cache at `positions`.
        new_k = jax.vmap(lambda c, upd, pos: c.at[pos].set(upd))(cache_k, k, positions)
        new_v = jax.vmap(lambda c, upd, pos: c.at[pos].set(upd))(cache_v, v, positions)
        scale = jnp.asarray(head_dim ** -0.5, self.dtype)
        logits = jnp.einsum("bthd,bshd->bhts", q * scale, new_k).astype(jnp.float32)
        # Valid keys: cache slots <= current query position.
        slot = jnp.arange(S)[None, None, None, :]
        qpos = positions[:, None, :, None]
        mask = slot <= qpos
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(self.dtype)
        out = jnp.einsum("bhts,bshd->bthd", probs, new_v).reshape(B, T, d)
        return nn.Dense(d, dtype=self.dtype, name="out")(out), new_k, new_v


class DecoderBlock(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, cache_k, cache_v, positions):
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x).astype(self.dtype)
        attn_out, ck, cv = CachedSelfAttention(self.num_heads, self.dtype, name="attn")(
            h, cache_k, cache_v, positions
        )
        x = x + attn_out
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x).astype(self.dtype)
        x = x + MLP(4 * x.shape[-1], x.shape[-1], self.dtype, name="mlp")(h)
        return x, ck, cv


class DecoderLM(nn.Module, ServedModel):
    """Also the serving protocol of ``models/serving.ContinuousBatcher``: its
    slot state is the per-layer ``(k, v)`` rows."""

    cfg: DecoderLMConfig

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    @property
    def max_seq_len(self) -> int:
        return self.cfg.max_seq_len

    @nn.nowrap
    def init_state(self, slots: int, positions: int):
        return init_caches(self.cfg, slots, positions)

    @nn.nowrap
    def prefill(self, params, state, tokens, slots, starts, lengths):
        """Advance ``slots`` over one right-padded chunk of their prompts. Keys
        past a prompt's end are written, lie behind every query's position and
        are overwritten as the slot decodes."""
        positions = starts[:, None] + jnp.arange(tokens.shape[1])[None, :]
        rows = jax.tree_util.tree_map(lambda a: a[slots], state)
        logits, rows = self.apply(params, tokens, rows, positions)
        keep = (lengths > 0).reshape(-1, 1, 1, 1)  # a row with nothing to add leaves its slot alone
        state = jax.tree_util.tree_map(lambda a, r: a.at[slots].set(jnp.where(keep, r, a[slots])), state, rows)
        last = jnp.take_along_axis(logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        return state, last, {}

    @nn.nowrap
    def decode(self, params, state, tokens, positions, active):
        logits, state = self.apply(params, tokens[:, None], state, positions[:, None])
        return state, logits[:, 0], {}

    @nn.compact
    def __call__(self, tokens, caches, positions):
        """tokens: (B, T); caches: list[(k, v)] per layer; positions: (B, T)."""
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden,
                     embedding_init=nn.initializers.normal(0.02), name="tok_embed")(tokens)
        x = x.astype(cfg.dtype)
        pos_emb = self.param("pos_embed", nn.initializers.normal(0.01),
                             (1, cfg.max_seq_len, cfg.hidden))
        x = x + jnp.take_along_axis(
            jnp.broadcast_to(pos_emb, (tokens.shape[0],) + pos_emb.shape[1:]),
            positions[:, :, None], axis=1, mode="clip",  # padding of a last chunk may lie past the table
        ).astype(cfg.dtype)
        new_caches = []
        for i in range(cfg.layers):
            ck, cv = caches[i]
            x, ck, cv = DecoderBlock(cfg.heads, cfg.dtype, name=f"block_{i}")(x, ck, cv, positions)
            new_caches.append((ck, cv))
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32, name="lm_head")(x)
        return logits, new_caches


def init_caches(cfg: DecoderLMConfig, batch: int, seq_len: Optional[int] = None):
    S = seq_len or cfg.max_seq_len
    head_dim = cfg.hidden // cfg.heads
    return [
        (jnp.zeros((batch, S, cfg.heads, head_dim), cfg.dtype),
         jnp.zeros((batch, S, cfg.heads, head_dim), cfg.dtype))
        for _ in range(cfg.layers)
    ]


def init_lm_params(cfg: DecoderLMConfig, seed: int = 0, batch: int = 2, prompt_len: int = 8):
    model = DecoderLM(cfg)
    rng = jax.random.PRNGKey(seed)
    tokens = jnp.zeros((batch, prompt_len), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(prompt_len), (batch, prompt_len))
    caches = init_caches(cfg, batch, cfg.max_seq_len)
    params = init_params(model, rng, tokens, caches, positions)
    return model, params


def generate(model: DecoderLM, params, prompt_tokens: jax.Array, prompt_lengths: jax.Array,
             max_new_tokens: int = 32, temperature: float = 0.0,
             seed: int = 0, eos_id: int = 2) -> jax.Array:
    """Batched generation: prefill + lax.scan decode with KV cache.

    prompt_tokens: (B, P) int32 right-padded with 0; prompt_lengths: (B,).
    Returns (B, max_new_tokens) generated ids (0 after EOS).
    """
    cfg = model.cfg
    B, P = prompt_tokens.shape
    S = min(cfg.max_seq_len, P + max_new_tokens)
    caches = init_caches(cfg, B, S)
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    logits, caches = model.apply(params, prompt_tokens, caches, positions)
    last_pos = prompt_lengths - 1
    next_logits = logits[jnp.arange(B), last_pos]

    def sample(lg, key):
        if temperature <= 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / temperature, axis=-1).astype(jnp.int32)

    flat_caches, treedef = jax.tree_util.tree_flatten(caches)

    def step(carry, key):
        flat, cur_logits, pos, done = carry
        tok = sample(cur_logits, key)
        tok = jnp.where(done, 0, tok)
        cs = jax.tree_util.tree_unflatten(treedef, flat)
        lgts, cs = model.apply(params, tok[:, None], cs, pos[:, None])
        new_done = done | (tok == eos_id)
        new_flat = jax.tree_util.tree_flatten(cs)[0]
        return (new_flat, lgts[:, 0], pos + 1, new_done), tok

    keys = jax.random.split(jax.random.PRNGKey(seed), max_new_tokens)
    init = (flat_caches, next_logits, prompt_lengths, jnp.zeros((B,), bool))
    _, tokens = jax.lax.scan(step, init, keys)
    return tokens.T  # (B, max_new_tokens)
