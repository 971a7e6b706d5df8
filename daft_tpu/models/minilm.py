"""MiniLM-style sentence encoder (all-MiniLM-L6-v2 shape) in Flax.

TPU-native replacement for the reference's sentence-transformers text
embedder (daft/ai/transformers provider, torch). Mean-pooled bidirectional
transformer; static max_length with attention masking.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from daft_tpu.models.layers import TransformerBlock, init_params


@dataclass(frozen=True)
class MiniLMConfig:
    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 6
    heads: int = 12
    max_length: int = 256
    embed_dim: int = 384
    dtype: Any = jnp.bfloat16

    @staticmethod
    def tiny() -> "MiniLMConfig":
        return MiniLMConfig(vocab_size=512, hidden=64, layers=2, heads=2,
                            max_length=32, embed_dim=64)

    @staticmethod
    def from_name(name: str) -> "MiniLMConfig":
        if "tiny" in name.lower():
            return MiniLMConfig.tiny()
        return MiniLMConfig()


class MiniLMEncoder(nn.Module):
    cfg: MiniLMConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """tokens: (B, L) int32, 0 = pad. Returns (B, embed_dim) mean-pooled."""
        cfg = self.cfg
        B, L = tokens.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden,
                     embedding_init=nn.initializers.normal(0.02), name="tok_embed")(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02), (1, cfg.max_length, cfg.hidden))
        x = (x + pos[:, :L]).astype(cfg.dtype)
        attn_valid = (tokens != 0)
        # (B, 1, 1, L) key mask — bidirectional.
        mask = attn_valid[:, None, None, :]
        for i in range(cfg.layers):
            x = TransformerBlock(cfg.heads, dtype=cfg.dtype, name=f"block_{i}")(x, mask)
        x = x.astype(jnp.float32)
        weights = attn_valid.astype(jnp.float32)[:, :, None]
        pooled = (x * weights).sum(axis=1) / weights.sum(axis=1).clip(1.0)
        pooled = pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True).clip(1e-6)
        return pooled


def init_minilm_params(cfg: MiniLMConfig, seed: int = 0):
    model = MiniLMEncoder(cfg)
    rng = jax.random.PRNGKey(seed)
    tokens = jnp.zeros((2, cfg.max_length), jnp.int32)
    return model, init_params(model, rng, tokens)
