"""CLIP (vision + text dual encoder) in Flax.

TPU-native replacement for the reference's ``TransformersImageEmbedder``
(daft/ai/transformers/protocols/image_embedder.py:56-80 — torch CLIP with
``.to(device)``): a ViT image tower + causal text tower whose forwards are
pure jittable functions over bf16 params, ready for pjit sharding across a
mesh when the model exceeds one chip.

Named configs match the public CLIP family (ViT-B/32, ViT-B/16, ViT-L/14).
Weights: random-init by default (zero-egress environment); `load_params(path)`
accepts a local .msgpack/.npz checkpoint when available.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.models.layers import (
    MultiHeadAttention,
    TransformerBlock,
    causal_mask,
    init_params,
)


@dataclass(frozen=True)
class CLIPConfig:
    image_size: int = 224
    patch_size: int = 14
    vision_width: int = 1024
    vision_layers: int = 24
    vision_heads: int = 16
    text_width: int = 768
    text_layers: int = 12
    text_heads: int = 12
    vocab_size: int = 49408
    context_length: int = 77
    embed_dim: int = 768
    dtype: Any = jnp.bfloat16
    # Checkpoint-faithful knobs (converters set these from HF config.json;
    # defaults preserve the random-init behavior).
    hidden_act: str = "gelu"
    ln_eps: float = 1e-6
    vision_mlp_ratio: float = 4.0
    text_mlp_ratio: float = 4.0
    # Text tower may differ from vision in HF CLIPConfig; None = same.
    text_hidden_act: Optional[str] = None
    text_ln_eps: Optional[float] = None
    # Text pooling position. "last_nonpad": last non-pad token (hashing
    # tokenizer semantics, pad = 0). "first_eos": first position equal to
    # eos_token_id (HF CLIP, explicit eos config). "argmax_id": position of
    # the HIGHEST token id (HF's legacy eos_token_id==2 branch — OpenAI
    # checkpoints ship eos_token_id=2 in config.json while the real eot id
    # is 49407, the top of the vocab).
    text_pool: str = "last_nonpad"
    eos_token_id: Optional[int] = None

    @staticmethod
    def vit_b_32() -> "CLIPConfig":
        return CLIPConfig(patch_size=32, vision_width=768, vision_layers=12,
                          vision_heads=12, text_width=512, text_layers=12,
                          text_heads=8, embed_dim=512)

    @staticmethod
    def vit_b_16() -> "CLIPConfig":
        return CLIPConfig(patch_size=16, vision_width=768, vision_layers=12,
                          vision_heads=12, text_width=512, text_layers=12,
                          text_heads=8, embed_dim=512)

    @staticmethod
    def vit_l_14() -> "CLIPConfig":
        return CLIPConfig()  # defaults are ViT-L/14

    @staticmethod
    def tiny() -> "CLIPConfig":
        """Test-sized config for CI / virtual-device runs."""
        return CLIPConfig(image_size=32, patch_size=16, vision_width=64,
                          vision_layers=2, vision_heads=2, text_width=64,
                          text_layers=2, text_heads=2, vocab_size=512,
                          context_length=16, embed_dim=32)

    @staticmethod
    def from_name(name: str) -> "CLIPConfig":
        key = name.lower().replace("openai/clip-", "").replace("clip-", "")
        table = {
            "vit-b/32": CLIPConfig.vit_b_32, "vit-base-patch32": CLIPConfig.vit_b_32,
            "vit-b/16": CLIPConfig.vit_b_16, "vit-base-patch16": CLIPConfig.vit_b_16,
            "vit-l/14": CLIPConfig.vit_l_14, "vit-large-patch14": CLIPConfig.vit_l_14,
            "tiny": CLIPConfig.tiny,
        }
        if key in table:
            return table[key]()
        return CLIPConfig.vit_l_14()


# OpenAI CLIP normalisation constants.
CLIP_IMAGE_MEAN = np.array([0.48145466, 0.4578275, 0.40821073], dtype=np.float32)
CLIP_IMAGE_STD = np.array([0.26862954, 0.26130258, 0.27577711], dtype=np.float32)


class CLIPImageEncoder(nn.Module):
    cfg: CLIPConfig
    partitioned: bool = False  # see layers.MultiHeadAttention

    @nn.compact
    def __call__(self, pixels: jax.Array) -> jax.Array:
        """pixels: (B, H, W, 3) float in [0,1] or uint8. Returns (B, embed_dim).

        Normalisation happens ON DEVICE so uint8 image batches go straight
        from Arrow memory into HBM with no host-side float conversion —
        4x less host->device bandwidth than shipping f32.
        """
        cfg = self.cfg
        with jax.named_scope("pixel_norm"):
            x = pixels.astype(jnp.float32)
            if jnp.issubdtype(pixels.dtype, jnp.integer):  # static at trace time
                x = x / 255.0
            x = (x - CLIP_IMAGE_MEAN) / CLIP_IMAGE_STD
            x = x.astype(cfg.dtype)
        # Patchify via conv (lowered to one big matmul on the MXU).
        x = nn.Conv(cfg.vision_width, kernel_size=(cfg.patch_size, cfg.patch_size),
                    strides=(cfg.patch_size, cfg.patch_size), use_bias=False,
                    dtype=cfg.dtype, name="patch_embed")(x)
        B = x.shape[0]
        x = x.reshape(B, -1, cfg.vision_width)
        n_patches = x.shape[1]
        cls = self.param("cls", nn.initializers.normal(0.02), (1, 1, cfg.vision_width))
        x = jnp.concatenate([jnp.broadcast_to(cls.astype(cfg.dtype), (B, 1, cfg.vision_width)), x], axis=1)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, n_patches + 1, cfg.vision_width))
        x = x + pos.astype(cfg.dtype)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln_pre")(x).astype(cfg.dtype)
        for i in range(cfg.vision_layers):
            x = TransformerBlock(cfg.vision_heads, mlp_ratio=cfg.vision_mlp_ratio,
                                 dtype=cfg.dtype, act=cfg.hidden_act,
                                 ln_eps=cfg.ln_eps, partitioned=self.partitioned,
                                 name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln_post")(x[:, 0])
        x = nn.Dense(cfg.embed_dim, use_bias=False, dtype=jnp.float32, name="proj")(x)
        return x


class CLIPTextEncoder(nn.Module):
    cfg: CLIPConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """tokens: (B, L) int32. Returns (B, embed_dim) — embedding at the
        last token position (CLIP's EOS pooling)."""
        cfg = self.cfg
        L = tokens.shape[1]
        emb = nn.Embed(cfg.vocab_size, cfg.text_width,
                       embedding_init=nn.initializers.normal(0.02), name="tok_embed")
        x = emb(tokens).astype(cfg.dtype)
        pos = self.param("pos_embed", nn.initializers.normal(0.01), (1, cfg.context_length, cfg.text_width))
        x = x + pos[:, :L].astype(cfg.dtype)
        mask = causal_mask(L)
        for i in range(cfg.text_layers):
            x = TransformerBlock(cfg.text_heads, mlp_ratio=cfg.text_mlp_ratio,
                                 dtype=cfg.dtype,
                                 act=cfg.text_hidden_act or cfg.hidden_act,
                                 ln_eps=cfg.text_ln_eps if cfg.text_ln_eps is not None else cfg.ln_eps,
                                 name=f"block_{i}")(x, mask)
        x = nn.LayerNorm(dtype=jnp.float32,
                         epsilon=cfg.text_ln_eps if cfg.text_ln_eps is not None else cfg.ln_eps,
                         name="ln_final")(x)
        if cfg.text_pool == "first_eos" and cfg.eos_token_id is not None:
            # First eos_token_id position (argmax of the boolean hit mask) —
            # real vocabs can contain token id 0 mid-sequence, so
            # last-non-pad would pool the wrong position.
            pool_pos = jnp.argmax((tokens == cfg.eos_token_id).astype(jnp.int32), axis=1)
        elif cfg.text_pool == "argmax_id":
            pool_pos = jnp.argmax(tokens, axis=1)
        else:
            # Hashing-tokenizer semantics: last non-pad token (pad = 0).
            pool_pos = jnp.maximum(
                jnp.sum((tokens != 0).astype(jnp.int32), axis=1) - 1, 0)
        pooled = x[jnp.arange(x.shape[0]), pool_pos]
        return nn.Dense(cfg.embed_dim, use_bias=False, dtype=jnp.float32, name="proj")(pooled)


class CLIPModel(nn.Module):
    """Full dual encoder with a contrastive logit scale (usable as a training
    step target for the multi-chip dry run)."""

    cfg: CLIPConfig
    #: The forward is partitioned over a mesh (the provider says so): the
    #: vision tower's attention then takes XLA's path. The text tower is
    #: masked and takes it always.
    partitioned: bool = False

    def setup(self):
        self.vision = CLIPImageEncoder(self.cfg, self.partitioned)
        self.text = CLIPTextEncoder(self.cfg)
        self.logit_scale = self.param("logit_scale", nn.initializers.constant(2.6592), ())

    def __call__(self, pixels: jax.Array, tokens: jax.Array):
        img = self.vision(pixels)
        txt = self.text(tokens)
        img = img / jnp.linalg.norm(img, axis=-1, keepdims=True).clip(1e-6)
        txt = txt / jnp.linalg.norm(txt, axis=-1, keepdims=True).clip(1e-6)
        scale = jnp.exp(self.logit_scale)
        logits = scale * img @ txt.T
        return logits, img, txt

    def encode_image(self, pixels):
        return self.vision(pixels)

    def encode_text(self, tokens):
        return self.text(tokens)


def init_clip_params(cfg: CLIPConfig, seed: int = 0):
    model = CLIPModel(cfg)
    rng = jax.random.PRNGKey(seed)
    pixels = jnp.zeros((2, cfg.image_size, cfg.image_size, 3), jnp.uint8)
    tokens = jnp.zeros((2, cfg.context_length), jnp.int32)
    return model, init_params(model, rng, pixels, tokens)


def load_params(path: str, cfg: CLIPConfig):
    """Load a locally-available checkpoint (orbax dir, .msgpack, or .npz)."""
    from daft_tpu.models.checkpoint import load_params as _load

    model, params = init_clip_params(cfg)
    return model, _load(path, params)
