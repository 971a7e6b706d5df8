"""Shared transformer building blocks (Flax linen).

Written MXU-first: all matmuls stay large and batched; activations default to
bfloat16 with float32 layernorm/softmax accumulation (standard TPU mixed
precision). No data-dependent Python control flow — everything traces once
under jit.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any


@functools.partial(jax.jit, static_argnums=0)
def init_params(model: nn.Module, *args):
    """``model.init(*args)`` as one jitted program. Run eagerly, init is the
    whole forward dispatched op by op: most of a model's set-up time on a
    cold chip, in programs too small for the persistent compile cache to
    keep. Jitted, it is compiled once per (model config, shapes) in a process
    and once per cache directory across processes."""
    return model.init(*args)


class MLP(nn.Module):
    hidden_dim: int
    out_dim: int
    dtype: Dtype = jnp.bfloat16
    act: Callable = nn.gelu

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(self.hidden_dim, dtype=self.dtype, name="fc1")(x)
        x = self.act(x)
        x = nn.Dense(self.out_dim, dtype=self.dtype, name="fc2")(x)
        return x


class MultiHeadAttention(nn.Module):
    """Self-attention over ``x``. Unmasked attention on a TPU is one fused
    kernel that reads the ``qkv`` projection's output in place
    (``ops/pallas_attention.py``); masked attention, other backends, head
    sizes that do not fill 128-lane tiles, a partitioned forward and ``init``
    (which wants shapes only) take ``jax.nn.dot_product_attention``. The choice
    is made here, when the forward traces, and noted on the open
    ``provider.forward`` span as ``attn``."""

    num_heads: int
    dtype: Dtype = jnp.bfloat16
    #: Set by the provider, never by a user: this forward is partitioned over a
    #: mesh. GSPMD does not partition a ``pallas_call``, so the kernel is not
    #: taken there.
    partitioned: bool = False

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None):
        from daft_tpu.ops import pallas_attention
        from daft_tpu.profiling import open_device_span

        d = x.shape[-1]
        assert d % self.num_heads == 0
        head_dim = d // self.num_heads
        qkv = nn.Dense(3 * d, dtype=self.dtype, name="qkv")(x)
        fused = (mask is None and not self.partitioned
                 and not self.is_initializing()
                 and pallas_attention.fused_attention_applies(
                     qkv.shape, qkv.dtype, self.num_heads))
        forward = open_device_span("provider.forward")
        if forward is not None:
            forward.count["attn"] = "fused" if fused else "xla"
        # Flax names every module's operations after the module; the attention
        # core is no module, so it gets its scope here (metadata only).
        if fused:
            # A kernel that fails to trace or lower fails the forward.
            with jax.named_scope("attn_core"):
                out = pallas_attention.fused_attention(qkv, self.num_heads)
        else:
            q, k, v = (t.reshape(t.shape[:-1] + (self.num_heads, head_dim))
                       for t in jnp.split(qkv, 3, axis=-1))  # (B, T, H, hd)
            if mask is not None and mask.ndim == 4:
                # Broadcast (1|B, 1, T, T) or (B, 1, 1, T) to (B, H, T, T).
                B, T = q.shape[0], q.shape[1]
                mask = jnp.broadcast_to(mask, (B, self.num_heads if mask.shape[1] == 1 else mask.shape[1], T, T))
            with jax.named_scope("attn_core"):
                out = jax.nn.dot_product_attention(q, k, v, mask=mask)
            out = out.reshape(x.shape)
        return nn.Dense(d, dtype=self.dtype, name="out")(out)


def resolve_act(name: str) -> Callable:
    """Activation registry keyed the way HF config.json names them.
    ``gelu`` keeps flax's default (tanh approximation — the existing
    random-init behavior); checkpoint converters pass the faithful variant."""
    table = {
        "gelu": nn.gelu,
        "gelu_exact": lambda x: nn.gelu(x, approximate=False),
        "gelu_python": lambda x: nn.gelu(x, approximate=False),
        "gelu_new": nn.gelu,
        "gelu_fast": nn.gelu,
        "gelu_pytorch_tanh": nn.gelu,
        "quick_gelu": lambda x: x * nn.sigmoid(1.702 * x),
        "relu": nn.relu,
        "silu": nn.silu,
        "swish": nn.silu,
        "tanh": jnp.tanh,
    }
    if name not in table:
        from daft_tpu.errors import DaftValueError

        raise DaftValueError(
            f"Unsupported activation {name!r} (checkpoint hidden_act); "
            f"supported: {sorted(table)}")
    return table[name]


class TransformerBlock(nn.Module):
    """Pre-norm transformer block (ViT / CLIP / GPT style)."""

    num_heads: int
    mlp_ratio: float = 4.0
    dtype: Dtype = jnp.bfloat16
    act: str = "gelu"
    ln_eps: float = 1e-6
    partitioned: bool = False  # see MultiHeadAttention

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None):
        d = x.shape[-1]
        h = nn.LayerNorm(dtype=jnp.float32, epsilon=self.ln_eps,
                         name="ln1")(x).astype(self.dtype)
        x = x + MultiHeadAttention(self.num_heads, self.dtype, self.partitioned,
                                   name="attn")(h, mask)
        h = nn.LayerNorm(dtype=jnp.float32, epsilon=self.ln_eps,
                         name="ln2")(x).astype(self.dtype)
        # round(): converted checkpoints carry intermediate/hidden as a float
        # ratio, and int() would truncate 119.9999... for valid size pairs.
        x = x + MLP(round(d * self.mlp_ratio), d, self.dtype,
                    act=resolve_act(self.act), name="mlp")(h)
        return x


def causal_mask(seq_len: int) -> jax.Array:
    return jnp.tril(jnp.ones((1, 1, seq_len, seq_len), dtype=bool))


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    pos = jnp.arange(length)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, dim, 2).astype(jnp.float32) * (-jnp.log(10000.0) / dim))
    out = jnp.zeros((length, dim), dtype=jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(pos * div))
    out = out.at[:, 1::2].set(jnp.cos(pos * div))
    return out
