"""Pallas flash attention for the model towers.

A TPU-native fused attention kernel (online softmax — logits never
materialise in HBM), used by ``models/layers.MultiHeadAttention`` when
``DAFT_PALLAS_ATTENTION=1``. Handles non-causal (ViT/BERT) and key-padding
via an explicit valid-length: ViT-L's 257-token sequence pads to a lane-tiled
384 and the padded keys are masked inside the kernel.

Grid: (batch*heads, q_blocks, kv_blocks) with the kv dimension innermost —
each (bh, q) output block is revisited across kv steps, with running max /
denominator / accumulator kept in VMEM scratch (the canonical pallas flash
pattern). f32 accumulation over bf16 inputs.

The kernel runs on a TPU backend only (``pallas_attention_enabled`` gates on
it); there a kernel that fails to compile fails its caller. Tests run it in
interpret mode on CPU for exactness against the reference attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
_NEG_INF = float(-1e30)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, valid_len: int, block_kv: int, scale: float):
    from jax.experimental import pallas as pl

    kv_idx = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)           # (block_q, d)
    k = k_ref[0].astype(jnp.float32)           # (block_kv, d)
    v = v_ref[0].astype(jnp.float32)           # (block_kv, d)
    logits = (q * scale) @ k.T                 # (block_q, block_kv) on the MXU

    # Mask padded key positions (global kv index >= valid_len).
    kv_positions = kv_idx * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1
    )
    logits = jnp.where(kv_positions < valid_len, logits, _NEG_INF)

    m_prev = m_ref[:]                          # (block_q, 1)
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                # (block_q, block_kv)
    correction = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * correction + p @ v
    m_ref[:] = m_new

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = DEFAULT_BLOCK_Q, block_kv: int = DEFAULT_BLOCK_KV,
                    interpret: bool = False) -> jax.Array:
    """Non-causal attention. q/k/v: (B, T, H, D) -> (B, T, H, D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import math

    B, T, H, D = q.shape
    scale = D ** -0.5
    # Pad T up to a common multiple of BOTH block sizes (a kv block count of
    # T_pad // block_kv must cover every key); padded keys are masked, padded
    # queries produce garbage rows sliced off at the end.
    step = math.lcm(block_q, block_kv)
    T_pad = ((T + step - 1) // step) * step
    if T_pad != T:
        pad = [(0, 0), (0, T_pad - T), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # (B, T, H, D) -> (B*H, T, D)
    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, T_pad, D)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    n_q = T_pad // block_q
    n_kv = T_pad // block_kv

    kernel = functools.partial(_attn_kernel, valid_len=T, block_kv=block_kv, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb)
    out = out.reshape(B, H, T_pad, D).transpose(0, 2, 1, 3)
    return out[:, :T]


_AUTO_PROBE: "bool | None" = None


def _probe_pallas_wins() -> bool:
    """One-shot real-device A/B: compile+run the pallas kernel and
    jax.nn.dot_product_attention at a ViT-L-shaped slice; enable pallas only
    when it is numerically consistent AND not slower. A kernel that does
    not compile raises out of the probe."""
    import logging
    import time

    log = logging.getLogger(__name__)
    B, T, H, D = 4, 257, 16, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    ref_fn = jax.jit(lambda a, b, c: jax.nn.dot_product_attention(a, b, c))
    out_p = np.asarray(flash_attention(q, k, v))
    out_r = np.asarray(ref_fn(q, k, v))
    if not np.allclose(out_p.astype(np.float32), out_r.astype(np.float32),
                       atol=3e-2, rtol=3e-2):
        log.warning("pallas attention probe: numeric mismatch; disabled")
        return False

    def best_of(fn, n=3):
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            # daftlint: disable=DTL005 -- microbenchmark: the sync IS the measurement
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        return min(times)

    tp = best_of(lambda: flash_attention(q, k, v))
    tr = best_of(lambda: ref_fn(q, k, v))
    win = tp <= tr * 1.05
    log.info("pallas attention probe: pallas %.4fs vs xla %.4fs -> %s",
             tp, tr, "on" if win else "off")
    return win


def pallas_attention_enabled() -> bool:
    """Gate for the model towers. ``DAFT_PALLAS_ATTENTION``:
    ``1``/``true`` force-on (TPU only), ``0``/``false`` force-off (default),
    ``auto`` probes the real device once per process and enables pallas only
    when it matches XLA numerically and is not slower. The kernel is baked
    into jaxprs at trace time and cannot lower on other platforms, so the
    gate is the actual backend."""
    from daft_tpu.config import daft_env

    env = daft_env("DAFT_PALLAS_ATTENTION", "0")
    if env in ("0", "false"):
        return False
    if jax.default_backend() != "tpu":
        return False
    if env in ("1", "true"):
        return True
    if env == "auto":
        global _AUTO_PROBE
        if _AUTO_PROBE is None:
            _AUTO_PROBE = _probe_pallas_wins()
        return _AUTO_PROBE
    return False
