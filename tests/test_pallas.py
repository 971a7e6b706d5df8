"""Pallas flash-attention kernel tests (interpret mode on CPU — the
fake-device-mesh CI pattern; real TPU compile is opt-in via
DAFT_PALLAS_ATTENTION=1)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from daft_tpu.ops.pallas_attention import flash_attention


@pytest.mark.parametrize("T", [128, 257, 300])
def test_flash_attention_matches_reference(T):
    rng = np.random.default_rng(0)
    B, H, D = 2, 4, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    ref = jax.nn.dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(1)
    B, T, H, D = 1, 200, 2, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype=jnp.bfloat16)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


def test_env_toggle_off_tpu_keeps_kernel_off(monkeypatch):
    """With the flag on, a backend that is not a TPU keeps the kernel off
    (the backend gate) and the model layer computes through XLA attention."""
    monkeypatch.setenv("DAFT_PALLAS_ATTENTION", "1")
    from daft_tpu.models.clip import CLIPConfig, init_clip_params

    cfg = CLIPConfig.tiny()
    model, params = init_clip_params(cfg)
    px = jnp.zeros((2, cfg.image_size, cfg.image_size, 3), jnp.uint8)
    out = model.apply(params, px, method=model.encode_image)
    assert np.isfinite(np.asarray(out)).all()


def test_forced_kernel_failure_propagates(monkeypatch):
    """DAFT_PALLAS_ATTENTION=1 on a TPU backend with a kernel that raises:
    the error leaves MultiHeadAttention — XLA's result is not substituted."""
    from daft_tpu.models.layers import MultiHeadAttention
    from daft_tpu.ops import pallas_attention as pa

    mha = MultiHeadAttention(num_heads=2, dtype=jnp.float32)
    x = jnp.ones((1, 8, 16), jnp.float32)
    params = mha.init(jax.random.PRNGKey(0), x)  # kernel off: XLA path

    def broken_kernel(q, k, v):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setenv("DAFT_PALLAS_ATTENTION", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "flash_attention", broken_kernel)
    assert pa.pallas_attention_enabled() is True
    with pytest.raises(RuntimeError, match="mosaic refused"):
        mha.apply(params, x)
    # The masked path never takes the kernel.
    out = mha.apply(params, x, jnp.ones((1, 1, 8, 8), bool))
    assert np.isfinite(np.asarray(out)).all()


def test_auto_gate_modes(monkeypatch):
    """DAFT_PALLAS_ATTENTION: 0/absent -> off; auto on a CPU backend -> off
    (the probe is TPU-only); 1 on CPU backend -> off (backend gate)."""
    from daft_tpu.ops import pallas_attention as pa

    monkeypatch.delenv("DAFT_PALLAS_ATTENTION", raising=False)
    assert pa.pallas_attention_enabled() is False
    monkeypatch.setenv("DAFT_PALLAS_ATTENTION", "auto")
    assert pa.pallas_attention_enabled() is False  # cpu backend, probe gated
    monkeypatch.setenv("DAFT_PALLAS_ATTENTION", "0")
    assert pa.pallas_attention_enabled() is False
