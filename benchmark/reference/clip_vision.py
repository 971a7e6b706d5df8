"""Plain reference for the CLIP vision tower: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the published description
(Radford et al. 2021; ViT of Dosovitskiy et al. 2020): patch embedding without
bias, class token, learned positions, ``ln_pre``, pre-norm blocks (LayerNorm,
multi-head self-attention, residual; LayerNorm, MLP, residual), ``ln_post`` on
the class token, projection without bias, L2 normalisation.

It imports nothing of the program and takes nothing the program has made. The
weights are drawn here from the seed, by the rule Flax's ``Module.init`` follows
(the key of a parameter is the root key folded with the SHA-1 of its module
path and its creation count), so that the same seed gives the program and the
reference the same float32 parameters without either handing them to the other.

Departures from the published checkpoint's ``config.json``, both taken from the
configuration file as the program runs it: ``hidden_act`` ("gelu", the tanh
approximation, where OpenAI's checkpoints use quick_gelu) and
``layer_norm_eps`` (1e-6 for 1e-5).

``precision="fp8"`` is the control of the benchmark's comparison: the same
forward with both operands of every matrix product rounded to float8_e4m3 under
a per-tensor scale, the step below the bfloat16 the configuration states.
"""

from __future__ import annotations

import hashlib
import io

import jax
import jax.numpy as jnp
import numpy as np

IMAGE_MEAN = np.array([0.48145466, 0.4578275, 0.40821073], np.float32)
IMAGE_STD = np.array([0.26862954, 0.26130258, 0.27577711], np.float32)

from lib.flops import vit_forward_flops_per_row as forward_flops_per_row  # noqa: F401  (the count that goes with this forward)

_LECUN = jax.nn.initializers.lecun_normal()
_NORMAL02 = jax.nn.initializers.normal(0.02)


def _key(root, path, count):
    """Flax's key for the ``count``-th parameter created in module ``path``."""
    m = hashlib.sha1()
    for part in path:
        m.update(part.encode("utf-8"))
    m.update(count.to_bytes((count.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _dense(root, path, fan_in, fan_out, bias=True):
    out = {"kernel": _LECUN(_key(root, path, 1), (fan_in, fan_out), jnp.float32)}
    if bias:
        out["bias"] = jnp.zeros((fan_out,), jnp.float32)
    return out


def _layernorm(width):
    return {"scale": jnp.ones((width,), jnp.float32),
            "bias": jnp.zeros((width,), jnp.float32)}


def make_weights(cfg: dict, seed: int) -> dict:
    """The vision tower's float32 parameters for ``seed``, on the default device,
    in one jitted call. The key is an argument, so every seed runs one program."""
    return jax.jit(lambda root: _weights(cfg, root))(jax.random.PRNGKey(seed))


def _weights(cfg: dict, root) -> dict:
    w, p, c = cfg["hidden_size"], cfg["patch_size"], cfg["num_channels"]
    tokens = (cfg["image_size"] // p) ** 2 + 1
    v = ("vision",)
    out = {
        "patch_embed": {"kernel": _LECUN(_key(root, v + ("patch_embed",), 1),
                                         (p, p, c, w), jnp.float32)},
        "cls": _NORMAL02(_key(root, v, 1), (1, 1, w), jnp.float32),
        "pos_embed": _NORMAL02(_key(root, v, 2), (1, tokens, w), jnp.float32),
        "ln_pre": _layernorm(w), "ln_post": _layernorm(w),
        "proj": _dense(root, v + ("proj",), w, cfg["projection_dim"], bias=False),
    }
    for i in range(cfg["num_hidden_layers"]):
        b = v + (f"block_{i}",)
        out[f"block_{i}"] = {
            "ln1": _layernorm(w), "ln2": _layernorm(w),
            "attn": {"qkv": _dense(root, b + ("attn", "qkv"), w, 3 * w),
                     "out": _dense(root, b + ("attn", "out"), w, w)},
            "mlp": {"fc1": _dense(root, b + ("mlp", "fc1"), w, cfg["intermediate_size"]),
                    "fc2": _dense(root, b + ("mlp", "fc2"), cfg["intermediate_size"], w)},
        }
    return out


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _forward(cfg: dict, fp8: bool):
    heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
    if cfg["hidden_act"] != "gelu":
        raise ValueError(f"reference knows hidden_act 'gelu', got {cfg['hidden_act']!r}")
    q8 = _fp8 if fp8 else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def ln(x, p):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]

    def dense(x, p):
        y = mm("...i,io->...o", x, p["kernel"])
        return y + p["bias"] if "bias" in p else y

    def block(x, p):
        b, t, w = x.shape
        qkv = dense(ln(x, p["ln1"]), p["attn"]["qkv"])
        q, k, v = (z.reshape(b, t, heads, w // heads) for z in jnp.split(qkv, 3, -1))
        logits = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(w // heads)
        att = mm("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v).reshape(b, t, w)
        x = x + dense(att, p["attn"]["out"])
        h = dense(ln(x, p["ln2"]), p["mlp"]["fc1"])
        return x + dense(jax.nn.gelu(h, approximate=True), p["mlp"]["fc2"])

    def forward(params, pixels):
        p = cfg["patch_size"]
        b, hh, ww, c = pixels.shape
        x = (pixels.astype(jnp.float32) / 255.0 - IMAGE_MEAN) / IMAGE_STD
        x = x.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
        x = mm("bnk,kw->bnw", x.reshape(b, -1, p * p * c),
               params["patch_embed"]["kernel"].reshape(p * p * c, -1))
        cls = jnp.broadcast_to(params["cls"], (b, 1, x.shape[-1]))
        x = ln(jnp.concatenate([cls, x], 1) + params["pos_embed"], params["ln_pre"])
        for i in range(cfg["num_hidden_layers"]):
            x = block(x, params[f"block_{i}"])
        e = dense(ln(x[:, 0], params["ln_post"]), params["proj"])
        return e / jnp.clip(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-6)

    return jax.jit(forward)


def preprocess(cfg: dict, rows) -> np.ndarray:
    """Pool rows as the traffic made them -> (n, size, size, 3) uint8. Raw pixel
    rows pass through; encoded rows are decoded, converted to RGB and resized
    (bilinear) to the model's input, as the published preprocessing does."""
    size = cfg["image_size"]
    if isinstance(rows, np.ndarray):
        return rows.reshape(len(rows), size, size, 3)
    from PIL import Image

    out = np.zeros((len(rows), size, size, 3), np.uint8)
    for i, raw in enumerate(rows):
        img = Image.open(io.BytesIO(raw)).convert("RGB")
        out[i] = np.asarray(img.resize((size, size), Image.BILINEAR))
    return out


def embed(cfg: dict, seed: int, pixels: np.ndarray, precision: str = "f32",
          block_rows: int = 32) -> np.ndarray:
    """Embeddings of ``pixels`` (n, size, size, 3) uint8, in blocks of rows so
    that the float32 activations fit beside nothing else on one chip."""
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)
    fwd = _forward(cfg, precision == "fp8")
    with jax.default_matmul_precision("highest"):
        params = make_weights(cfg, seed)
        outs = []
        for s in range(0, len(pixels), block_rows):
            blk = pixels[s:s + block_rows]
            n = len(blk)
            if n < block_rows:  # one shape, one compile
                blk = np.concatenate([blk, np.zeros((block_rows - n,) + blk.shape[1:], blk.dtype)])
            outs.append(np.asarray(fwd(params, blk))[:n])
    return np.concatenate(outs, 0)
