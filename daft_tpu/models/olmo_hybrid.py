"""Olmo-Hybrid decoder (``olmo_hybrid``): gated delta-rule linear attention in
three layers of four and full attention in the fourth, a SwiGLU MLP after every
mixer, the Olmo 2 / 3 family's reordered norm (RMSNorm on what a sub-layer
*gives*, not on what it takes), RMSNorm eps 1e-6, no bias, no positional
encoding, untied embedding and head. ``h`` is the residual stream (float32
here; products take bfloat16 operands and accumulate in float32):

    h = E[tok]
    h += RMSNorm(Mixer(h))                  # by layer_types: linear_attention x 3, full_attention
    h += RMSNorm(MLP(h))                    # W_out (silu(W_gate h) * W_up h)
    logits = RMSNorm(h) @ W_head

    linear_attention (Gated DeltaNet, arXiv:2412.06464), a head of ``linear_num_value_heads``:
        q, k, v = silu(conv(W_q h)), silu(conv(W_k h)), silu(conv(W_v h))   # causal depthwise conv over
                                                                            # ``linear_conv_kernel_dim`` steps, no bias
        q = q / |q| * key_dim ** -0.5,  k = k / |k|
        beta = 2 sigmoid(W_b h)             # the 2 is ``linear_allow_neg_eigval``: the transition's eigenvalue
                                            # along k lies in (-1, 1)
        g = -exp(A_log) softplus(W_a h + dt_bias),  alpha = exp(g)
        S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     # S (value_dim, key_dim), float32
        o_t = S_t q_t
        out = W_o (RMSNorm_value_dim(o) * silu(W_g h))
    full_attention: q, k = RMSNorm(W_q h), RMSNorm(W_k h) over the whole projection (QK-norm), v = W_v h,
        heads of ``head_dim``, scale head_dim ** -0.5, causal softmax, W_o; no rotary positions (``rope_theta`` null)

**The delta rule in chunks** (``gated_delta_chunked``, ``ops/pallas_delta_rule.py``). The write ``beta (v -
alpha S k)`` reads the state, so inside a chunk of C steps the writes
``u_i = beta_i (v_i - alpha_i S_{i-1} k_i)`` solve a unit-lower-triangular system.
With ``gamma_i`` the decay accumulated from the chunk's start and
``Gamma_ij = gamma_i / gamma_j``:

    A = tril(diag(beta) (K K^T * Gamma), -1),  T = (I + A)^-1           # forward substitution, float32
    W = T diag(beta) (K * gamma),  U' = T diag(beta) V
    U = U' - W S_0^T                                                     # the carried state enters a product
    O = (Q * gamma) S_0^T + tril(Q K^T * Gamma) U
    S_C = gamma_C S_0 + U^T (K * gamma_C / gamma)

Two forms of this algebra, which share no code on purpose. On a TPU, at bfloat16
inputs, whole chunks of 64 steps and an even head group inside the VMEM budget
(``ops/pallas_delta_rule.delta_rule_applies``, asked while the prefill program
traces: no option), the call is **one Pallas kernel**
(``pallas_delta_rule.gated_delta_fused``): grid (rows, head groups, chunks in
order), the group's state in a VMEM scratch from ``S_0`` at a row's first chunk
to the result after its last, and ``K K^T``, the decays, ``A``, ``T``, ``W``,
``U'``, ``U`` never in HBM; its solve substitutes inside diagonal blocks of 16
steps, two heads side by side in the lanes, and merges them by block products
(that module's head has the grid, the solve and the edges). Everywhere else (the CPU, the tiny test sizes, the
rehearsals) and as the kernel's check on the chip, ``gated_delta_chunked`` below
is **XLA's form**: everything that does not read ``S_0`` computed for all chunks
of a call at once, a ``lax.scan`` over the chunks carrying the state through
four small products each, the solve a row at a time. In both, q, k and v arrive
in bfloat16 and ``K K^T`` and ``Q K^T`` take them so; the decays, the solve, the
state and every product that reads the solve's result or the state are float32
(``Precision.HIGHEST``). Padding has ``g = 0`` and ``beta = 0``: it neither
decays nor writes. ``cfg.linear_chunk_size`` is the one chunk both read. Decode
is one step of the recurrence a slot (``gated_delta_step``), XLA's in every
program. Which form a program traced is noted on the batcher's open span as
``delta`` = ``fused`` | ``chunked`` | ``recurrent``.

**The cut.** ``num_hidden_layers`` keeps the first layers, whole periods of the
layer pattern only. Every width, the vocabulary included, is as published.

**Serving protocol** (``models/serving.ContinuousBatcher``): ``init_state``,
``prefill``, ``decode``, ``copy_state``. Slot state is one entry a layer:
``{"S" (slots, heads, value_dim, key_dim) float32, "conv" (slots, K - 1, q | k |
v channels)}`` for a linear layer, whatever the length; ``{"k", "v" (slots,
heads, positions in whole tiles of ``ROW_TILE``, head_dim)}`` for an attention
layer (row-major with no axis padded to a tile: 30 heads next to the head size
would be laid out as 32),
15,360 B a token a layer at the published sizes: a quarter of the depth holds
97% of the cache. A prefill writes only its chunk into the slots' rows, in
place (``ops/pallas_cache_blocks.write_blocks``), and attends, for each row of
the call, only the blocks up to that row's own chunk; a decode step writes each
slot's new row and attends the blocks up to each slot's own position. On a TPU at
widths that fill lane tiles both are ``ops/pallas_cache_attention.py``, one
kernel over the rows where they lie (scores in VMEM, no block beyond a row's
depth fetched); on the CPU and at the tiny sizes ``decoders.attention_chunk``
over XLA's slices (every row of a call as deep as the deepest) and
``decoders.attention_core`` over every position a slot could hold, the core
``models/granite_hybrid.py`` runs, as it does the conv with its carried tail.
Which one a program traced is noted on the batcher's open span as ``attn`` =
``fused`` | ``xla``. A slot admitted anew starts from zero recurrent state.

Plain functions over a parameter tree, drawn tensor by tensor on the device in
bfloat16; ``jax.named_scope`` names the parts (``lin_proj``, ``delta_rule``,
``attn_proj``, ``attn_core``, ``mlp``, ``head``) for the device trace.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders
from daft_tpu.models.decoders import draw, gated_mlp, mm, rms
from daft_tpu.ops import pallas_attention, pallas_cache_attention, pallas_cache_blocks, pallas_delta_rule

LINEAR, FULL = "linear_attention", "full_attention"
#: Published sizes by exact model name (``config.json`` of the source). Kept as data: no substring rule.
#: ``head_dim`` is not in the published file: hidden_size / num_attention_heads. ``linear_chunk_size`` is the
#: program's own (the chunk of the delta rule's chunked form), no property of the model.
PUBLISHED: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json
    "Olmo-Hybrid-7B": dict(
        vocab_size=100352, hidden_size=3840, intermediate_size=11008, num_hidden_layers=32,
        layer_types=((LINEAR,) * 3 + (FULL,)) * 8, num_attention_heads=30, num_key_value_heads=30, head_dim=128,
        rms_norm_eps=1e-6, linear_num_key_heads=30, linear_num_value_heads=30, linear_key_head_dim=96,
        linear_value_head_dim=192, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, linear_chunk_size=64),
}
#: Not published: the same ratios at a width the CPU tests and ``chip_smoke.py`` can afford.
TEST_SIZES: Dict[str, Dict[str, Any]] = {
    "olmo-hybrid-tiny": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
        layer_types=(LINEAR,) * 3 + (FULL,), num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        rms_norm_eps=1e-6, linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=16, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, linear_chunk_size=8),
}
#: Every name ``from_name`` resolves.
SIZES = {**PUBLISHED, **TEST_SIZES}
#: Options of ``prompt`` that cut a published model to one chip's share.
CUT_OPTIONS = ("num_hidden_layers",)
#: Embedding and head are drawn in blocks of this many rows (the drawing rule the decoders share).
EMBED_BLOCK_ROWS = 64
#: Standard deviation of the embedding's rows (the head is untied and drawn at fan-in scale: logits spread ~1).
EMBED_STD = 1.0
#: Under the root of the q / k normalisation.
L2_EPS = 1e-6
#: A slot's key/value rows are held in whole multiples of this many positions (a sublane tile of bfloat16), so that
#: the rows have no padding to lose. A TPU keeps an array in whichever order of its axes pads least: with rows that
#: end inside a tile and a slot count that is whole tiles (8, 16) it keeps ``(slots, heads, positions, head size)``
#: *slots-minor*, the cache kernels, which take the rows row-major, each get a copy of 1 GB, and the prefill does
#: not fit (compiled for a described v5e at 8 slots x 16,449 positions, PR 35; at 16,464 the order is row-major).
ROW_TILE = 16


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool
    linear_chunk_size: int
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_name(name: str, num_hidden_layers: int = None) -> "OlmoHybridConfig":
        if name not in SIZES:
            raise DaftValueError(
                f"unknown Olmo-Hybrid decoder {name!r}; the published sizes on record are {sorted(PUBLISHED)}")
        cfg = OlmoHybridConfig(**SIZES[name])
        if cfg.linear_num_key_heads != cfg.linear_num_value_heads:
            raise DaftValueError("the delta rule here takes as many key heads as value heads")
        layers = int(num_hidden_layers or cfg.num_hidden_layers)
        period = cfg.layer_types.index(FULL) + 1
        if not 0 < layers <= cfg.num_hidden_layers or layers % period:
            raise DaftValueError(
                f"num_hidden_layers={layers} is no whole number of {name!r}'s periods of {period} layers "
                f"(at most {cfg.num_hidden_layers})")
        return replace(cfg, num_hidden_layers=layers, layer_types=cfg.layer_types[:layers])

    # -- derived sizes ---------------------------------------------------- #
    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: q, k and v side by side."""
        return 2 * self.key_dim + self.value_dim


# ---------------------------------------------------------------------- #
# Parameters: drawn tensor by tensor on the device, bfloat16              #
# ---------------------------------------------------------------------- #
def tensor_specs(cfg: OlmoHybridConfig, kind: str) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape,
    rule). ``benchmark/reference/olmo_hybrid.py`` states the same rules."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    if kind == LINEAR:
        H, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        mixer = [("q", (d, cfg.key_dim), "matrix"), ("k", (d, cfg.key_dim), "matrix"),
                 ("v", (d, cfg.value_dim), "matrix"), ("g", (d, cfg.value_dim), "matrix"),
                 ("b", (d, H), "matrix"), ("a", (d, H), "matrix"),
                 ("conv_w", (cfg.linear_conv_kernel_dim, cfg.conv_dim), "matrix"),
                 ("A_log", (H,), "A_log"), ("dt_bias", (H,), "dt_bias"),
                 ("o_norm", (dv,), "norm"), ("o", (cfg.value_dim, d), "matrix")]
    else:
        q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
        mixer = [("q", (d, q), "matrix"), ("k", (d, kv), "matrix"), ("v", (d, kv), "matrix"),
                 ("q_norm", (q,), "norm"), ("k_norm", (kv,), "norm"), ("o", (q, d), "matrix")]
    return mixer + [("mixer_norm", (d,), "norm"), ("mlp_in", (d, 2 * f), "matrix"),
                    ("mlp_out", (f, d), "matrix"), ("mlp_norm", (d,), "norm")]


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_tensor(cfg: OlmoHybridConfig, key, shape: tuple, rule: str):
    """One tensor, so that no more than one is ever held in float32; ``A_log``
    and ``dt_bias`` stay float32 (30 values each)."""
    return draw(key, shape, rule).astype(jnp.float32 if rule in ("A_log", "dt_bias") else cfg.dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_rows(cfg: OlmoHybridConfig, key, std: float):
    """The embedding or the head, an eighth of its blocks at a time: drawn whole, the float32 rows are 1.54 GB of
    temporaries at the published sizes, which a TPU keeps reserved for as long as the program stays loaded (PR 35:
    ``bytes_reserved`` 1,541,439,488 B from here to the end of the process, beside a prefill that needs 0.49 GB)."""
    blocks = cfg.vocab_size // EMBED_BLOCK_ROWS
    groups = math.gcd(blocks, 8)

    def group(g):
        rows = decoders.draw_row_blocks(key, g * (blocks // groups), blocks // groups, EMBED_BLOCK_ROWS, cfg.hidden_size)
        return (rows * std).astype(cfg.dtype)

    return jax.lax.map(group, jnp.arange(groups)).reshape(cfg.vocab_size, cfg.hidden_size)


def _init_layer(cfg: OlmoHybridConfig, key, kind: str):
    """A layer's tensors as drawn, then the projections that read the same input side by side: ``qkv`` (the conv's
    channels in its order, or the attention's three) and, for the linear mixer, ``ba``."""
    p = {name: _init_tensor(cfg, jax.random.fold_in(key, j), shape, rule)
         for j, (name, shape, rule) in enumerate(tensor_specs(cfg, kind))}
    p["qkv"] = jnp.concatenate([p.pop("q"), p.pop("k"), p.pop("v")], axis=1)
    if kind == LINEAR:
        p["ba"] = jnp.concatenate([p.pop("b"), p.pop("a")], axis=1)
    return p


def init_olmo_params(cfg: OlmoHybridConfig, seed: int = 0):
    """-> (model, params). Key 0 of the seed draws the embedding (0), the final
    norm (1) and the head (2); key i + 1 layer i, tensor j of ``tensor_specs``
    from the layer's key folded with j."""
    root = jax.random.PRNGKey(seed)
    k0 = jax.random.fold_in(root, 0)
    params = {"embed": _init_rows(cfg, jax.random.fold_in(k0, 0), EMBED_STD),
              "final_norm": draw(jax.random.fold_in(k0, 1), (cfg.hidden_size,), "norm").astype(cfg.dtype),
              "head": _init_rows(cfg, jax.random.fold_in(k0, 2), cfg.hidden_size ** -0.5),
              "layers": [_init_layer(cfg, jax.random.fold_in(root, i + 1), kind)
                         for i, kind in enumerate(cfg.layer_types)]}
    return OlmoHybridLM(cfg), params


# ---------------------------------------------------------------------- #
# The gated delta rule                                                    #
# ---------------------------------------------------------------------- #
def gated_delta_step(q, k, v, g, beta, s):
    """One step of the recurrence for every row: q, k (B, H, dk) normalised, v
    (B, H, dv), g, beta (B, H) float32 (both 0 leave the state as it was), s
    (B, H, dv, dk) float32. -> (o (B, H, dv) float32, the state after)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.exp(g)[..., None, None] * s
    u = beta[..., None] * (v - jnp.sum(s * k[:, :, None, :], axis=-1))      # what is written: beta (v - alpha S k)
    s = s + u[..., None] * k[:, :, None, :]
    return jnp.sum(s * q[:, :, None, :], axis=-1), s


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., C, C) strictly lower triangular, float32, by
    forward substitution a row at a time: row i of the inverse less the identity
    is ``-a_i - sum_{j<i} a_ij m_j``. Elementwise (no matrix unit: its float32
    products round to bfloat16 unless asked otherwise, and the solve is what the
    chunked form's agreement with the recurrence rests on)."""
    C = a.shape[-1]

    def row(i, m):
        mi = jax.lax.dynamic_index_in_dim(m, i, axis=-2, keepdims=False)     # still -a_i: zero from column i on
        mi = mi + jnp.sum(mi[..., :, None] * m, axis=-2)                    # rows j >= i are weighed by zero
        return jax.lax.dynamic_update_index_in_dim(m, mi, i, axis=-2)

    return jax.lax.fori_loop(1, C, row, -a) + jnp.eye(C, dtype=a.dtype)


def gated_delta_chunked(q, k, v, g, beta, s0, chunk: int):
    """The gated delta rule over ``T`` steps in chunks (the module's head has the
    algebra). q, k (B, T, H, dk) normalised, v (B, T, H, dv); g (B, T, H) float32
    <= 0, the log of the decay, and beta (B, T, H) float32, both 0 at padding;
    s0 (B, H, dv, dk) float32. -> (o (B, T, H, dv) float32, the state after the
    last step)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"{T} steps are no multiple of the chunk {C}")
    nc = T // C
    heads_first = lambda x: jnp.moveaxis(x.reshape((B, nc, C) + x.shape[2:]), 3, 2)  # noqa: E731  (B, nc, H, C, ...)
    q, k, v, g, beta = (heads_first(x) for x in (q, k, v, g, beta))
    cs = jnp.cumsum(g, axis=-1)                                          # (B, nc, H, C): log gamma
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :], -jnp.inf))    # Gamma_ij, j <= i; else 0
    kk = jnp.einsum("bchid,bchjd->bchij", k, k, preferred_element_type=jnp.float32)
    t = _unit_lower_inverse(jnp.where(jnp.tril(lower, -1), beta[..., None] * kk * decay, 0.0))
    gamma = jnp.exp(cs)[..., None]
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    # What follows the solve multiplies its result and the carried state: float32 operands at full precision (the
    # state is a sum over the whole prefix, and rounding it to bfloat16 at every chunk's edge read 2-3x the gap
    # to the reference on the CPU at width 64); these products are C x C or C x dk x dv a head, a few percent of a layer's.
    hi = jax.lax.Precision.HIGHEST
    w = jnp.einsum("bchij,bchjd->bchid", t, beta[..., None] * gamma * k32, precision=hi)
    u_own = jnp.einsum("bchij,bchjd->bchid", t, beta[..., None] * v32, precision=hi)
    qk = jnp.einsum("bchid,bchjd->bchij", q, k, preferred_element_type=jnp.float32) * decay   # tril(Q K^T * Gamma)
    q_in = q32 * gamma                                                   # what meets the carried state
    k_end = k32 * jnp.exp(cs[..., -1:] - cs)[..., None]                  # K * gamma_C / gamma
    whole = jnp.exp(cs[..., -1])                                         # (B, nc, H): a chunk's decay

    def step(s, inp):
        w_c, u_c, qk_c, q_c, k_c, whole_c = inp
        u = u_c - jnp.einsum("bhik,bhvk->bhiv", w_c, s, precision=hi)
        o = jnp.einsum("bhik,bhvk->bhiv", q_c, s, precision=hi) + jnp.einsum("bhij,bhjv->bhiv", qk_c, u, precision=hi)
        return whole_c[..., None, None] * s + jnp.einsum("bhiv,bhik->bhvk", u, k_c, precision=hi), o

    s_last, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (w, u_own, qk, q_in, k_end, whole)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)                        # (B, nc, C, H, dv)
    return o.reshape(B, T, H, dv), s_last


def _l2_normalised(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_inputs(cfg, p, u, tail, valid, lengths):
    """What the recurrence takes, from the layer's input u (B, T, d) and the
    conv's carried tail: -> (q, k (B, T, H, dk) normalised, v (B, T, H, dv),
    g, beta (B, T, H) float32 and 0 at padding, gate (B, T, H, dv) float32, the
    new tail)."""
    B, T, _ = u.shape
    H, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    qkv = mm(u, p["qkv"]).astype(cfg.dtype)
    qkv, tail = decoders.conv_with_tail(tail, qkv, p["conv_w"], None, lengths, jnp.float32)
    q, k, v = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)
    q = _l2_normalised(q.reshape(B, T, H, dk)) * dk ** -0.5
    k = _l2_normalised(k.reshape(B, T, H, dk))
    b, a = jnp.split(mm(u, p["ba"]), 2, axis=-1)
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    keep = valid[..., None]                                              # padding neither decays nor writes
    gate = mm(u, p["g"]).reshape(B, T, H, dv)
    return (q.astype(cfg.dtype), k.astype(cfg.dtype), v.reshape(B, T, H, dv).astype(cfg.dtype),
            jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0), gate, tail)


def _linear(cfg, p, u, st, valid, lengths, single_step: bool):
    """u (B, T, d); st {"S" (B, H, dv, dk), "conv" (B, K-1, C)} of these rows;
    valid (B, T); lengths (B,). -> (out (B, T, d) float32, new st)."""
    B, T, _ = u.shape
    with jax.named_scope("lin_proj"):
        q, k, v, g, beta, gate, tail = linear_inputs(cfg, p, u, st["conv"], valid, lengths)
    with jax.named_scope("delta_rule"):
        if single_step:
            o, s = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], st["S"])
            o = o[:, None]
            decoders.note_on_serving_span("delta", "recurrent")
        else:
            fused = pallas_delta_rule.delta_rule_applies(q.shape, v.shape, cfg.dtype, cfg.linear_chunk_size)
            decoders.note_on_serving_span("delta", "fused" if fused else "chunked")
            if fused:  # one kernel; what it needs at its edges (q's and k's pad, the running sum of g) is traced here, in this scope
                o, s = pallas_delta_rule.gated_delta_fused(q, k, v, g, beta, st["S"], chunk=cfg.linear_chunk_size)
            else:
                o, s = gated_delta_chunked(q, k, v, g, beta, st["S"], cfg.linear_chunk_size)
    with jax.named_scope("lin_proj"):
        y = rms(o, p["o_norm"], cfg.rms_norm_eps) * jax.nn.silu(gate)
        return mm(y.reshape(B, T, -1).astype(cfg.dtype), p["o"]), {"S": s, "conv": tail}


# ---------------------------------------------------------------------- #
# Full attention: QK-norm, no positions, the shared core                  #
# ---------------------------------------------------------------------- #
def _attn_project(cfg, p, u):
    """u (B, T, d) -> (q (B, T, KV, R, hd), k, v (B, T, KV, hd)), bfloat16: q and k normed over the whole projection."""
    B, T, _ = u.shape
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    nq = cfg.num_attention_heads * hd
    q, k, v = jnp.split(mm(u, p["qkv"]), [nq, nq + KV * hd], axis=-1)
    q = rms(q, p["q_norm"], cfg.rms_norm_eps).astype(cfg.dtype).reshape(B, T, KV, cfg.num_attention_heads // KV, hd)
    k = rms(k, p["k_norm"], cfg.rms_norm_eps).astype(cfg.dtype).reshape(B, T, KV, hd)
    return q, k, v.astype(cfg.dtype).reshape(B, T, KV, hd)


def _attn_out(cfg, p, out):
    B, T = out.shape[:2]
    return mm(out.astype(cfg.dtype).reshape(B, T, -1), p["o"])


def _attn_prefill(cfg, p, u, st, slots, starts, lengths):
    """One chunk for the rows ``slots`` of the cache st {"k", "v" (slots, KV, S,
    hd)}: write the chunk's valid rows in place (``lengths`` of each, from
    ``starts``), then attend over the blocks held. -> (out (B, T, d) float32, st)."""
    T, hd = u.shape[1], cfg.head_dim
    with jax.named_scope("attn_proj"):
        q, k, v = _attn_project(cfg, p, u)
    with jax.named_scope("attn_core"):
        # The chunk's valid rows go into the slots' rows in place (``ops/pallas_cache_blocks.py`` has why that is a
        # kernel on a TPU); the core then attends them where they lie.
        cache_k = pallas_cache_blocks.write_blocks(st["k"], k, slots, starts, lengths)
        cache_v = pallas_cache_blocks.write_blocks(st["v"], v, slots, starts, lengths)
        fused = pallas_cache_attention.cache_attention_applies(q.shape, cache_k.shape, cfg.dtype)
        decoders.note_on_serving_span("attn", "fused" if fused else "xla")
        if fused:  # every row over the blocks up to its own chunk; a kernel that fails to trace or lower fails the program
            out = pallas_cache_attention.cache_attention(q, cache_k, cache_v, slots, starts, lengths, scale=hd ** -0.5)
        else:
            # XLA's loop walks the blocks that the call's deepest row attends, for every row (rows of a call stand at
            # unlike depths: blocks beyond a row's own weigh 0). Its CPU backend has no bfloat16 product for a block
            # and does not widen it itself: it is widened here, which gives the same numbers (a bfloat16 value is
            # its float32 value; the products accumulate in float32 either way).
            def block(cache, j):
                rows = pallas_cache_blocks.read_blocks_xla(cache, slots, j * T, T)
                return rows if pallas_attention.backend_is_tpu() else rows.astype(jnp.float32)

            positions = starts[:, None] + jnp.arange(T)[None, :]
            out = decoders.attention_chunk(q, lambda j: (block(cache_k, j), block(cache_v, j)), positions,
                                           jnp.max(starts) // T + 1, hd ** -0.5, cfg.dtype)
    with jax.named_scope("attn_proj"):
        return _attn_out(cfg, p, out), {"k": cache_k, "v": cache_v}


def _attn_decode(cfg, p, u, st, positions, active):
    """One token for every slot: u (slots, 1, d), positions, active (slots,). -> (out, st)."""
    B = u.shape[0]
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q, k, v = _attn_project(cfg, p, u)
    with jax.named_scope("attn_core"):
        # The new rows go in through a view (slots, positions, heads, head size), the shape ``granite_hybrid`` keeps:
        # the swap moves nothing, and XLA then writes the position where the rows lie (compiled for a described v5e,
        # PR 35: no temporary of the cache's size; on the rows as kept it lays all six caches out anew, twice a step).
        # The kernel takes the rows as the state keeps them, XLA's core the view.
        def write(cache, new):  # slot by slot, in place; an inactive slot keeps what it held
            for b in range(B):
                at = (b, positions[b], 0, 0)
                old = jax.lax.dynamic_slice(cache, at, (1, 1, KV, hd))
                cache = jax.lax.dynamic_update_slice(cache, jnp.where(active[b], new[b][None], old), at)
            return cache

        view_k, view_v = write(jnp.swapaxes(st["k"], 1, 2), k), write(jnp.swapaxes(st["v"], 1, 2), v)
        cache_k, cache_v = jnp.swapaxes(view_k, 1, 2), jnp.swapaxes(view_v, 1, 2)
        fused = pallas_cache_attention.cache_attention_applies(q.shape, cache_k.shape, cfg.dtype)
        decoders.note_on_serving_span("attn", "fused" if fused else "xla")
        if fused:  # every active slot over the blocks up to its own position, the rows as the state keeps them
            out = pallas_cache_attention.cache_attention(q, cache_k, cache_v, jnp.arange(B), positions,
                                                         active.astype(jnp.int32), scale=hd ** -0.5)
        else:  # every slot over every position it could hold
            out = decoders.attention_core(q, view_k, view_v, positions[:, None], hd ** -0.5, cfg.dtype)
    with jax.named_scope("attn_proj"):
        return _attn_out(cfg, p, out), {"k": cache_k, "v": cache_v}


# ---------------------------------------------------------------------- #
# The model                                                               #
# ---------------------------------------------------------------------- #
class OlmoHybridLM:
    """The decoder over a parameter tree, as the serving protocol sees it."""

    def __init__(self, cfg: OlmoHybridConfig):
        self.cfg = cfg

    @property
    def vocab_size(self) -> int:
        return self.cfg.vocab_size

    @property
    def prefill_multiple(self) -> int:
        """A prefill chunk is whole chunks of the delta rule."""
        return self.cfg.linear_chunk_size

    def init_state(self, slots: int, positions: int):
        cfg = self.cfg
        state = []
        for kind in cfg.layer_types:
            if kind == LINEAR:
                state.append({
                    "S": jnp.zeros((slots, cfg.linear_num_value_heads, cfg.linear_value_head_dim,
                                    cfg.linear_key_head_dim), jnp.float32),
                    "conv": jnp.zeros((slots, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim), cfg.dtype)})
            else:
                kv = (slots, cfg.num_key_value_heads, -(-positions // ROW_TILE) * ROW_TILE, cfg.head_dim)
                state.append({"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype)})
        return state

    def copy_state(self, state, src, dst):
        return decoders.copy_slot(state, src, dst)

    def _forward(self, params, state, tokens, linear, attend):
        """The layers. ``linear(p, u, st)`` and ``attend(p, u, st)`` -> (out,
        st) are this program's two mixers. -> (h (B, T, d) float32, state)."""
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        h = params["embed"][tokens].astype(jnp.float32)
        new_state = []
        for i, (kind, p, st) in enumerate(zip(cfg.layer_types, params["layers"], state)):
            with jax.named_scope(f"layer_{i}"):
                out, st = (linear if kind == LINEAR else attend)(p, h.astype(cfg.dtype), st)
                new_state.append(st)
                h = h + rms(out, p["mixer_norm"], eps)
                with jax.named_scope("mlp"):
                    y = gated_mlp(h.astype(cfg.dtype), p["mlp_in"], p["mlp_out"], cfg.dtype)
                h = h + rms(y, p["mlp_norm"], eps)
        return h, new_state

    def _head(self, params, h):
        with jax.named_scope("head"):
            h = rms(h, params["final_norm"], self.cfg.rms_norm_eps).astype(self.cfg.dtype)
            return jnp.einsum("...d,vd->...v", h, params["head"], preferred_element_type=jnp.float32)

    def prefill(self, params, state, tokens, slots, starts, lengths):
        """Advance ``slots`` (B,) over one chunk: tokens (B, T) right-padded,
        the chunk's first position ``starts`` (B,) and its valid length
        ``lengths`` (B,; 0 leaves the slot as it was). -> (state, logits (B, V)
        after each row's last valid token, counts: none)."""
        cfg = self.cfg
        B, T = tokens.shape
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        fresh = (starts == 0) & (lengths > 0)

        def linear(p, u, st):  # these rows' recurrent state; a prompt's first chunk starts from nothing
            rows = jax.tree_util.tree_map(
                lambda a: jnp.where(fresh.reshape((B,) + (1,) * (a.ndim - 1)), 0, a[slots]), st)
            out, rows = _linear(cfg, p, u, rows, valid, lengths, single_step=False)
            return out, jax.tree_util.tree_map(lambda a, r: a.at[slots].set(r), st, rows)

        h, state = self._forward(params, state, tokens, linear,
                                 lambda p, u, st: _attn_prefill(cfg, p, u, st, slots, starts, lengths))
        last = jnp.take_along_axis(h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        return state, self._head(params, last), {}

    def decode(self, params, state, tokens, positions, active):
        """One token for every slot: tokens, positions, active (slots,). An
        inactive slot's state is left as it was. -> (state, logits, counts: none)."""
        cfg = self.cfg
        h, state = self._forward(
            params, state, tokens[:, None],
            lambda p, u, st: _linear(cfg, p, u, st, active[:, None], active.astype(jnp.int32), single_step=True),
            lambda p, u, st: _attn_decode(cfg, p, u, st, positions, active))
        return state, self._head(params, h[:, 0]), {}


decoders.register(SIZES, from_name=OlmoHybridConfig.from_name, init=init_olmo_params,
                  model=OlmoHybridLM, cut_options=CUT_OPTIONS)
