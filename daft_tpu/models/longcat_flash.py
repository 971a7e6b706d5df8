"""LongCat-Flash decoder: double layers of two latent attentions (MLA) and two
dense FFNs with one expert branch taken after the first attention and added
after the second FFN (shortcut-connected MoE), a router over routed and
zero-computation (identity) experts, RMSNorm (eps 1e-5), untied embedding and
head. ``x`` is the residual stream (float32 here; products take bfloat16
operands and accumulate in float32):

    a  = x  + MLA_0(norm(x))            u = norm(a)
    m  = MoE(u)                         # the shortcut branch: taken here ...
    b  = a  + FFN_0(u)                  # dense SwiGLU, hidden -> ffn_hidden -> hidden
    c  = b  + MLA_1(norm(b))
    d  = c  + FFN_1(norm(c))
    x' = d  + m                         # ... added here
    logits = norm(x_last) @ W_head

    MLA   q         = (norm(x W_qa) * s_q) W_qb  -> H x [q_nope | q_r],  s_q  = sqrt(hidden / q_lora_rank)
          [c | k_r] = x W_kva;   c = norm(c) * s_kv,                      s_kv = sqrt(hidden / kv_lora_rank)
          [k_nope | v] = c W_kvb -> H x [nope | v];   q_r, k_r = RoPE(q_r), RoPE(k_r)   (k_r one vector a token,
          shared by all heads; pairs interleaved (2i, 2i+1), theta = rope_theta)
          score = (q_nope . k_nope + q_r . k_r) / sqrt(nope + rope), causal;  out = softmax(score) v -> W_o
    MoE   s = softmax(u W_r) over all routed + zero outputs, float32;  chosen = top-k of (s + bias);
          w_e = routed_scaling_factor * s_e  (the bias moves the choice, not the weight; no renormalisation)
          MoE(u) = sum_{e chosen, routed} w_e SwiGLU_e(u)  +  (sum_{e chosen, zero} w_e) u

**The latent attention** (the cache row ``[c | RoPE(k_r)]``, slot state one
``{"kv": (slots, row, positions)}`` an attention, two a layer, positions minor;
prefill *expanded*, on a TPU at the published widths one Pallas kernel,
``ops/pallas_mla_attention.py``, elsewhere XLA's loop over blocks; decode
*absorbed*; noted on the batcher's open span as ``mla`` = ``fused`` |
``expanded`` | ``absorbed``) is ``models/latent_attention.py``, shared with
``models/deepseek_v32``: this model calls it with both LoRA scales, plain rotary
frequencies (theta ``rope_theta``), the softmax scale ``(nope + rope) ** -0.5``
and no selection.

**The cut.** ``expert_shard = (rank, size)``: the routed experts held (a
contiguous ``n_routed_experts / size``). The router ranks all routed and zero
outputs with the published top-k; the held experts' part and the identity part
are computed, what the absent experts would add is left out (the dispatch is
``models/decoders.held_experts_part``, shared with ``granite_hybrid``).
``vocab_shard`` slices embedding and head by rows: ids and logits are over the
slice. ``num_layers`` keeps the first layers. Nothing stands in for the other chips.

**Random weights and the two scales.** Matrices are drawn at fan-in scale, and
``W_kvb`` at fan-in scale over ``kv_scale`` (rule ``latent_up``): its input is
the normed latent times ``kv_scale``, so keys and values leave at the stream's
scale, while queries keep their ``q_scale`` (rms 2) and the scores spread ~2.
With ``W_kvb`` at plain fan-in scale the scores spread ~7 over thousands of
keys, every attention picks a handful of them, and rounding grows from layer to
layer until bfloat16 and float8 read alike against float32 (my chip run, PR 33).

Plain functions over a parameter tree, drawn tensor by tensor on the device in
bfloat16 (the router's bias in float32); ``jax.named_scope`` names the parts
(``mla_proj``, ``mla_core``, ``dense_mlp``, ``router``, ``experts``,
``zero_experts``, ``head``) for the device trace.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders, latent_attention
from daft_tpu.models.decoders import draw, gated_mlp, mm, rms

#: Published sizes by exact model name (``config.json`` of the source). Kept as data: no substring rule.
PUBLISHED: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json
    "LongCat-Flash-Chat": dict(
        vocab_size=131072, hidden_size=6144, ffn_hidden_size=12288, expert_ffn_hidden_size=2048, num_layers=28,
        num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
        qk_nope_head_dim=128, mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6.0,
        n_routed_experts=512, zero_expert_num=256, moe_topk=12, rms_norm_eps=1e-5, rope_theta=1e7),
}
#: Not published: the same ratios at a width the CPU tests and ``chip_smoke.py`` can afford. The scaling factor
#: keeps the expert branch's weight: 6 x the top 12 of 768 scores is ~0.5, and so is 1 x the top 3 of 12.
TEST_SIZES: Dict[str, Dict[str, Any]] = {
    "longcat-flash-tiny": dict(
        vocab_size=256, hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=24, num_layers=2,
        num_attention_heads=4, kv_lora_rank=8, q_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=1.0,
        n_routed_experts=8, zero_expert_num=4, moe_topk=3, rms_norm_eps=1e-5, rope_theta=1e7),
}
#: Every name ``from_name`` resolves.
SIZES = {**PUBLISHED, **TEST_SIZES}
#: Options of ``prompt`` that cut a published model to one chip's share.
CUT_OPTIONS = ("num_layers", "expert_shard", "vocab_shard")
#: Embedding and head are drawn in blocks of this many rows, so that a slice's rows are the whole table's.
EMBED_BLOCK_ROWS = 64
#: Standard deviation of the embedding's rows (the head is untied and drawn at fan-in scale).
EMBED_STD = 1.0


@dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int
    hidden_size: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    num_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    qk_rope_head_dim: int
    v_head_dim: int
    qk_nope_head_dim: int
    mla_scale_q_lora: bool
    mla_scale_kv_lora: bool
    routed_scaling_factor: float
    n_routed_experts: int
    zero_expert_num: int
    moe_topk: int
    rms_norm_eps: float
    rope_theta: float
    expert_shard: Tuple[int, int] = (0, 1)
    vocab_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_name(name: str, num_layers: int = None, expert_shard=(0, 1), vocab_shard=(0, 1)) -> "LongcatFlashConfig":
        if name not in SIZES:
            raise DaftValueError(
                f"unknown LongCat-Flash decoder {name!r}; the published sizes on record are {sorted(PUBLISHED)}")
        cfg = LongcatFlashConfig(**SIZES[name])
        layers = int(num_layers or cfg.num_layers)
        if not 0 < layers <= cfg.num_layers:
            raise DaftValueError(f"num_layers={layers} is outside the published {name!r}")
        cfg = replace(cfg, num_layers=layers, expert_shard=tuple(int(x) for x in expert_shard),
                      vocab_shard=tuple(int(x) for x in vocab_shard))
        decoders.check_shards((("expert_shard", cfg.expert_shard, cfg.n_routed_experts),
                               ("vocab_shard", cfg.vocab_shard, cfg.vocab_size // EMBED_BLOCK_ROWS)))
        return cfg

    # -- derived sizes ---------------------------------------------------- #
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """Values of one token's cache row: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def held_experts(self) -> int:
        return self.n_routed_experts // self.expert_shard[1]

    @property
    def first_expert(self) -> int:
        return self.expert_shard[0] * self.held_experts

    @property
    def held_vocab(self) -> int:
        return self.vocab_size // self.vocab_shard[1]

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0


# ---------------------------------------------------------------------- #
# Parameters: drawn tensor by tensor on the device, bfloat16              #
# ---------------------------------------------------------------------- #
def tensor_specs(cfg: LongcatFlashConfig) -> List[Tuple[str, tuple, str]]:
    """One double layer's tensors in the order their keys are folded: (name,
    shape, rule). ``benchmark/reference/longcat_flash.py`` states the same rules."""
    d, H = cfg.hidden_size, cfg.num_attention_heads
    f, fe = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    specs = []
    for s in ("0", "1"):
        specs += [("attn_norm" + s, (d,), "norm"),
                  ("q_a" + s, (d, cfg.q_lora_rank), "matrix"), ("q_a_norm" + s, (cfg.q_lora_rank,), "norm"),
                  ("q_b" + s, (cfg.q_lora_rank, H * cfg.qk_head_dim), "matrix"),
                  ("kv_a" + s, (d, cfg.cache_row), "matrix"), ("kv_a_norm" + s, (cfg.kv_lora_rank,), "norm"),
                  ("kv_b" + s, (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), "latent_up"),
                  ("o" + s, (H * cfg.v_head_dim, d), "matrix"),
                  ("ffn_norm" + s, (d,), "norm"),
                  ("ffn_in" + s, (d, 2 * f), "matrix"), ("ffn_out" + s, (f, d), "matrix")]
    return specs + [("router", (d, cfg.router_outputs), "matrix"), ("router_bias", (cfg.router_outputs,), "router_bias"),
                    ("w_in", (d, 2 * fe), "experts"), ("w_out", (fe, d), "experts")]


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_tensor(cfg: LongcatFlashConfig, key, shape: tuple, rule: str):
    """One tensor, so that no more than one is ever held in float32 (a layer's
    would be 5 GB at the published widths). An expert's weights come from its
    global id, whoever holds it, one expert at a time."""
    if rule == "experts":
        held = cfg.first_expert + jnp.arange(cfg.held_experts)
        return jax.lax.map(lambda e: draw(jax.random.fold_in(key, e), shape, "matrix").astype(cfg.dtype), held)
    if rule == "latent_up":  # its input arrives at kv_scale times the stream's scale: keys and values leave at the stream's
        return draw(key, shape, "matrix", gain=1.0 / cfg.kv_scale).astype(cfg.dtype)
    return draw(key, shape, rule).astype(jnp.float32 if rule == "router_bias" else cfg.dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_rows(cfg: LongcatFlashConfig, key, std: float):
    blocks = cfg.held_vocab // EMBED_BLOCK_ROWS
    rows = decoders.draw_row_blocks(key, cfg.vocab_shard[0] * blocks, blocks, EMBED_BLOCK_ROWS, cfg.hidden_size)
    return (rows * std).astype(cfg.dtype)


def init_longcat_params(cfg: LongcatFlashConfig, seed: int = 0):
    """-> (model, params). Key 0 of the seed draws the embedding (0), the final
    norm (1) and the head (2); key i + 1 layer i, tensor j of ``tensor_specs``
    from the layer's key folded with j."""
    root = jax.random.PRNGKey(seed)
    k0 = jax.random.fold_in(root, 0)
    params = {"embed": _init_rows(cfg, jax.random.fold_in(k0, 0), EMBED_STD),
              "final_norm": draw(jax.random.fold_in(k0, 1), (cfg.hidden_size,), "norm").astype(cfg.dtype),
              "head": _init_rows(cfg, jax.random.fold_in(k0, 2), cfg.hidden_size ** -0.5),
              "layers": [{name: _init_tensor(cfg, jax.random.fold_in(jax.random.fold_in(root, i + 1), j), shape, rule)
                          for j, (name, shape, rule) in enumerate(tensor_specs(cfg))}
                         for i in range(cfg.num_layers)]}
    return LongcatFlashLM(cfg), params


# ---------------------------------------------------------------------- #
# Latent attention: ``models/latent_attention.py``, with this model's scales #
# ---------------------------------------------------------------------- #
mla_core_absorbed = latent_attention.core_absorbed
mla_core_expanded = latent_attention.core_expanded
mla_expanded_over_slots = latent_attention.expanded_over_slots


def rope(x, positions, theta: float):
    """Rotary positions over the last axis, pairs interleaved: (x[2i], x[2i+1])
    turns by ``positions * theta ** (-2i / n)``. x (B, T, ..., n), positions
    (B, T). float32 in and out."""
    return latent_attention.rope(x, positions, latent_attention.frequencies(theta, x.shape[-1]))


def mla_project(cfg, p, s: str, x, positions):
    """x (B, T, d) normed -> (q (B, T, H, nope + rope) with its rotary part turned,
    the tokens' cache rows (B, T, cache_row)), both bfloat16: the shared
    projection with both LoRA scales and plain rotary frequencies."""
    inv = latent_attention.frequencies(cfg.rope_theta, cfg.qk_rope_head_dim)
    return latent_attention.project(cfg, p, s, x, positions, inv, cfg.q_scale, cfg.kv_scale)[:2]


def _mla_prefill(cfg, p, s, x, kv, slots, starts, lengths):
    """One chunk for the rows ``slots`` of ``kv`` (slots, cache_row, S): write
    the chunk's valid rows (``lengths`` of each, from ``starts``), then attend
    over the blocks held. -> (out (B, T, d) float32, kv)."""
    B, T, _ = x.shape
    positions = starts[:, None] + jnp.arange(T)[None, :]
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    with jax.named_scope("mla_proj"):
        q, rows = mla_project(cfg, p, s, x, positions)
    with jax.named_scope("mla_core"):
        kv = latent_attention.write_chunk(kv, rows, slots, starts, valid)
        out = latent_attention.attend_chunk(cfg, latent_attention.kv_b(cfg, p, s), q, kv, slots, starts, lengths)
    with jax.named_scope("mla_proj"):
        return mm(out.astype(cfg.dtype).reshape(B, T, -1), p["o" + s]), kv


def _mla_decode(cfg, p, s, x, kv, positions, active):
    """One token for every slot: x (slots, 1, d), positions, active (slots,). -> (out, kv)."""
    B = x.shape[0]
    with jax.named_scope("mla_proj"):
        q, rows = mla_project(cfg, p, s, x, positions[:, None])
    with jax.named_scope("mla_core"):
        kv = latent_attention.write_token(kv, rows[:, 0], positions, active)
        out = latent_attention.attend_token(cfg, latent_attention.kv_b(cfg, p, s), q, kv, positions)
    with jax.named_scope("mla_proj"):
        return mm(out.astype(cfg.dtype).reshape(B, 1, -1), p["o" + s]), kv


# ---------------------------------------------------------------------- #
# The expert branch                                                       #
# ---------------------------------------------------------------------- #
def route(cfg, p, u):
    """u (n, d) float32 normed -> (idx (n, k) chosen outputs, weights (n, k)
    float32): the softmax over all routed and zero outputs in float32, the
    choice by score + bias, the weight by the score alone times the scaling
    factor, not renormalised."""
    r = jnp.einsum("nd,de->ne", u, p["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(r, axis=-1)
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.moe_topk)
    return idx, cfg.routed_scaling_factor * jnp.take_along_axis(s, idx, axis=-1)


def _moe(cfg, p, u, valid):
    """u (n, d) float32 normed; valid (n,). -> (the held experts' part + the
    identity experts' part (n, d) float32, counts)."""
    with jax.named_scope("router"):
        idx, w = route(cfg, p, u)
    with jax.named_scope("experts"):
        y, held, sizes = decoders.held_experts_part(u.astype(cfg.dtype), idx, w, valid, cfg.first_expert,
                                                    p["w_in"], p["w_out"], cfg.dtype)
    with jax.named_scope("zero_experts"):
        zero = (idx >= cfg.n_routed_experts) & valid[:, None]
        y = y + jnp.sum(jnp.where(zero, w, 0.0), axis=-1, keepdims=True) * u
    counts = {"assignments": jnp.sum(valid) * cfg.moe_topk, "zero_assignments": jnp.sum(zero),
              "held_assignments": jnp.sum(held), "max_expert_load": jnp.max(sizes),
              "experts_reached": jnp.sum(sizes > 0)}
    return y, counts


# ---------------------------------------------------------------------- #
# The model                                                               #
# ---------------------------------------------------------------------- #
class LongcatFlashLM:
    """The decoder over a parameter tree, as the serving protocol sees it."""

    def __init__(self, cfg: LongcatFlashConfig):
        self.cfg = cfg

    @property
    def vocab_size(self) -> int:
        """Ids and logits are over the held slice."""
        return self.cfg.held_vocab

    def init_state(self, slots: int, positions: int):
        cfg = self.cfg
        return [{"kv": jnp.zeros((slots, cfg.cache_row, positions), cfg.dtype)} for _ in range(2 * cfg.num_layers)]

    def copy_state(self, state, src, dst):
        return decoders.copy_slot(state, src, dst)

    def _forward(self, params, state, tokens, valid, attend):
        """The double layers. ``attend(p, s, x, kv) -> (out, kv)`` is the
        attention of this program. -> (x (B, T, d) float32, state, counts)."""
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.rms_norm_eps
        norm = lambda x, w: rms(x, w, eps)  # noqa: E731  (float32 in, float32 out)
        x = params["embed"][tokens].astype(jnp.float32)
        new_state, totals = [], None
        for i, p in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{i}"):
                out, kv0 = attend(p, "0", norm(x, p["attn_norm0"]).astype(cfg.dtype), state[2 * i]["kv"])
                x = x + out
                u = norm(x, p["ffn_norm0"])
                m, counts = _moe(cfg, p, u.reshape(B * T, -1), valid.reshape(-1))
                totals = decoders.add_counts(totals, counts)
                with jax.named_scope("dense_mlp"):
                    x = x + gated_mlp(u.astype(cfg.dtype), p["ffn_in0"], p["ffn_out0"], cfg.dtype)
                out, kv1 = attend(p, "1", norm(x, p["attn_norm1"]).astype(cfg.dtype), state[2 * i + 1]["kv"])
                x = x + out
                with jax.named_scope("dense_mlp"):
                    x = x + gated_mlp(norm(x, p["ffn_norm1"]).astype(cfg.dtype), p["ffn_in1"], p["ffn_out1"], cfg.dtype)
                x = x + m.reshape(B, T, -1)
                new_state += [{"kv": kv0}, {"kv": kv1}]
        return x, new_state, totals

    def _head(self, params, x):
        with jax.named_scope("head"):
            h = rms(x, params["final_norm"], self.cfg.rms_norm_eps).astype(self.cfg.dtype)
            return jnp.einsum("...d,vd->...v", h, params["head"], preferred_element_type=jnp.float32)

    def prefill(self, params, state, tokens, slots, starts, lengths):
        """Advance ``slots`` (B,) over one chunk: tokens (B, T) right-padded,
        the chunk's first position ``starts`` (B,) and its valid length
        ``lengths`` (B,; 0 leaves the slot as it was). -> (state, logits (B, V)
        after each row's last valid token, counts)."""
        cfg = self.cfg
        T = tokens.shape[1]
        x, state, counts = self._forward(
            params, state, tokens, jnp.arange(T)[None, :] < lengths[:, None],
            lambda p, s, x, kv: _mla_prefill(cfg, p, s, x, kv, slots, starts, lengths))
        last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        return state, self._head(params, last), counts

    def decode(self, params, state, tokens, positions, active):
        """One token for every slot: tokens, positions, active (slots,). An
        inactive slot's state is left as it was. -> (state, logits, counts)."""
        cfg = self.cfg
        x, state, counts = self._forward(
            params, state, tokens[:, None], active[:, None],
            lambda p, s, x, kv: _mla_decode(cfg, p, s, x, kv, positions, active))
        return state, self._head(params, x[:, 0]), counts


decoders.register(SIZES, from_name=LongcatFlashConfig.from_name, init=init_longcat_params,
                  model=LongcatFlashLM, cut_options=CUT_OPTIONS)
