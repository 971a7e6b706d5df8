"""DeepSeek-V3.2-Exp decoder: latent attention (MLA) whose keys a learned indexer
selects (DeepSeek sparse attention: the ``index_topk`` best keys of every query,
exactly), YaRN-scaled rotary positions, leading dense layers, then expert layers
with a sigmoid router limited to ``topk_group`` of ``n_group`` expert groups
beside a shared expert every token passes. RMSNorm (eps 1e-6) but the
indexer's key norm (LayerNorm with bias); embedding and head untied. ``x`` is the
residual stream (float32 here; products take bfloat16 operands and accumulate in
float32):

    layer i <  first_k_dense_replace:  a = x + Attn(norm(x));  x' = a + SwiGLU_dense(norm(a))
    layer i >= first_k_dense_replace:  a = x + Attn(norm(x));  u = norm(a)
                                       x' = a + Shared(u) + sum_{e chosen} w_e Expert_e(u)
    logits = norm(x_last) @ W_head

    MLA    ``models/latent_attention.py`` with no LoRA scales, YaRN's frequencies f' and
           scale = (nope + rope) ** -0.5 * mscale ** 2,  mscale = 0.1 * mscale_all_dim * ln(factor) + 1
    YaRN   f_i = theta ** (-2i / n), n = qk_rope_head_dim;  low, high = floor / ceil of d(beta_fast), d(beta_slow),
           d(b) = n ln(original / (2 pi b)) / (2 ln theta), clipped to [0, n - 1];
           ramp_i = clip((i - low) / (high - low), 0, 1);   f'_i = f_i / factor * ramp_i + f_i * (1 - ramp_i)
    Index  q^I = c_q W^I_q -> Hi x Di (c_q: the MLA's normed low-rank query);  k^I = LayerNorm(x W^I_k) (Di, bias);
           the FIRST qk_rope_head_dim of each Di turned by RoPE with f', halves paired (x[i], x[i + n / 2]);
           w = x W^I_w * Hi ** -0.5 * Di ** -0.5  (float32)
           I(t, s) = sum_h w_h(t) relu(q^I_h(t) . k^I(s)),  s <= t;   S(t) = the min(t + 1, index_topk) keys of largest I(t, .)
    Attn   softmax over s in S(t) only;  out = (sum_s p v) W_o
    Router s = sigmoid(u W_r) (float32);  c = s + bias;  a group's score = the sum of its 2 largest c;
           keep the topk_group best groups;  chosen = top num_experts_per_tok of c inside them;
           w_e = routed_scaling_factor * s_e / sum_{chosen} s    (the bias moves the choice only)

**Selection is exact and masked.** ``kth_threshold`` finds each query's
``index_topk``-th largest index score by bisection over the float32 bit
pattern (16 readings of the scores, two bits each, no sort): the set kept
is ``{s <= t : I(t, s) >= threshold(t)}``, the top-k set itself unless several
keys tie at the threshold (all of them are then kept: one or more keys over
``index_topk``; with continuous scores that is a rounding event). A query at
a position below ``index_topk`` keeps every causal key. The latent attention
then runs in its *masked* form: every block of cache rows a row holds is
expanded and scored and the keys not kept are masked out of the softmax
(``latent_attention.attend_chunk``, on a TPU ``ops/pallas_mla_attention.py``
with the selection as an input), and a decode step masks the absorbed form.
Noted on the batcher's open span as ``dsa`` = ``masked``. The index scores of a
prefill call are ``ops/pallas_dsa_index.py`` on a TPU at widths that fill lane
tiles and ``index_scores_expanded`` (XLA's loop over the blocks the deepest
row attends) elsewhere. The indexer runs in bfloat16 with float32 accumulation
(the published one in FP8 after a Hadamard rotation of q and k, which is
orthogonal and leaves q . k as it is in exact arithmetic).

**Slot state** is two row leaves a layer: ``kv`` (slots, kv_lora_rank +
qk_rope_head_dim, positions), the latent cache, and ``ik`` (slots,
index_head_dim, positions), the indexer's keys; positions minor in both.

**The cut.** ``num_layers`` keeps the first dense layer and the expert layers
after it (leading dense layers count once: a cut model has one);
``expert_shard = (rank, size)``: the routed experts held (a contiguous
``n_routed_experts / size``); the router ranks all routed experts, renormalises
over all chosen, the held experts' part and the shared expert's are computed
and what the absent experts would add is left out. ``vocab_shard`` slices
embedding and head by rows. Nothing stands in for the other chips. The
multi-token-prediction block (``num_nextn_predict_layers``) is not part of the
served model's logits and is left out.

**Random weights and the two gains.** Matrices are drawn at fan-in scale. At the
published size ``W_qb`` is drawn at a quarter of it (``query_gain``) and each routed
expert's ``W_out`` at a tenth (``expert_gain``): a hard top-k and a router whose
choices weigh 0.31 both turn bfloat16 rounding into discrete events (a key
swapped at a threshold, an expert flipped at a near tie), and at plain fan-in
scale those, not the products' rounding, set the distance to the float32
reference (``PUBLISHED`` has the readings). Neither moves a shape or a count.

Plain functions over a parameter tree, drawn tensor by tensor on the device in
bfloat16 (the router's bias in float32); ``jax.named_scope`` names the parts
(``mla_proj``, ``indexer``, ``select``, ``mla_core``, ``dense_mlp``, ``router``,
``experts``, ``head``) for the device trace.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders, latent_attention
from daft_tpu.models.decoders import draw, gated_mlp, mm, rms
from daft_tpu.ops import pallas_dsa_index

#: Published sizes by exact model name (``config.json`` of the source). Kept as data: no substring rule.
PUBLISHED: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json
    "DeepSeek-V3.2-Exp": dict(
        vocab_size=129280, hidden_size=7168, intermediate_size=18432, moe_intermediate_size=2048,
        num_hidden_layers=61, first_k_dense_replace=3, num_attention_heads=128, kv_lora_rank=512, q_lora_rank=1536,
        qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128, index_n_heads=64, index_head_dim=128,
        index_topk=2048, n_routed_experts=256, n_shared_experts=1, num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000.0, rope_factor=40.0,
        original_max_position_embeddings=4096, beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0,
        # Not published: a drawing rule. W_qb is drawn at fan-in scale times this, so that the attention's scores spread
        # ~0.5 and not the ~1.9 plain fan-in weights give under mscale ** 2. A hard top-k amplifies rounding: ~15 of a
        # query's 2,048 kept keys differ between the bfloat16 program and the float32 reference (keys within rounding of
        # the threshold), and with random weights the indexer's choice is independent of the attention's scores. At a
        # spread of 1.9 a softmax rests on ~55 of its keys and a swap of one of those moves percents of it, enough to
        # flip near-tied router choices downstream: the program read logprob_gap 0.27-0.61 against the fp8 control's
        # 0.54-1.03 on seven seeds and no limit parted them (my chip run, PR 40); at ~0.5 it rests on ~1,500 keys. (At
        # the tiny top 32 the same gain does harm, every swap then being 1/32 of a flat softmax: the tiny size keeps 1.)
        query_gain=0.25,
        # Not published either: each routed expert's W_out is drawn at fan-in scale times this. The router's eight
        # choices weigh 2.5 / 8 = 0.31 each, and a near tie between the eighth and ninth of 256 sigmoid scores flips on
        # bfloat16 rounding in a few token-layers in a hundred; where the flipped expert is held, a token's logits move
        # by 0.3-0.6 at fan-in scale (LongCat's choices weigh ~0.05, which is why its logprob_gap reads 0.02-0.08). With
        # the two gains the seven seeds read 0.17-0.54 before this one and the control 0.40-0.92: still no limit. A CPU
        # run at the tiny widths with this router (256 experts, top 8 of 4 groups, 16 held, no selection) read spikes of
        # 0.49-0.61 on 1-2 tokens of 97 at gain 1 and 0.08-0.12 at 0.2, the fp8 control's median 0.25 either way.
        expert_gain=0.1),
}
#: Not published: the same mechanisms at a width the CPU tests and ``chip_smoke.py`` can afford: selection bites
#: beyond 32 positions, positions pass YaRN's original length of 16, three experts of two of four groups; 16 index heads, so that
#: no key's index score is an exact 0 (every head's ReLU shut: 2 ** -16 a key), which would tie at a threshold.
TEST_SIZES: Dict[str, Dict[str, Any]] = {
    "deepseek-v32-tiny": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=24,
        num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=8, q_lora_rank=16,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, index_n_heads=16, index_head_dim=16,
        index_topk=32, n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=3, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, rms_norm_eps=1e-6, rope_theta=10000.0, rope_factor=4.0,
        original_max_position_embeddings=16, beta_fast=32.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
}
#: Every name ``from_name`` resolves.
SIZES = {**PUBLISHED, **TEST_SIZES}
#: Options of ``prompt`` that cut a published model to one chip's share.
CUT_OPTIONS = ("num_layers", "expert_shard", "vocab_shard")
#: Embedding and head are drawn in blocks of this many rows, so that a slice's rows are the whole table's
#: (an eighth of 129,280 rows is 16,160: no multiple of 64).
EMBED_BLOCK_ROWS = 32
#: Standard deviation of the embedding's rows (the head is untied and drawn at fan-in scale).
EMBED_STD = 1.0
#: Heads a grid step of the prefill kernel takes at most here: the visits of one block read its (T, T) index scores
#: once a head group, so fewer groups (128 heads in 32 steps a visit, as LongCat's 64 in 32).
KERNEL_HEADS = 4
#: A slot's rows are held in whole lane tiles of positions: with positions that end inside a tile (32,833) XLA lays the
#: indexer's keys (slots, 128, positions) out positions-major, slots x values filling a tile exactly, and every
#: kernel and product over them gets a copy of the leaf in and another out (ten a prefill call at the cell's cut, as
#: compiled for a described v5e, PR 40; ``olmo_hybrid.ROW_TILE`` met the same).
POSITION_TILE = 128
_LOW = float(np.finfo(np.float32).min)


@dataclass(frozen=True)
class DeepseekV32Config:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    kv_lora_rank: int
    q_lora_rank: int
    qk_rope_head_dim: int
    v_head_dim: int
    qk_nope_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    rope_factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    query_gain: float = 1.0
    expert_gain: float = 1.0
    expert_shard: Tuple[int, int] = (0, 1)
    vocab_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_name(name: str, num_layers: int = None, expert_shard=(0, 1), vocab_shard=(0, 1)) -> "DeepseekV32Config":
        if name not in SIZES:
            raise DaftValueError(
                f"unknown DeepSeek-V3.2 decoder {name!r}; the published sizes on record are {sorted(PUBLISHED)}")
        cfg = DeepseekV32Config(**SIZES[name])
        layers = int(num_layers or cfg.num_hidden_layers)
        if not 1 < layers <= cfg.num_hidden_layers:
            raise DaftValueError(f"num_layers={layers} is outside the published {name!r} (a dense and an expert layer at least)")
        # leading dense layers count once: a cut keeps the first of them and the expert layers that follow
        dense = cfg.first_k_dense_replace if layers == cfg.num_hidden_layers else 1
        cfg = replace(cfg, num_hidden_layers=layers, first_k_dense_replace=dense,
                      expert_shard=tuple(int(x) for x in expert_shard), vocab_shard=tuple(int(x) for x in vocab_shard))
        decoders.check_shards((("expert_shard", cfg.expert_shard, cfg.n_routed_experts),
                               ("vocab_shard", cfg.vocab_shard, cfg.vocab_size // EMBED_BLOCK_ROWS)))
        return cfg

    # -- derived sizes ---------------------------------------------------- #
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """Values of one token's latent cache row: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

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
    def attention_mscale(self) -> float:
        return 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0 if self.rope_factor > 1 else 1.0

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5 * self.attention_mscale ** 2


def yarn_frequencies(cfg) -> np.ndarray:
    """YaRN's rotary frequencies (float32, ``qk_rope_head_dim / 2`` of them; the
    latent attention and the indexer turn by the same): dimensions that turn
    more than ``beta_fast`` times over the original length keep their frequency,
    those under ``beta_slow`` turns are divided by ``factor``, a linear ramp between."""
    n = cfg.qk_rope_head_dim
    i = np.arange(n // 2, dtype=np.float64)
    f = cfg.rope_theta ** (-2.0 * i / n)

    def dim_of(turns: float) -> float:
        return n * math.log(cfg.original_max_position_embeddings / (2 * math.pi * turns)) / (2 * math.log(cfg.rope_theta))

    low, high = max(math.floor(dim_of(cfg.beta_fast)), 0), min(math.ceil(dim_of(cfg.beta_slow)), n - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / cfg.rope_factor * ramp + f * (1.0 - ramp)).astype(np.float32)


# ---------------------------------------------------------------------- #
# Parameters: drawn tensor by tensor on the device, bfloat16              #
# ---------------------------------------------------------------------- #
def tensor_specs(cfg: DeepseekV32Config, dense: bool) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape,
    rule). ``benchmark/reference/deepseek_v32.py`` states the same rules."""
    d, H, Hi, Di = cfg.hidden_size, cfg.num_attention_heads, cfg.index_n_heads, cfg.index_head_dim
    specs = [("attn_norm", (d,), "norm"),
             ("q_a", (d, cfg.q_lora_rank), "matrix"), ("q_a_norm", (cfg.q_lora_rank,), "norm"),
             ("q_b", (cfg.q_lora_rank, H * cfg.qk_head_dim), "query_up"),
             ("kv_a", (d, cfg.cache_row), "matrix"), ("kv_a_norm", (cfg.kv_lora_rank,), "norm"),
             ("kv_b", (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), "matrix"),
             ("o", (H * cfg.v_head_dim, d), "matrix"),
             ("idx_q", (cfg.q_lora_rank, Hi * Di), "matrix"), ("idx_k", (d, Di), "matrix"),
             ("idx_k_norm", (Di,), "norm"), ("idx_k_bias", (Di,), "bias"), ("idx_w", (d, Hi), "matrix"),
             ("ffn_norm", (d,), "norm")]
    if dense:
        f = cfg.intermediate_size
        return specs + [("ffn_in", (d, 2 * f), "matrix"), ("ffn_out", (f, d), "matrix")]
    fs, fe = cfg.n_shared_experts * cfg.moe_intermediate_size, cfg.moe_intermediate_size
    return specs + [("shared_in", (d, 2 * fs), "matrix"), ("shared_out", (fs, d), "matrix"),
                    ("router", (d, cfg.n_routed_experts), "matrix"), ("router_bias", (cfg.n_routed_experts,), "router_bias"),
                    ("w_in", (d, 2 * fe), "experts"), ("w_out", (fe, d), "experts_out")]


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_tensor(cfg: DeepseekV32Config, key, shape: tuple, rule: str):
    """One tensor, so that no more than one is ever held in float32. An expert's
    weights come from its global id, whoever holds it, one expert at a time."""
    if rule in ("experts", "experts_out"):
        held = cfg.first_expert + jnp.arange(cfg.held_experts)
        gain = cfg.expert_gain if rule == "experts_out" else 1.0
        return jax.lax.map(lambda e: draw(jax.random.fold_in(key, e), shape, "matrix", gain=gain).astype(cfg.dtype), held)
    if rule == "query_up":
        return draw(key, shape, "matrix", gain=cfg.query_gain).astype(cfg.dtype)
    return draw(key, shape, rule).astype(jnp.float32 if rule == "router_bias" else cfg.dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_rows(cfg: DeepseekV32Config, key, std: float):
    blocks = cfg.held_vocab // EMBED_BLOCK_ROWS
    rows = decoders.draw_row_blocks(key, cfg.vocab_shard[0] * blocks, blocks, EMBED_BLOCK_ROWS, cfg.hidden_size)
    return (rows * std).astype(cfg.dtype)


def init_deepseek_params(cfg: DeepseekV32Config, seed: int = 0):
    """-> (model, params). Key 0 of the seed draws the embedding (0), the final
    norm (1) and the head (2); key i + 1 layer i, tensor j of ``tensor_specs``
    from the layer's key folded with j."""
    root = jax.random.PRNGKey(seed)
    k0 = jax.random.fold_in(root, 0)
    params = {"embed": _init_rows(cfg, jax.random.fold_in(k0, 0), EMBED_STD),
              "final_norm": draw(jax.random.fold_in(k0, 1), (cfg.hidden_size,), "norm").astype(cfg.dtype),
              "head": _init_rows(cfg, jax.random.fold_in(k0, 2), cfg.hidden_size ** -0.5),
              "layers": [{name: _init_tensor(cfg, jax.random.fold_in(jax.random.fold_in(root, i + 1), j), shape, rule)
                          for j, (name, shape, rule) in enumerate(tensor_specs(cfg, i < cfg.first_k_dense_replace))}
                         for i in range(cfg.num_hidden_layers)]}
    return DeepseekV32LM(cfg), params


# ---------------------------------------------------------------------- #
# The indexer and the selection                                           #
# ---------------------------------------------------------------------- #
def index_project(cfg, p, x, cq, positions, inv):
    """x (B, T, d) normed, cq (B, T, q_lora_rank) the MLA's normed low-rank query
    -> (q^I (B, T, Hi, Di) bfloat16, k^I (B, T, Di) bfloat16, w (B, T, Hi) float32):
    the first ``qk_rope_head_dim`` of every Di turned, halves paired."""
    B, T, _ = x.shape
    Hi, Di, n = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    turn = lambda v: jnp.concatenate(  # noqa: E731
        [latent_attention.rope(v[..., :n], positions, inv, interleaved=False), v[..., n:]], -1)
    q = turn(mm(cq, p["idx_q"]).reshape(B, T, Hi, Di))
    k = mm(x, p["idx_k"])
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + cfg.rms_norm_eps)
    k = turn(k * p["idx_k_norm"].astype(jnp.float32) + p["idx_k_bias"].astype(jnp.float32))
    w = mm(x, p["idx_w"]) * (Hi ** -0.5 * Di ** -0.5)
    return q.astype(cfg.dtype), k.astype(cfg.dtype), w


def index_scores_expanded(q, w, ik, slots, starts):
    """I(t, s) of one chunk's queries q (B, T, Hi, Di), weights w (B, T, Hi),
    against the indexer's keys ``ik`` (slots, Di, S) of the rows ``slots``, over
    the blocks of T positions that the call's deepest row attends. -> (B, T,
    blocks * T) float32; the blocks beyond hold the lowest float."""
    B, T = q.shape[:2]
    wide = -(-ik.shape[2] // T) * T

    def body(j, out):
        k = jnp.concatenate([jax.lax.dynamic_slice(ik, (slots[b], 0, j * T), (1, ik.shape[1], T)) for b in range(B)])
        # float32 operands (bfloat16 values, so the products are the same): the CPU backend has no thunk for
        # this contraction over a positions-minor operand in bfloat16
        s = jnp.einsum("bthd,bds->bhts", q.astype(jnp.float32), k.astype(jnp.float32))
        block = jnp.sum(jnp.maximum(s, 0.0) * jnp.swapaxes(w, 1, 2)[..., None], axis=1)
        return jax.lax.dynamic_update_slice(out, block, (0, 0, j * T))

    return jax.lax.fori_loop(0, jnp.max(starts) // T + 1, body, jnp.full((B, T, wide), _LOW, jnp.float32))


def index_scores_token(q, w, ik):
    """One token a slot: q (slots, Hi, Di), w (slots, Hi) against each slot's own keys (slots, Di, S) -> (slots, S)."""
    s = jnp.einsum("bhd,bds->bhs", q, ik, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=1)


def _ordered(x):
    """float32 -> uint32 whose order is the floats' (negative floats below positive ones, by magnitude downwards)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(b ^ ((b >> 31) & 0x7FFFFFFF), jnp.uint32) ^ jnp.uint32(0x80000000)


def _unordered(u):
    b = jax.lax.bitcast_convert_type(u ^ jnp.uint32(0x80000000), jnp.int32)
    return jax.lax.bitcast_convert_type(b ^ ((b >> 31) & 0x7FFFFFFF), jnp.float32)


#: Widths a prefill call's search may take: the scores are ``(B, T, positions a slot may hold)`` and a call's deepest
#: row reaches a part of them, so the passes run over the narrowest of this many even steps that holds it (a
#: partition's calls stand at every depth of its longest document, 43% of the full width on average at eight steps).
SEARCH_WIDTHS = 8


def _kth_ordered(u, k: int):
    """u (..., S) uint32 -> (...): the largest value that at least ``k`` of the S reach, 0 where fewer than ``k``
    are above 0. Two bits a pass: three candidates are counted in one reading of ``u``, 16 readings in all."""
    def two_bits(i, t):
        shift = jnp.asarray(30 - 2 * i, jnp.uint32)
        best = t
        for step in (1, 2, 3):  # counts fall as the candidate rises: the last one with enough stands
            cand = t | (jnp.uint32(step) << shift)
            enough = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
            best = jnp.where(enough, cand, best)
        return best

    return jax.lax.fori_loop(0, 16, two_bits, jnp.zeros(u.shape[:-1], jnp.uint32))


def kth_threshold(index, positions, k: int, reach=None):
    """Each query's ``k``-th largest index score among its causal keys: index (...,
    S) float32 over the positions 0 .. S - 1 of its slot, positions (...) the
    query's own. -> (...) float32: the largest value that at least ``k`` causal
    scores reach, and -inf for a query with fewer than ``k`` causal keys (it keeps
    all, as does one with exactly ``k``). Exact: a bisection over the bit pattern
    (compare and count), no sort; whatever lies beyond a query's position is not
    read. ``reach``: a traced count of positions beyond which no query of the
    call sees (every position < reach); the passes then read the narrowest of
    ``SEARCH_WIDTHS`` even steps of S that holds it."""
    S = index.shape[-1]
    seen = jnp.arange(S) <= positions[..., None]
    u = jnp.where(seen, _ordered(index), jnp.uint32(0))
    if reach is None:
        t = _kth_ordered(u, k)
    else:
        widths = sorted({-(-S * i // SEARCH_WIDTHS) for i in range(1, SEARCH_WIDTHS + 1)})
        tier = jnp.sum(jnp.asarray(widths) < reach)
        t = jax.lax.switch(tier, [lambda u, w=w: _kth_ordered(u[..., :w], k) for w in widths], u)
    return jnp.where(t == 0, -jnp.inf, _unordered(t))


def selected_pairs(lengths, k: int) -> int:
    """(query, key) pairs the selection keeps over prompts of ``lengths`` tokens: sum over positions of min(position + 1, k)."""
    n = np.asarray(lengths, np.int64)
    full = np.minimum(n, k)
    return int((full * (full + 1) // 2 + (n - full) * k).sum())


def _attend_prefill(cfg, p, inv, x, state, slots, starts, lengths):
    """One chunk for the rows ``slots``: project, write the chunk's latent rows
    and indexer keys, score, select, attend. -> (out (B, T, d) float32, state)."""
    B, T, _ = x.shape
    positions = starts[:, None] + jnp.arange(T)[None, :]
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    with jax.named_scope("mla_proj"):
        q, rows, cq = latent_attention.project(cfg, p, "", x, positions, inv)
    with jax.named_scope("indexer"):
        qi, ki, w = index_project(cfg, p, x, cq, positions, inv)
        ik = latent_attention.write_chunk(state["ik"], ki, slots, starts, valid)
        if pallas_dsa_index.index_scores_applies(qi.shape, qi.dtype):
            index = pallas_dsa_index.index_scores(qi, w, ik, slots, starts, lengths)
        else:
            index = index_scores_expanded(qi, w, ik, slots, starts)
    with jax.named_scope("select"):
        threshold = kth_threshold(index, positions, cfg.index_topk, reach=jnp.max(starts) + T)
    with jax.named_scope("mla_core"):
        kv = latent_attention.write_chunk(state["kv"], rows, slots, starts, valid)
        out = latent_attention.attend_chunk(cfg, latent_attention.kv_b(cfg, p, ""), q, kv, slots, starts, lengths,
                                            cfg.softmax_scale, (index, threshold), KERNEL_HEADS)
        decoders.note_on_serving_span("dsa", "masked")
    with jax.named_scope("mla_proj"):
        return mm(out.astype(cfg.dtype).reshape(B, T, -1), p["o"]), {"kv": kv, "ik": ik}


def _attend_decode(cfg, p, inv, x, state, positions, active):
    """One token for every slot: x (slots, 1, d), positions, active (slots,). -> (out, state)."""
    B = x.shape[0]
    with jax.named_scope("mla_proj"):
        q, rows, cq = latent_attention.project(cfg, p, "", x, positions[:, None], inv)
    with jax.named_scope("indexer"):
        qi, ki, w = index_project(cfg, p, x, cq, positions[:, None], inv)
        ik = latent_attention.write_token(state["ik"], ki[:, 0], positions, active)
        index = index_scores_token(qi[:, 0], w[:, 0], ik)
    with jax.named_scope("select"):
        keep = index >= kth_threshold(index, positions, cfg.index_topk)[:, None]
    with jax.named_scope("mla_core"):
        kv = latent_attention.write_token(state["kv"], rows[:, 0], positions, active)
        out = latent_attention.attend_token(cfg, latent_attention.kv_b(cfg, p, ""), q, kv, positions,
                                            cfg.softmax_scale, keep[:, None])
        decoders.note_on_serving_span("dsa", "masked")
    with jax.named_scope("mla_proj"):
        return mm(out.astype(cfg.dtype).reshape(B, 1, -1), p["o"]), {"kv": kv, "ik": ik}


# ---------------------------------------------------------------------- #
# The expert layer                                                        #
# ---------------------------------------------------------------------- #
def route(cfg, p, u):
    """u (n, d) float32 normed -> (idx (n, k) chosen experts, weights (n, k)
    float32): sigmoid scores in float32, the choice by score + bias inside the
    ``topk_group`` groups whose two best (score + bias) sum highest, the weight
    by the score alone, renormalised over the chosen, times the scaling factor."""
    n, E, G = u.shape[0], cfg.n_routed_experts, cfg.n_group
    r = jnp.einsum("nd,de->ne", u, p["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(r)
    c = s + p["router_bias"]
    by_group = c.reshape(n, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(by_group, min(2, E // G))[0], axis=-1)
    _, groups = jax.lax.top_k(group_score, cfg.topk_group)
    kept = jnp.any(groups[:, :, None] == jnp.arange(G)[None, None, :], axis=1)          # (n, G)
    _, idx = jax.lax.top_k(jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(n, E), cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, cfg.routed_scaling_factor * w / jnp.sum(w, axis=-1, keepdims=True)


def _moe(cfg, p, u, valid):
    """u (n, d) float32 normed; valid (n,). -> (the shared expert's part + the held experts' part (n, d) float32, counts)."""
    with jax.named_scope("dense_mlp"):
        y = gated_mlp(u.astype(cfg.dtype), p["shared_in"], p["shared_out"], cfg.dtype)
    with jax.named_scope("router"):
        idx, w = route(cfg, p, u)
    with jax.named_scope("experts"):
        routed, held, sizes = decoders.held_experts_part(u.astype(cfg.dtype), idx, w, valid, cfg.first_expert,
                                                         p["w_in"], p["w_out"], cfg.dtype)
    counts = {"assignments": jnp.sum(valid) * cfg.num_experts_per_tok, "held_assignments": jnp.sum(held),
              "max_expert_load": jnp.max(sizes), "experts_reached": jnp.sum(sizes > 0)}
    return y + routed, counts


# ---------------------------------------------------------------------- #
# The model                                                               #
# ---------------------------------------------------------------------- #
class DeepseekV32LM:
    """The decoder over a parameter tree, as the serving protocol sees it."""

    def __init__(self, cfg: DeepseekV32Config):
        self.cfg = cfg

    @property
    def vocab_size(self) -> int:
        """Ids and logits are over the held slice."""
        return self.cfg.held_vocab

    def init_state(self, slots: int, positions: int):
        cfg = self.cfg
        positions = -(-positions // POSITION_TILE) * POSITION_TILE
        return [{"kv": jnp.zeros((slots, cfg.cache_row, positions), cfg.dtype),
                 "ik": jnp.zeros((slots, cfg.index_head_dim, positions), cfg.dtype)} for _ in range(cfg.num_hidden_layers)]

    def copy_state(self, state, src, dst):
        return decoders.copy_slot(state, src, dst)

    def prefill_counts(self, lengths) -> Dict[str, int]:
        """Host-side counts of an admission round whose prompts have ``lengths`` tokens, for the batcher's
        ``serve.prefill`` span: the pairs the indexer scores (every causal one) and the pairs the selection keeps."""
        n = np.asarray(lengths, np.int64)
        return {"index_pairs": int((n * (n + 1) // 2).sum()), "selected_pairs": selected_pairs(n, self.cfg.index_topk)}

    def _forward(self, params, state, tokens, valid, attend):
        """The layers. ``attend(p, x, layer state) -> (out, layer state)`` is the
        attention of this program. -> (x (B, T, d) float32, state, counts)."""
        cfg = self.cfg
        B, T = tokens.shape
        eps = cfg.rms_norm_eps
        x = params["embed"][tokens].astype(jnp.float32)
        new_state, totals = [], None
        for i, p in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{i}"):
                out, held = attend(p, rms(x, p["attn_norm"], eps).astype(cfg.dtype), state[i])
                x = x + out
                u = rms(x, p["ffn_norm"], eps)
                if "router" in p:
                    m, counts = _moe(cfg, p, u.reshape(B * T, -1), valid.reshape(-1))
                    totals = decoders.add_counts(totals, counts)
                    x = x + m.reshape(B, T, -1)
                else:
                    with jax.named_scope("dense_mlp"):
                        x = x + gated_mlp(u.astype(cfg.dtype), p["ffn_in"], p["ffn_out"], cfg.dtype)
                new_state.append(held)
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
        cfg, inv = self.cfg, jnp.asarray(yarn_frequencies(self.cfg))
        T = tokens.shape[1]
        x, state, counts = self._forward(
            params, state, tokens, jnp.arange(T)[None, :] < lengths[:, None],
            lambda p, x, held: _attend_prefill(cfg, p, inv, x, held, slots, starts, lengths))
        last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        return state, self._head(params, last), counts

    def decode(self, params, state, tokens, positions, active):
        """One token for every slot: tokens, positions, active (slots,). An
        inactive slot's state is left as it was. -> (state, logits, counts)."""
        cfg, inv = self.cfg, jnp.asarray(yarn_frequencies(self.cfg))
        x, state, counts = self._forward(
            params, state, tokens[:, None], active[:, None],
            lambda p, x, held: _attend_decode(cfg, p, inv, x, held, positions, active))
        return state, self._head(params, x[:, 0]), counts


decoders.register(SIZES, from_name=DeepseekV32Config.from_name, init=init_deepseek_params,
                  model=DeepseekV32LM, cut_options=CUT_OPTIONS)
