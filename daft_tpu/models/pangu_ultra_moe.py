"""openPangu-Ultra-MoE-718B decoder (``pangu_ultra_moe``): sandwich-normed layers
of latent attention (MLA) and, after the leading dense ones, experts behind a
sigmoid router over all of them beside a shared expert, and a
multi-token-prediction (MTP) block that drafts the token after next. RMSNorm eps
1e-5, embedding and head untied. ``x`` is the residual stream (float32 here;
every norm is float32, products take bfloat16 operands and accumulate in float32):

    layer:  a = MLA(norm_in(x));   h = x + norm_post_attn(a)
            m = FFN(norm_pre_mlp(h));   x' = h + norm_post_mlp(m)
    FFN     the first ``first_k_dense_replace`` layers: SwiGLU of ``intermediate_size``;
            after them: Shared(u) + sum_{e chosen} w_e Expert_e(u)
    Router  s = sigmoid(u W_r) (float32);  chosen = the top ``num_experts_per_tok`` of s + b over all experts
            (``deepseek_v32.route`` with one group);  w_e = routed_scaling_factor * s_e / sum_{chosen} s
    h_i = norm_final(x_last at position i);   logits_i = h_i @ W_head
    MTP     g_i = W_eh [norm_e(E[t_{i+1}]) ; norm_h(h_i)];  one sandwich-normed expert layer over g with a latent
            cache of its own (its row i at position i);  q_{i+2} = norm_shared_head(.) @ W_head  (embedding and
            head are the main model's)

    MLA  ``models/latent_attention.py`` with no LoRA scales, plain rotary frequencies (``rope_theta``, no scaling),
         pairs interleaved, softmax scale (nope + rope) ** -0.5, every causal key

**Speculative decoding** (``draft_length`` 1): the batcher's decode program for
this model (``models/serving.py``) calls ``draft``, the MTP block over the (one
or two) positions whose pair ``(h_i, t_{i+1})`` became known since it last ran,
which writes their rows into the MTP's cache and gives the logits ``q`` of the
token after the slot's pending one; then ``verify``, the main model over the
pending token and the draft at their two positions, which writes both rows and
gives both positions' logits; the batcher's rejection sampler decides. Slot state
keeps the main model's normed hidden states at the two positions its last forward
ran (``hidden``, (slots, 2, d) float32): ``draft`` reads the first where one pair
is new (after a prefill or a rejection) and both where two are. A rejected draft's
row is overwritten by the next step, the slot's position moving on by what was
emitted.

**Prefill** runs the main layers over the chunk and, for the MTP block, only what
its cache needs: the rows of the chunk's pairs, position ``start - 1`` (the hidden
state kept from the previous chunk, the chunk's first token) to ``start + length
- 2``; a chunk that begins a prompt has no pair at ``-1`` and is shifted by one.
The pair of a prompt's last position waits for the first token sampled after it.

**Slot state**: ``{"layers": [{"kv"}] a layer, "mtp": {"kv"}, "hidden"}``, the
latent rows ``(slots, kv_lora_rank + qk_rope_head_dim, positions)`` held in whole
tiles of ``POSITION_TILE`` positions (positions minor, as ``deepseek_v32``'s).

**The cut.** ``num_layers`` keeps the first dense layer and the expert layers after
it (the leading dense layers count once); the MTP block is kept whole.
``expert_shard = (rank, size)``: the routed experts held, in every expert layer and
the MTP block's; the router ranks all of them, renormalises over all chosen, and
what the absent experts would add is left out. ``vocab_shard`` slices embedding
and head by rows.

**Random weights.** Matrices are drawn at fan-in scale, each routed expert's
``W_out`` at a tenth of it (``expert_gain``, the drawing rule of ``deepseek_v32``,
whose router this is: a choice weighs 2.5 / 8, and a near tie that bfloat16
rounding flips moves a token's logits by half a unit at fan-in scale).

Plain functions over a parameter tree, drawn tensor by tensor on the device in
bfloat16 (the router's bias in float32); ``jax.named_scope`` names the parts
(``mla_proj``, ``mla_core``, ``dense_mlp``, ``router``, ``experts``, ``head``) for
the device trace, and ``mtp`` all of the MTP block (its parts carry the same
names with the prefix ``mtp_``, so that no class inside it takes its time). The
programs note ``mla`` = ``fused`` | ``expanded`` (prefill) | ``absorbed_fused`` |
``absorbed`` (the steps: ``ops/pallas_mla_attention.mla_absorbed_attention`` on
a TPU at the published widths, XLA's form elsewhere) and ``moe`` on the
batcher's open span.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders, deepseek_v32, latent_attention
from daft_tpu.models.decoders import draw, gated_mlp, mm, rms
from daft_tpu.models.serving import ServedModel

#: Published sizes by exact model name (``config.json`` of the source). Kept as data: no substring rule.
PUBLISHED: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json
    "openPangu-Ultra-MoE-718B": dict(
        vocab_size=153600, hidden_size=7680, intermediate_size=18432, moe_intermediate_size=2048,
        num_hidden_layers=61, first_k_dense_replace=3, num_attention_heads=128, kv_lora_rank=512, q_lora_rank=1536,
        qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128, n_routed_experts=256, n_shared_experts=1,
        num_experts_per_tok=8, routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000.0,
        num_nextn_predict_layers=1,
        # Not published: a drawing rule (``deepseek_v32``'s, for the same router): each routed expert's W_out at a
        # tenth of the fan-in scale, so that an expert flipped at a near tie by rounding moves the logits by a
        # twentieth of a unit, not half of one.
        expert_gain=0.1),
}
#: Not published: the same mechanisms at a width the CPU tests can afford (three of eight experts a token, the MTP
#: block and its router among them), with the published size's drawing rule: at gain 1 the tiny router's near ties
#: moved a 24-token answer's log-probabilities by up to 0.48 and its drafts' by 0.87 in bfloat16, on two seeds of two.
TEST_SIZES: Dict[str, Dict[str, Any]] = {
    "pangu-ultra-moe-tiny": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=24,
        num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=8, q_lora_rank=16,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=3, routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000.0,
        num_nextn_predict_layers=1, expert_gain=0.1),
}
#: Every name ``from_name`` resolves.
SIZES = {**PUBLISHED, **TEST_SIZES}
#: Options of ``prompt`` that cut a published model to one chip's share.
CUT_OPTIONS = ("num_layers", "expert_shard", "vocab_shard")
#: Embedding and head are drawn in blocks of this many rows, so that a slice's rows are the whole table's.
EMBED_BLOCK_ROWS = 64
#: Standard deviation of the embedding's rows (the head is untied and drawn at fan-in scale).
EMBED_STD = 1.0
#: A slot's rows are held in whole lane tiles of positions (``deepseek_v32.POSITION_TILE``).
POSITION_TILE = 128
#: The MTP block's tensors beside its layer, in the order their keys are folded (its layer folds the next index).
MTP_SPECS = (("enorm", "norm"), ("hnorm", "norm"), ("eh_proj", "matrix"), ("shared_head_norm", "norm"))


@dataclass(frozen=True)
class PanguUltraMoeConfig:
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
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    rms_norm_eps: float
    rope_theta: float
    num_nextn_predict_layers: int
    expert_gain: float = 1.0
    #: The router is not limited to groups: ``deepseek_v32.route`` with one group of all experts.
    n_group: int = 1
    topk_group: int = 1
    expert_shard: Tuple[int, int] = (0, 1)
    vocab_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_name(name: str, num_layers: int = None, expert_shard=(0, 1), vocab_shard=(0, 1)) -> "PanguUltraMoeConfig":
        if name not in SIZES:
            raise DaftValueError(
                f"unknown openPangu-Ultra-MoE decoder {name!r}; the published sizes on record are {sorted(PUBLISHED)}")
        cfg = PanguUltraMoeConfig(**SIZES[name])
        layers = int(num_layers or cfg.num_hidden_layers)
        if not 1 < layers <= cfg.num_hidden_layers:
            raise DaftValueError(f"num_layers={layers} is outside the published {name!r} (a dense and an expert layer at least)")
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
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5


# ---------------------------------------------------------------------- #
# Parameters: drawn tensor by tensor on the device, bfloat16              #
# ---------------------------------------------------------------------- #
def tensor_specs(cfg: PanguUltraMoeConfig, dense: bool) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape, rule).
    ``benchmark/reference/pangu_ultra_moe.py`` states the same rules."""
    d, H = cfg.hidden_size, cfg.num_attention_heads
    specs = [("input_norm", (d,), "norm"),
             ("q_a", (d, cfg.q_lora_rank), "matrix"), ("q_a_norm", (cfg.q_lora_rank,), "norm"),
             ("q_b", (cfg.q_lora_rank, H * cfg.qk_head_dim), "matrix"),
             ("kv_a", (d, cfg.cache_row), "matrix"), ("kv_a_norm", (cfg.kv_lora_rank,), "norm"),
             ("kv_b", (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), "matrix"),
             ("o", (H * cfg.v_head_dim, d), "matrix"),
             ("post_attn_norm", (d,), "norm"), ("pre_mlp_norm", (d,), "norm"), ("post_mlp_norm", (d,), "norm")]
    if dense:
        f = cfg.intermediate_size
        return specs + [("ffn_in", (d, 2 * f), "matrix"), ("ffn_out", (f, d), "matrix")]
    fs, fe = cfg.n_shared_experts * cfg.moe_intermediate_size, cfg.moe_intermediate_size
    return specs + [("shared_in", (d, 2 * fs), "matrix"), ("shared_out", (fs, d), "matrix"),
                    ("router", (d, cfg.n_routed_experts), "matrix"), ("router_bias", (cfg.n_routed_experts,), "router_bias"),
                    ("w_in", (d, 2 * fe), "experts"), ("w_out", (fe, d), "experts_out")]


def mtp_shapes(cfg: PanguUltraMoeConfig) -> Dict[str, tuple]:
    d = cfg.hidden_size
    return {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d), "shared_head_norm": (d,)}


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_tensor(cfg: PanguUltraMoeConfig, key, shape: tuple, rule: str):
    """One tensor, so that no more than one is ever held in float32. An expert's
    weights come from its global id, whoever holds it, one expert at a time."""
    if rule in ("experts", "experts_out"):
        held = cfg.first_expert + jnp.arange(cfg.held_experts)
        gain = cfg.expert_gain if rule == "experts_out" else 1.0
        return jax.lax.map(lambda e: draw(jax.random.fold_in(key, e), shape, "matrix", gain=gain).astype(cfg.dtype), held)
    return draw(key, shape, rule).astype(jnp.float32 if rule == "router_bias" else cfg.dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_rows(cfg: PanguUltraMoeConfig, key, std: float):
    blocks = cfg.held_vocab // EMBED_BLOCK_ROWS
    rows = decoders.draw_row_blocks(key, cfg.vocab_shard[0] * blocks, blocks, EMBED_BLOCK_ROWS, cfg.hidden_size)
    return (rows * std).astype(cfg.dtype)


def _init_layer(cfg: PanguUltraMoeConfig, key, dense: bool) -> Dict[str, jax.Array]:
    return {name: _init_tensor(cfg, jax.random.fold_in(key, j), shape, rule)
            for j, (name, shape, rule) in enumerate(tensor_specs(cfg, dense))}


def init_pangu_params(cfg: PanguUltraMoeConfig, seed: int = 0):
    """-> (model, params). Key 0 of the seed draws the embedding (0), the final
    norm (1), the head (2) and the MTP block (3: its tensors of ``MTP_SPECS``
    fold 0 .. 3, its layer 4); key i + 1 layer i, tensor j of ``tensor_specs``
    from the layer's key folded with j."""
    root = jax.random.PRNGKey(seed)
    k0 = jax.random.fold_in(root, 0)
    km = jax.random.fold_in(k0, 3)
    shapes = mtp_shapes(cfg)
    mtp = {name: _init_tensor(cfg, jax.random.fold_in(km, j), shapes[name], rule) for j, (name, rule) in enumerate(MTP_SPECS)}
    mtp["layer"] = _init_layer(cfg, jax.random.fold_in(km, len(MTP_SPECS)), dense=False)
    params = {"embed": _init_rows(cfg, jax.random.fold_in(k0, 0), EMBED_STD),
              "final_norm": draw(jax.random.fold_in(k0, 1), (cfg.hidden_size,), "norm").astype(cfg.dtype),
              "head": _init_rows(cfg, jax.random.fold_in(k0, 2), cfg.hidden_size ** -0.5),
              "layers": [_init_layer(cfg, jax.random.fold_in(root, i + 1), i < cfg.first_k_dense_replace)
                         for i in range(cfg.num_hidden_layers)],
              "mtp": mtp}
    return PanguUltraMoeLM(cfg), params


# ---------------------------------------------------------------------- #
# The sandwich-normed layer                                               #
# ---------------------------------------------------------------------- #
def _scope(prefix: str, name: str):
    """The part's scope: ``name`` in the main model, ``mtp_<name>`` inside the MTP block (filed under ``mtp``)."""
    return jax.named_scope(prefix + name)


def _attend_prefill(cfg, p, x, kv, slots, starts, lengths, pre: str = ""):
    """One chunk for the rows ``slots`` of ``kv``: project, write the chunk's valid rows, attend over the blocks
    held. -> (out (B, T, d) float32, kv)."""
    B, T, _ = x.shape
    positions = starts[:, None] + jnp.arange(T)[None, :]
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    inv = latent_attention.frequencies(cfg.rope_theta, cfg.qk_rope_head_dim)
    with _scope(pre, "mla_proj"):
        q, rows, _ = latent_attention.project(cfg, p, "", x, positions, inv)
    with _scope(pre, "mla_core"):
        kv = latent_attention.write_chunk(kv, rows, slots, starts, valid)
        out = latent_attention.attend_chunk(cfg, latent_attention.kv_b(cfg, p, ""), q, kv, slots, starts, lengths,
                                            cfg.softmax_scale)
    with _scope(pre, "mla_proj"):
        return mm(out.astype(cfg.dtype).reshape(B, T, -1), p["o"]), kv


def _attend_tokens(cfg, p, x, kv, positions, writes, pre: str = ""):
    """T tokens a slot, x (slots, T, d) at ``positions`` (slots, T): the rows ``writes`` (slots, T) marks are written,
    then every query attends its slot's rows up to its position in the absorbed form (a slot that writes no row is
    inactive). -> (out, kv)."""
    B, T, _ = x.shape
    inv = latent_attention.frequencies(cfg.rope_theta, cfg.qk_rope_head_dim)
    with _scope(pre, "mla_proj"):
        q, rows, _ = latent_attention.project(cfg, p, "", x, positions, inv)
    with _scope(pre, "mla_core"):
        for t in range(T):
            kv = latent_attention.write_token(kv, rows[:, t], positions[:, t], writes[:, t])
        out = latent_attention.core_absorbed(cfg, latent_attention.kv_b(cfg, p, ""), q, kv, positions, cfg.softmax_scale,
                                             active=writes.any(axis=1))
    with _scope(pre, "mla_proj"):
        return mm(out.astype(cfg.dtype).reshape(B, T, -1), p["o"]), kv


def _moe(cfg, p, u, valid, pre: str = ""):
    """u (n, d) float32 normed; valid (n,). -> (the shared expert's part + the held experts' part (n, d) float32,
    counts). The router is ``deepseek_v32.route`` with one group."""
    with _scope(pre, "dense_mlp"):
        y = gated_mlp(u.astype(cfg.dtype), p["shared_in"], p["shared_out"], cfg.dtype)
    with _scope(pre, "router"):
        idx, w = deepseek_v32.route(cfg, p, u)
    with _scope(pre, "experts"):
        routed, held, sizes = decoders.held_experts_part(u.astype(cfg.dtype), idx, w, valid, cfg.first_expert,
                                                         p["w_in"], p["w_out"], cfg.dtype)
    counts = {"assignments": jnp.sum(valid) * cfg.num_experts_per_tok, "held_assignments": jnp.sum(held),
              "max_expert_load": jnp.max(sizes), "experts_reached": jnp.sum(sizes > 0)}
    return y + routed, counts


def _layer(cfg, p, x, kv, valid, attend, pre: str = ""):
    """One sandwich-normed layer: x (B, T, d) float32, ``attend(p, normed x, kv) -> (out, kv)``. -> (x', kv, counts
    of its expert layer or None)."""
    B, T, _ = x.shape
    eps = cfg.rms_norm_eps
    a, kv = attend(p, rms(x, p["input_norm"], eps).astype(cfg.dtype), kv)
    h = x + rms(a, p["post_attn_norm"], eps)
    u = rms(h, p["pre_mlp_norm"], eps)
    counts = None
    if "router" in p:
        m, counts = _moe(cfg, p, u.reshape(B * T, -1), valid.reshape(-1), pre)
        m = m.reshape(B, T, -1)
    else:
        with _scope(pre, "dense_mlp"):
            m = gated_mlp(u.astype(cfg.dtype), p["ffn_in"], p["ffn_out"], cfg.dtype)
    return h + rms(m, p["post_mlp_norm"], eps), kv, counts


# ---------------------------------------------------------------------- #
# The model                                                               #
# ---------------------------------------------------------------------- #
class PanguUltraMoeLM(ServedModel):
    """The decoder over a parameter tree, as the serving protocol sees it: a token model that drafts one token a
    step through its MTP block (``draft``) and verifies it (``verify``)."""

    draft_length = 1

    def __init__(self, cfg: PanguUltraMoeConfig):
        self.cfg = cfg

    @property
    def vocab_size(self) -> int:
        """Ids and logits are over the held slice."""
        return self.cfg.held_vocab

    def init_state(self, slots: int, positions: int):
        cfg = self.cfg
        positions = -(-positions // POSITION_TILE) * POSITION_TILE
        row = lambda: {"kv": jnp.zeros((slots, cfg.cache_row, positions), cfg.dtype)}  # noqa: E731
        return {"layers": [row() for _ in range(cfg.num_hidden_layers)], "mtp": row(),
                "hidden": jnp.zeros((slots, 2, cfg.hidden_size), jnp.float32)}

    def prefill_counts(self, lengths) -> Dict[str, int]:
        """Host-side counts of an admission round for ``serve.prefill``: the positions whose MTP rows its calls
        write (every prompt position but the last, whose pair waits for the first sampled token)."""
        return {"mtp_positions": int(np.maximum(np.asarray(lengths, np.int64) - 1, 0).sum())}

    # -- the parts ---------------------------------------------------------- #
    def _trunk(self, params, layers, tokens, valid, attend):
        """The main layers and the final norm: -> (h (B, T, d) float32, the layers' state, counts)."""
        cfg = self.cfg
        x = params["embed"][tokens].astype(jnp.float32)
        new, totals = [], None
        for i, p in enumerate(params["layers"]):
            with jax.named_scope(f"layer_{i}"):
                x, kv, counts = _layer(cfg, p, x, layers[i]["kv"], valid, attend)
            new.append({"kv": kv})
            if counts is not None:
                totals = decoders.add_counts(totals, counts)
        with jax.named_scope("head"):
            h = rms(x, params["final_norm"], cfg.rms_norm_eps)
        return h, new, totals

    def _head(self, params, h, name: str = "head"):
        with jax.named_scope(name):
            return jnp.einsum("...d,vd->...v", h.astype(self.cfg.dtype), params["head"], preferred_element_type=jnp.float32)

    def _mtp_input(self, params, hidden, tokens):
        """g = W_eh [norm_e(E[t]) ; norm_h(h)] (B, T, d) float32: the MTP block's input of the pairs (h_i, t_{i+1})."""
        mp, eps = params["mtp"], self.cfg.rms_norm_eps
        e = rms(params["embed"][tokens].astype(jnp.float32), mp["enorm"], eps)
        return mm(jnp.concatenate([e, rms(hidden, mp["hnorm"], eps)], -1).astype(self.cfg.dtype), mp["eh_proj"])

    # -- the serving protocol --------------------------------------------- #
    def prefill(self, params, state, tokens, slots, starts, lengths):
        """Advance ``slots`` (B,) over one chunk: tokens (B, T) right-padded, the chunk's first position ``starts``
        (B,) and its valid length ``lengths`` (B,; 0 leaves the slot as it was). Writes the main layers' rows and the
        MTP block's rows of the chunk's pairs. -> (state, logits (B, V) after each row's last valid token, counts)."""
        cfg = self.cfg
        T = tokens.shape[1]
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        h, layers, counts = self._trunk(
            params, state["layers"], tokens, valid,
            lambda p, x, kv: _attend_prefill(cfg, p, x, kv, slots, starts, lengths))
        last = jnp.take_along_axis(h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = self._head(params, last)
        kept = state["hidden"][slots, 0]
        with jax.named_scope("mtp"):
            # the pairs (h_{i}, t_{i+1}) at i = start - 1 .. start + length - 2; a prompt's first chunk has none at -1
            first = (starts == 0)[:, None, None]
            hid = jnp.where(first, h, jnp.concatenate([kept[:, None], h[:, :-1]], 1))
            tok = jnp.where(first[..., 0], jnp.roll(tokens, -1, axis=1), tokens)
            m_starts = jnp.maximum(starts - 1, 0)
            m_valid = jnp.arange(T)[None, :] < jnp.maximum(lengths - (starts == 0), 0)[:, None]
            x = rms(self._mtp_input(params, hid, tok), params["mtp"]["layer"]["input_norm"], cfg.rms_norm_eps)
            positions = m_starts[:, None] + jnp.arange(T)[None, :]
            _, rows, _ = latent_attention.project(cfg, params["mtp"]["layer"], "", x.astype(cfg.dtype), positions,
                                                  latent_attention.frequencies(cfg.rope_theta, cfg.qk_rope_head_dim))
            mtp = {"kv": latent_attention.write_chunk(state["mtp"]["kv"], rows, slots, m_starts, m_valid)}
        hidden = state["hidden"].at[slots, 0].set(jnp.where((lengths > 0)[:, None], last, kept))
        return {"layers": layers, "mtp": mtp, "hidden": hidden}, logits, counts

    def decode(self, params, state, tokens, positions, active):
        """One token for every slot (no draft): tokens, positions, active (slots,). -> (state, logits, counts)."""
        cfg = self.cfg
        writes = active[:, None]
        h, layers, counts = self._trunk(
            params, state["layers"], tokens[:, None], writes,
            lambda p, x, kv: _attend_tokens(cfg, p, x, kv, positions[:, None], writes))
        return dict(state, layers=layers), self._head(params, h[:, 0]), counts

    def draft(self, params, state, tokens, positions, pairs, active):
        """The MTP block over the two positions before each slot's pending token: tokens (slots, 2) the pairs' tokens
        t_{i+1} (the second the pending one), positions (slots, 2) their i, ``pairs`` (slots,) how many of the two
        are new (1: the first is left as it was). Reads the hidden states the last forward kept (the first where one
        pair is new, both where two are). -> (state, logits (slots, V) of the token after the pending one, counts)."""
        cfg = self.cfg
        kept = state["hidden"]
        two = pairs == 2
        hidden = jnp.stack([kept[:, 0], jnp.where(two[:, None], kept[:, 1], kept[:, 0])], 1)
        writes = jnp.stack([two & active, active], 1)
        with jax.named_scope("mtp"):
            x, kv, counts = _layer(
                cfg, params["mtp"]["layer"], self._mtp_input(params, hidden, tokens), state["mtp"]["kv"], writes,
                lambda p, x, kv: _attend_tokens(cfg, p, x, kv, positions, writes, "mtp_"), "mtp_")
            out = rms(x[:, 1], params["mtp"]["shared_head_norm"], cfg.rms_norm_eps)
            logits = self._head(params, out, "mtp_head")
        return dict(state, mtp={"kv": kv}), logits, counts

    def verify(self, params, state, tokens, positions, active):
        """The main model over each slot's pending token and its draft: tokens, positions (slots, 2). Writes both
        rows and keeps both hidden states. -> (state, logits (slots, 2, V), counts)."""
        cfg = self.cfg
        writes = jnp.broadcast_to(active[:, None], tokens.shape)
        h, layers, counts = self._trunk(
            params, state["layers"], tokens, writes,
            lambda p, x, kv: _attend_tokens(cfg, p, x, kv, positions, writes))
        hidden = jnp.where(active[:, None, None], h, state["hidden"])
        return dict(state, layers=layers, hidden=hidden), self._head(params, h), counts


decoders.register(SIZES, from_name=PanguUltraMoeConfig.from_name, init=init_pangu_params,
                  model=PanguUltraMoeLM, cut_options=CUT_OPTIONS)
