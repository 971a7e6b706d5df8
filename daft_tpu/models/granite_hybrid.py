"""Granite 4.0-H decoder (``granitemoehybrid``): Mamba-2 and attention mixers
under one residual stream, a 72-way top-10 expert layer with a shared expert
after every mixer, RMSNorm, no positional encoding, four multipliers, a tied
head.

    h = embedding_multiplier * E[tok]
    h += residual_multiplier * Mixer(RMSNorm(h))          # Mamba-2 or attention, by layer_types
    h += residual_multiplier * (MoE(v) + Shared(v)),  v = RMSNorm(h)
    logits = RMSNorm(h) @ E.T / logits_scaling

The model is plain functions over a parameter tree (no Flax module): the
parameters are drawn tensor by tensor on the device and kept in bfloat16, and
``jax.named_scope`` names the parts (``mamba``, ``ssd_scan``, ``attn``,
``router``, ``experts``, ``shared_mlp``, ``head``) for the device trace.

**The cut.** ``expert_shard = (rank, size)`` tells the expert layer which
experts it holds (a contiguous ``num_local_experts / size`` of them). It routes
over all of them with the published top-k, computes the held experts' part
``sum_{e in I and held} g_e y_e`` with the gates as the full softmax gave them,
and leaves the absent experts' part out; assignments to absent experts and to
padded tokens sort behind the held groups of the two grouped products, which on
a TPU are one kernel that visits the held rows alone
(``ops/pallas_grouped_matmul.py``; ``models/decoders.grouped_mlp`` has the rule, and
the dispatch after the router is ``decoders.held_experts_part``, shared with
``models/longcat_flash.py``).
``vocab_shard`` slices the tied embedding by rows: ids and logits are over the
slice. Nothing stands in for the other chips.

**Serving protocol** (``models/serving.ServedModel``): ``init_state``,
``prefill`` (advances some slots' state over one chunk of their prompts, true
lengths known: padding has delta = 0 and is written neither to the conv tail
nor to the KV rows; a chunk is whole chunks of the scan) and ``decode`` (one
token for every slot).
Slot state is a list with one entry a layer: ``{"ssm", "conv"}`` for a Mamba
layer, ``{"k", "v"}`` for an attention layer.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders
from daft_tpu.models.decoders import draw, gated_mlp as _gated_mlp, mm as _mm, rms as _rms
from daft_tpu.models.serving import ServedModel

#: Published sizes by exact model name (``config.json`` of the source). Kept as
#: data: no substring rule.
PUBLISHED: Dict[str, Dict[str, Any]] = {
    # https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
    "granite-4.0-h-small": dict(
        vocab_size=100352, hidden_size=4096, num_hidden_layers=40,
        layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
        num_attention_heads=32, num_key_value_heads=8,
        attention_multiplier=0.0078125, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0, rms_norm_eps=1e-5,
        intermediate_size=768, shared_intermediate_size=1536,
        num_local_experts=72, num_experts_per_tok=10,
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256),
}
#: Not published: the same ratios at a width the CPU tests and ``chip_smoke.py``
#: can afford (m-m-a-m, 8 experts top-3).
TEST_SIZES: Dict[str, Dict[str, Any]] = {
    "granite-hybrid-tiny": dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        num_attention_heads=4, num_key_value_heads=1,
        attention_multiplier=0.0625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=16.0, rms_norm_eps=1e-5,
        intermediate_size=12, shared_intermediate_size=24,
        num_local_experts=8, num_experts_per_tok=3,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
        mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8),
}
#: Every name ``from_name`` resolves.
SIZES = {**PUBLISHED, **TEST_SIZES}
#: Options of ``prompt`` that cut a published model to one chip's share.
CUT_OPTIONS = ("num_hidden_layers", "expert_shard", "vocab_shard")
#: The embedding is drawn in blocks of this many rows, so that a slice's rows
#: are the whole table's whatever the split.
EMBED_BLOCK_ROWS = 64
#: Standard deviation of the embedding's rows. Tied and random, a larger one makes
#: the last input token's own logit win every greedy step.
EMBED_STD = 0.002


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float
    intermediate_size: int
    shared_intermediate_size: int
    num_local_experts: int
    num_experts_per_tok: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_expand: int
    mamba_chunk_size: int
    expert_shard: Tuple[int, int] = (0, 1)
    vocab_shard: Tuple[int, int] = (0, 1)
    dtype: Any = jnp.bfloat16

    @staticmethod
    def from_name(name: str, num_hidden_layers: int = None, expert_shard=(0, 1),
                  vocab_shard=(0, 1)) -> "GraniteHybridConfig":
        if name not in SIZES:
            raise DaftValueError(
                f"unknown hybrid decoder {name!r}; the published sizes on record are {sorted(PUBLISHED)}")
        cfg = GraniteHybridConfig(**SIZES[name])
        layers = int(num_hidden_layers or cfg.num_hidden_layers)
        cfg = replace(cfg, num_hidden_layers=layers, layer_types=cfg.layer_types[:layers],
                      expert_shard=tuple(int(x) for x in expert_shard),
                      vocab_shard=tuple(int(x) for x in vocab_shard))
        decoders.check_shards((("expert_shard", cfg.expert_shard, cfg.num_local_experts),
                               ("vocab_shard", cfg.vocab_shard, cfg.vocab_size // EMBED_BLOCK_ROWS)))
        if not 0 < layers <= len(SIZES[name]["layer_types"]):
            raise DaftValueError(f"num_hidden_layers={layers} is outside the published {name!r}")
        return cfg

    # -- derived sizes ---------------------------------------------------- #
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def held_experts(self) -> int:
        return self.num_local_experts // self.expert_shard[1]

    @property
    def first_expert(self) -> int:
        return self.expert_shard[0] * self.held_experts

    @property
    def held_vocab(self) -> int:
        return self.vocab_size // self.vocab_shard[1]


# ---------------------------------------------------------------------- #
# Parameters: drawn tensor by tensor on the device, bfloat16              #
# ---------------------------------------------------------------------- #
def tensor_specs(cfg: GraniteHybridConfig, kind: str) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape,
    rule). ``benchmark/reference/granite_hybrid.py`` states the same rules."""
    d, f, fs, e = cfg.hidden_size, cfg.intermediate_size, cfg.shared_intermediate_size, cfg.num_local_experts
    if kind == "mamba":
        h = cfg.mamba_n_heads
        mixer = [("norm", (d,), "norm"),
                 ("in_proj", (d, 2 * cfg.d_inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state + h), "matrix"),
                 ("conv_w", (cfg.mamba_d_conv, cfg.conv_dim), "matrix"), ("conv_b", (cfg.conv_dim,), "bias"),
                 ("dt_bias", (h,), "dt_bias"), ("A_log", (h,), "A_log"), ("D", (h,), "norm"),
                 ("gate_norm", (cfg.d_inner,), "norm"), ("out_proj", (cfg.d_inner, d), "matrix")]
    else:
        q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
        mixer = [("norm", (d,), "norm"), ("q", (d, q), "matrix"), ("k", (d, kv), "matrix"),
                 ("v", (d, kv), "matrix"), ("o", (q, d), "matrix")]
    return mixer + [("moe_norm", (d,), "norm"), ("router", (d, e), "matrix"),
                    ("w_in", (d, 2 * f), "experts"), ("w_out", (f, d), "experts"),
                    ("shared_in", (d, 2 * fs), "matrix"), ("shared_out", (fs, d), "matrix")]


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_layer(cfg: GraniteHybridConfig, key, kind: str):
    held = cfg.first_expert + jnp.arange(cfg.held_experts)
    out = {}
    for j, (name, shape, rule) in enumerate(tensor_specs(cfg, kind)):
        k = jax.random.fold_in(key, j)
        if rule == "experts":  # an expert's weights come from its global id, whoever holds it
            w = jax.vmap(lambda e: draw(jax.random.fold_in(k, e), shape, "matrix"))(held)
        else:
            w = draw(k, shape, rule)
        out[name] = w.astype(cfg.dtype)
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _init_embedding(cfg: GraniteHybridConfig, key):
    blocks = cfg.held_vocab // EMBED_BLOCK_ROWS
    rows = decoders.draw_row_blocks(key, cfg.vocab_shard[0] * blocks, blocks, EMBED_BLOCK_ROWS, cfg.hidden_size)
    return (rows * EMBED_STD).astype(cfg.dtype)


def init_granite_params(cfg: GraniteHybridConfig, seed: int = 0):
    """-> (model, params). Key 0 of the seed draws the embedding and the final
    norm, key i + 1 layer i; no float32 copy of the tree ever exists."""
    root = jax.random.PRNGKey(seed)
    k0 = jax.random.fold_in(root, 0)
    params = {"embed": _init_embedding(cfg, jax.random.fold_in(k0, 0)),
              "final_norm": draw(jax.random.fold_in(k0, 1), (cfg.hidden_size,), "norm").astype(cfg.dtype),
              "layers": [_init_layer(cfg, jax.random.fold_in(root, i + 1), kind)
                         for i, kind in enumerate(cfg.layer_types)]}
    return GraniteHybridLM(cfg), params


# ---------------------------------------------------------------------- #
# Mamba-2                                                                 #
# ---------------------------------------------------------------------- #
def ssd_chunked(x, dt, a, b, c, s0, chunk: int):
    """The selective state-space recurrence over ``T`` steps in chunks.

    x (B, T, H, P); dt (B, T, H) float32, 0 at padding; a (H,) negative;
    b, c (B, T, N); s0 (B, H, P, N) float32. Returns y (B, T, H, P) float32
    and the state after the last step. ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    (x) b_t``, ``y_t = S_t c_t``: inside a chunk as one masked product, between
    chunks as a recurrence over the chunk states."""
    B, T, H, P = x.shape
    N = b.shape[-1]
    L = min(chunk, T)
    if T % L:
        raise ValueError(f"{T} steps are no multiple of the chunk {L}")
    nc = T // L
    bf = x.dtype
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(bf).reshape(B, nc, L, H, P)
    b = b.reshape(B, nc, L, N)
    c = c.reshape(B, nc, L, N)
    cs = jnp.cumsum((dt * a).reshape(B, nc, L, H), axis=2)          # (B, nc, L, H), <= 0
    cs_h = jnp.moveaxis(cs, 3, 2)                                   # (B, nc, H, L)
    # inside the chunk: y_l = sum_{s <= l} (c_l . b_s) exp(cs_l - cs_s) dt_s x_s
    cb = jnp.einsum("bcln,bcsn->bcls", c, b, preferred_element_type=jnp.float32)
    diff = cs_h[..., :, None] - cs_h[..., None, :]                  # (B, nc, H, L, L)
    lower = jnp.tril(jnp.ones((L, L), bool))
    m = (jnp.exp(jnp.where(lower, diff, -jnp.inf)) * cb[:, :, None]).astype(bf)
    y = jnp.einsum("bchls,bcshp->bclhp", m, xdt, preferred_element_type=jnp.float32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)                         # (B, nc, L, H)
    xdt_end = (xdt.astype(jnp.float32) * to_end[..., None]).astype(bf)
    own = jnp.einsum("bclhp,bcln->bchpn", xdt_end, b, preferred_element_type=jnp.float32)
    whole = jnp.exp(cs[:, :, -1, :])                                # (B, nc, H): a chunk's decay

    def step(s, inp):
        own_c, whole_c = inp
        return whole_c[..., None, None] * s + own_c, s              # carries on; emits the state before

    s_last, before = jax.lax.scan(step, s0, (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                             # (B, nc, H, P, N)
    c_in = (c.astype(jnp.float32)[:, :, :, None, :] * jnp.exp(cs)[..., None]).astype(bf)  # (B, nc, L, H, N)
    y = y + jnp.einsum("bclhn,bchpn->bclhp", c_in, before.astype(bf), preferred_element_type=jnp.float32)
    return y.reshape(B, T, H, P), s_last


def ssd_step(x, dt, a, b, c, s):
    """One step of the recurrence for every row: x (B, H, P), dt (B, H), b, c (B, N), s (B, H, P, N)."""
    decay = jnp.exp(dt * a)
    s = decay[..., None, None] * s + (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * b.astype(jnp.float32)[:, None, None, :]
    return jnp.einsum("bhpn,bn->bhp", s, c.astype(jnp.float32)), s


def _mamba(cfg, p, u, st, valid, lengths, single_step: bool):
    """u (B, T, d) normed; st {"ssm" (B, H, P, N), "conv" (B, K-1, C)} of these
    rows; valid (B, T); lengths (B,) valid steps of each row. -> (out, new st)."""
    B, T, _ = u.shape
    H, P, N, di = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.d_inner
    zxbcdt = _mm(u, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, di + cfg.conv_dim], axis=-1)
    xbc = xbc.astype(cfg.dtype)
    xbc, tail = decoders.conv_with_tail(st["conv"], xbc, p["conv_w"], p["conv_b"], lengths, cfg.dtype)
    x, b, c = jnp.split(xbc, [di, di + cfg.mamba_n_groups * N], axis=-1)
    x = x.reshape(B, T, H, P)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    dt = jnp.where(valid[..., None], dt, 0.0)                       # padding: no decay, no input
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("ssd_scan"):
        if single_step:
            y, ssm = ssd_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], st["ssm"])
            y = y[:, None]
        else:
            y, ssm = ssd_chunked(x, dt, a, b, c, st["ssm"], cfg.mamba_chunk_size)
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = (y.reshape(B, T, di) * jax.nn.silu(z)).astype(cfg.dtype)
    y = _rms(y, p["gate_norm"], cfg.rms_norm_eps)
    return _mm(y, p["out_proj"]).astype(cfg.dtype), {"ssm": ssm, "conv": tail}


# ---------------------------------------------------------------------- #
# Attention: grouped queries, no positions, a fixed score scale           #
# ---------------------------------------------------------------------- #
def _attention(cfg, p, u, rows_k, rows_v, positions, valid):
    """u (B, T, d); rows_k, rows_v (B, S, KV, hd): these rows' cache; positions
    (B, T) of the new tokens; valid (B, T). -> (out, new rows_k, new rows_v).
    The core (scores, softmax, weighted values over the cache) is
    ``decoders.attention_core``, shared with ``models/olmo_hybrid.py``."""
    B, T, _ = u.shape
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    R = cfg.num_attention_heads // KV
    q = _mm(u, p["q"]).astype(cfg.dtype).reshape(B, T, KV, R, hd)
    k = _mm(u, p["k"]).astype(cfg.dtype).reshape(B, T, KV, hd)
    v = _mm(u, p["v"]).astype(cfg.dtype).reshape(B, T, KV, hd)

    def write(rows, new):  # valid tokens only: padding leaves the rows as they were
        def one(r, n, pos, ok):
            old = jax.lax.dynamic_slice_in_dim(r, pos[0], T, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(r, jnp.where(ok[:, None, None], n, old), pos[0], axis=0)
        return jax.vmap(one)(rows, new, positions, valid)

    rows_k, rows_v = write(rows_k, k), write(rows_v, v)

    out = decoders.attention_core(q, rows_k, rows_v, positions, cfg.attention_multiplier, cfg.dtype)
    out = out.astype(cfg.dtype).reshape(B, T, KV * R * hd)
    return _mm(out, p["o"]).astype(cfg.dtype), rows_k, rows_v


# ---------------------------------------------------------------------- #
# Experts                                                                 #
# ---------------------------------------------------------------------- #
def _moe(cfg, p, v, valid):
    """v (n, d) normed; valid (n,). -> (held experts' part + shared expert
    (n, d) float32, counts {assignments, held_assignments, max_expert_load})."""
    k = cfg.num_experts_per_tok
    with jax.named_scope("router"):
        r = _mm(v, p["router"])                                     # (n, E) float32
        top, idx = jax.lax.top_k(r, k)
        gates = jax.nn.softmax(top, axis=-1)                        # over the chosen k only
    with jax.named_scope("experts"):
        y, held, sizes = decoders.held_experts_part(v, idx, gates, valid, cfg.first_expert,
                                                    p["w_in"], p["w_out"], cfg.dtype)
    with jax.named_scope("shared_mlp"):
        y = y + _gated_mlp(v, p["shared_in"], p["shared_out"], cfg.dtype)
    counts = {"assignments": jnp.sum(valid) * k, "held_assignments": jnp.sum(held),
              "max_expert_load": jnp.max(sizes)}
    return y, counts


# ---------------------------------------------------------------------- #
# The model                                                               #
# ---------------------------------------------------------------------- #
class GraniteHybridLM(ServedModel):
    """The decoder over a parameter tree, as the serving protocol sees it."""

    def __init__(self, cfg: GraniteHybridConfig):
        self.cfg = cfg

    @property
    def vocab_size(self) -> int:
        """Ids and logits are over the held slice."""
        return self.cfg.held_vocab

    @property
    def prefill_multiple(self) -> int:
        """A prefill chunk is whole chunks of the scan."""
        return self.cfg.mamba_chunk_size

    def init_state(self, slots: int, positions: int):
        cfg = self.cfg
        state = []
        for kind in cfg.layer_types:
            if kind == "mamba":
                state.append({
                    "ssm": jnp.zeros((slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32),
                    "conv": jnp.zeros((slots, cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype)})
            else:
                kv = (slots, positions, cfg.num_key_value_heads, cfg.head_dim)
                state.append({"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype)})
        return state

    def _forward(self, params, rows, tokens, positions, valid, lengths, fresh, single_step):
        """The layers over ``rows`` (each layer's state of the rows in play).
        -> (h (B, T, d), new rows, counts summed over the layers)."""
        cfg = self.cfg
        B, T = tokens.shape
        h = (params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier).astype(cfg.dtype)
        new_rows, totals = [], None
        for i, (kind, p, st) in enumerate(zip(cfg.layer_types, params["layers"], rows)):
            with jax.named_scope(f"layer_{i}"):
                u = _rms(h, p["norm"], cfg.rms_norm_eps)
                if kind == "mamba":
                    with jax.named_scope("mamba"):
                        # a prompt's first chunk starts from nothing, whatever the slot held
                        st = jax.tree_util.tree_map(
                            lambda a: jnp.where(fresh.reshape((B,) + (1,) * (a.ndim - 1)), 0, a), st)
                        out, st = _mamba(cfg, p, u, st, valid, lengths, single_step)
                else:
                    with jax.named_scope("attn"):
                        out, k, v = _attention(cfg, p, u, st["k"], st["v"], positions, valid)
                        st = {"k": k, "v": v}
                new_rows.append(st)
                h = (h + cfg.residual_multiplier * out).astype(cfg.dtype)
                v = _rms(h, p["moe_norm"], cfg.rms_norm_eps)
                y, counts = _moe(cfg, p, v.reshape(B * T, -1), valid.reshape(-1))
                totals = decoders.add_counts(totals, counts)
                h = (h + cfg.residual_multiplier * y.reshape(B, T, -1)).astype(cfg.dtype)
        return h, new_rows, totals

    def _head(self, params, h):
        with jax.named_scope("head"):
            h = _rms(h, params["final_norm"], self.cfg.rms_norm_eps)
            return jnp.einsum("...d,vd->...v", h, params["embed"],
                              preferred_element_type=jnp.float32) / self.cfg.logits_scaling

    def prefill(self, params, state, tokens, slots, starts, lengths):
        """Advance ``slots`` (B,) over one chunk: tokens (B, T) right-padded,
        the chunk's first position ``starts`` (B,) and its valid length
        ``lengths`` (B,; 0 leaves the slot as it was). -> (state, logits (B, V)
        after each row's last valid token, counts)."""
        T = tokens.shape[1]
        steps = jnp.arange(T)[None, :]
        valid = steps < lengths[:, None]
        fresh = (starts == 0) & (lengths > 0)
        rows = jax.tree_util.tree_map(lambda a: a[slots], state)
        h, rows, counts = self._forward(params, rows, tokens, starts[:, None] + steps, valid, lengths,
                                        fresh, single_step=False)
        state = jax.tree_util.tree_map(lambda a, r: a.at[slots].set(r), state, rows)
        last = jnp.take_along_axis(h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
        return state, self._head(params, last), counts

    def decode(self, params, state, tokens, positions, active):
        """One token for every slot: tokens, positions, active (slots,). An
        inactive slot's state is left as it was. -> (state, logits, counts)."""
        valid = active[:, None]
        h, state, counts = self._forward(params, state, tokens[:, None], positions[:, None], valid,
                                         active.astype(jnp.int32), jnp.zeros_like(active), single_step=True)
        return state, self._head(params, h[:, 0]), counts


decoders.register(SIZES, from_name=GraniteHybridConfig.from_name, init=init_granite_params,
                  model=GraniteHybridLM, cut_options=CUT_OPTIONS)
