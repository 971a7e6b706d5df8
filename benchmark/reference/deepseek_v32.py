"""Plain reference for the DeepSeek-V3.2-Exp decoder: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the configuration's keys
and the published ``inference/model.py`` as remembered (each item from memory is
in the configuration's ``assumed``): no cache, no chunks, no batching, no
absorbed form of the latent attention (every key and value is expanded), every
held expert looped over every token, the index scores of every causal pair and
an exact ``top_k`` of them. ``x`` is the residual stream, every norm RMSNorm but
the indexer's key norm:

    layer i <  first_k_dense_replace:  a = x + Attn(norm(x));   x' = a + SwiGLU_intermediate_size(norm(a))
    layer i >= first_k_dense_replace:  a = x + Attn(norm(x));   u = norm(a)
                                       x' = a + Shared(u) + sum_{e chosen} w_e Expert_e(u)
    logits = norm(x_last) @ W_head                                  (embedding and head untied)

    MLA    c_q = norm(x W_qa);  q = c_q W_qb -> H x [q_nope (qk_nope_head_dim) | q_r (qk_rope_head_dim)]
           [c | k_r] = x W_kva;  c = norm(c);   [k_nope | v] = c W_kvb -> H x [qk_nope_head_dim | v_head_dim]
           q_r, k_r = RoPE(q_r), RoPE(k_r): k_r one vector a token, shared by all heads; pairs interleaved
           (x[2i], x[2i+1]) turned by position * f'_i
           score = (q_nope . k_nope + q_r . k_r) * (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 * mscale ** 2,
           mscale = 0.1 * mscale_all_dim * ln(factor) + 1
    YaRN   f_i = rope_theta ** (-2i / n), n = qk_rope_head_dim, i = 0 .. n / 2 - 1;
           d(b) = n ln(original_max_position_embeddings / (2 pi b)) / (2 ln rope_theta);
           low = max(floor(d(beta_fast)), 0), high = min(ceil(d(beta_slow)), n - 1);
           ramp_i = clip((i - low) / (high - low), 0, 1);   f'_i = f_i / factor * ramp_i + f_i * (1 - ramp_i)
    Index  q^I = c_q W^I_q -> index_n_heads x index_head_dim;   k^I = LayerNorm(x W^I_k) (with bias);
           the FIRST qk_rope_head_dim of each index_head_dim turned by RoPE with f', halves paired (x[i], x[i + n / 2]);
           w = x W^I_w * index_n_heads ** -0.5 * index_head_dim ** -0.5
           I(t, s) = sum_h w_h(t) relu(q^I_h(t) . k^I(s)),  s <= t;    S(t) = the min(t + 1, index_topk) keys of largest I(t, .)
    Attn   softmax over s in S(t) only of score(t, s);   out = (sum_s p v) W_o
    Router s = sigmoid(u W_r) over all ``router_outputs`` experts;  c = s + bias;  a group's score = the sum of its 2 largest c
           (n_group groups of consecutive experts);  keep the topk_group best groups;  chosen = top num_experts_per_tok of c
           inside them;  w_e = routed_scaling_factor * s_e / sum_{chosen} s       (norm_topk_prob; the bias moves the choice only)

It imports nothing of the program and takes nothing the program has made. The
share of a deployment is an argument: ``experts = (first, count)`` are the routed
experts held (the router still ranks all of them and renormalises over all
chosen; what the absent ones would add is left out; the shared expert is whole)
and ``vocab = (first row, rows)`` the slice of embedding and head. The attention
runs a block of queries at a time against every key (one float32 score matrix of
128 heads at 24,624 positions is 310 GB), 16 heads at a time (each group expands
its own queries, keys and values from the latents; all 128 heads' would be 9 GB):
still one softmax a row, nothing carried between blocks. The weights are drawn here from the seed, a layer at a
time when the forward reaches it (an expert layer in float32 is 3.8 GB at the
published widths and 16 held experts), by the rules below, and rounded to
bfloat16 as the configuration states.

Weight rules (key = PRNGKey(seed); layer i folds i + 1, then the tensor's index
in ``tensor_specs``; an expert folds its global id; embedding, final norm and
head fold 0 then 0 / 1 / 2; embedding and head fold their 32-row block): matrices
normal with std fan_in ** -0.5 (the head too, its fan-in being ``hidden_size``),
``W_qb`` with std fan_in ** -0.5 x ``query_gain`` (the scores then spread ~0.5: at
plain fan-in scale they spread ~1.9, a softmax rests on a few dozen of its 2,048
keys, and a key that rounding swaps at a threshold moves percents of it), each
routed expert's ``W_out`` with std fan_in ** -0.5 x ``expert_gain`` (a choice of the
router weighs 0.31, and a near tie that rounding flips moves a token's logits by
half a unit at fan-in scale);
embedding normal, std ``embedding_std``; norm weights 1 + 0.1 normal; the
indexer's key norm's bias 0.1 normal; the router's bias ``router_bias_std`` x
normal, kept in float32.

``precision="fp8"`` is the control of the benchmark's comparison: the same
forward with both operands of every matrix product (the index scores' among
them) rounded to float8_e4m3 under a per-tensor scale, the step below the
bfloat16 the configuration states.

The counts at the end (``step_flops``, ``index_*``, ``mla_core_*``,
``expert_matmul_*``) are of the least work, from token counts and shapes, apart
from any implementation: the indexer scores every causal pair, the core only the
pairs selected.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_BLOCK_ROWS = 32
#: Queries the attention takes at once (the largest divisor of the length up to this).
QUERY_BLOCK = 128
#: Heads whose queries, keys and values are expanded at once (the largest divisor of the heads up to this).
HEAD_GROUP = 16
#: ``forward_many`` pads a sequence to the smallest of this many even steps up to ``pad_to`` that holds it: a
#: compiled layer serves every sequence of a step, and a short one does not pay the longest's pairs.
PAD_STEPS = 4


# -- sizes ----------------------------------------------------------------------
def share(cfg: dict) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """The configuration's own share: ((first expert, experts held), (first row, rows held)).
    ``n_routed_experts`` and ``vocab_size`` are as run: what this chip holds."""
    held, rows = cfg["n_routed_experts"], cfg["vocab_size"]
    return (cfg["options"]["expert_shard"][0] * held, held), (cfg["options"]["vocab_shard"][0] * rows, rows)


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def tensor_specs(cfg: dict, dense: bool) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape of one, rule)."""
    d, H, Hi, Di = cfg["hidden_size"], cfg["num_attention_heads"], cfg["index_n_heads"], cfg["index_head_dim"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    specs = [("attn_norm", (d,), "norm"),
             ("q_a", (d, ql), "matrix"), ("q_a_norm", (ql,), "norm"), ("q_b", (ql, H * (nope + rp)), "query_up"),
             ("kv_a", (d, kl + rp), "matrix"), ("kv_a_norm", (kl,), "norm"), ("kv_b", (kl, H * (nope + dv)), "matrix"),
             ("o", (H * dv, d), "matrix"),
             ("idx_q", (ql, Hi * Di), "matrix"), ("idx_k", (d, Di), "matrix"),
             ("idx_k_norm", (Di,), "norm"), ("idx_k_bias", (Di,), "bias"), ("idx_w", (d, Hi), "matrix"),
             ("ffn_norm", (d,), "norm")]
    if dense:
        f = cfg["intermediate_size"]
        return specs + [("ffn_in", (d, 2 * f), "matrix"), ("ffn_out", (f, d), "matrix")]
    fs, fe = cfg["n_shared_experts"] * cfg["moe_intermediate_size"], cfg["moe_intermediate_size"]
    return specs + [("shared_in", (d, 2 * fs), "matrix"), ("shared_out", (fs, d), "matrix"),
                    ("router", (d, cfg["router_outputs"]), "matrix"), ("router_bias", (cfg["router_outputs"],), "router_bias"),
                    ("w_in", (d, 2 * fe), "experts"), ("w_out", (fe, d), "experts_out")]


def _as_drawn(x):
    """A draw as the generator gave it: inside a jitted program XLA would fold
    the scale that follows into the generator's own last product, and round
    otherwise than the same two steps taken one by one."""
    return jax.lax.optimization_barrier(x)


def _draw(cfg, key, shape, rule):
    n = _as_drawn(jax.random.normal(key, shape, jnp.float32))
    if rule == "matrix":
        return n * (shape[0] ** -0.5)
    if rule == "query_up":  # W_qb: the attention's scores then spread ~0.5 (the configuration's assumed.weights says why)
        return n * (shape[0] ** -0.5 * cfg["query_gain"])
    if rule == "norm":
        return 0.1 * n + 1.0
    if rule == "bias":
        return 0.1 * n
    if rule == "router_bias":
        return cfg["router_bias_std"] * n
    raise ValueError(rule)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(cfg: dict, seed: int, i: int, experts: Tuple[int, int]) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors as float32 holding bfloat16 values (the router's bias
    as drawn); ``w_in`` and ``w_out`` are stacked over the experts ``first .. first + count``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i + 1)
    out = {}
    for j, (name, shape, rule) in enumerate(tensor_specs(cfg, is_dense(cfg, i))):
        k = jax.random.fold_in(key, j)
        if rule in ("experts", "experts_out"):  # an expert's weights come from its global id, whoever holds it
            gain = cfg["expert_gain"] if rule == "experts_out" else 1.0
            out[name] = jnp.stack([_bf16(_draw(cfg, jax.random.fold_in(k, e), shape, "matrix") * gain)
                                   for e in range(experts[0], experts[0] + experts[1])])
        else:
            w = _draw(cfg, k, shape, rule)
            out[name] = w if rule == "router_bias" else _bf16(w)
    return out


def _rows(cfg: dict, key, vocab: Tuple[int, int], std: float):
    first, count = vocab[0] // EMBED_BLOCK_ROWS, vocab[1] // EMBED_BLOCK_ROWS
    blocks = [jax.random.normal(jax.random.fold_in(key, b), (EMBED_BLOCK_ROWS, cfg["hidden_size"]), jnp.float32)
              for b in range(first, first + count)]
    return _bf16(jnp.concatenate(blocks) * std)


def embedding(cfg: dict, seed: int, vocab: Tuple[int, int]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (rows ``first .. first + count`` of the embedding, the final norm's weight, the same rows of the head)."""
    k0 = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    return (_rows(cfg, jax.random.fold_in(k0, 0), vocab, cfg["embedding_std"]),
            _bf16(_draw(cfg, jax.random.fold_in(k0, 1), (cfg["hidden_size"],), "norm")),
            _rows(cfg, jax.random.fold_in(k0, 2), vocab, cfg["hidden_size"] ** -0.5))


# -- positions --------------------------------------------------------------------
def yarn_frequencies(cfg: dict) -> np.ndarray:
    """f' (qk_rope_head_dim / 2,), float32: see the module's text."""
    sc, n, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    i = np.arange(n // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / n)
    d = lambda b: n * math.log(sc["original_max_position_embeddings"] / (2 * math.pi * b)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(d(sc["beta_fast"])), 0), min(math.ceil(d(sc["beta_slow"])), n - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / sc["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32)


def mscale(cfg: dict) -> float:
    sc = cfg["rope_scaling"]
    return 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0 if sc["factor"] > 1 else 1.0


def _angles(t: int, inv, ndim: int):
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    return ang.reshape((t,) + (1,) * (ndim - 2) + (ang.shape[-1],))


def _rope_interleaved(x, inv):
    """x (T, ..., n) at positions 0 .. T - 1: the pair (x[2i], x[2i+1]) turned by position * inv[i]."""
    ang = _angles(x.shape[0], inv, x.ndim)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _rope_halves(x, inv):
    """x (T, ..., n) at positions 0 .. T - 1: the pair (x[i], x[i + n / 2]) turned by position * inv[i]."""
    ang = _angles(x.shape[0], inv, x.ndim)
    h = x.shape[-1] // 2
    a, b = x[..., :h], x[..., h:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], -1)


# -- the forward ----------------------------------------------------------------
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def selected_keys(index, k: int):
    """index (Q, T): each query's index scores with the keys it may not see at
    -inf -> (Q, T) bool: its min(keys it sees, k) keys of largest score, by an
    exact ``top_k`` (ties go to the earlier key)."""
    vals, idx = jax.lax.top_k(index, min(k, index.shape[1]))
    rows = jnp.broadcast_to(jnp.arange(index.shape[0])[:, None], idx.shape)
    return jnp.zeros(index.shape, bool).at[rows, idx].set(vals > -jnp.inf)


def _ops(cfg: dict, fp8: bool):
    q8 = _fp8 if fp8 else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def mlp(x, w_in, w_out):
        a, b = jnp.split(mm("ti,io->to", x, w_in), 2, -1)
        return mm("ti,io->to", jax.nn.silu(a) * b, w_out)

    def route(p, u):
        E, G = cfg["router_outputs"], cfg["n_group"]
        s = jax.nn.sigmoid(mm("ti,io->to", u, p["router"]))
        c = (s + p["router_bias"]).reshape(-1, G, E // G)
        group_score = jnp.sum(jax.lax.top_k(c, min(2, E // G))[0], -1)                 # (T, G)
        _, groups = jax.lax.top_k(group_score, cfg["topk_group"])
        kept = jnp.zeros(group_score.shape, bool).at[jnp.arange(c.shape[0])[:, None], groups].set(True)
        _, idx = jax.lax.top_k(jnp.where(kept[:, :, None], c, -jnp.inf).reshape(-1, E), cfg["num_experts_per_tok"])
        w = jnp.take_along_axis(s, idx, -1)
        if cfg["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        return idx, cfg["routed_scaling_factor"] * w

    return mm, mlp, route


def _layer(cfg: dict, fp8: bool, first_expert: int, dense: bool, keep_selection: bool = False):
    """-> jitted ``(weights, x (T, d)) -> x`` for one layer (with ``keep_selection``: -> (x, the (T, T) mask of
    the keys each query attends, the index scores))."""
    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    kl, nope, rp, dv = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    Hi, Di, topk = cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]
    inv = yarn_frequencies(cfg)
    softmax_scale = (nope + rp) ** -0.5 * mscale(cfg) ** 2
    mm, mlp, route = _ops(cfg, fp8)

    def attention(p, x):
        t = x.shape[0]
        c_q = _rms(mm("ti,io->to", x, p["q_a"]), p["q_a_norm"], eps)
        ckr = mm("ti,io->to", x, p["kv_a"])
        c = _rms(ckr[:, :kl], p["kv_a_norm"], eps)
        k_r = _rope_interleaved(ckr[:, kl:], inv)                    # one rotary key a token, shared by all heads
        blk = max(b for b in range(1, min(t, QUERY_BLOCK) + 1) if t % b == 0)
        firsts = jnp.arange(0, t, blk)
        # the indexer: its own queries from c_q, one key a token, a weight a head and query
        qi = mm("ti,io->to", c_q, p["idx_q"]).reshape(t, Hi, Di)
        qi = jnp.concatenate([_rope_halves(qi[..., :rp], inv), qi[..., rp:]], -1)
        ki = mm("ti,io->to", x, p["idx_k"])
        ki = ki - jnp.mean(ki, -1, keepdims=True)
        ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True) + eps) * p["idx_k_norm"] + p["idx_k_bias"]
        ki = jnp.concatenate([_rope_halves(ki[:, :rp], inv), ki[:, rp:]], -1)
        wi = mm("ti,io->to", x, p["idx_w"]) * (Hi ** -0.5 * Di ** -0.5)

        def select(args):                                            # blk queries' index scores against every key
            qib, wib, first = args
            causal = (first + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
            index = mm("qh,hqk->qk", wib, jax.nn.relu(mm("qhd,kd->hqk", qib, ki)))
            index = jnp.where(causal, index, -jnp.inf)
            return selected_keys(index, topk), index

        chosen, index = jax.lax.map(select, (qi.reshape(t // blk, blk, Hi, Di), wi.reshape(t // blk, blk, Hi), firsts))

        # the latent attention, HEAD_GROUP heads at a time (every key and value of all 128 heads at 24,624 positions
        # would be 9 GB in float32): each group expands its own queries, keys and values; one softmax a row
        hg = max(g for g in range(1, min(H, HEAD_GROUP) + 1) if H % g == 0)
        by_group = lambda w, width: jnp.moveaxis(w.reshape(w.shape[0], H // hg, hg * width), 1, 0)  # noqa: E731

        def group(args):
            w_q, w_kv = args
            q = mm("ti,io->to", c_q, w_q).reshape(t, hg, nope + rp)
            q = jnp.concatenate([q[..., :nope], _rope_interleaved(q[..., nope:], inv)], -1)
            kv = mm("ti,io->to", c, w_kv).reshape(t, hg, nope + dv)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (t, hg, rp))], -1)

            def block(args):                                         # blk queries against every key, the selected ones kept
                qb, kept = args
                scores = mm("qhd,khd->hqk", qb, k) * softmax_scale
                return mm("hqk,khd->qhd", jax.nn.softmax(jnp.where(kept, scores, -jnp.inf), -1), kv[..., nope:])

            return jax.lax.map(block, (q.reshape(t // blk, blk, hg, nope + rp), chosen)).reshape(t, hg * dv)

        out = jax.lax.map(group, (by_group(p["q_b"], nope + rp), by_group(p["kv_b"], nope + dv)))
        y = mm("ti,io->to", jnp.moveaxis(out, 0, 1).reshape(t, H * dv), p["o"])
        return (y, chosen.reshape(t, t), index.reshape(t, t)) if keep_selection else y

    def moe(p, u):
        idx, w = route(p, u)

        def one(y, expert):                                          # every held expert, every token
            e, w_in, w_out = expert
            g = jnp.sum(jnp.where(idx == first_expert + e, w, 0.0), -1)
            return y + g[:, None] * mlp(u, w_in, w_out), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(p["w_in"].shape[0]), p["w_in"], p["w_out"]))
        return y + mlp(u, p["shared_in"], p["shared_out"])           # the shared expert: every token, whole

    @jax.jit
    def layer(p, x):
        with jax.default_matmul_precision("highest"):
            att = attention(p, _rms(x, p["attn_norm"], eps))
            a = x + (att[0] if keep_selection else att)
            u = _rms(a, p["ffn_norm"], eps)
            y = a + (mlp(u, p["ffn_in"], p["ffn_out"]) if dense else moe(p, u))
            return (y,) + tuple(att[1:]) if keep_selection else y

    return layer


def _padded_length(n: int, pad_to: Optional[int]) -> int:
    if not pad_to:
        return n
    step = -(-pad_to // PAD_STEPS)
    return min(-(-n // step) * step, max(pad_to, n))


def forward_many(cfg: dict, seed: int, sequences, experts: Optional[Tuple[int, int]] = None,
                 vocab: Optional[Tuple[int, int]] = None, precisions=("f32",), logits_from=None,
                 pad_to: Optional[int] = None, layers_out: Optional[list] = None,
                 selection_out: Optional[list] = None) -> Dict[str, List[np.ndarray]]:
    """Logits of each sequence of token ids (ids are rows of the held slice), in
    each of ``precisions``: ``{precision: [(T_i - logits_from[i], rows held), ...]}``.
    Each layer's weights are drawn once and every sequence goes through before
    the next layer's are. With ``pad_to`` a sequence is right-padded to the
    smallest of ``PAD_STEPS`` even steps up to it that holds it, so that a few
    compiled layers serve all (the model is causal, and a query selects among
    the keys before it: what follows a position never reaches it).
    ``layers_out`` receives sequence 0's float32 layer outputs, ``selection_out``
    its (mask of the keys each query attends, index scores) of every layer (tests)."""
    if set(precisions) - {"f32", "fp8"}:
        raise ValueError(f"precisions {precisions!r}")
    own_experts, own_vocab = share(cfg)
    experts, vocab = experts or own_experts, vocab or own_vocab
    emb, final_norm, head = embedding(cfg, seed, vocab)
    lens = [len(t) for t in sequences]
    logits_from = list(logits_from) if logits_from is not None else [0] * len(lens)
    padded = [np.pad(np.asarray(t), (0, _padded_length(n, pad_to) - n)) for t, n in zip(sequences, lens)]
    hs = {pr: [emb[jnp.asarray(t)] for t in padded] for pr in precisions}
    fns = {(pr, dense): _layer(cfg, pr == "fp8", experts[0], dense) for pr in precisions for dense in (True, False)}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(cfg, seed, i, experts)
        if selection_out is not None:
            _, chosen, index = _layer(cfg, False, experts[0], is_dense(cfg, i), keep_selection=True)(w, hs["f32"][0])
            selection_out.append((np.asarray(chosen)[:lens[0], :lens[0]], np.asarray(index)[:lens[0], :lens[0]]))
        for pr in precisions:
            hs[pr] = [fns[pr, is_dense(cfg, i)](w, h) for h in hs[pr]]
        # The host runs ahead of the device: without this wait the next layer's weights (3.8 GB in float32)
        # are placed while this one's are still held by its queued products.
        jax.block_until_ready(hs)
        if layers_out is not None:
            layers_out.append(np.asarray(hs["f32"][0][:lens[0]]))
        del w
    out = {}
    with jax.default_matmul_precision("highest"):
        for pr in precisions:
            q8 = _fp8 if pr == "fp8" else (lambda x: x)
            out[pr] = [np.asarray(jnp.einsum("td,vd->tv", q8(_rms(h[a:n], final_norm, cfg["rms_norm_eps"])), q8(head)))
                       for h, a, n in zip(hs[pr], logits_from, lens)]
    return out


def forward(cfg: dict, seed: int, tokens, experts: Optional[Tuple[int, int]] = None,
            vocab: Optional[Tuple[int, int]] = None, precision: str = "f32", logits_from: int = 0,
            layers_out: Optional[list] = None, selection_out: Optional[list] = None) -> np.ndarray:
    """Logits (T - logits_from, rows held) of one sequence."""
    keep = precision == "f32"
    return forward_many(cfg, seed, [tokens], experts, vocab, (precision,), [logits_from],
                        layers_out=layers_out if keep else None,
                        selection_out=selection_out if keep else None)[precision][0]


def route(cfg: dict, p: dict, u):
    """The router alone over normed inputs ``u`` (T, d): (chosen experts (T, k), their weights)."""
    with jax.default_matmul_precision("highest"):
        return _ops(cfg, False)[2](p, u)


def expert_layer(cfg: dict, seed: int, i: int, u, experts: Tuple[int, int], shared: bool = True) -> np.ndarray:
    """Layer ``i``'s expert branch alone over normed inputs ``u`` (T, d): the part
    the routed experts ``(first, count)`` give, with or without the shared expert's part."""
    p = layer_weights(cfg, seed, i, experts)
    _, mlp, router = _ops(cfg, False)
    with jax.default_matmul_precision("highest"):
        idx, w = router(p, u)
        y = jnp.zeros_like(u)
        for e in range(experts[1]):
            g = jnp.sum(jnp.where(idx == experts[0] + e, w, 0.0), -1)
            y = y + g[:, None] * mlp(u, p["w_in"][e], p["w_out"][e])
        if shared:
            y = y + mlp(u, p["shared_in"], p["shared_out"])
        return np.asarray(y)


# -- counts of the work ---------------------------------------------------------
def _expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def selected_pairs(length: float, topk: int) -> float:
    """(query, key) pairs a sequence of ``length`` tokens attends: sum over positions of min(position + 1, topk)."""
    full = min(length, topk)
    return full * (full + 1) / 2 + (length - full) * topk


def _per_token_flops(cfg: dict, held_share: float) -> float:
    """Matrix products one token needs in the whole model but the head, without
    the index scores and the attention's scores and weighted values, which
    depend on the pairs: the projections of the attention and of the indexer and
    the expansion of its own latent row to keys and values, once; the dense
    layers' MLP; the shared expert, the router, and the held share of its
    ``num_experts_per_tok`` routed experts."""
    d, H, Hi, Di = cfg["hidden_size"], cfg["num_attention_heads"], cfg["index_n_heads"], cfg["index_head_dim"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    attention = 2 * (d * ql + ql * H * (nope + rp) + d * (kl + rp) + kl * H * (nope + dv) + H * dv * d)
    indexer = 2 * (ql * Hi * Di + d * Di + d * Hi)
    fe = cfg["moe_intermediate_size"]
    moe = 6 * d * fe * cfg["n_shared_experts"] + 2 * d * cfg["router_outputs"] + held_share * cfg["num_experts_per_tok"] * 6 * d * fe
    return float(cfg["num_hidden_layers"] * (attention + indexer)
                 + cfg["first_k_dense_replace"] * 6 * d * cfg["intermediate_size"] + _expert_layers(cfg) * moe)


def index_flops(cfg: dict, pairs: float) -> float:
    """The least work of one layer's index scores: 2 x heads x width a causal (query, key) pair."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] * pairs


def index_bytes(cfg: dict, query_tokens: float, rows_read: float) -> float:
    """The least one layer's index scores move through HBM: each held indexer key
    read once a call (``rows_read``: summed over calls and sequences; bfloat16),
    every query's ``index_n_heads`` vectors (bfloat16) and weights (float32) read once."""
    return float(2 * cfg["index_head_dim"] * rows_read
                 + (2 * cfg["index_n_heads"] * cfg["index_head_dim"] + 4 * cfg["index_n_heads"]) * query_tokens)


def mla_core_flops(cfg: dict, selected: float) -> float:
    """The least work of one attention's core: scores and weighted values of every
    *selected* (query, key) pair over expanded heads, 2 x heads x (qk + v) a pair
    (the expansion of each token's own row is counted with the projections)."""
    return 2.0 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) * selected


def mla_core_bytes(cfg: dict, query_tokens: float, rows_read: float) -> float:
    """The least one attention's core moves through HBM: each latent row a query
    of the call selected read once (``rows_read``: summed over calls and
    sequences, at most the rows held; bfloat16), every query read and every output written once."""
    H = cfg["num_attention_heads"]
    per_query = 2 * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return float(2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * rows_read + per_query * query_tokens)


def expert_matmul_flops(cfg: dict, held_assignments: float) -> float:
    """The routed experts' two products for the assignments that reach a held
    expert, one layer: 2 x d x 2f and 2 x f x d each."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * held_assignments


def expert_matmul_bytes(cfg: dict, held_assignments: float, experts_read: float) -> float:
    """The least one layer's routed experts move: the weights of every expert an
    assignment reached, once a call (``experts_read``: summed over calls;
    bfloat16), and each assignment's input row read and output row written."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return float(experts_read * 3 * d * f * 2 + held_assignments * 2 * d * 2)


def step_flops(cfg: dict, tokens: float, index_pairs: float, selected: float, held_share: float) -> float:
    """Operations the whole model needs for ``tokens`` tokens (prefill or decode
    alike) whose queries see ``index_pairs`` causal pairs and attend ``selected``
    of them, in every layer; the head is counted once a sequence by the caller
    (``head_flops``). ``held_share``: of the router's assignments, the share that
    reaches an expert held here (absent experts cost nothing here)."""
    return float(_per_token_flops(cfg, held_share) * tokens
                 + cfg["num_hidden_layers"] * (index_flops(cfg, index_pairs) + mla_core_flops(cfg, selected)))


def head_flops(cfg: dict, rows: float) -> float:
    return 2.0 * cfg["hidden_size"] * share(cfg)[1][1] * rows
