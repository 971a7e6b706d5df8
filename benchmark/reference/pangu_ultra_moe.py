"""Plain reference for the openPangu-Ultra-MoE-718B decoder and its
multi-token-prediction (MTP) block: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the configuration's keys
(each item from memory is in the configuration's ``assumed``): no cache, no
chunks, no batching, no absorbed form of the latent attention (every key and
value is expanded), every held expert looped over every token. ``x`` is the
residual stream, every norm RMSNorm:

    layer:  a = Attn(norm_in(x));   h = x + norm_post_attn(a);   m = FFN(norm_pre_mlp(h));   x' = h + norm_post_mlp(m)
    FFN     layer i < first_k_dense_replace: SwiGLU_intermediate_size;  else Shared(u) + sum_{e chosen} w_e Expert_e(u)
    h_i = norm_final(x_last at i);   logits_i = h_i @ W_head                   (embedding and head untied)
    MTP     g_i = W_eh [norm_e(E[t_{i+1}]) ; norm_h(h_i)]  (i = 0 .. T - 2);  y = one expert layer as above over g
            (its own causal attention over the positions i);   q_{i+2} = norm_shared_head(y_i) @ W_head

    MLA    c_q = norm(x W_qa);  q = c_q W_qb -> H x [q_nope (qk_nope_head_dim) | q_r (qk_rope_head_dim)]
           [c | k_r] = x W_kva;  c = norm(c);   [k_nope | v] = c W_kvb -> H x [qk_nope_head_dim | v_head_dim]
           q_r, k_r = RoPE(q_r), RoPE(k_r): k_r one vector a token, shared by all heads; pairs interleaved
           (x[2i], x[2i+1]) turned by position * rope_theta ** (-2i / qk_rope_head_dim)
           score = (q_nope . k_nope + q_r . k_r) * (qk_nope_head_dim + qk_rope_head_dim) ** -0.5, causal;
           out = (sum_s softmax(score) v) W_o
    Router s = sigmoid(u W_r) over all ``router_outputs`` experts;  chosen = top num_experts_per_tok of s + bias
           (no groups);  w_e = routed_scaling_factor * s_e / sum_{chosen} s     (norm_topk_prob; the bias moves the choice only)

It imports nothing of the program and takes nothing the program has made. The
share of a deployment is an argument: ``experts = (first, count)`` are the routed
experts held, in every expert layer and the MTP block's (the router still ranks
all of them and renormalises over all chosen; what the absent ones would add is
left out; the shared expert is whole) and ``vocab = (first row, rows)`` the slice
of embedding and head. The attention runs a block of queries at a time against
every key, 16 heads at a time, one softmax a row. The weights are drawn here from
the seed, a layer at a time when the forward reaches it, by the rules below, and
rounded to bfloat16 as the configuration states.

Weight rules (key = PRNGKey(seed); layer i folds i + 1, then the tensor's index in
``tensor_specs``; an expert folds its global id; embedding, final norm, head and
the MTP block fold 0 then 0 / 1 / 2 / 3; the MTP block's ``MTP_SPECS`` fold 0 .. 3
and its layer 4, then its tensors' indices; embedding and head fold their 64-row
block): matrices normal with std fan_in ** -0.5 (the head too, its fan-in being
``hidden_size``; ``W_eh``'s is 2 x ``hidden_size``), each routed expert's
``W_out`` with std fan_in ** -0.5 x ``expert_gain``; embedding normal, std
``embedding_std``; norm weights 1 + 0.1 normal; the router's bias
``router_bias_std`` x normal, kept in float32.

``precision="fp8"`` is the control of the benchmark's comparison: the same
forward with both operands of every matrix product rounded to float8_e4m3 under a
per-tensor scale, the step below the bfloat16 the configuration states.

The counts at the end (``step_flops``, ``mtp_flops``, ``head_flops``,
``spec_step_bytes``) are of the least work and the least bytes, from token counts
and shapes, apart from any implementation.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_BLOCK_ROWS = 64
#: Queries the attention takes at once (the largest divisor of the length up to this).
QUERY_BLOCK = 128
#: Heads whose queries, keys and values are expanded at once (the largest divisor of the heads up to this).
HEAD_GROUP = 16
#: ``forward_many`` pads a sequence to the smallest of this many even steps up to ``pad_to`` that holds it.
PAD_STEPS = 4
MTP_SPECS = (("enorm", "norm"), ("hnorm", "norm"), ("eh_proj", "matrix"), ("shared_head_norm", "norm"))


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
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    specs = [("input_norm", (d,), "norm"),
             ("q_a", (d, ql), "matrix"), ("q_a_norm", (ql,), "norm"), ("q_b", (ql, H * (nope + rp)), "matrix"),
             ("kv_a", (d, kl + rp), "matrix"), ("kv_a_norm", (kl,), "norm"), ("kv_b", (kl, H * (nope + dv)), "matrix"),
             ("o", (H * dv, d), "matrix"),
             ("post_attn_norm", (d,), "norm"), ("pre_mlp_norm", (d,), "norm"), ("post_mlp_norm", (d,), "norm")]
    if dense:
        f = cfg["intermediate_size"]
        return specs + [("ffn_in", (d, 2 * f), "matrix"), ("ffn_out", (f, d), "matrix")]
    fs, fe = cfg["n_shared_experts"] * cfg["moe_intermediate_size"], cfg["moe_intermediate_size"]
    return specs + [("shared_in", (d, 2 * fs), "matrix"), ("shared_out", (fs, d), "matrix"),
                    ("router", (d, cfg["router_outputs"]), "matrix"), ("router_bias", (cfg["router_outputs"],), "router_bias"),
                    ("w_in", (d, 2 * fe), "experts"), ("w_out", (fe, d), "experts_out")]


def _draw(cfg, key, shape, rule, gain: float = 1.0):
    n = jax.lax.optimization_barrier(jax.random.normal(key, shape, jnp.float32))  # as the generator gave it
    if rule == "matrix":
        return n * (shape[0] ** -0.5 * gain)
    if rule == "norm":
        return 0.1 * n + 1.0
    if rule == "router_bias":
        return cfg["router_bias_std"] * n
    raise ValueError(rule)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _weights(cfg: dict, key, dense: bool, experts: Tuple[int, int]) -> Dict[str, jax.Array]:
    out = {}
    for j, (name, shape, rule) in enumerate(tensor_specs(cfg, dense)):
        k = jax.random.fold_in(key, j)
        if rule in ("experts", "experts_out"):  # an expert's weights come from its global id, whoever holds it
            gain = cfg["expert_gain"] if rule == "experts_out" else 1.0
            out[name] = jnp.stack([_bf16(_draw(cfg, jax.random.fold_in(k, e), shape, "matrix", gain))
                                   for e in range(experts[0], experts[0] + experts[1])])
        else:
            w = _draw(cfg, k, shape, rule)
            out[name] = w if rule == "router_bias" else _bf16(w)
    return out


def layer_weights(cfg: dict, seed: int, i: int, experts: Tuple[int, int]) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors as float32 holding bfloat16 values (the router's bias
    as drawn); ``w_in`` and ``w_out`` are stacked over the experts ``first .. first + count``."""
    return _weights(cfg, jax.random.fold_in(jax.random.PRNGKey(seed), i + 1), is_dense(cfg, i), experts)


def mtp_weights(cfg: dict, seed: int, experts: Tuple[int, int]) -> Dict[str, jax.Array]:
    """The MTP block's tensors: ``MTP_SPECS`` and its expert layer under ``layer``."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), 0), 3)
    d = cfg["hidden_size"]
    shapes = {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d), "shared_head_norm": (d,)}
    out = {name: _bf16(_draw(cfg, jax.random.fold_in(key, j), shapes[name], rule)) for j, (name, rule) in enumerate(MTP_SPECS)}
    out["layer"] = _weights(cfg, jax.random.fold_in(key, len(MTP_SPECS)), False, experts)
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


# -- the forward ----------------------------------------------------------------
def frequencies(cfg: dict) -> np.ndarray:
    n = cfg["qk_rope_head_dim"]
    return (cfg["rope_theta"] ** (-np.arange(0, n, 2, dtype=np.float64) / n)).astype(np.float32)


def _rope(x, inv, first: int = 0):
    """x (T, ..., n) at positions first .. first + T - 1: the pair (x[2i], x[2i+1]) turned by position * inv[i]."""
    ang = (first + jnp.arange(x.shape[0], dtype=jnp.float32))[:, None] * jnp.asarray(inv)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ops(cfg: dict, fp8: bool):
    q8 = _fp8 if fp8 else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def mlp(x, w_in, w_out):
        a, b = jnp.split(mm("ti,io->to", x, w_in), 2, -1)
        return mm("ti,io->to", jax.nn.silu(a) * b, w_out)

    def route(p, u):
        s = jax.nn.sigmoid(mm("ti,io->to", u, p["router"]))
        _, idx = jax.lax.top_k(s + p["router_bias"], cfg["num_experts_per_tok"])
        w = jnp.take_along_axis(s, idx, -1)
        if cfg["norm_topk_prob"]:
            w = w / jnp.sum(w, -1, keepdims=True)
        return idx, cfg["routed_scaling_factor"] * w

    return mm, mlp, route


def _layer(cfg: dict, fp8: bool, first_expert: int, dense: bool):
    """-> jitted ``(weights, x (T, d)) -> x'`` for one sandwich-normed layer."""
    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    kl, nope, rp, dv = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    inv = frequencies(cfg)
    scale = (nope + rp) ** -0.5
    mm, mlp, route = _ops(cfg, fp8)

    def attention(p, x):
        t = x.shape[0]
        c_q = _rms(mm("ti,io->to", x, p["q_a"]), p["q_a_norm"], eps)
        ckr = mm("ti,io->to", x, p["kv_a"])
        c = _rms(ckr[:, :kl], p["kv_a_norm"], eps)
        k_r = _rope(ckr[:, kl:], inv)                                # one rotary key a token, shared by all heads
        blk = max(b for b in range(1, min(t, QUERY_BLOCK) + 1) if t % b == 0)
        hg = max(g for g in range(1, min(H, HEAD_GROUP) + 1) if H % g == 0)
        by_group = lambda w, width: jnp.moveaxis(w.reshape(w.shape[0], H // hg, hg * width), 1, 0)  # noqa: E731

        def group(args):                                             # hg heads: their queries, keys and values
            w_q, w_kv = args
            q = mm("ti,io->to", c_q, w_q).reshape(t, hg, nope + rp)
            q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv)], -1)
            kv = mm("ti,io->to", c, w_kv).reshape(t, hg, nope + dv)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (t, hg, rp))], -1)

            def block(args):                                         # blk queries against every key, causal
                qb, first = args
                scores = mm("qhd,khd->hqk", qb, k) * scale
                causal = (first + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
                return mm("hqk,khd->qhd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), kv[..., nope:])

            return jax.lax.map(block, (q.reshape(t // blk, blk, hg, nope + rp), jnp.arange(0, t, blk))).reshape(t, hg * dv)

        out = jax.lax.map(group, (by_group(p["q_b"], nope + rp), by_group(p["kv_b"], nope + dv)))
        return mm("ti,io->to", jnp.moveaxis(out, 0, 1).reshape(t, H * dv), p["o"])

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
            h = x + _rms(attention(p, _rms(x, p["input_norm"], eps)), p["post_attn_norm"], eps)
            u = _rms(h, p["pre_mlp_norm"], eps)
            m = mlp(u, p["ffn_in"], p["ffn_out"]) if dense else moe(p, u)
            return h + _rms(m, p["post_mlp_norm"], eps)

    return layer


def _mtp_input(cfg: dict, fp8: bool):
    """-> jitted ``(MTP weights, embedding rows of t_{i+1} (T - 1, d), h_i (T - 1, d)) -> g``."""
    mm, _, _ = _ops(cfg, fp8)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def g(w, e, h):
        with jax.default_matmul_precision("highest"):
            return mm("ti,io->to", jnp.concatenate([_rms(e, w["enorm"], eps), _rms(h, w["hnorm"], eps)], -1), w["eh_proj"])

    return g


def _padded_length(n: int, pad_to: Optional[int]) -> int:
    if not pad_to:
        return n
    step = -(-pad_to // PAD_STEPS)
    return min(-(-n // step) * step, max(pad_to, n))


def forward_many(cfg: dict, seed: int, sequences, experts: Optional[Tuple[int, int]] = None,
                 vocab: Optional[Tuple[int, int]] = None, precisions=("f32",), logits_from=None,
                 pad_to: Optional[int] = None, mtp: bool = True) -> Dict[str, List[np.ndarray]]:
    """Logits of each sequence of token ids (ids are rows of the held slice), in each of ``precisions``:
    ``{precision: [(T_i - logits_from[i], rows held), ...]}``, row k the main model's prediction of token
    ``logits_from[i] + k + 1``; with ``mtp``, also ``{"mtp." + precision: [(T_i - 1 - logits_from[i], rows held)]}``,
    row k the MTP block's, at position ``logits_from[i] + k``, of token ``logits_from[i] + k + 2`` (from the main
    model's normed hidden state there and token ``logits_from[i] + k + 1``), teacher-forced. Each layer's weights are
    drawn once and every sequence goes through before the next layer's are; with ``pad_to`` a sequence is
    right-padded to the smallest of ``PAD_STEPS`` even steps up to it that holds it (the model is causal)."""
    if set(precisions) - {"f32", "fp8"}:
        raise ValueError(f"precisions {precisions!r}")
    own_experts, own_vocab = share(cfg)
    experts, vocab = experts or own_experts, vocab or own_vocab
    emb, final_norm, head = embedding(cfg, seed, vocab)
    eps = cfg["rms_norm_eps"]
    lens = [len(t) for t in sequences]
    logits_from = list(logits_from) if logits_from is not None else [0] * len(lens)
    padded = [np.pad(np.asarray(t), (0, _padded_length(n, pad_to) - n)) for t, n in zip(sequences, lens)]
    hs = {pr: [emb[jnp.asarray(t)] for t in padded] for pr in precisions}
    fns = {(pr, dense): _layer(cfg, pr == "fp8", experts[0], dense) for pr in precisions for dense in (True, False)}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(cfg, seed, i, experts)
        for pr in precisions:
            hs[pr] = [fns[pr, is_dense(cfg, i)](w, h) for h in hs[pr]]
        jax.block_until_ready(hs)  # the next layer's weights are placed only when this one's are free
        del w
    out = {}
    with jax.default_matmul_precision("highest"):
        normed = {pr: [_rms(h, final_norm, eps) for h in hs[pr]] for pr in precisions}
        for pr in precisions:
            q8 = _fp8 if pr == "fp8" else (lambda x: x)
            out[pr] = [np.asarray(jnp.einsum("td,vd->tv", q8(h[a:n]), q8(head))) for h, a, n in zip(normed[pr], logits_from, lens)]
    if not mtp:
        return out
    w = mtp_weights(cfg, seed, experts)
    for pr in precisions:
        fp8 = pr == "fp8"
        q8 = _fp8 if fp8 else (lambda x: x)
        g = _mtp_input(cfg, fp8)
        # every padded position, the last one's pair from a padding token (causal: no earlier row sees it), so that the
        # main expert layers' compiled function serves the block's
        ys = [fns[pr, False](w["layer"], g(w, emb[jnp.asarray(np.append(t[1:], 0))], h)) for t, h in zip(padded, normed[pr])]
        with jax.default_matmul_precision("highest"):
            out["mtp." + pr] = [np.asarray(jnp.einsum("td,vd->tv", q8(_rms(y[a:n - 1], w["shared_head_norm"], eps)), q8(head)))
                                for y, a, n in zip(ys, logits_from, lens)]
    return out


def forward(cfg: dict, seed: int, tokens, experts: Optional[Tuple[int, int]] = None,
            vocab: Optional[Tuple[int, int]] = None, precision: str = "f32", logits_from: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """One sequence: (main logits (T - logits_from, rows held), MTP logits (T - 1 - logits_from, rows held))."""
    got = forward_many(cfg, seed, [tokens], experts, vocab, (precision,), [logits_from])
    return got[precision][0], got["mtp." + precision][0]


def route(cfg: dict, p: dict, u):
    """The router alone over normed inputs ``u`` (T, d): (chosen experts (T, k), their weights)."""
    with jax.default_matmul_precision("highest"):
        return _ops(cfg, False)[2](p, u)


def expert_layer(cfg: dict, seed: int, i: int, u, experts: Tuple[int, int], shared: bool = True) -> np.ndarray:
    """Layer ``i``'s FFN alone over normed inputs ``u`` (T, d): the part the routed experts ``(first, count)`` give,
    with or without the shared expert's part (before the post-MLP norm)."""
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
def _attention_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return d * ql + ql * H * (nope + rp) + d * (kl + rp) + kl * H * (nope + dv) + H * dv * d


def _expert_layer_flops(cfg: dict, held_share: float) -> float:
    """An expert layer's products but the attention's: the shared expert, the router and the held share of the
    ``num_experts_per_tok`` routed experts, a token."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (6 * d * fe * cfg["n_shared_experts"] + 2 * d * cfg["router_outputs"]
            + held_share * cfg["num_experts_per_tok"] * 6 * d * fe)


def mla_core_flops(cfg: dict, pairs: float) -> float:
    """The least work of one attention's core: scores and weighted values over ``pairs`` causal (query, key) pairs,
    expanded heads, 2 x heads x (qk + v) a pair."""
    return 2.0 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) * pairs


def mla_core_bytes(cfg: dict, query_tokens: float, rows_read: float) -> float:
    """The least one attention's core moves through HBM: each held latent row read once a call (``rows_read``: summed
    over calls and sequences; bfloat16), every query read and every output written once."""
    per_query = 2 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return float(2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * rows_read + per_query * query_tokens)


def step_flops(cfg: dict, tokens: float, pairs: float, held_share: float) -> float:
    """Operations the main layers need for ``tokens`` tokens whose queries see ``pairs`` causal pairs in every layer
    (the projections and the expansion of each token's own latent row once, the dense layers' MLP, the expert
    layers' shared expert, router and held share of routed experts); the head is ``head_flops``."""
    dense = cfg["first_k_dense_replace"]
    per_token = (cfg["num_hidden_layers"] * 2 * _attention_params(cfg) + dense * 6 * cfg["hidden_size"] * cfg["intermediate_size"]
                 + (cfg["num_hidden_layers"] - dense) * _expert_layer_flops(cfg, held_share))
    return float(per_token * tokens + cfg["num_hidden_layers"] * mla_core_flops(cfg, pairs))


def mtp_flops(cfg: dict, tokens: float, pairs: float, held_share: float) -> float:
    """The MTP block over ``tokens`` positions whose queries see ``pairs`` pairs: W_eh and one expert layer (its head
    is ``head_flops``)."""
    d = cfg["hidden_size"]
    return float((2 * 2 * d * d + 2 * _attention_params(cfg) + _expert_layer_flops(cfg, held_share)) * tokens
                 + mla_core_flops(cfg, pairs))


def mtp_rows_flops(cfg: dict, tokens: float) -> float:
    """What the MTP block's cache needs of a prefilled position: W_eh and its latent row's projection."""
    d = cfg["hidden_size"]
    return float((2 * 2 * d * d + 2 * d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])) * tokens)


def head_flops(cfg: dict, rows: float) -> float:
    return 2.0 * cfg["hidden_size"] * share(cfg)[1][1] * rows


def held_weight_bytes(cfg: dict, experts_reached: float) -> float:
    """Bytes (bfloat16) of the weights one speculative step reads: every held parameter of the main layers and of
    the MTP block but the routed experts' and the embedding's (a step gathers a few rows of it), the head, and the
    weights of ``experts_reached`` routed experts (summed over the expert layers and the MTP block)."""
    d, fe, L = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    expert_layer = 3 * d * fe * cfg["n_shared_experts"] + d * cfg["router_outputs"]
    params = (L + 1) * (_attention_params(cfg) + 4 * d) + dense * 3 * d * cfg["intermediate_size"] \
        + (L - dense + 1) * expert_layer + 2 * d * d + 3 * d + share(cfg)[1][1] * d
    return 2.0 * (params + experts_reached * 3 * d * fe)


def spec_step_bytes(cfg: dict, experts_reached: float, keys: float, active: float) -> float:
    """The least one speculative step of ``active`` slots moves through HBM: ``held_weight_bytes`` and every cache row
    its queries see, once (``keys``: the active slots' positions up to the verified draft's, m + 2 a slot, in each main
    attention; two fewer a slot in the MTP block's; bfloat16)."""
    row = 2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return held_weight_bytes(cfg, experts_reached) + float(row * (cfg["num_hidden_layers"] * keys + keys - 2 * active))
