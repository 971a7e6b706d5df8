"""Plain reference for the LongCat-Flash decoder: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the configuration's keys
and the published modelling: no cache, no chunks, no batching, no absorbed form
of the latent attention (every key and value is expanded), every held expert
looped over every token. ``x`` is the residual stream, every norm RMSNorm:

    a  = x  + MLA_0(norm(x))            u = norm(a)
    m  = MoE(u)                         # the shortcut branch: taken here ...
    b  = a  + FFN_0(u)                  # dense SwiGLU, hidden_size -> ffn_hidden_size -> hidden_size
    c  = b  + MLA_1(norm(b))
    d  = c  + FFN_1(norm(c))
    x' = d  + m                         # ... added here
    logits = norm(x_last) @ W_head      # embedding and head untied

    MLA   q         = (norm(x W_qa) * s_q) W_qb -> H x [q_nope | q_r],   s_q  = sqrt(hidden_size / q_lora_rank)
          [c | k_r] = x W_kva;  c = norm(c) * s_kv,                       s_kv = sqrt(hidden_size / kv_lora_rank)
          [k_nope | v] = c W_kvb -> H x [qk_nope_head_dim | v_head_dim]
          q_r, k_r = RoPE(q_r), RoPE(k_r): k_r one vector a token, shared by all heads; pairs interleaved
          (x[2i], x[2i+1]) turned by position * rope_theta ** (-2i / qk_rope_head_dim)
          score = (q_nope . k_nope + q_r . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim), causal
          out = softmax(score) v -> W_o
    MoE   s = softmax(u W_r) over all ``router_outputs`` (routed + zero), chosen = top-``moe_topk`` of (s + bias),
          w_e = routed_scaling_factor * s_e: the bias moves the choice, not the weight; no renormalisation
          MoE(u) = sum_{e chosen, e routed} w_e SwiGLU_e(u) + (sum_{e chosen, e zero} w_e) u      (identity experts)

It imports nothing of the program and takes nothing the program has made. The
share of a deployment is an argument: ``experts = (first, count)`` are the routed
experts held (the router still ranks all routed and zero outputs, what the
absent ones would add is left out) and ``vocab = (first row, rows)`` the slice
of embedding and head. The attention runs a block of queries at a time against
every key (one float32 score matrix of 64 heads at 16,448 positions is 69 GB):
still one softmax a row, nothing carried between blocks. The weights are drawn
here from the seed, a layer at a time when the forward reaches it (a whole layer
in float32 is 5.0 GB at the published widths and 16 held experts), by the rules
below, and rounded to bfloat16 as the configuration states.

Weight rules (key = PRNGKey(seed); layer i folds i + 1, then the tensor's index
in ``tensor_specs``; an expert folds its global id; embedding, final norm and
head fold 0 then 0 / 1 / 2; embedding and head fold their 64-row block): matrices
normal with std fan_in ** -0.5 (the head too, its fan-in being ``hidden_size``),
``W_kvb`` with std fan_in ** -0.5 / s_kv (keys and values then leave at the
stream's scale and the scores spread ~2; at plain fan-in scale they spread ~7
and every attention picks a handful of keys);
embedding normal, std ``embedding_std``; norm weights 1 + 0.1 normal; the
router's bias ``router_bias_std`` x normal, kept in float32.

``precision="fp8"`` is the control of the benchmark's comparison: the same
forward with both operands of every matrix product rounded to float8_e4m3 under
a per-tensor scale, the step below the bfloat16 the configuration states.

The counts at the end (``step_flops``, ``mla_core_*``, ``expert_matmul_*``) are
of the work, from token counts and shapes, apart from any implementation.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_BLOCK_ROWS = 64
#: Queries the attention takes at once (the largest divisor of the length up to this).
QUERY_BLOCK = 512


# -- sizes ----------------------------------------------------------------------
def share(cfg: dict) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """The configuration's own share: ((first expert, experts held), (first row, rows held)).
    ``n_routed_experts`` and ``vocab_size`` are as run: what this chip holds."""
    held, rows = cfg["n_routed_experts"], cfg["vocab_size"]
    return (cfg["options"]["expert_shard"][0] * held, held), (cfg["options"]["vocab_shard"][0] * rows, rows)


def routed_experts(cfg: dict) -> int:
    """Routed experts of the whole model: the router's outputs less the zero experts."""
    return cfg["router_outputs"] - cfg["zero_expert_num"]


def tensor_specs(cfg: dict) -> List[Tuple[str, tuple, str]]:
    """One double layer's tensors in the order their keys are folded: (name, shape of one, rule)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    f, fe = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    specs = []
    for s in ("0", "1"):
        specs += [("attn_norm" + s, (d,), "norm"),
                  ("q_a" + s, (d, ql), "matrix"), ("q_a_norm" + s, (ql,), "norm"),
                  ("q_b" + s, (ql, H * (nope + rp)), "matrix"),
                  ("kv_a" + s, (d, kl + rp), "matrix"), ("kv_a_norm" + s, (kl,), "norm"),
                  ("kv_b" + s, (kl, H * (nope + dv)), "latent_up"),
                  ("o" + s, (H * dv, d), "matrix"),
                  ("ffn_norm" + s, (d,), "norm"),
                  ("ffn_in" + s, (d, 2 * f), "matrix"), ("ffn_out" + s, (f, d), "matrix")]
    return specs + [("router", (d, cfg["router_outputs"]), "matrix"), ("router_bias", (cfg["router_outputs"],), "router_bias"),
                    ("w_in", (d, 2 * fe), "experts"), ("w_out", (fe, d), "experts")]


def _as_drawn(x):
    """A draw as the generator gave it: inside a jitted program XLA would fold
    the scale that follows into the generator's own last product, and round
    otherwise than the same two steps taken one by one."""
    return jax.lax.optimization_barrier(x)


def _draw(cfg, key, shape, rule):
    n = _as_drawn(jax.random.normal(key, shape, jnp.float32))
    if rule == "matrix":
        return n * (shape[0] ** -0.5)
    if rule == "latent_up":  # W_kvb: its input is the normed latent times s_kv; keys and values leave at the stream's scale
        s_kv = (cfg["hidden_size"] / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
        return n * (shape[0] ** -0.5 * (1.0 / s_kv))
    if rule == "norm":
        return 0.1 * n + 1.0
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
    for j, (name, shape, rule) in enumerate(tensor_specs(cfg)):
        k = jax.random.fold_in(key, j)
        if rule == "experts":  # an expert's weights come from its global id, whoever holds it
            out[name] = jnp.stack([_bf16(_draw(cfg, jax.random.fold_in(k, e), shape, "matrix"))
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


# -- the forward ----------------------------------------------------------------
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x (T, ..., n) at positions 0 .. T - 1: the pair (x[2i], x[2i+1]) turned by position * theta ** (-2i / n)."""
    t, n = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (n // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _ops(cfg: dict, fp8: bool):
    q8 = _fp8 if fp8 else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def mlp(x, w_in, w_out):
        a, b = jnp.split(mm("ti,io->to", x, w_in), 2, -1)
        return mm("ti,io->to", jax.nn.silu(a) * b, w_out)

    def route(p, u):
        s = jax.nn.softmax(mm("ti,io->to", u, p["router"]), -1)
        _, idx = jax.lax.top_k(s + p["router_bias"], cfg["moe_topk"])
        return idx, cfg["routed_scaling_factor"] * jnp.take_along_axis(s, idx, -1)

    return mm, mlp, route


def _layer(cfg: dict, fp8: bool, first_expert: int):
    """-> jitted ``(weights, x (T, d)) -> x`` for one double layer."""
    eps, d, H = cfg["rms_norm_eps"], cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    s_q = (d / ql) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    s_kv = (d / kl) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
    mm, mlp, route = _ops(cfg, fp8)

    def mla(p, s, x):
        t = x.shape[0]
        q = mm("ti,io->to", _rms(mm("ti,io->to", x, p["q_a" + s]), p["q_a_norm" + s], eps) * s_q,
               p["q_b" + s]).reshape(t, H, nope + rp)
        ckr = mm("ti,io->to", x, p["kv_a" + s])
        c = _rms(ckr[:, :kl], p["kv_a_norm" + s], eps) * s_kv
        kv = mm("ti,io->to", c, p["kv_b" + s]).reshape(t, H, nope + dv)
        k_r = jnp.broadcast_to(_rope(ckr[:, kl:], cfg["rope_theta"])[:, None, :], (t, H, rp))
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg["rope_theta"])], -1)
        k, v = jnp.concatenate([kv[..., :nope], k_r], -1), kv[..., nope:]
        blk = max(b for b in range(1, min(t, QUERY_BLOCK) + 1) if t % b == 0)

        def block(args):                                             # blk queries against every key
            qb, first = args
            scores = mm("qhd,khd->hqk", qb, k) * (nope + rp) ** -0.5
            causal = (first + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
            return mm("hqk,khd->qhd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)

        out = jax.lax.map(block, (q.reshape(t // blk, blk, H, nope + rp), jnp.arange(0, t, blk)))
        return mm("ti,io->to", out.reshape(t, H * dv), p["o" + s])

    def moe(p, u):
        idx, w = route(p, u)

        def one(y, expert):                                          # every held expert, every token
            e, w_in, w_out = expert
            g = jnp.sum(jnp.where(idx == first_expert + e, w, 0.0), -1)
            return y + g[:, None] * mlp(u, w_in, w_out), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u), (jnp.arange(p["w_in"].shape[0]), p["w_in"], p["w_out"]))
        zero = jnp.sum(jnp.where(idx >= routed_experts(cfg), w, 0.0), -1)
        return y + zero[:, None] * u                                 # the identity experts

    @jax.jit
    def layer(p, x):
        with jax.default_matmul_precision("highest"):
            a = x + mla(p, "0", _rms(x, p["attn_norm0"], eps))
            u = _rms(a, p["ffn_norm0"], eps)
            m = moe(p, u)
            b = a + mlp(u, p["ffn_in0"], p["ffn_out0"])
            c = b + mla(p, "1", _rms(b, p["attn_norm1"], eps))
            return c + mlp(_rms(c, p["ffn_norm1"], eps), p["ffn_in1"], p["ffn_out1"]) + m

    return layer


def forward_many(cfg: dict, seed: int, sequences, experts: Optional[Tuple[int, int]] = None,
                 vocab: Optional[Tuple[int, int]] = None, precisions=("f32",), logits_from=None,
                 pad_to: Optional[int] = None, layers_out: Optional[list] = None) -> Dict[str, List[np.ndarray]]:
    """Logits of each sequence of token ids (ids are rows of the held slice), in
    each of ``precisions``: ``{precision: [(T_i - logits_from[i], rows held), ...]}``.
    Each layer's weights are drawn once and every sequence goes through before
    the next layer's are. ``pad_to`` right-pads every sequence to one length,
    so that one compiled layer serves all (the model is causal: what follows a
    position never reaches it). ``layers_out`` receives sequence 0's float32
    layer outputs (tests)."""
    if set(precisions) - {"f32", "fp8"}:
        raise ValueError(f"precisions {precisions!r}")
    own_experts, own_vocab = share(cfg)
    experts, vocab = experts or own_experts, vocab or own_vocab
    emb, final_norm, head = embedding(cfg, seed, vocab)
    lens = [len(t) for t in sequences]
    logits_from = list(logits_from) if logits_from is not None else [0] * len(lens)
    padded = [np.pad(np.asarray(t), (0, (pad_to or n) - n)) for t, n in zip(sequences, lens)]
    hs = {pr: [emb[jnp.asarray(t)] for t in padded] for pr in precisions}
    fns = {pr: _layer(cfg, pr == "fp8", experts[0]) for pr in precisions}
    for i in range(cfg["num_layers"]):
        w = layer_weights(cfg, seed, i, experts)
        for pr in precisions:
            hs[pr] = [fns[pr](w, h) for h in hs[pr]]
        # The host runs ahead of the device: without this wait the next layer's weights (5.0 GB in float32)
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
            layers_out: Optional[list] = None) -> np.ndarray:
    """Logits (T - logits_from, rows held) of one sequence."""
    return forward_many(cfg, seed, [tokens], experts, vocab, (precision,), [logits_from],
                        layers_out=layers_out if precision == "f32" else None)[precision][0]


def expert_layer(cfg: dict, seed: int, i: int, u, experts: Tuple[int, int], zero: bool = True) -> np.ndarray:
    """Layer ``i``'s expert branch alone over normed inputs ``u`` (T, d): the part
    the routed experts ``(first, count)`` give, with or without the identity experts' part."""
    p = layer_weights(cfg, seed, i, experts)
    _, mlp, route = _ops(cfg, False)
    with jax.default_matmul_precision("highest"):
        idx, w = route(p, u)
        y = jnp.zeros_like(u)
        for e in range(experts[1]):
            g = jnp.sum(jnp.where(idx == experts[0] + e, w, 0.0), -1)
            y = y + g[:, None] * mlp(u, p["w_in"][e], p["w_out"][e])
        if zero:
            y = y + jnp.sum(jnp.where(idx >= routed_experts(cfg), w, 0.0), -1)[:, None] * u
        return np.asarray(y)


# -- counts of the work ---------------------------------------------------------
def _per_token_layer_flops(cfg: dict, held_share: float) -> float:
    """Matrix products one token needs in one double layer, without the
    attention's scores and weighted values, which depend on the length."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl, nope, rp, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    mla = 2 * (d * ql + ql * H * (nope + rp) + d * (kl + rp) + kl * H * (nope + dv) + H * dv * d)
    ffn = 6 * d * cfg["ffn_hidden_size"]
    moe = 2 * d * cfg["router_outputs"] + held_share * cfg["moe_topk"] * 6 * d * cfg["expert_ffn_hidden_size"]
    return float(2 * (mla + ffn) + moe)


def mla_core_flops(cfg: dict, query_tokens: float, mean_keys: float) -> float:
    """The least work of one attention's core: scores and weighted values of
    every causal (query, key) pair over expanded heads, 2 x heads x (qk + v) a pair."""
    return 2.0 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) \
        * query_tokens * mean_keys


def mla_core_bytes(cfg: dict, query_tokens: float, rows_read: float) -> float:
    """The least one attention's core moves through HBM: each held latent row
    read once a call (``rows_read``: summed over calls and sequences; bfloat16),
    every query read and every output written once."""
    H = cfg["num_attention_heads"]
    per_query = 2 * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return float(2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * rows_read + per_query * query_tokens)


def expert_matmul_flops(cfg: dict, held_assignments: float) -> float:
    """The routed experts' two products for the assignments that reach a held
    expert, one layer: 2 x d x 2f and 2 x f x d each."""
    return 6.0 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"] * held_assignments


def expert_matmul_bytes(cfg: dict, held_assignments: float, experts_read: float) -> float:
    """The least one layer's routed experts move: the weights of every expert an
    assignment reached, once a call (``experts_read``: summed over calls;
    bfloat16), and each assignment's input row read and output row written."""
    d, f = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    return float(experts_read * 3 * d * f * 2 + held_assignments * 2 * d * 2)


def step_flops(cfg: dict, tokens: float, mean_keys: float, held_share: float) -> float:
    """Operations the whole model needs for ``tokens`` tokens (prefill or decode
    alike), each attending ``mean_keys`` positions in every attention; the head
    is counted once a sequence by the caller (``head_flops``). ``held_share``:
    of the router's assignments, the share that reaches an expert held here
    (identity experts and absent experts cost nothing here)."""
    layers = cfg["num_layers"]
    return float(layers * (_per_token_layer_flops(cfg, held_share) * tokens
                           + 2 * mla_core_flops(cfg, tokens, mean_keys)))


def head_flops(cfg: dict, rows: float) -> float:
    return 2.0 * cfg["hidden_size"] * share(cfg)[1][1] * rows
