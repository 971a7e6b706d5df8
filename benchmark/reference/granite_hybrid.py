"""Plain reference for the Granite 4.0-H decoder (``granitemoehybrid``): float32
``jax.numpy`` under ``default_matmul_precision("highest")``, written from the
configuration's keys and the published modelling: no cache, no chunks, no
batching, a sequential ``lax.scan`` for the Mamba-2 recurrence, every expert
looped over every token.

    h = embedding_multiplier * E[tok]
    h += residual_multiplier * Mixer(RMSNorm(h))        Mamba-2 or attention by layer_types
    h += residual_multiplier * (MoE(v) + Shared(v)),  v = RMSNorm(h)
    logits = RMSNorm(h) @ E.T / logits_scaling

    attention  32 query heads over 8 key/value heads of 128; softmax(q k.T * attention_multiplier + causal) v;
               no rotary, no learned positions
    MoE        r = v W_r; I = the top-k of r; g = softmax(r[I]) over those k only;
               y_e = W_out,e (silu(a) * b), [a; b] = W_in,e v; MoE = sum_{e in I} g_e y_e; Shared: the same MLP, always on
    Mamba-2    [z | xBC | dt] = u W_in; xBC = silu(causal depthwise conv_k(xBC) + b); x, B, C = split(xBC);
               D_t = softplus(dt + dt_bias); A = -exp(A_log); S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t;
               y_t = S_t C_t + D * x_t; y = RMSNorm(y * silu(z)); out = y W_out

It imports nothing of the program and takes nothing the program has made. The
share of a deployment is an argument: ``experts = (first, count)`` are the routed
experts held (the router still ranks all of them, the gates are not
renormalised, what the absent ones would add is left out) and ``vocab = (first
row, rows)`` the slice of the tied embedding. The weights are drawn here from
the seed, a layer at a time when the forward reaches it (a whole layer in
float32 is 1.84 GB at the published widths), by the rules below, and rounded
to bfloat16 as the configuration states: the same seed gives the program the
same values without either handing them to the other.

Weight rules (key = PRNGKey(seed); layer i folds i + 1, then the tensor's index
in ``tensor_specs``; an expert folds its global id; embedding and final norm
fold 0 then 0 / 1; the embedding folds its 64-row block): matrices normal with
std fan_in ** -0.5; embedding normal, std ``embedding_std``; norm weights and D
1 + 0.1 normal; conv bias 0.1 normal; ``A_log`` = log U(1, 16); ``dt_bias`` the
inverse softplus of a delta log-uniform in [1e-3, 1e-1].

``precision="fp8"`` is the control of the benchmark's comparison: the same
forward with both operands of every matrix product rounded to float8_e4m3 under
a per-tensor scale, the step below the bfloat16 the configuration states.

The counts at the end (``step_flops``, ``ssd_scan_*``, ``expert_matmul_*``) are
of the work, from token counts and shapes, apart from any implementation.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_BLOCK_ROWS = 64


# -- sizes ----------------------------------------------------------------------
def layer_types(cfg: dict) -> List[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def share(cfg: dict) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """The configuration's own share: ((first expert, experts held), (first row, rows held)).
    ``num_local_experts`` and ``vocab_size`` are as run: what this chip holds."""
    held, rows = cfg["num_local_experts"], cfg["vocab_size"]
    return (cfg["options"]["expert_shard"][0] * held, held), (cfg["options"]["vocab_shard"][0] * rows, rows)


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    return {"d": d, "di": di, "conv": di + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
            "hd": d // cfg["num_attention_heads"]}


def tensor_specs(cfg: dict, kind: str) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape of one, rule)."""
    z = _dims(cfg)
    d, f, fs = z["d"], cfg["intermediate_size"], cfg["shared_intermediate_size"]
    if kind == "mamba":
        h = cfg["mamba_n_heads"]
        mixer = [("norm", (d,), "norm"),
                 ("in_proj", (d, 2 * z["di"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] + h), "matrix"),
                 ("conv_w", (cfg["mamba_d_conv"], z["conv"]), "matrix"), ("conv_b", (z["conv"],), "bias"),
                 ("dt_bias", (h,), "dt_bias"), ("A_log", (h,), "A_log"), ("D", (h,), "norm"),
                 ("gate_norm", (z["di"],), "norm"), ("out_proj", (z["di"], d), "matrix")]
    else:
        q, kv = cfg["num_attention_heads"] * z["hd"], cfg["num_key_value_heads"] * z["hd"]
        mixer = [("norm", (d,), "norm"), ("q", (d, q), "matrix"), ("k", (d, kv), "matrix"),
                 ("v", (d, kv), "matrix"), ("o", (q, d), "matrix")]
    return mixer + [("moe_norm", (d,), "norm"), ("router", (d, cfg["router_outputs"]), "matrix"),
                    ("w_in", (d, 2 * f), "experts"), ("w_out", (f, d), "experts"),
                    ("shared_in", (d, 2 * fs), "matrix"), ("shared_out", (fs, d), "matrix")]


def _as_drawn(x):
    """A draw as the generator gave it: inside a jitted program XLA would fold
    the scale that follows into the generator's own last product, and round
    otherwise than the same two steps taken one by one."""
    return jax.lax.optimization_barrier(x)


def _draw(key, shape, rule):
    if rule in ("matrix", "norm", "bias"):
        n = _as_drawn(jax.random.normal(key, shape, jnp.float32))
        return n * (shape[0] ** -0.5) if rule == "matrix" else 0.1 * n + (rule == "norm")
    if rule == "A_log":
        return jnp.log(_as_drawn(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)))
    if rule == "dt_bias":
        dt = jnp.exp(_as_drawn(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(rule)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(cfg: dict, seed: int, i: int, experts: Tuple[int, int]) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors as float32 holding bfloat16 values; ``w_in`` and
    ``w_out`` are stacked over the experts ``first .. first + count``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i + 1)
    out = {}
    for j, (name, shape, rule) in enumerate(tensor_specs(cfg, layer_types(cfg)[i])):
        k = jax.random.fold_in(key, j)
        if rule == "experts":  # an expert's weights come from its global id, whoever holds it
            w = jax.vmap(lambda e: _draw(jax.random.fold_in(k, e), shape, "matrix"))(
                experts[0] + jnp.arange(experts[1]))
        else:
            w = _draw(k, shape, rule)
        out[name] = _bf16(w)
    return out


def embedding(cfg: dict, seed: int, vocab: Tuple[int, int]) -> Tuple[jax.Array, jax.Array]:
    """-> (rows ``first .. first + count`` of the tied embedding, the final norm's weight)."""
    k0 = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    ke = jax.random.fold_in(k0, 0)
    first, count = vocab[0] // EMBED_BLOCK_ROWS, vocab[1] // EMBED_BLOCK_ROWS
    blocks = [jax.random.normal(jax.random.fold_in(ke, b), (EMBED_BLOCK_ROWS, cfg["hidden_size"]), jnp.float32)
              for b in range(first, first + count)]
    rows = jnp.concatenate(blocks) * cfg["embedding_std"]
    return _bf16(rows), _bf16(_draw(jax.random.fold_in(k0, 1), (cfg["hidden_size"],), "norm"))


# -- the forward ----------------------------------------------------------------
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer(cfg: dict, kind: str, fp8: bool, first_expert: int):
    """-> jitted ``(weights, h (T, d)) -> h`` for one layer of ``kind``."""
    z = _dims(cfg)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    q8 = _fp8 if fp8 else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def mlp(x, w_in, w_out):
        a, b = jnp.split(mm("ti,io->to", x, w_in), 2, -1)
        return mm("ti,io->to", jax.nn.silu(a) * b, w_out)

    def mamba(p, u):
        t = u.shape[0]
        heads, hd, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
        k = cfg["mamba_d_conv"]
        zz, xbc, dt = jnp.split(mm("ti,io->to", u, p["in_proj"]), [z["di"], z["di"] + z["conv"]], -1)
        padded = jnp.concatenate([jnp.zeros((k - 1, z["conv"]), jnp.float32), xbc])
        xbc = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][j] for j in range(k)) + p["conv_b"])
        x, b, c = jnp.split(xbc, [z["di"], z["di"] + cfg["mamba_n_groups"] * n], -1)
        x = x.reshape(t, heads, hd)
        delta = jax.nn.softplus(dt + p["dt_bias"])                   # (t, heads)
        a = -jnp.exp(p["A_log"])

        def step(s, inp):                                            # s (heads, hd, n)
            x_t, b_t, c_t, d_t = inp
            s = jnp.exp(d_t * a)[:, None, None] * s + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
            return s, jnp.sum(s * c_t[None, None, :], -1)

        _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32), (x, b, c, delta))
        y = y + p["D"][:, None] * x
        y = _rms(y.reshape(t, z["di"]) * jax.nn.silu(zz), p["gate_norm"], eps)
        return mm("ti,io->to", y, p["out_proj"])

    def attention(p, u):
        t = u.shape[0]
        kv, hd = cfg["num_key_value_heads"], z["hd"]
        r = cfg["num_attention_heads"] // kv
        q = mm("ti,io->to", u, p["q"]).reshape(t, kv, r, hd)
        k = mm("ti,io->to", u, p["k"]).reshape(t, kv, hd)
        v = mm("ti,io->to", u, p["v"]).reshape(t, kv, hd)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def group(args):                                             # one key/value head and its r query heads
            qg, kg, vg = args                                        # (t, r, hd), (t, hd), (t, hd)
            scores = mm("trd,sd->rts", qg, kg) * cfg["attention_multiplier"]
            return mm("rts,sd->trd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), vg)

        # a group at a time: at 4,160 positions all 32 heads' scores at once are 2.2 GB
        out = jax.lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        return mm("ti,io->to", jnp.moveaxis(out, 0, 1).reshape(t, kv * r * hd), p["o"])

    def moe(p, v):
        r = mm("ti,io->to", v, p["router"])
        top, idx = jax.lax.top_k(r, cfg["num_experts_per_tok"])
        gates = jax.nn.softmax(top, -1)                              # over the chosen ones only

        def one(y, expert):                                          # every held expert, every token
            e, w_in, w_out = expert
            g = jnp.sum(jnp.where(idx == first_expert + e, gates, 0.0), -1)
            return y + g[:, None] * mlp(v, w_in, w_out), None

        held = p["w_in"].shape[0]
        y, _ = jax.lax.scan(one, jnp.zeros_like(v), (jnp.arange(held), p["w_in"], p["w_out"]))
        return y + mlp(v, p["shared_in"], p["shared_out"])

    @jax.jit
    def layer(p, h):
        with jax.default_matmul_precision("highest"):
            u = _rms(h, p["norm"], eps)
            h = h + res * (mamba(p, u) if kind == "mamba" else attention(p, u))
            return h + res * moe(p, _rms(h, p["moe_norm"], eps))

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
    emb, final_norm = embedding(cfg, seed, vocab)
    lens = [len(t) for t in sequences]
    logits_from = list(logits_from) if logits_from is not None else [0] * len(lens)
    padded = [np.pad(np.asarray(t), (0, (pad_to or n) - n)) for t, n in zip(sequences, lens)]
    hs = {pr: [cfg["embedding_multiplier"] * emb[jnp.asarray(t)] for t in padded] for pr in precisions}
    fns = {(kind, pr): _layer(cfg, kind, pr == "fp8", experts[0])
           for kind in set(layer_types(cfg)) for pr in precisions}
    for i, kind in enumerate(layer_types(cfg)):
        w = layer_weights(cfg, seed, i, experts)
        for pr in precisions:
            hs[pr] = [fns[kind, pr](w, h) for h in hs[pr]]
        # The host runs ahead of the device: without this wait the next layers' weights (1.84 GB each in
        # float32) are placed while this one's are still held by its queued products.
        jax.block_until_ready(hs)
        if layers_out is not None:
            layers_out.append(np.asarray(hs["f32"][0][:lens[0]]))
        del w
    out = {}
    with jax.default_matmul_precision("highest"):
        for pr in precisions:
            q8 = _fp8 if pr == "fp8" else (lambda x: x)
            out[pr] = [np.asarray(jnp.einsum("td,vd->tv", q8(_rms(h[a:n], final_norm, cfg["rms_norm_eps"])), q8(emb))
                                  / cfg["logits_scaling"]) for h, a, n in zip(hs[pr], logits_from, lens)]
    return out


def forward(cfg: dict, seed: int, tokens, experts: Optional[Tuple[int, int]] = None,
            vocab: Optional[Tuple[int, int]] = None, precision: str = "f32", logits_from: int = 0,
            layers_out: Optional[list] = None) -> np.ndarray:
    """Logits (T - logits_from, rows held) of one sequence."""
    return forward_many(cfg, seed, [tokens], experts, vocab, (precision,), [logits_from],
                        layers_out=layers_out if precision == "f32" else None)[precision][0]


def expert_layer(cfg: dict, seed: int, i: int, v, experts: Tuple[int, int], shared: bool = True) -> np.ndarray:
    """Layer ``i``'s expert layer alone over normed inputs ``v`` (T, d): the part
    the experts ``(first, count)`` give, with or without the shared expert."""
    p = layer_weights(cfg, seed, i, experts)
    if not shared:
        p["shared_out"] = jnp.zeros_like(p["shared_out"])
    with jax.default_matmul_precision("highest"):
        r = jnp.einsum("ti,io->to", v, p["router"])
        top, idx = jax.lax.top_k(r, cfg["num_experts_per_tok"])
        gates = jax.nn.softmax(top, -1)
        y = jnp.zeros_like(v)

        def mlp(x, w_in, w_out):
            a, b = jnp.split(x @ w_in, 2, -1)
            return (jax.nn.silu(a) * b) @ w_out

        for e in range(experts[1]):
            g = jnp.sum(jnp.where(idx == experts[0] + e, gates, 0.0), -1)
            y = y + g[:, None] * mlp(v, p["w_in"][e], p["w_out"][e])
        return np.asarray(y + mlp(v, p["shared_in"], p["shared_out"]))


# -- counts of the work ---------------------------------------------------------
def _per_token_layer_flops(cfg: dict, kind: str, held_share: float) -> float:
    """Matrix products one token needs in one layer, without the sequence mixing
    (scan and attention scores), which depend on the length."""
    z = _dims(cfg)
    d, f, fs, k = z["d"], cfg["intermediate_size"], cfg["shared_intermediate_size"], cfg["num_experts_per_tok"]
    if kind == "mamba":
        mixer = 2 * d * (2 * z["di"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] + cfg["mamba_n_heads"]) \
            + 2 * z["di"] * d + 2 * cfg["mamba_d_conv"] * z["conv"]
    else:
        q, kv = cfg["num_attention_heads"] * z["hd"], cfg["num_key_value_heads"] * z["hd"]
        mixer = 2 * d * (2 * q + 2 * kv)
    moe = 2 * d * cfg["router_outputs"] + held_share * k * 6 * d * f + 6 * d * fs
    return float(mixer + moe)


def ssd_scan_flops(cfg: dict, tokens: float) -> float:
    """The recurrence itself for ``tokens`` steps of one Mamba layer: the decay
    times the state, the outer product into it and the readout, 2 operations an
    element of the (heads, d_head, d_state) state each: 6 H P N a step."""
    return 6.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * tokens


def ssd_scan_bytes(cfg: dict, tokens: float, sequences: float) -> float:
    """The least one Mamba layer's scan moves through HBM: x, B, C, delta read
    and y written once a step (bfloat16), and each sequence's float32 state read
    and written once a call that carries it (a prefill chunk or a decode step)."""
    z = _dims(cfg)
    per_step = 2 * (2 * z["di"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"] + cfg["mamba_n_heads"])
    state = 4 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return float(per_step * tokens + 2 * state * sequences)


def expert_matmul_flops(cfg: dict, held_assignments: float) -> float:
    """The routed experts' two products for the assignments that reach a held
    expert, one layer: 2 x d x 2f and 2 x f x d each."""
    return 6.0 * cfg["hidden_size"] * cfg["intermediate_size"] * held_assignments


def expert_matmul_bytes(cfg: dict, held_assignments: float, calls: float, experts_held: int) -> float:
    """The least one layer's routed experts move: every held expert's weights
    once a call (bfloat16; with ten of 72 chosen a token, every expert is reached
    from a few dozen tokens on), and each assignment's input row read and output
    row written."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return float(calls * experts_held * 3 * d * f * 2 + held_assignments * 2 * d * 2)


def attention_flops(cfg: dict, query_tokens: float, mean_keys: float) -> float:
    """Scores and weighted values of one attention layer: 4 x heads x head_dim a query and key."""
    return 4.0 * cfg["hidden_size"] * query_tokens * mean_keys


def step_flops(cfg: dict, tokens: float, mean_keys: float, held_share: float = 0.5) -> float:
    """Operations the whole model needs for ``tokens`` tokens (prefill or decode
    alike), each attending ``mean_keys`` positions in the attention layers; the
    head is counted once a sequence by the caller (``head_flops``)."""
    kinds = layer_types(cfg)
    total = sum(_per_token_layer_flops(cfg, k, held_share) for k in kinds) * tokens
    total += sum(ssd_scan_flops(cfg, tokens) for k in kinds if k == "mamba")
    total += sum(attention_flops(cfg, tokens, mean_keys) for k in kinds if k == "attention")
    return float(total)


def head_flops(cfg: dict, rows: float) -> float:
    return 2.0 * cfg["hidden_size"] * share(cfg)[1][1] * rows
