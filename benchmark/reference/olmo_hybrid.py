"""Plain reference for the Olmo-Hybrid decoder: float32 ``jax.numpy`` under
``default_matmul_precision("highest")``, written from the configuration's keys
and the published description: no cache, no chunks, no batching; the gated
delta rule **token by token** (``lax.scan`` over positions, one state update a
step, no chunked form, no triangular solve), full attention over the whole
square. ``h`` is the residual stream, every norm RMSNorm (eps ``rms_norm_eps``):

    h = E[tok]
    h += norm(Mixer(h))                     # ``layer_types``: linear_attention x 3, full_attention
    h += norm(W_out (silu(W_gate h) * W_up h))          # ``mlp_in`` = [W_gate | W_up], ``intermediate_size`` wide
    logits = norm(h_last) @ W_head          # embedding and head untied

    linear_attention (Gated DeltaNet, arXiv:2412.06464), head i of ``linear_num_value_heads``:
        q, k, v = silu(conv(W_q h)), silu(conv(W_k h)), silu(conv(W_v h))
                  # causal depthwise conv over ``linear_conv_kernel_dim`` steps, zeros before the first token, no bias
        q = q / sqrt(|q|^2 + 1e-6) * linear_key_head_dim ** -0.5,   k = k / sqrt(|k|^2 + 1e-6)
        beta = 2 sigmoid(W_b h)   (the 2 where ``linear_allow_neg_eigval``),   alpha = exp(-exp(A_log) softplus(W_a h + dt_bias))
        S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,   S_0 = 0,  S (value_dim, key_dim)
        o_t = S_t q_t;   out = W_o (norm_value_dim(o) * silu(W_g h))
    full_attention: q, k = norm(W_q h), norm(W_k h) over the whole projection, v = W_v h; heads of ``head_dim``;
        score = q . k * head_dim ** -0.5, causal softmax; W_o. No positions anywhere.

**Departures from the published description, and what is assumed** (the
catalog's row gives the ``config.json`` keys and no modelling code; each is
also under the configuration's ``assumed``): ``head_dim`` is not given and is
taken as hidden_size / num_attention_heads; the QK-norm over the whole projected
q and k and the norm's place (on what a sub-layer gives, none on what it takes)
are the Olmo 2 / 3 family's convention, taken for both kinds of mixer;
``rope_parameters.rope_theta`` is null and is read as no rotary positions;
the conv has no bias and q, k, v each pass it (Gated DeltaNet's reference code);
the output gate is ``norm(o) * silu(W_g h)`` with one norm weight of
``linear_value_head_dim`` shared by the heads.

It imports nothing of the program and takes nothing the program has made. The
attention runs a block of queries at a time against every key (one float32
score matrix of 30 heads at 16,448 positions is 32 GB): still one softmax a
row, nothing carried between blocks. The weights are drawn here from the seed,
a layer at a time when the forward reaches it, by the rules below, and rounded
to bfloat16 as the configuration states.

Weight rules (key = PRNGKey(seed); layer i folds i + 1, then the tensor's index
in ``tensor_specs``; embedding, final norm and head fold 0 then 0 / 1 / 2;
embedding and head fold their 64-row block): matrices normal with std
fan_in ** -0.5 (the conv's fan-in is its kernel; the head's ``hidden_size``);
embedding normal, std ``embedding_std``; norm weights 1 + 0.1 normal; ``A_log``
log U(1, 16) and ``dt_bias`` the inverse softplus of a delta log-uniform in
[1e-3, 1e-1], both kept float32 (as Mamba-2 and Gated DeltaNet initialise them).

``precision="fp8"`` is the control of the benchmark's comparison: the same
forward with both operands of every matrix product rounded to float8_e4m3 under
a per-tensor scale, the step below the bfloat16 the configuration states; the
recurrence's own operands (q, k, v) are rounded the same way, its state and
decays stay float32 (as the program's do).

The counts at the end (``step_flops``, ``delta_rule_*``, ``attn_core_*``) are of
the work, from token counts and shapes, apart from any implementation.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EMBED_BLOCK_ROWS = 64
#: Queries the attention takes at once (the largest divisor of the length up to this).
QUERY_BLOCK = 512
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


# -- sizes ----------------------------------------------------------------------
def layer_types(cfg: dict) -> List[str]:
    """The layers as run: the first ``num_hidden_layers`` of the published pattern."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _dims(cfg: dict) -> dict:
    H, dk, dv = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    assert cfg["linear_num_key_heads"] == H
    return {"H": H, "dk": dk, "dv": dv, "key": H * dk, "value": H * dv, "conv": 2 * H * dk + H * dv,
            "q": cfg["num_attention_heads"] * cfg["head_dim"], "kv": cfg["num_key_value_heads"] * cfg["head_dim"]}


def tensor_specs(cfg: dict, kind: str) -> List[Tuple[str, tuple, str]]:
    """One layer's tensors in the order their keys are folded: (name, shape, rule)."""
    d, f, z = cfg["hidden_size"], cfg["intermediate_size"], _dims(cfg)
    if kind == LINEAR:
        mixer = [("q", (d, z["key"]), "matrix"), ("k", (d, z["key"]), "matrix"), ("v", (d, z["value"]), "matrix"),
                 ("g", (d, z["value"]), "matrix"), ("b", (d, z["H"]), "matrix"), ("a", (d, z["H"]), "matrix"),
                 ("conv_w", (cfg["linear_conv_kernel_dim"], z["conv"]), "matrix"),
                 ("A_log", (z["H"],), "A_log"), ("dt_bias", (z["H"],), "dt_bias"),
                 ("o_norm", (z["dv"],), "norm"), ("o", (z["value"], d), "matrix")]
    else:
        mixer = [("q", (d, z["q"]), "matrix"), ("k", (d, z["kv"]), "matrix"), ("v", (d, z["kv"]), "matrix"),
                 ("q_norm", (z["q"],), "norm"), ("k_norm", (z["kv"],), "norm"), ("o", (z["q"], d), "matrix")]
    return mixer + [("mixer_norm", (d,), "norm"), ("mlp_in", (d, 2 * f), "matrix"),
                    ("mlp_out", (f, d), "matrix"), ("mlp_norm", (d,), "norm")]


def layer_parameters(cfg: dict, kind: str) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in tensor_specs(cfg, kind))


def _as_drawn(x):
    """A draw as the generator gave it: inside a jitted program XLA would fold
    the scale that follows into the generator's own last product, and round
    otherwise than the same two steps taken one by one."""
    return jax.lax.optimization_barrier(x)


def _draw(key, shape, rule):
    if rule == "A_log":
        return jnp.log(_as_drawn(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)))
    if rule == "dt_bias":
        dt = jnp.exp(_as_drawn(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))))
        return dt + jnp.log(-jnp.expm1(-dt))
    n = _as_drawn(jax.random.normal(key, shape, jnp.float32))
    if rule == "matrix":
        return n * (shape[0] ** -0.5)
    if rule == "norm":
        return 0.1 * n + 1.0
    raise ValueError(rule)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(cfg: dict, seed: int, i: int) -> Dict[str, jax.Array]:
    """Layer ``i``'s tensors as float32 holding bfloat16 values (``A_log`` and ``dt_bias`` as drawn)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), i + 1)
    out = {}
    for j, (name, shape, rule) in enumerate(tensor_specs(cfg, layer_types(cfg)[i])):
        w = _draw(jax.random.fold_in(key, j), shape, rule)
        out[name] = w if rule in ("A_log", "dt_bias") else _bf16(w)
    return out


def _rows(cfg: dict, key, std: float):
    blocks = [jax.random.normal(jax.random.fold_in(key, b), (EMBED_BLOCK_ROWS, cfg["hidden_size"]), jnp.float32)
              for b in range(cfg["vocab_size"] // EMBED_BLOCK_ROWS)]
    return _bf16(jnp.concatenate(blocks) * std)


def embedding(cfg: dict, seed: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (the embedding, the final norm's weight, the head)."""
    k0 = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    return (_rows(cfg, jax.random.fold_in(k0, 0), cfg["embedding_std"]),
            _bf16(_draw(jax.random.fold_in(k0, 1), (cfg["hidden_size"],), "norm")),
            _rows(cfg, jax.random.fold_in(k0, 2), cfg["hidden_size"] ** -0.5))


# -- the forward ----------------------------------------------------------------
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, alpha, beta, s0=None):
    """The gated delta rule token by token: q, k (T, H, dk), v (T, H, dv), alpha,
    beta (T, H). -> (o (T, H, dv), the state after the last step (H, dv, dk))."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, x):                                                  # s (H, dv, dk)
        q_t, k_t, v_t, a_t, b_t = x
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.sum(s * k_t[:, None, :], -1))  # beta (v - alpha S k)
        s = s + u[:, :, None] * k_t[:, None, :]
        return s, jnp.sum(s * q_t[:, None, :], -1)

    s, o = jax.lax.scan(step, jnp.zeros((H, dv, dk), jnp.float32) if s0 is None else s0, (q, k, v, alpha, beta))
    return o, s


def _layer(cfg: dict, kind: str, fp8: bool):
    """-> jitted ``(weights, h (T, d)) -> h`` for one layer of ``kind``."""
    z, eps = _dims(cfg), cfg["rms_norm_eps"]
    q8 = _fp8 if fp8 else (lambda x: x)

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a), q8(b))

    def linear(p, h):
        t, K = h.shape[0], cfg["linear_conv_kernel_dim"]
        H, dk, dv = z["H"], z["dk"], z["dv"]
        qkv = jnp.concatenate([mm("ti,io->to", h, p[n]) for n in ("q", "k", "v")], -1)
        padded = jnp.concatenate([jnp.zeros((K - 1, z["conv"]), jnp.float32), qkv])
        qkv = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][j] for j in range(K)))
        q, k, v = jnp.split(qkv, [z["key"], 2 * z["key"]], -1)
        q = _l2(q.reshape(t, H, dk)) * dk ** -0.5
        k = _l2(k.reshape(t, H, dk))
        beta = jax.nn.sigmoid(mm("ti,io->to", h, p["b"])) * (2.0 if cfg["linear_allow_neg_eigval"] else 1.0)
        alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(mm("ti,io->to", h, p["a"]) + p["dt_bias"]))
        o, _ = delta_rule(q8(q), q8(k), q8(v.reshape(t, H, dv)), alpha, beta)
        y = _rms(o, p["o_norm"], eps) * jax.nn.silu(mm("ti,io->to", h, p["g"]).reshape(t, H, dv))
        return mm("ti,io->to", y.reshape(t, z["value"]), p["o"])

    def attention(p, h):
        t = h.shape[0]
        heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        r = heads // kv
        q = _rms(mm("ti,io->to", h, p["q"]), p["q_norm"], eps).reshape(t, kv, r, hd)
        k = _rms(mm("ti,io->to", h, p["k"]), p["k_norm"], eps).reshape(t, kv, hd)
        v = mm("ti,io->to", h, p["v"]).reshape(t, kv, hd)
        blk = max(b for b in range(1, min(t, QUERY_BLOCK) + 1) if t % b == 0)

        def block(args):                                             # blk queries against every key
            qb, first = args
            scores = mm("qgrd,kgd->grqk", qb, k) * hd ** -0.5
            causal = (first + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
            return mm("grqk,kgd->qgrd", jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)

        out = jax.lax.map(block, (q.reshape(t // blk, blk, kv, r, hd), jnp.arange(0, t, blk)))
        return mm("ti,io->to", out.reshape(t, heads * hd), p["o"])

    def mlp(p, x):
        a, b = jnp.split(mm("ti,io->to", x, p["mlp_in"]), 2, -1)
        return mm("ti,io->to", jax.nn.silu(a) * b, p["mlp_out"])

    @jax.jit
    def layer(p, h):
        with jax.default_matmul_precision("highest"):
            h = h + _rms((linear if kind == LINEAR else attention)(p, h), p["mixer_norm"], eps)
            return h + _rms(mlp(p, h), p["mlp_norm"], eps)

    return layer


def forward_many(cfg: dict, seed: int, sequences, precisions=("f32",), logits_from=None,
                 pad_to: Optional[int] = None, layers_out: Optional[list] = None) -> Dict[str, List[np.ndarray]]:
    """Logits of each sequence of token ids, in each of ``precisions``:
    ``{precision: [(T_i - logits_from[i], vocab_size), ...]}``. Each layer's
    weights are drawn once and every sequence goes through before the next
    layer's are. ``pad_to`` right-pads every sequence to one length, so that one
    compiled layer serves all (the model is causal: what follows a position
    never reaches it). ``layers_out`` receives sequence 0's float32 layer
    outputs (tests)."""
    if set(precisions) - {"f32", "fp8"}:
        raise ValueError(f"precisions {precisions!r}")
    emb, final_norm, head = embedding(cfg, seed)
    lens = [len(t) for t in sequences]
    logits_from = list(logits_from) if logits_from is not None else [0] * len(lens)
    padded = [np.pad(np.asarray(t), (0, (pad_to or n) - n)) for t, n in zip(sequences, lens)]
    hs = {pr: [emb[jnp.asarray(t)] for t in padded] for pr in precisions}
    fns = {(kind, pr): _layer(cfg, kind, pr == "fp8") for kind in (LINEAR, FULL) for pr in precisions}
    for i, kind in enumerate(layer_types(cfg)):
        w = layer_weights(cfg, seed, i)
        for pr in precisions:
            hs[pr] = [fns[kind, pr](w, h) for h in hs[pr]]
        # The host runs ahead of the device: without this wait the next layer's weights are placed while
        # this one's are still held by its queued products.
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


def forward(cfg: dict, seed: int, tokens, precision: str = "f32", logits_from: int = 0,
            layers_out: Optional[list] = None) -> np.ndarray:
    """Logits (T - logits_from, vocab_size) of one sequence."""
    return forward_many(cfg, seed, [tokens], (precision,), [logits_from],
                        layers_out=layers_out if precision == "f32" else None)[precision][0]


# -- counts of the work ---------------------------------------------------------
def _per_token_flops(cfg: dict, kind: str) -> float:
    """Matrix products one token needs in one layer of ``kind``, without what
    depends on the length (the attention's scores and weighted values) and
    without the recurrence: 2 x the layer's matrix parameters."""
    return 2.0 * sum(int(np.prod(shape)) for name, shape, rule in tensor_specs(cfg, kind)
                     if rule == "matrix" and name != "conv_w")


def delta_rule_flops(cfg: dict, tokens: float) -> float:
    """The token-by-token recurrence for ``tokens`` steps of one linear layer:
    the decay times the state, S k, the outer product into the state and S q:
    2 operations an element of the (heads, value_dim, key_dim) state each but
    the decay's one: 7 H dv dk a step."""
    z = _dims(cfg)
    return 7.0 * z["H"] * z["dv"] * z["dk"] * tokens


def delta_rule_bytes(cfg: dict, tokens: float, sequences: float) -> float:
    """The least one linear layer's recurrence moves through HBM: q, k, v read
    and o written once a token (bfloat16), beta and g once a token (float32),
    and each sequence's float32 state read and written once a call that carries
    it (a prefill chunk or a decode step: ``sequences`` counts them)."""
    z = _dims(cfg)
    per_token = 2 * (2 * z["key"] + 2 * z["value"]) + 2 * 4 * z["H"]
    return float(per_token * tokens + 2 * state_bytes(cfg) * sequences)


def state_bytes(cfg: dict) -> int:
    """One sequence's recurrent state in one linear layer: heads x value_dim x key_dim float32."""
    z = _dims(cfg)
    return 4 * z["H"] * z["dv"] * z["dk"]


def kv_bytes_per_token(cfg: dict) -> int:
    """One token's keys and values in one attention layer, bfloat16."""
    return 2 * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def attn_core_flops(cfg: dict, pairs: float) -> float:
    """The least work of one attention's core: the score and the weighted value
    of every causal (query, key) pair, 2 x heads x 2 head_dim a pair."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def attn_core_bytes(cfg: dict, query_tokens: float, rows_read: float) -> float:
    """The least one attention's core moves through HBM: each held key/value
    row read once a call (``rows_read``: summed over calls and sequences), every
    query read and every output written once (bfloat16)."""
    per_query = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return float(kv_bytes_per_token(cfg) * rows_read + per_query * query_tokens)


def step_flops(cfg: dict, tokens: float, mean_keys: float) -> float:
    """Operations the whole model needs for ``tokens`` tokens (prefill or decode
    alike), each attending ``mean_keys`` positions in every attention layer and
    taking one step of the recurrence in every linear layer; the head is counted
    once a sequence by the caller (``head_flops``)."""
    kinds = layer_types(cfg)
    n_lin, n_full = kinds.count(LINEAR), kinds.count(FULL)
    return float(n_lin * (_per_token_flops(cfg, LINEAR) * tokens + delta_rule_flops(cfg, tokens))
                 + n_full * (_per_token_flops(cfg, FULL) * tokens + attn_core_flops(cfg, tokens * mean_keys)))


def head_flops(cfg: dict, rows: float) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * rows


def forward_flops_per_row(cfg: dict) -> float:
    """For ``tools/compile_for_v5e.py``: one prefill call's operations (the batcher's 2,048 tokens at mid-depth of a
    mean document) over the configuration's batch."""
    return step_flops(cfg, 2048.0, 2556.0) / cfg["batch_size"]
