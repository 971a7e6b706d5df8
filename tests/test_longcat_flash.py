"""The LongCat-Flash decoder (``models/longcat_flash.py``: latent attention with
a rotary part, shortcut-connected double layers, a router over routed and
identity experts, a sharded expert layer) against its plain reference
(``benchmark/reference/longcat_flash.py``), through the continuous batcher and
through ``prompt``, at a small size on the CPU: widths in the published ratios,
two double layers, 8 routed + 4 identity experts top-3, two shares.

Tolerances. The program's products take bfloat16 operands (float32
accumulation, residual stream and router), the reference computes in float32:
a bfloat16 product is off by 2**-9 of its operands. The head is untied and
drawn at fan-in scale, so logits spread ~1 and the limits are in those units.
Each sits three times or more above the largest reading over the seeds tried
(in its comment), and the control (the reference with every matrix product's
operands in float8_e4m3, put in the program's place) has to break it.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import manifest  # noqa: E402

from daft_tpu.errors import DaftValueError  # noqa: E402
from daft_tpu.models import decoders, granite_hybrid as gh, longcat_flash as lc  # noqa: E402
from daft_tpu.models.serving import ContinuousBatcher, Request  # noqa: E402
from daft_tpu.ops import pallas_attention, pallas_mla_attention  # noqa: E402

TINY = "longcat-flash-tiny"
#: |program log-probability - reference's| of a chosen token, and the regret of the greedy choice. Readings over
#: seeds 0-5 (48 tokens each): 0.011 to 0.021, regret 0; the fp8 control 0.33 to 0.44.
LOGPROB_GAP_MAX = 0.08
#: |logits - reference's| after a chunked prefill and through decode steps (128 logits x 6 positions). Readings over
#: seeds 0-5: 0.021 to 0.033; the fp8 control 0.52 to 0.71. (With ``W_kvb`` drawn at plain fan-in scale, scores
#: spreading ~6, the same readings were 0.07 to 0.35 and grew with width and depth: ``models/longcat_flash``.)
LOGIT_GAP_MAX = 0.12


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "longcat_flash.py"))


def ref_config(rank: int = 0, size: int = 2) -> dict:
    """The tiny model as the reference reads a configuration file: sizes as run."""
    p = lc.TEST_SIZES[TINY]
    return dict(p, router_outputs=p["n_routed_experts"] + p["zero_expert_num"],
                n_routed_experts=p["n_routed_experts"] // size, vocab_size=p["vocab_size"] // size,
                embedding_std=lc.EMBED_STD, router_bias_std=1e-4,
                options={"expert_shard": [rank, size], "vocab_shard": [rank, size]})


#: The tiny decoder with an attention one lane tile wide: the narrowest the prefill kernel serves.
LANE_TILE = dict(num_attention_heads=2, kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=16, v_head_dim=128)


def program(seed: int, rank: int = 0, size: int = 2, **sizes):
    cfg = lc.LongcatFlashConfig.from_name(TINY, expert_shard=(rank, size), vocab_shard=(rank, size))
    return lc.init_longcat_params(dataclasses.replace(cfg, **sizes), seed)


@pytest.fixture
def fused_prefill(monkeypatch):
    """The backend rule answers as on a TPU and the prefill kernel it then
    selects runs interpreted (the experts stay too narrow for theirs)."""
    real = pallas_mla_attention.mla_prefill_attention
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pallas_mla_attention, "mla_prefill_attention",
                        lambda *a, nope: real(*a, nope=nope, interpret=True))


def prompts(seed: int, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 128, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_draws_the_programs_weights(ref, seed):
    _, params = program(seed, rank=1)
    rcfg = ref_config(rank=1)
    experts, vocab = ref.share(rcfg)
    assert (experts, vocab) == ((4, 4), (128, 128))
    for i, layer in enumerate(params["layers"]):
        want = ref.layer_weights(rcfg, seed, i, experts)
        assert set(want) == set(layer)
        for name, w in want.items():
            assert layer[name].dtype == (jnp.float32 if name == "router_bias" else jnp.bfloat16)
            assert float(jnp.max(jnp.abs(w - layer[name].astype(jnp.float32)))) == 0.0, (i, name)
        assert 0 < float(jnp.max(jnp.abs(layer["router_bias"]))) < 1e-3  # small and not zero
    emb, final_norm, head = ref.embedding(rcfg, seed, vocab)
    for name, w in (("embed", emb), ("final_norm", final_norm), ("head", head)):
        assert float(jnp.max(jnp.abs(w - params[name].astype(jnp.float32)))) == 0.0, name
    assert not np.array_equal(np.asarray(emb), np.asarray(head))  # untied


# (a) chunked prefill, then decoding through the cache, against the reference's full forward
@pytest.mark.parametrize("seed", [0, 3])
def test_chunked_prefill_then_decode_agrees_with_the_references_logits(ref, seed):
    """A prompt of 21 tokens as three chunks of 8 (positions past one chunk turn
    the rotary part further), then five decode steps teacher-forced on arbitrary
    tokens: the logits after the prompt and after every step against the
    reference's one forward over the whole sequence."""
    model, params = program(seed)
    toks = np.random.default_rng(seed).integers(2, 128, 26).astype(np.int32)
    n, T = 21, 8
    state = model.init_state(3, 40)
    slots = jnp.asarray([2, 0], jnp.int32)  # the second row of each call carries no prompt: slot 0 stays as it was
    before = [np.asarray(s["kv"][0]) for s in state]
    prefill = jax.jit(model.prefill)
    for c in range(3):
        part = np.zeros((2, T), np.int32)
        here = min(T, n - c * T)
        part[0, :here] = toks[c * T:c * T + here]
        state, logits, counts = prefill(params, state, part, slots, jnp.full((2,), c * T, jnp.int32),
                                        jnp.asarray([here, 0], jnp.int32))
    assert all(np.array_equal(np.asarray(s["kv"][0]), b) for s, b in zip(state, before))
    assert int(counts["assignments"]) == 5 * 3 * 2  # the last chunk's five valid tokens, top 3, two layers
    want = ref.forward(ref_config(), seed, toks)
    got = [np.asarray(logits[0])]
    decode = jax.jit(model.decode)
    active = jnp.asarray([False, False, True])
    for i in range(n, 26):
        state, logits, _ = decode(params, state, jnp.full((3,), toks[i], jnp.int32),
                                  jnp.full((3,), i, jnp.int32), active)
        got.append(np.asarray(logits[2]))
    gaps = [float(np.max(np.abs(g - want[n - 1 + j]))) for j, g in enumerate(got)]
    assert max(gaps) <= LOGIT_GAP_MAX, gaps
    assert float(np.std(want)) > 0.5  # logits spread ~1: the limit is an eighth of it
    # the control, one precision step down, is outside the limit
    low = ref.forward(ref_config(), seed, toks, precision="fp8")
    assert float(np.max(np.abs(low[n - 1:] - want[n - 1:]))) > LOGIT_GAP_MAX


def test_chunked_prefill_through_the_kernel_then_decode_agrees_with_the_references_logits(ref, fused_prefill):
    """At an attention one lane tile wide the prefill takes the kernel: a prompt of
    293 tokens as three chunks of 128 beside one of 100 that ends in the first
    (its row goes on with length 0: no block of it is visited, its slot keeps its
    rows), then three decode steps on the first through the absorbed path, against
    the reference's forward over each whole sequence."""
    seed, T = 0, 128
    model, params = program(seed, **LANE_TILE)
    rcfg = dict(ref_config(), **LANE_TILE)
    rng = np.random.default_rng(seed)
    long_toks, short_toks = rng.integers(2, 128, 296).astype(np.int32), rng.integers(2, 128, 100).astype(np.int32)
    lens = np.asarray([293, 100])
    state = model.init_state(3, 3 * T)
    slots = jnp.asarray([2, 0], jnp.int32)
    prefill = jax.jit(model.prefill)
    got = {}
    for c in range(3):
        part = np.zeros((2, T), np.int32)
        here = np.clip(lens - c * T, 0, T)
        part[0, :here[0]] = long_toks[c * T:c * T + here[0]]
        part[1, :here[1]] = short_toks[c * T:c * T + here[1]]
        if c == 1:
            short_rows = [np.asarray(s["kv"][0]) for s in state]
        state, logits, _ = prefill(params, state, part, slots, jnp.full((2,), c * T, jnp.int32), jnp.asarray(here, jnp.int32))
        if c == 0:
            got["short"] = np.asarray(logits[1])
    assert all(np.array_equal(np.asarray(s["kv"][0]), b) for s, b in zip(state, short_rows))
    want = ref.forward(rcfg, seed, long_toks)
    got["long"] = [np.asarray(logits[0])]
    decode = jax.jit(model.decode)
    for i in range(293, 296):
        state, logits, _ = decode(params, state, jnp.full((3,), long_toks[i], jnp.int32), jnp.full((3,), i, jnp.int32),
                                  jnp.asarray([False, False, True]))
        got["long"].append(np.asarray(logits[2]))
    gaps = [float(np.max(np.abs(g - want[292 + j]))) for j, g in enumerate(got["long"])]
    gaps.append(float(np.max(np.abs(got["short"] - ref.forward(rcfg, seed, short_toks)[-1]))))
    assert max(gaps) <= LOGIT_GAP_MAX, gaps  # read 0.010 to 0.023
    assert float(np.std(want)) > 0.5
    low = ref.forward(rcfg, seed, long_toks, precision="fp8")
    assert float(np.max(np.abs(low[292:] - want[292:]))) > LOGIT_GAP_MAX


def test_the_batcher_at_lane_tile_widths_prefills_through_the_kernel_and_counts_its_visits(monkeypatch, fused_prefill):
    """Three prompts of unlike length in one group of four rows: the span says
    which attention the prefill program traced and how many (row, block) pairs
    held a query, of those a call of static shape spans."""
    from daft_tpu.profiling import newest_device_span

    model, params = program(1, **LANE_TILE)
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=400, eos_id=None, prefill_chunk=128)
    out = b.run([Request(tokens=t, max_new_tokens=2) for t in prompts(1, [300, 100, 140])])
    assert all(len(o) == 2 for o in out)
    assert b._noted == {"serve.prefill": {"mla": "fused", "moe": "xla"}, "serve.decode_step": {"mla": "absorbed", "moe": "xla"}}
    count = newest_device_span("serve.prefill").count
    assert (count["mla"], count["chunks"], count["row_chunks"]) == ("fused", 3, 3 + 1 + 2)
    assert (count["block_rows"], count["padded_block_rows"]) == (6 + 1 + 3, 4 * 6)
    # the same prompts on XLA's path choose the same tokens
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: False)
    x = ContinuousBatcher(model, params, num_slots=4, max_seq_len=400, eos_id=None, prefill_chunk=128)
    assert x.run([Request(tokens=t, max_new_tokens=2) for t in prompts(1, [300, 100, 140])]) == out
    assert x._noted["serve.prefill"]["mla"] == "expanded"


def _served(seed, lengths=(5, 17, 33, 40, 9, 20), new=8, **kw):
    model, params = program(seed)
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=64, eos_id=None,
                          **dict(dict(prefill_chunk=8), **kw))
    reqs = [Request(tokens=t, max_new_tokens=new) for t in prompts(seed, lengths)]
    out = b.run(reqs)
    return reqs, out, b.last_logprobs


def _teacher_forced(ref, seed, reqs, out, precision="f32"):
    want, regret = [], []
    for r, toks in zip(reqs, out):
        logits = ref.forward(ref_config(), seed, np.concatenate([r.tokens, toks]),
                             precision=precision, logits_from=len(r.tokens) - 1)[:-1]
        lp = np.asarray(jax.nn.log_softmax(logits, -1))
        at = np.arange(len(toks))
        want.append(lp[at, toks])
        regret.append(logits.max(-1) - logits[at, toks])
    return want, regret


@pytest.mark.parametrize("seed", [0, 3])
def test_batcher_logprobs_agree_with_the_references_full_forward(ref, seed):
    reqs, out, logprobs = _served(seed)
    assert all(len(o) == 8 for o in out) and len({tuple(o) for o in out}) > 1
    want, regret = _teacher_forced(ref, seed, reqs, out)
    gap = max(float(np.max(np.abs(np.asarray(lp) - w))) for lp, w in zip(logprobs, want))
    assert gap <= LOGPROB_GAP_MAX, gap
    assert max(float(r.max()) for r in regret) <= LOGPROB_GAP_MAX  # the reference's argmax or a near tie
    low, _ = _teacher_forced(ref, seed, reqs, out, precision="fp8")
    assert max(float(np.max(np.abs(l - w))) for l, w in zip(low, want)) > LOGPROB_GAP_MAX


# (b) the two attention paths
@pytest.mark.parametrize("seed", [0, 1])
def test_the_absorbed_and_the_expanded_attention_agree(seed):
    cfg = lc.LongcatFlashConfig.from_name(TINY)
    rng = np.random.default_rng(seed)
    B, T, S = 2, 8, 24
    w = jnp.asarray(rng.normal(size=(cfg.kv_lora_rank, cfg.num_attention_heads,
                                     cfg.qk_nope_head_dim + cfg.v_head_dim)) * cfg.kv_lora_rank ** -0.5, jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, T, cfg.num_attention_heads, cfg.qk_head_dim)), jnp.bfloat16)
    cache = jnp.asarray(rng.normal(size=(B, cfg.cache_row, S)), jnp.bfloat16)  # positions are the minor axis
    positions = jnp.asarray([[16 + t for t in range(T)], [8 + t for t in range(T)]])  # the rows' third and second chunk
    absorbed = lc.mla_core_absorbed(cfg, w, q, cache, positions)
    block_of = lambda j: jax.lax.dynamic_slice_in_dim(cache, j * T, T, axis=2)  # noqa: E731
    expanded = lc.mla_core_expanded(cfg, w, q, block_of, 3, positions)
    assert absorbed.shape == expanded.shape == (B, T, cfg.num_attention_heads, cfg.v_head_dim)
    scale = float(jnp.max(jnp.abs(expanded)))
    assert float(jnp.max(jnp.abs(absorbed - expanded))) <= 3e-2 * scale  # bfloat16 products in two orders; read 1.1e-2
    # the third path: the prefill kernel (interpreted; a chip takes it only at lane-tile widths) over the same cache
    fused = pallas_mla_attention.mla_prefill_attention(q, cache, w, jnp.arange(B, dtype=jnp.int32), positions[:, 0],
                                                       jnp.full((B,), T, jnp.int32), nope=cfg.qk_nope_head_dim, interpret=True)
    assert fused.shape == expanded.shape and fused.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(fused - expanded))) <= 1e-2 * scale  # the same products in the same order, rounded once more
    assert float(jnp.max(jnp.abs(fused - absorbed))) <= 3e-2 * scale
    # neither looks past a query's position: rows behind it may hold anything
    junk = cache.at[:, :, 20:].set(99.0).at[1, :, 14:].set(-99.0)
    again = lc.mla_core_expanded(cfg, w, q[:, :4], lambda j: jax.lax.dynamic_slice_in_dim(junk, j * T, T, axis=2),
                                 3, positions[:, :4])
    assert float(jnp.max(jnp.abs(again - expanded[:, :4]))) == 0.0
    assert float(jnp.max(jnp.abs(lc.mla_core_absorbed(cfg, w, q[:, :4], junk, positions[:, :4]) - absorbed[:, :4]))) == 0.0


def test_rope_turns_interleaved_pairs_by_position(ref):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 40, 3, 8)).astype(np.float32)
    at = np.arange(40)[None, :]
    got = np.asarray(lc.rope(jnp.asarray(x), jnp.asarray(at), 1e7))
    assert np.max(np.abs(got - np.asarray(ref._rope(jnp.asarray(x[0]), 1e7))[None])) <= 1e-5
    # by hand: the pair (x[2i], x[2i+1]) at position p turns by p * theta ** (-2i / n)
    p, i = 37, 1
    ang = p * 1e7 ** (-2 * i / 8)
    a, b = x[0, p, 2, 2 * i], x[0, p, 2, 2 * i + 1]
    assert np.allclose(got[0, p, 2, 2 * i:2 * i + 2], [a * np.cos(ang) - b * np.sin(ang), a * np.sin(ang) + b * np.cos(ang)],
                       atol=1e-5)
    assert np.allclose(got[0, 0], x[0, 0]) and not np.allclose(got[0, 9], x[0, 9])  # position 0 turns nothing
    # a chunk that starts past position 0 turns as the whole sequence does there
    late = np.asarray(lc.rope(jnp.asarray(x[:, 16:24]), jnp.asarray(at[:, 16:24]), 1e7))
    assert np.array_equal(late, got[:, 16:24])


# (c) the router
def _layer_and_inputs(seed=5, n=24, rank=0, size=1):
    model, params = program(seed, rank, size)
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(n, 64)), jnp.float32)
    return model.cfg, params["layers"][0], u


def _scores(p, u):
    r = np.asarray(u, np.float64) @ np.asarray(p["router"].astype(jnp.float32), np.float64)
    e = np.exp(r - r.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def test_identity_experts_cost_nothing_and_weigh_by_their_scores():
    """A token whose every choice is an identity expert gets (sum of its
    weights) x itself; one with none gets the routed experts' part alone."""
    cfg, p, u = _layer_and_inputs()
    s = _scores(p, u)
    valid = jnp.ones((24,), bool)
    zero_bias = np.zeros(12, np.float32)
    zero_bias[8:] = 2.0                                               # scores are below 1: the four identity experts lead
    y, counts = lc._moe(cfg, dict(p, router_bias=jnp.asarray(zero_bias)), u, valid)
    top3 = np.sort(s[:, 8:], -1)[:, -3:].sum(-1)
    assert int(counts["zero_assignments"]) == int(counts["assignments"]) == 24 * 3
    assert int(counts["held_assignments"]) == int(counts["max_expert_load"]) == 0
    assert np.allclose(np.asarray(y), cfg.routed_scaling_factor * top3[:, None] * np.asarray(u), rtol=2e-5, atol=1e-6)
    y, counts = lc._moe(cfg, dict(p, router_bias=jnp.asarray(-zero_bias)), u, valid)
    assert int(counts["zero_assignments"]) == 0 and int(counts["held_assignments"]) == 24 * 3
    alone, _ = decoders.held_experts_part(u.astype(cfg.dtype), *lc.route(cfg, dict(p, router_bias=jnp.asarray(-zero_bias)), u),
                                          valid, 0, p["w_in"], p["w_out"], cfg.dtype)[:2]
    assert np.array_equal(np.asarray(y), np.asarray(alone))
    # padded tokens choose nothing and receive nothing
    y, counts = lc._moe(cfg, p, u, jnp.arange(24) < 10)
    assert int(counts["assignments"]) == 30 and not np.asarray(y[10:]).any() and np.asarray(y[:10]).any()


def test_the_bias_moves_the_choice_and_not_the_weight():
    cfg, p, u = _layer_and_inputs()
    s = _scores(p, u)
    idx0, w0 = lc.route(cfg, dict(p, router_bias=jnp.zeros((12,), jnp.float32)), u)
    assert np.allclose(np.asarray(w0), cfg.routed_scaling_factor * np.take_along_axis(s, np.asarray(idx0), -1), rtol=1e-5)
    assert np.allclose(np.asarray(w0).sum(-1) / cfg.routed_scaling_factor, np.sort(s, -1)[:, -3:].sum(-1), rtol=1e-5)  # not renormalised
    # token 0: lift its fourth-ranked output over its third by a bias a little larger than their gap
    order = np.argsort(-s[0])
    third, fourth = int(order[2]), int(order[3])
    bias = np.zeros(12, np.float32)
    bias[fourth] = 1.5 * (s[0, third] - s[0, fourth])
    idx1, w1 = lc.route(cfg, dict(p, router_bias=jnp.asarray(bias)), u)
    assert third in np.asarray(idx0[0]) and fourth not in np.asarray(idx0[0])
    assert fourth in np.asarray(idx1[0]) and third not in np.asarray(idx1[0])
    # the weight of the lifted choice is its score, not score + bias
    at = int(np.flatnonzero(np.asarray(idx1[0]) == fourth)[0])
    assert abs(float(w1[0, at]) - cfg.routed_scaling_factor * s[0, fourth]) <= 1e-5 < cfg.routed_scaling_factor * bias[fourth]
    # the drawn bias is of that kind: small against the scores, and it does flip near ties somewhere
    drawn = np.asarray(p["router_bias"])
    assert 0 < np.abs(drawn).max() < 0.01 * s.max()


# (d) the shares add up
@pytest.mark.parametrize("layer", [0, 1])
def test_the_shares_expert_branches_add_up_to_the_uncut_layer(ref, layer):
    """Over all shares of a split in four: the held experts' parts, with the
    identity experts' part (which every chip computes alike) counted once, add up
    to the uncut reference's expert branch."""
    seed, size = 4, 4
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(40, 64)), jnp.float32)
    valid = jnp.ones((40,), bool)
    whole = ref.expert_layer(ref_config(0, 1), seed, layer, u, (0, 8))
    identity = whole - ref.expert_layer(ref_config(0, 1), seed, layer, u, (0, 8), zero=False)
    parts, counts = [], []
    for rank in range(size):
        model, params = program(seed, rank=rank, size=size)
        y, n = jax.jit(lambda p, u, m=model: lc._moe(m.cfg, p, u, valid))(params["layers"][layer], u)
        parts.append(np.asarray(y))
        counts.append({k: int(v) for k, v in n.items()})
    total = sum(parts) - (size - 1) * identity
    assert np.max(np.abs(total - whole)) <= 2e-2 * np.max(np.abs(whole))  # bfloat16 products against float32
    assert np.max(np.abs(parts[0] - whole)) > 0.05 * np.max(np.abs(whole))  # a share alone is not the layer
    # the router ranked all twelve outputs in every share; every choice is some share's or an identity expert's
    assert {c["assignments"] for c in counts} == {40 * 3} and len({c["zero_assignments"] for c in counts}) == 1
    assert sum(c["held_assignments"] for c in counts) + counts[0]["zero_assignments"] == 40 * 3
    assert 0 < counts[0]["zero_assignments"] < 40 * 3
    # the reference's own shares add up exactly as well
    quarters = [ref.expert_layer(ref_config(r, size), seed, layer, u, (2 * r, 2), zero=False) for r in range(size)]
    assert np.max(np.abs(sum(quarters) + identity - whole)) <= 1e-5 * np.max(np.abs(whole))


# (e) the batcher: slot isolation, copy_state, two decoders in one process
def test_slot_isolation_under_shuffled_admission():
    model, params = program(0)
    ps = prompts(7, [4, 11, 6, 9, 5, 13, 8, 10, 7, 12])

    def run(order):
        b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=64, eos_id=None, prefill_chunk=8)
        return b.run([Request(tokens=ps[i], max_new_tokens=6) for i in order])

    a = run(range(10))
    order = list(range(10))[::-1]
    b = run(order)
    for i, oi in enumerate(order):
        assert a[oi] == b[i], (i, oi)


def test_identical_prompts_share_one_prefill_through_copy_state():
    model, params = program(0)
    base = prompts(2, [19])[0]
    reqs = [Request(tokens=base.copy(), max_new_tokens=6) for _ in range(4)] \
        + [Request(tokens=prompts(3, [9])[0], max_new_tokens=6)]
    b = ContinuousBatcher(model, params, num_slots=5, max_seq_len=64, eos_id=None, prefill_chunk=8)
    out = b.run(reqs)
    assert b._prefill._cache_size() == 1  # one executable for every chunk of every prompt
    assert out[0] == out[1] == out[2] == out[3] != out[4]
    src = model.copy_state(b.state, 0, 3)
    assert all(np.array_equal(np.asarray(s["kv"][3]), np.asarray(s["kv"][0])) for s in src)
    alone = ContinuousBatcher(model, params, num_slots=5, max_seq_len=64, eos_id=None, prefill_chunk=8)
    assert alone.run([Request(tokens=base.copy(), max_new_tokens=6)])[0] == out[3]


def test_the_batcher_serves_both_decoders_in_one_process():
    """One process, two batchers over two decoders whose slot state differs in
    kind; each answers as it does alone, and each program's spans say which
    paths it traced."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    long_model, long_params = program(0)
    g_cfg = gh.GraniteHybridConfig.from_name("granite-hybrid-tiny", expert_shard=(0, 2), vocab_shard=(0, 2))
    g_model, g_params = gh.init_granite_params(g_cfg, 0)
    ps = prompts(11, [5, 19, 7, 12])

    def serve(model, params, **kw):
        b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=64, eos_id=None, **kw)
        return b, b.run([Request(tokens=t, max_new_tokens=4) for t in ps])

    alone_long, alone_g = serve(long_model, long_params, prefill_chunk=8)[1], serve(g_model, g_params, prefill_chunk=8)[1]
    began = span_clock_ns()
    b_long, out_long = serve(long_model, long_params, prefill_chunk=8)
    b_g, out_g = serve(g_model, g_params, prefill_chunk=8)
    assert out_long == alone_long and out_g == alone_g and out_long != out_g
    assert b_long._noted == {"serve.prefill": {"mla": "expanded", "moe": "xla"},
                             "serve.decode_step": {"mla": "absorbed", "moe": "xla"}}
    assert b_g._noted == {"serve.prefill": {"moe": "xla"}, "serve.decode_step": {"moe": "xla"}}
    assert [set(s) for s in b_long.state] == [{"kv"}] * 4 and b_long.state[0]["kv"].shape == (2, 16, 64)
    spans = [s for s in recent_device_spans() if s.start_ns >= began]
    # every prompt's causal pairs are counted once, whatever the decoder: an attention's least work
    pairs = sum(s.count["pairs"] for s in spans if s.name == "serve.prefill")
    assert pairs == 2 * sum(len(t) * (len(t) + 1) // 2 for t in ps)
    steps = [s for s in spans if s.name == "serve.decode_step"]
    ours = [s for s in steps if "moe.zero_assignments" in s.count]
    assert ours and len(ours) < len(steps)
    for s in ours:  # all 3 a token a layer; the identity experts' and the held experts' among them
        assert s.count["moe.assignments"] == s.count["active"] * 3 * 2 and s.count["mla"] == "absorbed"
        assert s.count["moe.zero_assignments"] + s.count["moe.held_assignments"] <= s.count["moe.assignments"]
        assert s.count["moe.max_expert_load"] <= s.count["moe.held_assignments"]
        assert s.count["moe.experts_reached"] <= min(s.count["moe.held_assignments"], 2 * 4)  # 4 held experts, two layers


# (f) prompt(...) through a dataframe
def test_prompt_runs_the_decoder_through_the_normal_path(ref):
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt
    from daft_tpu.profiling import newest_device_span, recent_device_spans

    docs = [" ".join(f"w{(7 * i + j) % 50}" for j in range(5 + 6 * i)) for i in range(6)]
    df = daft_tpu.from_pydict({"id": list(range(6)), "doc": docs})
    cut = dict(num_layers=2, expert_shard=[0, 2], vocab_shard=[0, 2])
    expr = prompt(col("doc"), provider="flax_random", model=TINY, seed=9, max_new_tokens=5, ignore_eos=True,
                  logprobs=True, num_slots=4, max_prompt_tokens=48, **cut)
    out = df.with_column("answer", expr).to_pydict()
    for a in out["answer"]:
        assert len(a["token_ids"]) == len(a["logprobs"]) == 5
        assert all(0 <= t < 128 for t in a["token_ids"]) and all(np.isfinite(a["logprobs"]))
    names = {s.name for s in recent_device_spans()}
    assert {"udf.call", "prompt.tokenize", "prompt.run", "serve.prefill", "serve.decode_step", "serve.fetch",
            "provider.init_params", "provider.place_params"} <= names
    inst = expr._expr.udf._instances[0]
    assert isinstance(inst.model, lc.LongcatFlashLM) and inst.params["head"].dtype == jnp.bfloat16
    # what the batcher holds, on the span: four latent caches of (4 slots, 54 positions, 16 values) bfloat16
    run = newest_device_span("prompt.run").count
    assert (run["slots"], run["positions"], run["state_bytes"]) == (4, 54, 4 * 4 * 54 * 16 * 2)
    assert newest_device_span("provider.init_params").count["param_bytes"] > 0
    # and the answers are the reference's, teacher-forced on the hashed prompt
    tokens, lengths = inst.tokenizer.encode_batch(docs)
    for i in (0, 5):
        toks = np.asarray(out["answer"][i]["token_ids"])
        seq = np.concatenate([tokens[i, :lengths[i]], toks])
        lp = np.asarray(jax.nn.log_softmax(ref.forward(ref_config(), 9, seq, logits_from=lengths[i] - 1)[:-1], -1))
        assert np.max(np.abs(lp[np.arange(5), toks] - out["answer"][i]["logprobs"])) <= LOGPROB_GAP_MAX


def test_release_after_an_abandoned_stream_leaves_nothing_of_the_prompter():
    """The stream is closed after its first partition (a benchmark window closes so, and a ``limit`` does); the next
    morsel may still be running on the feeder thread. The UDF's ``release`` waits for it and drops the prompter, whose
    batcher is in a cycle with its own jitted programs: parameters and latent rows go with them."""
    import weakref

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt

    docs = [" ".join(f"w{(7 * i + j) % 50}" for j in range(5 + i % 30)) for i in range(64)]
    df = daft_tpu.from_pydict({"id": list(range(64)), "doc": docs})
    with daft_tpu.execution_config_ctx(default_morsel_size=4, result_cache_enabled=False):
        expr = prompt(col("doc"), provider="flax_random", model=TINY, seed=9, batch_size=4, max_new_tokens=24,
                      ignore_eos=True, logprobs=True, num_slots=4, max_prompt_tokens=48)
        it = df.with_column("answer", expr).select("id", "answer").iter_partitions()
        assert len(next(it)) == 4
        it.close()
    udf = expr._expr.udf
    inst = udf._instances[0]
    alive = [weakref.ref(o) for o in (inst, inst._batcher, inst.params["layers"][0]["w_in"], inst._batcher.state[0]["kv"])]
    del inst
    assert udf.release() is True and not udf._instances
    assert [r() for r in alive] == [None] * 4


def test_names_and_cuts_are_looked_up_in_one_record():
    from daft_tpu.ai import flax_provider
    from daft_tpu.ai.flax_provider import FlaxPrompter

    assert {"LongCat-Flash-Chat", TINY, "granite-4.0-h-small", "granite-hybrid-tiny"} < set(decoders.DECODERS)
    assert set(flax_provider.CUT_OPTIONS) == {"num_layers", "num_hidden_layers", "expert_shard", "vocab_shard"}
    assert set(flax_provider.CUT_OPTIONS) < set(flax_provider.PROMPTER_OPTIONS)
    with pytest.raises(DaftValueError, match="LongCat-Flash-Chat.*granite-4.0-h-small"):
        FlaxPrompter("LongCat-Flash", expert_shard=[0, 32])  # no substring rule; the names on record are listed
    with pytest.raises(DaftValueError, match="num_hidden_layers"):
        FlaxPrompter(TINY, num_hidden_layers=2)  # another decoder's cut
    with pytest.raises(DaftValueError, match="does not divide"):
        lc.LongcatFlashConfig.from_name(TINY, expert_shard=(0, 3))
    with pytest.raises(DaftValueError, match="num_layers=3"):
        lc.LongcatFlashConfig.from_name(TINY, num_layers=3)
    cfg = lc.LongcatFlashConfig.from_name("LongCat-Flash-Chat", num_layers=4, expert_shard=(0, 32), vocab_shard=(0, 8))
    assert (cfg.hidden_size, cfg.held_experts, cfg.held_vocab, cfg.moe_topk, cfg.router_outputs) == \
        (6144, 16, 16384, 12, 768)
    assert (cfg.cache_row, cfg.q_scale, cfg.kv_scale) == (576, 2.0, 12 ** 0.5)
    # the cut's parameters: 4 x (638.9M + 16 x 37.75M) + 2 x 100.7M = 5,173M, 10.35 GB in bfloat16
    shapes = jax.eval_shape(lambda: lc.init_longcat_params(cfg, 0)[1])
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 5.17e9 < n < 5.18e9
    state = jax.eval_shape(lambda: lc.LongcatFlashLM(cfg).init_state(16, 16449))
    assert sum(int(np.prod(a.shape)) * 2 for a in jax.tree_util.tree_leaves(state)) == 16 * 16449 * 8 * 1152
