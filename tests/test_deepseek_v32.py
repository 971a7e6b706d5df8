"""The DeepSeek-V3.2-Exp decoder (``models/deepseek_v32.py``: latent attention whose
keys an indexer selects, YaRN positions, a group-limited sigmoid router beside a
shared expert, a sharded expert layer) against its plain reference
(``benchmark/reference/deepseek_v32.py``), through the continuous batcher, at a
small size on the CPU: hidden 64, 4 heads, ``index_topk`` 32, 8 experts in 4
groups, YaRN factor 4 over 16 positions, chunks of 32, documents of 40-150
tokens, so that selection bites and positions pass the original length.

Tolerances. **In float32 the program is the reference** to summation order
(the limit 1e-4; read 2e-6): chunked prefill, the cache, the decode steps, the
selection, YaRN and the router are then held exactly. **In bfloat16, as
served, a hard top-k amplifies rounding**: a key whose index score lies within
rounding of its query's threshold is kept by one side and dropped by the other,
and with random weights the indexer's choice is independent of the attention's
scores, so such a key weighs as much as any of the 32 kept (at the published top
2,048 it is one of 2,048). So the bfloat16 program is held in two parts: where
every key is kept (``index_topk`` above every position: the selection runs and
masks nothing) its logits are within ``LOGIT_GAP_MAX`` of the reference's and
the fp8 control is outside; where selection bites, every key on which the
program's set and the reference's differ has an index score within
``MARGIN`` of the reference's threshold, and nine sets in ten are the same.
"""

from __future__ import annotations

import dataclasses
import math
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
from daft_tpu.models import decoders, deepseek_v32 as ds, latent_attention as la, longcat_flash as lc  # noqa: E402
from daft_tpu.models.serving import ContinuousBatcher, Request  # noqa: E402
from daft_tpu.ops import pallas_attention, pallas_dsa_index, pallas_mla_attention  # noqa: E402

TINY = "deepseek-v32-tiny"
#: |logits - reference's| of the float32 program: summation order alone. Read 2e-6 over seeds 0-3.
F32_GAP_MAX = 1e-4
#: |logits - reference's| of the bfloat16 program where no key is dropped. Read 0.017 to 0.063 over seeds 0-3
#: (logits spread ~1); the fp8 control 0.63 to 2.5.
LOGIT_GAP_MAX = 0.15
#: A key on which the bfloat16 program's selected set and the reference's differ lies this near the reference's
#: threshold, in units of the spread of that query's index scores (bfloat16 rounds them by ~2 ** -8 of it; read 0.02).
MARGIN = 0.08
T = 32


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "deepseek_v32.py"))


def ref_config(rank: int = 0, size: int = 2, **sizes) -> dict:
    """The tiny model as the reference reads a configuration file: sizes as run, YaRN's numbers as a group."""
    p = dict(ds.TEST_SIZES[TINY], **sizes)
    scaling = {"type": "yarn", "factor": p.pop("rope_factor"), "mscale": p.pop("mscale"), "mscale_all_dim": p.pop("mscale_all_dim"),
               "original_max_position_embeddings": p.pop("original_max_position_embeddings"),
               "beta_fast": p.pop("beta_fast"), "beta_slow": p.pop("beta_slow")}
    return dict(p, rope_scaling=scaling, router_outputs=p["n_routed_experts"], n_routed_experts=p["n_routed_experts"] // size,
                vocab_size=p["vocab_size"] // size, embedding_std=ds.EMBED_STD, query_gain=p.get("query_gain", 1.0), expert_gain=p.get("expert_gain", 1.0), router_bias_std=1e-4,
                norm_topk_prob=True,
                options={"expert_shard": [rank, size], "vocab_shard": [rank, size]})


def program(seed: int, rank: int = 0, size: int = 2, float32: bool = False, **sizes):
    cfg = dataclasses.replace(ds.DeepseekV32Config.from_name(TINY, expert_shard=(rank, size), vocab_shard=(rank, size)), **sizes)
    model, params = ds.init_deepseek_params(cfg, seed)
    if float32:  # the same bfloat16 values, every product and the cache in float32
        model = ds.DeepseekV32LM(dataclasses.replace(cfg, dtype=jnp.float32))
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return model, params


def served_logits(model, params, toks, n: int):
    """The first ``n`` tokens as chunks of ``T`` (beside a row that carries no prompt), then the rest as decode
    steps teacher-forced: the logits after the prompt and after every step, and the state."""
    state = model.init_state(3, 192)
    slots = jnp.asarray([2, 0], jnp.int32)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    for c in range(-(-n // T)):
        part = np.zeros((2, T), np.int32)
        here = min(T, n - c * T)
        part[0, :here] = toks[c * T:c * T + here]
        state, logits, counts = prefill(params, state, part, slots, jnp.full((2,), c * T, jnp.int32),
                                        jnp.asarray([here, 0], jnp.int32))
    got = [np.asarray(logits[0])]
    for i in range(n, len(toks)):
        state, logits, _ = decode(params, state, jnp.full((3,), toks[i], jnp.int32), jnp.full((3,), i, jnp.int32),
                                  jnp.asarray([False, False, True]))
        got.append(np.asarray(logits[2]))
    return np.stack(got), state, counts


def float32_gap(ref, seed: int = 0, n: int = 117, total: int = 122, **sizes) -> float:
    """The float32 program's largest |logit - reference's| over a prompt of ``n`` tokens in chunks and ``total - n`` decode steps."""
    model, params = program(seed, float32=True, **sizes)
    toks = np.random.default_rng(seed).integers(2, 128, total).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _, _ = served_logits(model, params, toks, n)
    want = ref.forward(ref_config(**sizes), seed, toks)
    return float(np.max(np.abs(got - want[n - 1:])))


def test_reference_draws_the_programs_weights(ref):
    seed = 3
    model, params = program(seed, rank=1)
    rcfg = ref_config(rank=1)
    experts, vocab = ref.share(rcfg)
    assert (experts, vocab) == ((4, 4), (128, 128)) and model.cfg.first_k_dense_replace == 1
    for i, layer in enumerate(params["layers"]):
        want = ref.layer_weights(rcfg, seed, i, experts)
        assert set(want) == set(layer) and ("router" in layer) == (i >= 1)
        for name, w in want.items():
            assert layer[name].dtype == (jnp.float32 if name == "router_bias" else jnp.bfloat16)
            assert float(jnp.max(jnp.abs(w - layer[name].astype(jnp.float32)))) == 0.0, (i, name)
    emb, final_norm, head = ref.embedding(rcfg, seed, vocab)
    for name, w in (("embed", emb), ("final_norm", final_norm), ("head", head)):
        assert float(jnp.max(jnp.abs(w - params[name].astype(jnp.float32)))) == 0.0, name
    assert not np.array_equal(np.asarray(emb), np.asarray(head))  # untied
    # the published size's drawing rule for W_qb (a gain under the fan-in scale), at the tiny widths
    # the published size's drawing rules for W_qb and the experts' W_out (gains under the fan-in scale), at the tiny widths
    _, quiet = program(seed, rank=1, query_gain=0.25, expert_gain=0.1)
    for layer, name, gain in ((0, "q_b", 0.25), (1, "w_out", 0.1)):
        want = ref.layer_weights(ref_config(rank=1, query_gain=0.25, expert_gain=0.1), seed, layer, experts)[name]
        assert float(jnp.max(jnp.abs(want - quiet["layers"][layer][name].astype(jnp.float32)))) == 0.0
        assert float(jnp.std(want)) == pytest.approx(gain * float(jnp.std(params["layers"][layer][name].astype(jnp.float32))), rel=0.05)
    published = ds.DeepseekV32Config.from_name("DeepSeek-V3.2-Exp")
    assert (published.query_gain, published.expert_gain, model.cfg.query_gain, model.cfg.expert_gain) == (0.25, 0.1, 1.0, 1.0)


# (a) chunked prefill, then decoding through the cache, against the reference's full forward
@pytest.mark.parametrize("seed", [0, 3])
def test_in_float32_chunked_prefill_then_decode_is_the_references_forward(ref, seed):
    """117 tokens as four chunks of 32 (selection bites from position 32, positions pass YaRN's original 16),
    then five decode steps that select too: every logit against the reference's one forward."""
    assert float32_gap(ref, seed) <= F32_GAP_MAX


@pytest.mark.parametrize("seed", [0, 3])
def test_in_bfloat16_with_every_key_kept_the_logits_agree_and_the_control_does_not(ref, seed):
    model, params = program(seed, index_topk=1000)
    toks = np.random.default_rng(seed).integers(2, 128, 122).astype(np.int32)
    got, state, counts = served_logits(model, params, toks, 117)
    assert int(counts["assignments"]) == 21 * 3 * 2  # the last chunk's 21 valid tokens, top 3, two expert layers
    assert all(not np.any(np.asarray(leaf[0])) for s in state for leaf in s.values())  # the row without a prompt wrote nothing
    rcfg = ref_config(index_topk=1000)
    want = ref.forward(rcfg, seed, toks)
    assert float(np.max(np.abs(got - want[116:]))) <= LOGIT_GAP_MAX and float(np.std(want)) > 0.5
    if seed == 0:
        low = ref.forward(rcfg, seed, toks, precision="fp8")
        assert float(np.max(np.abs(low[116:] - want[116:]))) > LOGIT_GAP_MAX


# (b) the selected set
def _layer0_selection(model, params, toks, lengths):
    """The program's own selection in layer 0 for prompts as one call of chunks at unlike depths and a decode step:
    -> {(row, position): set of kept keys}."""
    cfg, p = model.cfg, params["layers"][0]
    inv = jnp.asarray(ds.yarn_frequencies(cfg))
    state = model.init_state(len(lengths), 192)[0]
    kept = {}
    chunks = [-(-n // T) for n in lengths]
    for c in range(max(chunks)):
        rows = [r for r in range(len(lengths)) if c < chunks[r]]
        starts = jnp.asarray([c * T for _ in rows], jnp.int32)
        here = jnp.asarray([min(T, lengths[r] - c * T) for r in rows], jnp.int32)
        part = np.zeros((len(rows), T), np.int32)
        for k, r in enumerate(rows):
            part[k, :int(here[k])] = toks[r][c * T:c * T + int(here[k])]
        x = decoders.rms(params["embed"][part].astype(jnp.float32), p["attn_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
        slots = jnp.asarray(rows, jnp.int32)
        positions = starts[:, None] + jnp.arange(T)[None, :]
        _, _, cq = la.project(cfg, p, "", x, positions, inv)
        qi, ki, w = ds.index_project(cfg, p, x, cq, positions, inv)
        state = dict(state, ik=la.write_chunk(state["ik"], ki, slots, starts, jnp.arange(T)[None, :] < here[:, None]))
        index = ds.index_scores_expanded(qi, w, state["ik"], slots, starts)
        keep = np.asarray(index >= ds.kth_threshold(index, positions, cfg.index_topk)[..., None])
        for k, r in enumerate(rows):
            for t in range(int(here[k])):
                at = c * T + t
                kept[r, at] = set(np.flatnonzero(keep[k, t, :at + 1]))
    return kept


def test_in_float32_the_selected_set_is_the_references_top_k(ref):
    """Two prompts whose chunks stand at unlike depths of their slots (150 and 77 tokens: the third call holds a
    row at its end beside a row in its middle), layer 0: the keys each query keeps are the reference's ``top_k``
    set; a query below ``index_topk`` keeps every causal key."""
    seed, lengths = 1, [150, 77]
    model, params = program(seed, float32=True)
    rng = np.random.default_rng(seed)
    toks = [rng.integers(2, 128, n).astype(np.int32) for n in lengths]
    with jax.default_matmul_precision("highest"):
        kept = _layer0_selection(model, params, toks, lengths)
    for r, n in enumerate(lengths):
        selection = []
        ref.forward(ref_config(), seed, toks[r], selection_out=selection)
        chosen, _ = selection[0]
        for t in range(n):
            assert kept[r, t] == set(np.flatnonzero(chosen[t])), (r, t)
            assert len(kept[r, t]) == min(t + 1, 32)


def test_in_bfloat16_the_sets_differ_only_at_keys_within_rounding_of_the_threshold(ref):
    seed, n = 2, 150
    model, params = program(seed)
    toks = [np.random.default_rng(seed).integers(2, 128, n).astype(np.int32)]
    kept = _layer0_selection(model, params, toks, [n])
    selection = []
    ref.forward(ref_config(), seed, toks[0], selection_out=selection)
    chosen, index = selection[0]
    same = 0
    for t in range(n):
        want = set(np.flatnonzero(chosen[t]))
        same += kept[0, t] == want
        seen = index[t, :t + 1]
        for s in kept[0, t] ^ want:
            assert abs(seen[s] - np.sort(seen)[-32]) <= MARGIN * np.std(seen), (t, s)
    assert same >= 0.9 * n


def test_a_decode_step_selects_as_the_reference_does(ref):
    """After a prompt of 60 tokens in float32, the step at position 60 keeps the reference's 32 keys in layer 0."""
    seed, n = 0, 60
    model, params = program(seed, float32=True)
    cfg, p = model.cfg, params["layers"][0]
    toks = np.random.default_rng(seed).integers(2, 128, n + 1).astype(np.int32)
    inv = jnp.asarray(ds.yarn_frequencies(cfg))
    with jax.default_matmul_precision("highest"):
        x = decoders.rms(params["embed"][toks[None]].astype(jnp.float32), p["attn_norm"], cfg.rms_norm_eps)
        positions = jnp.arange(n + 1)[None]
        _, _, cq = la.project(cfg, p, "", x, positions, inv)
        qi, ki, w = ds.index_project(cfg, p, x, cq, positions, inv)
        ik = jnp.zeros((1, cfg.index_head_dim, 128), jnp.float32).at[:, :, :n + 1].set(jnp.swapaxes(ki, 1, 2))
        index = ds.index_scores_token(qi[:, n], w[:, n], ik)
        keep = np.asarray(index >= ds.kth_threshold(index, jnp.asarray([n]), cfg.index_topk)[:, None])[0]
    selection = []
    ref.forward(ref_config(), seed, toks, selection_out=selection)
    assert set(np.flatnonzero(keep[:n + 1])) == set(np.flatnonzero(selection[0][0][n])) and keep[:n + 1].sum() == 32


def test_kth_threshold_is_the_kth_largest_and_reads_nothing_beyond_a_position():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 200)).astype(np.float32)
    x[0, 0, :50] = 0.0                       # ties, and zeros of both signs
    x[0, 1, :40] = -0.0
    positions = rng.integers(0, 200, (3, 5))
    positions[1, 0], positions[1, 1] = 6, 7  # 7 and 8 causal keys: all are kept (fewer than 8: no threshold at all)
    junk = np.where(np.arange(200) <= positions[..., None], x, np.nan)
    for k, reach in ((8, None), (1, None), (8, 200), (8, int(positions.max()) + 1)):  # with a reach: a narrower search, the same answer
        got = np.asarray(ds.kth_threshold(jnp.asarray(junk), jnp.asarray(positions), k, reach=reach))
        for i in range(3):
            for j in range(5):
                seen = x[i, j, :positions[i, j] + 1]
                assert got[i, j] == (np.sort(seen)[-k] if len(seen) >= k else -np.inf), (i, j, k)


# (c) with every key kept the attention is the shared latent attention without a selection
def test_with_index_topk_above_every_position_selection_changes_nothing(monkeypatch):
    seed = 0
    toks = np.random.default_rng(seed).integers(2, 128, 100).astype(np.int32)
    model, params = program(seed, index_topk=1000)
    with_selection, _, _ = served_logits(model, params, toks, 96)
    biting, _, _ = served_logits(*program(seed), toks, 96)
    chunk, token = la.attend_chunk, la.attend_token
    monkeypatch.setattr(la, "attend_chunk", lambda cfg, w, q, kv, slots, starts, lengths, scale=None, select=None, max_heads=None:
                        chunk(cfg, w, q, kv, slots, starts, lengths, scale))
    monkeypatch.setattr(la, "attend_token", lambda cfg, w, q, kv, positions, scale=None, keep=None: token(cfg, w, q, kv, positions, scale))
    without, _, _ = served_logits(model, params, toks, 96)
    assert np.array_equal(with_selection, without)
    assert float(np.max(np.abs(biting - without))) > 0.3  # and at the tiny top 32 it does


def test_longcats_attention_is_the_shared_one():
    assert lc.mla_core_absorbed is la.core_absorbed and lc.mla_core_expanded is la.core_expanded
    cfg = lc.LongcatFlashConfig.from_name("longcat-flash-tiny")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 8)), jnp.float32)
    at = jnp.arange(8)[None] + jnp.asarray([[0], [8]])
    assert np.array_equal(np.asarray(lc.rope(x, at, cfg.rope_theta)),
                          np.asarray(la.rope(x, at, la.frequencies(cfg.rope_theta, 8))))


# (d) the share
def test_the_shares_parts_add_up_to_the_uncut_expert_layer(ref):
    """All eight shares of one expert each: the held experts' parts, with the shared expert's (which every chip
    computes alike) counted once, add up to the uncut reference's expert layer."""
    seed, layer = 0, 1
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 64)), jnp.float32)
    valid = jnp.ones((24,), bool)
    whole, params = program(seed, size=1)
    p = params["layers"][layer]
    shared = decoders.gated_mlp(u.astype(jnp.bfloat16), p["shared_in"], p["shared_out"], jnp.bfloat16)
    total = 0.0
    for rank in range(8):  # an expert's weights come from its global id, whoever holds it: share ``rank`` holds expert ``rank``
        cfg = dataclasses.replace(whole.cfg, expert_shard=(rank, 8))
        y, counts = ds._moe(cfg, dict(p, w_in=p["w_in"][rank:rank + 1], w_out=p["w_out"][rank:rank + 1]), u, valid)
        assert int(counts["assignments"]) == 24 * 3 and cfg.first_expert == rank and cfg.held_experts == 1
        total = total + (y - shared)
    want = ref.expert_layer(ref_config(size=1), seed, layer, u, (0, 8))
    assert float(np.max(np.abs(np.asarray(total + shared) - want))) <= 0.05 and float(np.std(want)) > 0.3  # read 0.012
    alone = ref.expert_layer(ref_config(size=1), seed, layer, u, (0, 8), shared=False)
    assert float(np.max(np.abs(want - alone))) > 0.3  # the shared expert is a part worth counting


# (e) the router
def test_the_router_stays_in_its_groups_and_the_bias_moves_a_choice_and_no_weight(ref):
    seed = 0
    model, params = program(seed)
    cfg, p = model.cfg, params["layers"][1]
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(64, 64)), jnp.float32)
    idx, w = (np.asarray(a) for a in ds.route(cfg, p, u))
    assert idx.shape == (64, 3) and np.allclose(w.sum(-1), 2.5, atol=1e-5)
    assert all(len({e // 2 for e in row}) <= cfg.topk_group for row in idx)  # 4 groups of 2; 2 kept
    ridx, rw = (np.asarray(a) for a in ref.route(ref_config(), ref.layer_weights(ref_config(), seed, 1, (0, 4)), u))
    assert np.array_equal(np.sort(idx, -1), np.sort(ridx, -1)) and np.allclose(np.sort(w, -1), np.sort(rw, -1), atol=1e-5)
    # a bias that lifts expert 7's group and expert moves choices to it; the weights are still the sigmoid scores', renormalised
    bias = np.zeros(8, np.float32)
    bias[7] = 10.0
    idx1, w1 = (np.asarray(a) for a in ds.route(cfg, dict(p, router_bias=jnp.asarray(bias)), u))
    assert np.all(np.any(idx1 == 7, -1)) and not np.all(np.any(idx == 7, -1))
    s = np.asarray(jax.nn.sigmoid(u @ p["router"].astype(jnp.float32)))
    assert np.allclose(w1, 2.5 * np.take_along_axis(s, idx1, -1) / np.take_along_axis(s, idx1, -1).sum(-1, keepdims=True), atol=2e-3)
    # group-limited: with expert 0 the best of all but its group poor, it is passed over
    bias = np.asarray([5.0, -20.0, 3.0, 3.0, 3.0, 3.0, 0.0, 0.0], np.float32)
    idx2, _ = ds.route(cfg, dict(p, router_bias=jnp.asarray(bias)), u)
    assert not np.any(np.asarray(idx2) == 0)  # groups 1 and 2 (sums ~6 + scores) beat group 0 (5 - 20)


# (f) positions
def test_yarns_frequencies_are_the_formulas_and_the_indexer_turns_halves():
    cfg = ds.DeepseekV32Config.from_name("DeepSeek-V3.2-Exp", num_layers=5, expert_shard=(0, 16), vocab_shard=(0, 8))
    got = ds.yarn_frequencies(cfg)
    want = []
    for i in range(32):
        f = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - 10) / (23 - 10), 0.0), 1.0)  # low = floor(10.47), high = ceil(22.5)
        want.append(f / 40 * ramp + f * (1 - ramp))
    assert np.allclose(got, np.asarray(want, np.float32), rtol=1e-6)
    d = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(10000.0))  # noqa: E731
    assert (math.floor(d(32)), math.ceil(d(1))) == (10, 23)
    assert got[10] == np.float32(10000.0 ** (-20 / 64)) and np.isclose(got[23], 10000.0 ** (-46 / 64) / 40, rtol=1e-6)
    assert np.isclose(cfg.attention_mscale, 0.1 * math.log(40) + 1) and np.isclose(cfg.softmax_scale, 192 ** -0.5 * 1.3689 ** 2, rtol=1e-4)
    # halves paired is the interleaved turn of the same pairs laid side by side: (x[i], x[i + n / 2]) <-> (y[2i], y[2i + 1])
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 3, 8)), jnp.float32)
    at = jnp.asarray([[0, 1, 2, 3, 4], [30, 31, 32, 33, 34]])
    inv = jnp.asarray(ds.yarn_frequencies(ds.DeepseekV32Config.from_name(TINY)))
    halves = np.asarray(la.rope(x, at, inv, interleaved=False))
    laid = jnp.stack([x[..., :4], x[..., 4:]], -1).reshape(x.shape)
    turned = np.asarray(la.rope(laid, at, inv)).reshape(2, 5, 3, 4, 2)
    assert np.allclose(halves, np.concatenate([turned[..., 0], turned[..., 1]], -1), atol=1e-6)
    assert float(np.max(np.abs(halves - np.asarray(la.rope(x, at, inv))))) > 0.1
    assert np.allclose(np.linalg.norm(halves, axis=-1), np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


# (g) programs that leave something out read not correct
def _no_selection(monkeypatch):
    monkeypatch.setattr(ds, "kth_threshold", lambda index, positions, k, reach=None: jnp.full(positions.shape, -jnp.inf))


def _index_without_relu(monkeypatch):
    def expanded(q, w, ik, slots, starts):
        k = ik[slots]
        return jnp.einsum("bthd,bds,bth->bts", q.astype(jnp.float32), k.astype(jnp.float32), w)
    monkeypatch.setattr(ds, "index_scores_expanded", expanded)
    monkeypatch.setattr(ds, "index_scores_token", lambda q, w, ik: jnp.einsum("bhd,bds,bh->bs", q, ik, w))


def _indexer_rope_interleaved(monkeypatch):
    sound = la.rope
    monkeypatch.setattr(la, "rope", lambda x, positions, inv, interleaved=True: sound(x, positions, inv))


def _no_mscale(monkeypatch):
    monkeypatch.setattr(ds.DeepseekV32Config, "attention_mscale", property(lambda self: 1.0))


def _weights_not_renormalised(monkeypatch):
    def route(cfg, p, u):
        idx, _ = sound(cfg, p, u)
        r = jnp.einsum("nd,de->ne", u, p["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        return idx, cfg.routed_scaling_factor * jnp.take_along_axis(jax.nn.sigmoid(r), idx, axis=-1)
    sound = ds.route
    monkeypatch.setattr(ds, "route", route)


def _plain_frequencies(monkeypatch):
    monkeypatch.setattr(ds, "yarn_frequencies", lambda cfg: (cfg.rope_theta ** (-np.arange(0, cfg.qk_rope_head_dim, 2)
                                                                                / cfg.qk_rope_head_dim)).astype(np.float32))


@pytest.mark.parametrize("fault", [_no_selection, _index_without_relu, _indexer_rope_interleaved, _no_mscale,
                                   _weights_not_renormalised, _plain_frequencies], ids=lambda f: f.__name__.strip("_"))
def test_a_program_that_leaves_something_out_reads_not_correct(ref, monkeypatch, fault):
    """In float32, where the sound program is the reference to 1e-4, each fault is a thousand times that off."""
    fault(monkeypatch)
    assert float32_gap(ref, 0, n=90, total=93) > 0.1


# (h) the kernels: interpreted here, and lowered for a described v5e in tests/test_pallas.py
def test_the_index_kernel_interpreted_equals_xlas_loop_where_a_query_may_look():
    rng = np.random.default_rng(0)
    B, Tk, Hi, Di, S = 3, 128, 4, 128, 3 * 128 + 40
    q = jnp.asarray(rng.normal(size=(B, Tk, Hi, Di)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(B, Tk, Hi)), jnp.float32)
    ik = jnp.asarray(rng.normal(size=(5, Di, S)), jnp.bfloat16)
    slots, starts, lengths = jnp.asarray([4, 0, 2]), jnp.asarray([256, 0, 128]), jnp.asarray([128, 0, 77])
    got = np.asarray(pallas_dsa_index.index_scores(q, w, ik, slots, starts, lengths, interpret=True))
    want = np.asarray(ds.index_scores_expanded(q, w, ik, slots, starts))
    assert got.shape == want.shape == (B, Tk, 4 * 128)
    for b, blocks in ((0, 3), (2, 2)):  # the blocks each row attends; beyond them the kernel writes nothing
        assert np.max(np.abs(got[b, :, :blocks * 128] - want[b, :, :blocks * 128])) <= 2e-3 * np.max(np.abs(want))
    assert not np.any(got[1, :, :128])  # the row without a query: zeros in its one visit
    assert not pallas_dsa_index.index_scores_applies(q.shape, q.dtype)  # the CPU backend takes XLA's loop


def test_the_prefill_kernel_with_a_selection_interpreted_equals_the_masked_loop():
    rng = np.random.default_rng(1)
    cfg = dataclasses.replace(ds.DeepseekV32Config.from_name(TINY), num_attention_heads=2, kv_lora_rank=128,
                              qk_nope_head_dim=128, qk_rope_head_dim=16, v_head_dim=128)
    B, Tk, S = 2, 128, 3 * 128
    q = jnp.asarray(rng.normal(size=(B, Tk, 2, 144)), jnp.bfloat16)
    kv = jnp.asarray(rng.normal(size=(3, 144, S)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(128, 2, 256)) * 128 ** -0.5, jnp.bfloat16)
    slots, starts, lengths = jnp.asarray([2, 0]), jnp.asarray([256, 128]), jnp.asarray([128, 100])
    index = jnp.asarray(rng.normal(size=(B, Tk, S)), jnp.float32)
    threshold = ds.kth_threshold(index, starts[:, None] + jnp.arange(Tk)[None], 50)
    fused = pallas_mla_attention.mla_prefill_attention(q, kv, w, slots, starts, lengths, nope=128, interpret=True, scale=0.11,
                                                       index=index, threshold=threshold, max_heads=2)
    masked = la.expanded_over_slots(cfg, w, q, kv, slots, starts, 0.11, index >= threshold[..., None])
    dense = la.expanded_over_slots(cfg, w, q, kv, slots, starts, 0.11)
    assert float(jnp.max(jnp.abs(fused.astype(jnp.float32) - masked))) <= 0.02  # bfloat16 rounding of the result; read 0.008
    assert float(jnp.max(jnp.abs(dense - masked))) > 0.2


def test_on_a_tpu_the_published_widths_take_both_kernels_and_the_model_runs_through_them(monkeypatch):
    """The backend rule answers as on a TPU and both kernels run interpreted: a model one lane tile wide prefills
    through them and agrees with XLA's path."""
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    assert pallas_dsa_index.index_scores_applies((4, 512, 64, 128), jnp.bfloat16)
    assert pallas_mla_attention.mla_prefill_applies((4, 512, 128, 192), jnp.bfloat16, 512, 128, 64, 128)
    assert not pallas_dsa_index.index_scores_applies((4, 32, 16, 16), jnp.bfloat16)
    wide = dict(num_attention_heads=2, kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=16, v_head_dim=128,
                index_n_heads=4, index_head_dim=128, index_topk=40, num_hidden_layers=2)
    model, params = program(0, **wide)
    toks = np.random.default_rng(0).integers(2, 128, (2, 256)).astype(np.int32)

    def two_chunks():
        state = model.init_state(3, 256)
        for c in range(2):
            state, logits, _ = model.prefill(params, state, toks[:, c * 128:(c + 1) * 128], jnp.asarray([2, 1]),
                                             jnp.full((2,), c * 128, jnp.int32), jnp.asarray([128, 128 if c == 0 else 60]))
        return np.asarray(logits)

    calls = []
    index, attend = pallas_dsa_index.index_scores, pallas_mla_attention.mla_prefill_attention
    monkeypatch.setattr(pallas_dsa_index, "index_scores",
                        lambda *a: calls.append("index") or index(*a, interpret=True))
    monkeypatch.setattr(pallas_mla_attention, "mla_prefill_attention",
                        lambda *a, **k: calls.append("mla") or attend(*a, interpret=True, **k))
    monkeypatch.setattr(decoders.gmm, "grouped_matmul_applies", lambda *a, **k: False)
    fused = two_chunks()
    assert calls.count("index") == calls.count("mla") == 2 * 2
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: False)
    xla = two_chunks()
    assert calls.count("index") == 4 and np.max(np.abs(fused - xla)) <= LOGIT_GAP_MAX  # rounding of a result, and a key at a threshold


# the configuration, the cut and the serving protocol
def test_names_cuts_and_sizes():
    with pytest.raises(DaftValueError, match="DeepSeek-V3.2-Exp"):
        ds.DeepseekV32Config.from_name("DeepSeek-V3.2")
    with pytest.raises(DaftValueError, match="expert_shard"):
        ds.DeepseekV32Config.from_name(TINY, expert_shard=(0, 3))
    with pytest.raises(DaftValueError, match="num_layers"):
        ds.DeepseekV32Config.from_name(TINY, num_layers=1)
    whole = ds.DeepseekV32Config.from_name("DeepSeek-V3.2-Exp")
    assert (whole.num_hidden_layers, whole.first_k_dense_replace) == (61, 3)
    cfg = ds.DeepseekV32Config.from_name("DeepSeek-V3.2-Exp", num_layers=5, expert_shard=(0, 16), vocab_shard=(0, 8))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.held_experts, cfg.held_vocab) == (5, 1, 16, 16160)
    shapes = jax.eval_shape(lambda: ds.init_deepseek_params(cfg, 0)[1])
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 4.60e9 < n < 4.70e9  # ISSUE 40's arithmetic: 597.4M + 4 x 951.6M + 231.7M, and the norms
    state = jax.eval_shape(lambda: ds.DeepseekV32LM(cfg).init_state(8, 32833))
    assert sorted(leaf.shape for leaf in jax.tree_util.tree_leaves(state)) == [(8, 128, 32896)] * 5 + [(8, 576, 32896)] * 5
    kinds = decoders.state_bytes_by_kind(ds.DeepseekV32LM(cfg).init_state(2, 100))  # whole tiles of 128 positions
    assert kinds == {"recurrent_bytes": 0, "kv_bytes": 2 * 128 * 5 * (576 + 128) * 2}
    assert TINY in decoders.DECODERS and "DeepSeek-V3.2-Exp" in decoders.DECODERS
    assert ds.selected_pairs([3, 20], 16) == 6 + (136 + 4 * 16) and ds.selected_pairs([5, 16], 16) == 15 + 136


def test_through_the_batcher_the_spans_carry_the_selections_counts_and_notes():
    from daft_tpu.profiling import recent_device_spans

    model, params = program(0)
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=200, eos_id=None, prefill_chunk=32, max_prompt_tokens=160)
    rng = np.random.default_rng(0)
    lengths = (40, 150, 77, 12, 100)
    out = b.run([Request(tokens=rng.integers(2, 128, n).astype(np.int32), max_new_tokens=4) for n in lengths])
    assert [len(o) for o in out] == [4] * 5
    mine = [s for s in recent_device_spans() if s.name == "serve.prefill"][-2:]
    assert sum(s.count["rows"] for s in mine) == 5
    for s in mine:
        assert s.count["index_pairs"] == s.count["pairs"] and 0 < s.count["selected_pairs"] < s.count["pairs"]
        assert (s.count["dsa"], s.count["mla"], s.count["moe"]) == ("masked", "expanded", "xla")
    assert sum(s.count["selected_pairs"] for s in mine) == ds.selected_pairs(lengths, 32)
    step = [s for s in recent_device_spans() if s.name == "serve.decode_step"][-1]
    assert (step.count["dsa"], step.count["mla"]) == ("masked", "absorbed") and step.count["moe.assignments"] == step.count["active"] * 3 * 2


def test_prompt_finds_the_decoder_by_name():
    from daft_tpu.ai.flax_provider import FlaxPrompter

    inst = FlaxPrompter(TINY, num_layers=2, expert_shard=(1, 2), vocab_shard=(0, 2), max_new_tokens=3, num_slots=2,
                        max_prompt_tokens=40, ignore_eos=True, logprobs=True)
    assert isinstance(inst.model, ds.DeepseekV32LM) and len(inst.params["layers"]) == 2 and "router" in inst.params["layers"][1]
    text, ids, logprobs = inst.prompt(["a b c d e f g h i j k l m n o p q r s t u v", "w x"])
    assert [len(i) for i in ids] == [3, 3] and all(np.all(np.isfinite(l)) for l in logprobs) and all(i.max() < 128 for i in ids)
