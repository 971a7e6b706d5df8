"""The openPangu-Ultra-MoE-718B decoder (``models/pangu_ultra_moe.py``: sandwich-normed
latent-attention layers, a sigmoid router over all experts beside a shared expert,
and a multi-token-prediction block that drafts a token a step) and the batcher's
speculative step (``models/serving.py``), against the plain reference
(``benchmark/reference/pangu_ultra_moe.py``), at a small size on the CPU: hidden
64, 4 heads, 8 experts of which a share holds 4, chunks of 16.

Tolerances. **In float32 the program is the reference** to summation order (the
limit 1e-4; read 1e-6): chunked prefill (the MTP block's rows of the prompt's
pairs among it), the caches, the speculative steps (the MTP block over the pairs
that became known, the verify over two positions, the rows a rejection leaves
behind to be overwritten) and the router are then held exactly, main model and
MTP block alike. **In bfloat16, as served**, the logits of both are within
``LOGIT_GAP_MAX`` of the reference's and the fp8 control's are not.
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
from daft_tpu.models import decoders, deepseek_v32 as ds, pangu_ultra_moe as pg  # noqa: E402
from daft_tpu.models.serving import ContinuousBatcher, Request, rejection_sample, residual  # noqa: E402

TINY = "pangu-ultra-moe-tiny"
#: |log-probability - reference's| of the float32 program, main model and MTP block: summation order. Read 1.5e-6.
F32_GAP_MAX = 1e-4
#: The same of the bfloat16 program. Read 0.035 to 0.052 (logits spread ~1); the fp8 control reads 0.3 and more.
LOGIT_GAP_MAX = 0.15
T = 16


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "pangu_ultra_moe.py"))


def ref_config(rank: int = 0, size: int = 2, **sizes) -> dict:
    """The tiny model as the reference reads a configuration file: the sizes as run."""
    p = dict(pg.TEST_SIZES[TINY], **sizes)
    return dict(p, router_outputs=p["n_routed_experts"], n_routed_experts=p["n_routed_experts"] // size,
                vocab_size=p["vocab_size"] // size, embedding_std=pg.EMBED_STD, expert_gain=p.get("expert_gain", 1.0),
                router_bias_std=1e-4, norm_topk_prob=True, options={"expert_shard": [rank, size], "vocab_shard": [rank, size]})


def program(seed: int, rank: int = 0, size: int = 2, float32: bool = False, **sizes):
    cfg = dataclasses.replace(pg.PanguUltraMoeConfig.from_name(TINY, expert_shard=(rank, size), vocab_shard=(rank, size)),
                              **sizes)
    model, params = pg.init_pangu_params(cfg, seed)
    if float32:  # the same bfloat16 values, every product and the caches in float32
        model = pg.PanguUltraMoeLM(dataclasses.replace(cfg, dtype=jnp.float32))
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return model, params


def _log_softmax(x):
    x = np.asarray(x, np.float64)
    m = x.max(-1, keepdims=True)
    return x - m - np.log(np.exp(x - m).sum(-1, keepdims=True))


def serve(model, params, prompts, new: int = 12, temperature: float = 1.0, slots: int = 3, seed: int = 3):
    b = ContinuousBatcher(model, params, num_slots=slots, max_seq_len=100, temperature=temperature, eos_id=None,
                          prefill_chunk=T, seed=seed)
    with jax.default_matmul_precision("highest"):
        out = b.run([Request(tokens=p, max_new_tokens=new) for p in prompts])
    return b, out


def gaps(ref, rcfg, seed, prompts, out, logprobs, drafts, precision="f32"):
    """The largest |program - reference| of the emitted tokens' log p and of the drafts' log q."""
    gap = draft_gap = 0.0
    for p, o, lp, dr in zip(prompts, out, logprobs, drafts):
        main, mtp = ref.forward(rcfg, seed, np.concatenate([p, o]).astype(np.int32), precision=precision,
                                logits_from=len(p) - 1)
        gap = max(gap, float(np.max(np.abs(_log_softmax(main)[np.arange(len(o)), o] - np.asarray(lp)))))
        lq = _log_softmax(mtp)
        draft_gap = max([draft_gap] + [abs(lq[j - 1, d] - q) for j, d, q in dr])
    return gap, draft_gap


def prompts_of(seed, lengths=(37, 5, 70, 1)):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 128, n).astype(np.int32) for n in lengths]


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
            assert float(jnp.max(jnp.abs(w - layer[name].astype(jnp.float32)))) == 0.0, (i, name)
    want = ref.mtp_weights(rcfg, seed, experts)
    assert set(want) == set(params["mtp"]) and set(want["layer"]) == set(params["mtp"]["layer"])
    for name, w in jax.tree_util.tree_leaves_with_path(want):
        got = params["mtp"]
        for k in name:
            got = got[k.key]
        assert float(jnp.max(jnp.abs(w - got.astype(jnp.float32)))) == 0.0, name
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    emb, final_norm, head = ref.embedding(rcfg, seed, vocab)
    for name, w in (("embed", emb), ("final_norm", final_norm), ("head", head)):
        assert float(jnp.max(jnp.abs(w - params[name].astype(jnp.float32)))) == 0.0, name
    _, loud = program(seed, rank=1, expert_gain=1.0)  # the drawing rule: the experts' W_out at a tenth of fan-in scale
    want = ref.layer_weights(ref_config(rank=1, expert_gain=1.0), seed, 1, experts)["w_out"]
    assert float(jnp.max(jnp.abs(want - loud["layers"][1]["w_out"].astype(jnp.float32)))) == 0.0
    assert float(jnp.std(want)) == pytest.approx(10 * float(jnp.std(params["layers"][1]["w_out"].astype(jnp.float32))), rel=0.05)
    assert pg.PanguUltraMoeConfig.from_name("openPangu-Ultra-MoE-718B").expert_gain == 0.1 == model.cfg.expert_gain


# (a) prefill and speculative decoding through the batcher against the reference's full forward
@pytest.mark.parametrize("seed", [1, 4])
def test_in_float32_speculative_decoding_gives_the_references_main_and_mtp_logits(ref, seed):
    """Prompts of 37, 5, 70 and 1 tokens in chunks of 16 on three slots (a slot refilled, a prompt of one chunk
    without a pair), 12 tokens each sampled at temperature 1: every emitted token's log p and every draft's log q
    against the reference teacher-forced over prompt and answer."""
    model, params = program(seed, float32=True)
    prompts = prompts_of(seed)
    b, out = serve(model, params, prompts)
    assert [len(o) for o in out] == [12] * 4
    assert all(6 <= len(dr) <= 11 for dr in b.last_drafts)  # a draft a step, one or two tokens a step
    gap, draft_gap = gaps(ref, ref_config(), seed, prompts, out, b.last_logprobs, b.last_drafts)
    assert gap <= F32_GAP_MAX and draft_gap <= F32_GAP_MAX, (gap, draft_gap)


def test_in_bfloat16_the_logprobs_agree_and_the_controls_do_not(ref):
    seed = 1
    model, params = program(seed)
    prompts = prompts_of(seed)
    b, out = serve(model, params, prompts)
    gap, draft_gap = gaps(ref, ref_config(), seed, prompts, out, b.last_logprobs, b.last_drafts)
    assert gap <= LOGIT_GAP_MAX and draft_gap <= LOGIT_GAP_MAX, (gap, draft_gap)
    # the fp8 reference in the program's place, judged the same way
    low = [ref.forward(ref_config(), seed, np.concatenate([p, o]).astype(np.int32), precision="fp8", logits_from=len(p) - 1)
           for p, o in zip(prompts, out)]
    lows = [_log_softmax(m)[np.arange(len(o)), o] for (m, _), o in zip(low, out)]
    lowq = [[(j, d, _log_softmax(q)[j - 1, d]) for j, d, _ in dr] for (_, q), dr in zip(low, b.last_drafts)]
    control = gaps(ref, ref_config(), seed, prompts, out, lows, lowq)
    assert min(control) > LOGIT_GAP_MAX, control


# (b) temperature 0: the speculative path is greedy decoding
class OneTokenAStep(pg.PanguUltraMoeLM):
    draft_length = None


def test_at_temperature_0_speculation_emits_what_one_token_a_step_emits():
    seed = 2
    model, params = program(seed, float32=True)
    prompts = prompts_of(seed, (21, 3, 50, 1, 16))
    spec, out = serve(model, params, prompts, new=15, temperature=0.0)
    plain, greedy = serve(OneTokenAStep(model.cfg), params, prompts, new=15, temperature=0.0)
    assert out == greedy
    np.testing.assert_allclose(np.concatenate(spec.last_logprobs), np.concatenate(plain.last_logprobs), atol=1e-5)
    assert spec.decode_steps < plain.decode_steps  # some drafts were the argmax


# (c) the rejection sampler
def _emitted(p, q):
    """What the sampler emits, from its formula: q(x) min(1, p(x) / q(x)) + P(reject) normalise(max(0, p - q))(x)."""
    accept = q * np.minimum(1.0, p / q)
    excess = np.asarray(residual(jnp.asarray(p), jnp.asarray(q)), np.float64)
    return accept + (1.0 - accept.sum()) * excess / excess.sum()


def test_the_rejection_samplers_emitted_distribution_is_p():
    rng = np.random.default_rng(0)
    for _ in range(5):
        p, q = (np.exp(x) / np.exp(x).sum() for x in rng.normal(size=(2, 11)))
        np.testing.assert_allclose(_emitted(p, q), p, atol=1e-6)
    # and a seeded sample of 20,000 drafts and verdicts, against p by chi-square
    V, n = 6, 20000
    p_logits, q_logits = (jnp.asarray(x, jnp.float32) for x in rng.normal(size=(2, V)) * 1.5)
    keys = jax.random.split(jax.random.PRNGKey(7), n)

    def one(key):
        k_draft, k_judge = jax.random.split(key)
        d = jax.random.categorical(k_draft, q_logits[None], axis=-1).astype(jnp.int32)
        accepted, c = rejection_sample(k_judge, d, p_logits[None], q_logits[None], 1.0)
        return jnp.where(accepted, d, c)[0], accepted[0]

    emitted, accepted = (np.asarray(a) for a in jax.vmap(one)(keys))
    p = np.asarray(jax.nn.softmax(p_logits), np.float64)
    q = np.asarray(jax.nn.softmax(q_logits), np.float64)
    counts = np.bincount(emitted, minlength=V)
    chi2 = float(((counts - n * p) ** 2 / (n * p)).sum())
    assert chi2 < 20.5  # the 0.999 quantile of chi-square with 5 degrees of freedom
    assert abs(accepted.mean() - np.minimum(p, q).sum()) < 4 * math.sqrt(0.25 / n)
    assert float(((np.bincount(emitted[accepted], minlength=V) - n * np.minimum(p, q)) ** 2 / (n * p)).sum()) < 40


def test_at_temperature_0_the_sampler_accepts_the_argmax_alone():
    p = jnp.asarray([[0.1, 2.0, 0.3], [3.0, 0.0, 0.1]])
    d = jnp.asarray([1, 2], jnp.int32)
    accepted, c = rejection_sample(jax.random.PRNGKey(0), d, p, p, 0.0)
    assert accepted.tolist() == [True, False] and c.tolist() == [1, 0]


# (d) the host loop
def test_the_host_loop_truncates_a_two_token_step_at_the_budget_and_refills_slots():
    """Budgets of 1, 2 and odd lengths on two slots: every answer is its budget, drafts stand inside it, a refilled
    slot starts afresh (its first token from its own prefill), and the MTP block's prefilled rows are counted."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params = program(0)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 128, n).astype(np.int32) for n in (9, 30, 4, 17, 9)]
    prompts[4] = prompts[0].copy()
    budgets = [1, 2, 7, 4, 6]
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=64, temperature=1.0, eos_id=None, prefill_chunk=T)
    began = span_clock_ns()
    out = b.run([Request(tokens=p, max_new_tokens=m) for p, m in zip(prompts, budgets)])
    assert [len(o) for o in out] == budgets == [len(lp) for lp in b.last_logprobs]
    assert all(all(1 <= j < len(o) for j, _, _ in dr) for o, dr in zip(out, b.last_drafts))
    assert not b.active.any() and all(s.request is None for s in b.slots)
    steps = [s for s in recent_device_spans() if s.start_ns >= began and s.name == "serve.decode_step"]
    assert all(s.count["forwards"] == 2 and s.count["verify_positions"] == 2 * s.count["active"] == 2 * s.count["drafted"]
               and s.count["drafted"] >= s.count["accepted"] >= 0 and s.count["mla"] == "absorbed" for s in steps)
    emitted = sum(s.count["tokens"] for s in steps)
    assert emitted >= sum(budgets) and emitted <= sum(budgets) + len(budgets)  # a 2-token step cut at its budget
    prefills = [s for s in recent_device_spans() if s.start_ns >= began and s.name == "serve.prefill"]
    assert sum(s.count["mtp_positions"] for s in prefills) == sum(len(p) - 1 for p in prompts)  # the twin, a round later, too



def test_on_a_tpu_the_draft_and_the_verify_take_the_absorbed_kernel(monkeypatch):
    """The backend rule answers as on a TPU and the absorbed kernel runs interpreted, at 8 heads over a latent of one
    lane tile (a slot's two queries are a sublane tile of rows) and slots of 512 positions: the speculative program's
    MTP attention and its three main ones are the kernel's (the prefill's chunk of 16 is no lane tile: XLA's loop),
    every step's span says ``absorbed_fused``, and at temperature 0 the answers are XLA's path's."""
    from daft_tpu.ops import pallas_attention, pallas_mla_attention as pm
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params = program(3, num_attention_heads=8, kv_lora_rank=128, qk_rope_head_dim=16)
    prompts = prompts_of(3, (37, 5, 70))

    def run():
        b = ContinuousBatcher(model, params, num_slots=3, max_seq_len=512, temperature=0.0, eos_id=None, prefill_chunk=T)
        return b, b.run([Request(tokens=p, max_new_tokens=6) for p in prompts])

    calls, real = [], pm.mla_absorbed_attention
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pm, "mla_absorbed_attention",
                        lambda *a, **k: calls.append(a[0].shape) or real(*a, interpret=True, **k))
    began = span_clock_ns()
    fused, out = run()
    steps = [s for s in recent_device_spans() if s.start_ns >= began and s.name == "serve.decode_step"]
    assert steps and all(s.count["mla"] == "absorbed_fused" for s in steps)
    assert calls == [(3, 2, 8, 128 + 16)] * 4 and fused._noted["serve.prefill"]["mla"] == "expanded"
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: False)
    xla, greedy = run()
    assert greedy == out and xla._noted["serve.decode_step"]["mla"] == "absorbed" and len(calls) == 4

def test_a_rejected_drafts_row_is_overwritten_before_it_is_read(ref):
    """At temperature 1 with a drafting block whose logits are 30 times as wide (each draft its own argmax, which p
    gives a few percent), nearly every draft is rejected: the rows verified for the drafts are each overwritten by
    the next step, and every emitted token's log p is still the reference's."""
    seed = 6
    model, params = program(seed, float32=True)
    params = dict(params, mtp=dict(params["mtp"], shared_head_norm=30 * params["mtp"]["shared_head_norm"]))
    prompts = prompts_of(seed, (20, 33))
    b, out = serve(model, params, prompts, new=10)
    rejected = sum(o[j] != d for o, dr in zip(out, b.last_drafts) for j, d, _ in dr)
    assert rejected >= 10
    main_gap = 0.0
    for p, o, lp in zip(prompts, out, b.last_logprobs):
        main, _ = ref.forward(ref_config(), seed, np.concatenate([p, o]).astype(np.int32), logits_from=len(p) - 1)
        main_gap = max(main_gap, float(np.max(np.abs(_log_softmax(main)[np.arange(len(o)), o] - np.asarray(lp)))))
    assert main_gap <= F32_GAP_MAX


# (e) the share and the router
def test_the_shares_parts_add_up_to_the_uncut_expert_layer(ref):
    """16 experts in 16 shares of one each: the held experts' parts, with the shared expert's (which every chip
    computes alike) counted once, add up to the uncut reference's expert layer."""
    seed, layer, E = 0, 1, 16
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(24, 64)), jnp.float32)
    valid = jnp.ones((24,), bool)
    whole, params = program(seed, size=1, n_routed_experts=E)
    p = params["layers"][layer]
    shared = decoders.gated_mlp(u.astype(jnp.bfloat16), p["shared_in"], p["shared_out"], jnp.bfloat16)
    total = 0.0
    for rank in range(E):  # an expert's weights come from its global id, whoever holds it
        cfg = dataclasses.replace(whole.cfg, expert_shard=(rank, E))
        y, counts = pg._moe(cfg, dict(p, w_in=p["w_in"][rank:rank + 1], w_out=p["w_out"][rank:rank + 1]), u, valid)
        assert int(counts["assignments"]) == 24 * 3 and cfg.first_expert == rank and cfg.held_experts == 1
        total = total + (y - shared)
    rcfg = ref_config(size=1, n_routed_experts=E)
    want = ref.expert_layer(rcfg, seed, layer, u, (0, E))
    assert float(np.max(np.abs(np.asarray(total + shared) - want))) <= 0.05 and float(np.std(want)) > 0.3
    assert float(np.max(np.abs(want - ref.expert_layer(rcfg, seed, layer, u, (0, E), shared=False)))) > 0.3


def test_route_with_one_group_is_the_plain_top_8_over_all_256():
    cfg = pg.PanguUltraMoeConfig.from_name("openPangu-Ultra-MoE-718B", num_layers=2)
    assert (cfg.n_group, cfg.topk_group, cfg.n_routed_experts, cfg.num_experts_per_tok) == (1, 1, 256, 8)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(size=(32, 256)) / 6, jnp.float32),
         "router_bias": jnp.asarray(rng.normal(size=256) * 0.05, jnp.float32)}
    idx, w = (np.asarray(a) for a in ds.route(cfg, p, u))
    s = np.asarray(jax.nn.sigmoid(u @ p["router"]), np.float64)
    want = np.argsort(-(s + np.asarray(p["router_bias"])), axis=-1, kind="stable")[:, :8]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    chosen = np.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # the same router limited to groups chooses otherwise: the one group is no group limit
    grouped = dataclasses.replace(cfg, n_group=8, topk_group=4)
    assert not np.array_equal(np.sort(np.asarray(ds.route(grouped, p, u)[0]), -1), np.sort(idx, -1))


# (f) the record, the protocol and prompt
def test_names_options_and_the_protocol():
    d = decoders.DECODERS["openPangu-Ultra-MoE-718B"]
    assert d is decoders.DECODERS[TINY] and d.cut_options == pg.CUT_OPTIONS and not d.blocks
    assert d.model.draft_length == 1 and d.model.block_length is None
    cfg = d.from_name("openPangu-Ultra-MoE-718B", num_layers=5, expert_shard=(0, 16), vocab_shard=(0, 8))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.held_experts, cfg.held_vocab) == (5, 1, 16, 19200)
    assert cfg.softmax_scale == 192 ** -0.5 and cfg.cache_row == 576 and cfg.rope_theta == 25600000.0
    with pytest.raises(DaftValueError, match="outside the published"):
        d.from_name("openPangu-Ultra-MoE-718B", num_layers=62)
    with pytest.raises(DaftValueError, match="does not divide"):
        d.from_name("openPangu-Ultra-MoE-718B", expert_shard=(0, 7))
    with pytest.raises(DaftValueError, match="unknown openPangu"):
        pg.PanguUltraMoeConfig.from_name("openPangu-Ultra-MoE-72B")
    model = d.model(cfg)
    state = jax.eval_shape(lambda: model.init_state(64, 2305))
    assert [leaf["kv"].shape for leaf in state["layers"] + [state["mtp"]]] == [(64, 576, 2432)] * 6
    assert state["hidden"].shape == (64, 2, 7680) and state["hidden"].dtype == jnp.float32


def test_prompt_runs_the_speculative_decoder_through_the_normal_path():
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt

    df = daft_tpu.from_pydict({"doc": ["a b c d e f", "x y", "one two three four five six seven eight nine ten"]})
    opts = dict(provider="flax_random", model=TINY, batch_size=3, seed=1, max_new_tokens=7, ignore_eos=True,
                temperature=1.0, num_slots=2, max_prompt_tokens=16, expert_shard=[0, 2], vocab_shard=[0, 2], num_layers=3)
    rows = df.with_column("a", prompt(col("doc"), logprobs=True, **opts)).to_pydict()["a"]
    for r in rows:
        assert len(r["token_ids"]) == len(r["logprobs"]) == 7 and all(0 <= t < 128 for t in r["token_ids"])
        assert len(r["draft_ids"]) == len(r["draft_positions"]) == len(r["draft_logprobs"]) >= 3
        assert r["draft_positions"] == sorted(set(r["draft_positions"])) and 1 <= r["draft_positions"][0]
    plain = df.with_column("a", prompt(col("doc"), **opts)).to_pydict()["a"]
    assert all(len(t.split()) == 7 for t in plain)
    # A decoder that drafts nothing adds no draft fields.
    other = df.with_column("a", prompt(col("doc"), provider="flax_random", model="granite-hybrid-tiny", logprobs=True,
                                       max_new_tokens=2)).to_pydict()["a"]
    assert not any(k.startswith("draft_") for k in other[0])


# (g) the cell's programs for a described v5e
def test_the_cells_programs_lower_and_fit_for_a_described_v5e(monkeypatch):
    """The prefill chunk and the speculative step at the cell's sizes (64 slots, 2,305 positions held in 2,432),
    compiled for a described v5e without the chip, as the chip traces them (``backend_is_tpu`` patched: the prefill
    takes ``ops/pallas_mla_attention.py`` and ``ops/pallas_grouped_matmul.py``). Read first, the new caches'
    ``entry_computation_layout``: ``bf16[64,576,2432]{2,1,0:T(8,128)(2,1)}`` a latent leaf (positions minor, 576
    values a tile of 8 apart: no axis padded, no copy around a product) and ``f32[64,2,7680]{2,1,0:T(2,128)}`` the
    kept hidden states. Compiled: 13.16 GB of arguments for the step (11.17 for the prefill, which reads none of the
    MTP block's expert layer), 0.40 / 0.63 GB of temporaries, the slot state aliased in and out."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from daft_tpu.ops import pallas_attention

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no described v5e here: {e}")
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    config = dict(manifest.load_json(os.path.join(BENCH, "configs", "pangu_ultra_moe_718b_prompt.json")))
    entry = manifest.load_module(os.path.join(BENCH, "entries", "prompt_speculative.py"))
    one = SingleDeviceSharding(topo.devices[0])
    state_bytes = 6 * 64 * 576 * 2432 * 2
    for name, (fn, shapes) in entry.lowerables(config).items():
        shapes = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), shapes)
        compiled = fn.lower(*shapes).compile()
        mem = compiled.memory_analysis()
        layout = compiled.as_text().split("entry_computation_layout=")[1].split("\n")[0]
        assert "bf16[64,576,2432]{2,1,0:T(8,128)(2,1)}" in layout and "f32[64,2,7680]{2,1,0:T(2,128)}" in layout
        assert mem.alias_size_in_bytes >= state_bytes, name
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert total < 14.5e9 and mem.temp_size_in_bytes < 1.0e9, (name, total, mem.temp_size_in_bytes)
