"""The SDAR-30B-A3B-Chat decoder (``models/sdar_moe.py``: QK-normed GQA with RoPE,
128 experts with top-8 renormalised gates, generation by diffusion over blocks)
against its plain reference (``benchmark/reference/sdar_moe.py``), through the
continuous batcher's block step, at a small size on the CPU: hidden 64, 4
layers, 8 experts top 2, 4 query and 2 key/value heads of 16, vocabulary 512,
blocks of 4, prefill chunks of 8.

Tolerances. **In float32 the program is the reference** to summation order
(``F32_GAP_MAX`` 1e-4; read ~1e-6): the block-causal chunked prefill, the cache,
every pass of every block step, the commit, the choice of tokens and of
positions, all replayed by ``forward_denoise``. **In bfloat16, as served**, the
logits of every replayed pass lie within ``LOGIT_GAP_MAX`` of the reference's and
the fp8 control's do not (read: the program's largest 0.013 to 0.077 over seeds
0-3, the control's median 0.20 to 0.22; the tiny size draws its experts' W2 at a
tenth of fan-in scale as the published one does: at full scale a router choice
of 2 of 8 weighs ~1/2 and flips on rounding at 0.7-0.9 of a logit).
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
from daft_tpu.models import decoders, granite_hybrid, sdar_moe as sd  # noqa: E402,F401  (granite enters its names)
from daft_tpu.models.serving import ContinuousBatcher, Request  # noqa: E402

TINY = "sdar-moe-tiny"
#: |logits - reference's| of the float32 program: summation order alone. Read ~1e-6.
F32_GAP_MAX = 1e-4
#: |logits - reference's| of the bfloat16 program at any position of a replayed pass (logits spread ~1).
LOGIT_GAP_MAX = 0.11
CHUNK = 8


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "sdar_moe.py"))


def ref_config(**sizes) -> dict:
    return dict(sd.TEST_SIZES[TINY], embedding_std=sd.EMBED_STD, expert_gain=sd.EXPERT_GAIN, **sizes)


def program(seed: int, float32: bool = False, **sizes):
    cfg = dataclasses.replace(sd.SdarMoeConfig.from_name(TINY), **sizes)
    model, params = sd.init_sdar_params(cfg, seed)
    if float32:  # the same bfloat16 values, every product and the cache in float32
        model = sd.SdarMoeLM(dataclasses.replace(cfg, dtype=jnp.float32))
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return model, params


def serve(model, params, prompts, news, slots: int = 3):
    b = ContinuousBatcher(model, params, num_slots=slots, max_seq_len=90, eos_id=None, prefill_chunk=CHUNK,
                          max_prompt_tokens=48)
    out = b.run([Request(tokens=p, max_new_tokens=m) for p, m in zip(prompts, news)])
    return out, b.last_logprobs, b.last_unmask_steps


def regrets(ref_out, answers, logprobs, steps, k: int = 1):
    """The largest |log c - reference's|, reference's (max logit - chosen logit) and (k-th best log c among the
    masked - chosen position's) over every token of every replayed pass, and the tokens compared."""
    gap = greedy = order = 0.0
    compared = 0
    for r, logits, ans, lp, st in zip(ref_out["replay"], ref_out["f32"], answers, logprobs, steps):
        for g, p in zip(*np.nonzero(r["replayable"])):
            here = np.flatnonzero((r["index"][g] >= 0) & (np.asarray(st)[np.clip(r["index"][g], 0, None)] == p))
            if not len(here):  # a first block's pass after its few masked positions were fixed
                continue
            x = logits[g, p].astype(np.float64)
            ls = x - x.max(-1, keepdims=True)
            ls = ls - np.log(np.exp(ls).sum(-1, keepdims=True))
            conf = ls.max(-1)
            kth = np.sort(conf[r["masked"][g, p]])[::-1][min(k, r["masked"][g, p].sum()) - 1]
            tok = np.asarray(ans)[r["index"][g, here]]
            gap = max(gap, float(np.max(np.abs(ls[here, tok] - np.asarray(lp)[r["index"][g, here]]), initial=0)))
            greedy = max(greedy, float(np.max(x[here].max(-1) - x[here, tok], initial=0)))
            order = max(order, float(np.max(kth - conf[here], initial=0)))
            compared += len(here)
    return gap, greedy, order, compared


#: Prompts of every length mod 4 and shorter than a block; more requests than slots and answers of unlike lengths,
#: so that slots retire mid-wave and are refilled while others decode.
LENGTHS = (3, 17, 9, 22, 12, 2, 31, 4)
NEWS = (9, 12, 8, 5, 16, 7, 10, 6)


def _prompts(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 500, n).astype(np.int32) for n in LENGTHS]


def test_reference_draws_the_programs_weights(ref):
    model, params = program(3)
    cfg = ref_config()
    for i in (0, 3):
        w, p = ref.layer_weights(cfg, 3, i), params["layers"][i]
        assert np.array_equal(np.asarray(p["qkv"], np.float32), np.concatenate([w["q"], w["k"], w["v"]], 1))
        for name in ("attn_norm", "q_norm", "k_norm", "o", "moe_norm", "router", "w_in", "w_out"):
            assert np.array_equal(np.asarray(p[name], np.float32), np.asarray(w[name])), name
    emb, final_norm, head = ref.embedding(cfg, 3)
    assert np.array_equal(np.asarray(params["embed"], np.float32), np.asarray(emb))
    assert np.array_equal(np.asarray(params["head"], np.float32), np.asarray(head))
    assert np.array_equal(np.asarray(params["final_norm"], np.float32), np.asarray(final_norm))
    # the experts' W2 at a tenth of fan-in scale (the drawing rule both state)
    assert float(np.std(np.asarray(params["layers"][0]["w_out"], np.float32))) == pytest.approx(0.1 * 24 ** -0.5, rel=0.1)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_in_float32_the_batchers_block_steps_are_the_references_replay(ref, seed):
    """Every pass of every block, each prompt's tail in its first block, through the batcher: the program fixed the
    reference's argmax at the reference's surest masked position, with the reference's log c."""
    prompts = _prompts(seed)
    answers, logprobs, steps = serve(*program(seed, float32=True), prompts, NEWS)
    assert [len(a) for a in answers] == list(NEWS) and [len(s) for s in steps] == list(NEWS)
    got = ref.forward_denoise(ref_config(), seed, prompts, answers, steps)
    gap, greedy, order, compared = regrets(got, answers, logprobs, steps)
    assert gap < F32_GAP_MAX and greedy < F32_GAP_MAX and order < F32_GAP_MAX
    assert compared >= sum(NEWS) - 3 * len(NEWS)  # only an answer's cut last block goes unreplayed
    for p, st in zip(prompts, steps):  # one position a pass; the first block holds 4 - P mod 4 of them
        first = 4 - len(p) % 4
        assert sorted(st[:first]) == list(range(first)) and all(sorted(st[i:i + 4]) == [0, 1, 2, 3]
                                                                for i in range(first, len(st) - 3, 4))


@pytest.fixture
def block_kernel(monkeypatch):
    """The block step takes ``ops/pallas_cache_attention.py``'s block mode, run interpreted, whatever the backend,
    dtype and widths (the tiny head of 16, 4 x 2 queries a head); the prefill keeps its path. ``calls`` keeps the
    shape of q at each call traced."""
    from daft_tpu.ops import pallas_cache_attention as pca

    calls = []
    attend = pca.cache_attention

    def interpreted(q, *rest, scale, own):
        calls.append(q.shape)
        return attend(q, *rest, scale=scale, own=own, interpret=True)

    monkeypatch.setattr(pca, "cache_attention_applies", lambda *a, own=False: own)
    monkeypatch.setattr(pca, "cache_attention", interpreted)
    return calls


def test_in_float32_the_block_kernels_steps_are_the_references_replay(ref, block_kernel):
    """The float32 replay above with the block step's core in the kernel: the same regrets (summation order), and
    every ``serve.decode_step`` span says ``fused``."""
    from daft_tpu.profiling import recent_device_spans

    seed = 1
    prompts = _prompts(seed)
    answers, logprobs, steps = serve(*program(seed, float32=True), prompts, NEWS)
    assert block_kernel and all(shape[1:] == (4, 2, 2, 16) for shape in block_kernel)
    got = ref.forward_denoise(ref_config(), seed, prompts, answers, steps)
    gap, greedy, order, compared = regrets(got, answers, logprobs, steps)
    assert gap < F32_GAP_MAX and greedy < F32_GAP_MAX and order < F32_GAP_MAX
    assert compared >= sum(NEWS) - 3 * len(NEWS)
    decode = [s.count for s in recent_device_spans() if s.name == "serve.decode_step"]
    assert decode and all(c["attn"] == "fused" for c in decode[-10:])


def test_on_a_tpu_the_published_widths_take_the_block_kernel_and_the_tiny_ones_do_not(monkeypatch):
    """The block step's attention traced with the backend rule answering as on a TPU (shapes only): at the
    published widths (4 key/value heads of 128, 8 queries each, 64 slots of 2,320 positions) it calls the kernel
    with the caches as the state holds them; at the tiny widths (heads of 16) it keeps ``decoders.attention_block``."""
    from daft_tpu.ops import pallas_attention, pallas_cache_attention as pca

    calls = []
    attend = pca.cache_attention
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pca, "cache_attention", lambda q, k, *rest, **kw: calls.append((q.shape, k.shape)) or attend(q, k, *rest, **kw))
    for name, slots, positions in (("SDAR-30B-A3B-Chat", 64, 2305), (TINY, 3, 90)):
        cfg = sd.SdarMoeConfig.from_name(name, num_layers=1)
        layer = jax.eval_shape(lambda: sd._init_layer(cfg, jax.random.PRNGKey(0)))
        st = jax.eval_shape(lambda: sd.SdarMoeLM(cfg).init_state(slots, positions))[0]
        u = jax.ShapeDtypeStruct((slots, cfg.block_length, cfg.hidden_size), cfg.dtype)
        ints, flags = jax.ShapeDtypeStruct((slots,), jnp.int32), jax.ShapeDtypeStruct((slots,), bool)
        for commit in (False, True):
            out, _ = jax.eval_shape(lambda p, u, st, at, act: sd._attn_block(cfg, p, u, st, at, act, commit), layer, u, st, ints, flags)
            assert out.shape == (slots, cfg.block_length, cfg.hidden_size)
    assert calls == [((64, 4, 4, 8, 128), (64, 4, 2320, 128))] * 2           # a pass and the commit; the tiny model none


def _teacher_forced(model, params, ref_out, prompt, answer, row=0):
    """Every pass's logits of one row from the model's own prefill and ``block_forward``, fed the replay's inputs,
    with a commit after each block. -> [(logits (L, V), reference's), ...] over the replayable passes."""
    r = ref_out["replay"][row]
    L, P = model.block_length, len(prompt)
    state = model.init_state(1, 96)
    fill = P - P % L
    prefill, fwd = jax.jit(model.prefill), jax.jit(model.block_forward, static_argnames="commit")
    for c in range(0, fill, CHUNK):
        part = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, fill - c)
        part[0, :n] = prompt[c:c + n]
        state, _, _ = prefill(params, state, part, jnp.zeros(1, jnp.int32), jnp.full(1, c, jnp.int32), jnp.full(1, n, jnp.int32))
    seq = np.concatenate([prompt, answer])
    out = []
    for g in range(r["tokens"].shape[0]):
        start = (r["first_block"] + g) * L
        for p in range(r["tokens"].shape[1]):
            state, logits, _ = fwd(params, state, r["tokens"][g, p][None], jnp.full(1, start, jnp.int32), jnp.ones(1, bool))
            if r["replayable"][g, p]:
                out.append((np.asarray(logits[0]), ref_out["f32"][row][g, p],
                            ref_out["fp8"][row][g, p] if "fp8" in ref_out else None))
        block = seq[np.clip(start + np.arange(L), 0, len(seq) - 1)].astype(np.int32)
        state, _, _ = fwd(params, state, block[None], jnp.full(1, start, jnp.int32), jnp.ones(1, bool), commit=True)
    return out


def _replayed(seed):
    rng = np.random.default_rng(seed)
    prompt, answer = rng.integers(0, 500, 21).astype(np.int32), rng.integers(0, 500, 24).astype(np.int32)
    steps = np.concatenate([[0, 1, 2], np.tile([2, 0, 3, 1], 6)])[:24]
    return prompt, answer, steps


@pytest.mark.parametrize("seed", [0, 1])
def test_in_float32_every_pass_and_commit_gives_the_references_logits(ref, seed):
    prompt, answer, steps = _replayed(seed)
    got = ref.forward_denoise(ref_config(), seed, [prompt], [answer], [steps])
    pairs = _teacher_forced(*program(seed, float32=True), got, prompt, answer)
    assert len(pairs) >= 6 * 4  # the tail's block, five whole blocks, what of the cut last one can be replayed
    assert max(float(np.abs(a - b).max()) for a, b, _ in pairs) < F32_GAP_MAX


@pytest.mark.parametrize("seed", [0, 3])
def test_in_bfloat16_the_logits_agree_and_the_controls_do_not(ref, seed):
    prompt, answer, steps = _replayed(seed)
    got = ref.forward_denoise(ref_config(), seed, [prompt], [answer], [steps], precisions=("f32", "fp8"))
    pairs = _teacher_forced(*program(seed), got, prompt, answer)
    mine = np.concatenate([np.abs(a - b).max(-1) for a, b, _ in pairs])
    control = np.concatenate([np.abs(c - b).max(-1) for _, b, c in pairs])
    assert mine.max() < LOGIT_GAP_MAX < np.median(control)
    assert np.mean(control > LOGIT_GAP_MAX) > 0.9


def test_two_passes_fix_two_positions_each(ref):
    model, params = program(1, float32=True, denoising_steps=2)
    prompts = [p for p in _prompts(1) if len(p) % 4 == 0] + [np.arange(5, 13, dtype=np.int32)]
    answers, logprobs, steps = serve(model, params, prompts, [8] * len(prompts))
    for st in steps:
        assert [sorted(st[i:i + 4]) for i in (0, 4)] == [[0, 0, 1, 1]] * 2
    got = ref.forward_denoise(ref_config(denoising_steps=2), 1, prompts, answers, steps)
    gap, greedy, order, _ = regrets(got, answers, logprobs, steps, k=2)
    assert gap < F32_GAP_MAX and greedy < F32_GAP_MAX and order < F32_GAP_MAX


def test_identical_prompts_share_a_prefill_and_their_block(ref):
    from daft_tpu.profiling import recent_device_spans

    model, params = program(2)
    rng = np.random.default_rng(2)
    same = rng.integers(0, 500, 14).astype(np.int32)  # a tail of 2 in the first block: host state the copy shares
    prompts = [same, rng.integers(0, 500, 9).astype(np.int32), same.copy()]
    answers, logprobs, steps = serve(model, params, prompts, [6, 6, 6])
    assert np.array_equal(answers[0], answers[2]) and np.array_equal(steps[0], steps[2]) and logprobs[0] == logprobs[2]
    assert any(s.name == "serve.copy_state" for s in recent_device_spans()[-12:])


def test_the_spans_carry_the_block_steps_counters_and_the_note():
    from daft_tpu.profiling import recent_device_spans

    model, params = program(0)
    b = ContinuousBatcher(model, params, num_slots=3, max_seq_len=90, eos_id=None, prefill_chunk=CHUNK, max_prompt_tokens=48)
    lengths = (3, 17, 9, 22)
    b.run([Request(tokens=np.arange(1, n + 1, dtype=np.int32), max_new_tokens=6) for n in lengths])
    spans = recent_device_spans()
    rounds = [s.count for s in spans if s.name == "serve.prefill"][-2:]
    assert sum(r["tail_tokens"] for r in rounds) == sum(n % 4 for n in lengths)
    assert sum(r["tokens"] for r in rounds) == sum(n - n % 4 for n in lengths)
    steps = [s.count for s in spans if s.name == "serve.decode_step"][-b.decode_steps:]
    assert all(s["tokens"] == 4 * s["active"] and s["forwards"] == 5 and s["block"] == 4 and s["unmask"] == "static"
               and s["attn"] == "xla" for s in steps)                   # the CPU's block step: decoders.attention_block
    assert all(s["active"] <= s["masked"] <= 4 * s["active"] and s["keys"] >= 4 * s["active"] for s in steps)
    assert sum(s["masked"] for s in steps) >= 6 * len(lengths)  # every answer token was decided by a pass
    assert all(s["moe.assignments"] == s["active"] * 4 * 5 * 2 * 4 for s in steps)  # 4 positions, 5 forwards, top 2, 4 layers
    assert b.cur_logits is None and b.positions.dtype == np.int32


def test_names_options_and_the_record():
    cfg = sd.SdarMoeConfig.from_name("SDAR-30B-A3B-Chat", num_layers=6)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.num_experts, cfg.moe_intermediate_size, cfg.num_experts_per_tok, cfg.vocab_size) == \
        (6, 2048, 32, 4, 128, 128, 768, 8, 151936)
    assert (cfg.block_length, cfg.denoising_steps, cfg.rope_theta, cfg.rms_norm_eps) == (4, 4, 1e6, 1e-6)
    assert decoders.DECODERS["SDAR-30B-A3B-Chat"].blocks and not decoders.DECODERS["granite-4.0-h-small"].blocks
    with pytest.raises(DaftValueError, match="outside"):
        sd.SdarMoeConfig.from_name(TINY, num_layers=5)
    with pytest.raises(DaftValueError, match="unknown SDAR"):
        sd.SdarMoeConfig.from_name("SDAR-8B-Chat")
    model, params = program(0)
    with pytest.raises(DaftValueError, match="greedily"):
        ContinuousBatcher(model, params, num_slots=2, max_seq_len=40, temperature=0.7)
    # a slot's rows in whole tiles of 16 positions: 64 slots x 2,305 positions at the published sizes hold 2,320
    assert sd.SdarMoeLM(cfg).init_state(2, 2305)[0]["k"].shape == (2, 4, 2320, 128)


def test_prompt_finds_the_decoder_by_name_and_returns_the_passes():
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt

    df = daft_tpu.from_pydict({"doc": ["a b c d e f g h i j", "k l", "m n o p q r s"]})
    expr = prompt(col("doc"), provider="flax_random", model=TINY, num_layers=4, max_new_tokens=7, num_slots=2,
                  max_prompt_tokens=24, ignore_eos=True, logprobs=True, seed=1)
    rows = df.with_column("answer", expr).select("answer").to_pydict()["answer"]
    assert set(rows[0]) == {"text", "token_ids", "logprobs", "unmask_steps"}
    for row, n in zip(rows, (10, 2, 7)):  # 7 tokens each; a first block of 4 - P mod 4
        assert len(row["token_ids"]) == len(row["unmask_steps"]) == len(row["logprobs"]) == 7
        first = 4 - n % 4
        assert sorted(row["unmask_steps"][:first]) == list(range(first))
        assert all(np.isfinite(row["logprobs"])) and max(row["token_ids"]) < 512
    one_token = prompt(col("doc"), provider="flax_random", model="granite-hybrid-tiny", max_new_tokens=2, num_slots=2,
                       max_prompt_tokens=24, ignore_eos=True, logprobs=True)
    got = df.with_column("answer", one_token).select("answer").to_pydict()["answer"]
    assert set(got[0]) == {"text", "token_ids", "logprobs"}


def test_an_end_id_in_a_block_ends_the_answer_there():
    """Without ``ignore_eos`` a block that holds the end id ends its answer after it (the rest of the block is
    dropped) and frees the slot; the other answers are what they were."""
    model, params = program(4)
    prompts = _prompts(4)[:4]
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=90, eos_id=None, prefill_chunk=CHUNK, max_prompt_tokens=48)
    free = b.run([Request(tokens=p, max_new_tokens=12) for p in prompts])
    end = free[1][6]  # a token of the second answer's second or third block
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=90, eos_id=end, prefill_chunk=CHUNK, max_prompt_tokens=48)
    got = b.run([Request(tokens=p, max_new_tokens=12) for p in prompts])
    for g, f, steps in zip(got, free, b.last_unmask_steps):
        cut = f.index(end) + 1 if end in f else len(f)
        assert g == f[:cut] and len(steps) == len(g)
