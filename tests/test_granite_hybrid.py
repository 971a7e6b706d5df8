"""The hybrid decoder (``models/granite_hybrid.py``: Mamba-2, attention without
positions, a sharded expert layer) against its plain reference
(``benchmark/reference/granite_hybrid.py``), through the continuous batcher and
through ``prompt``, at a small size on the CPU: widths in the published ratios,
four layers m-m-a-m, 8 experts top-3, two shares.

Tolerances. The program computes in bfloat16 with float32 accumulation and
state, the reference in float32: a bfloat16 product is off by 2**-9 of its
operands, and through four layers that reads 1-2% of a logit's spread (0.001
here, by the embedding's scale). The limits below sit three to five times above
the largest reading over the seeds tried (in each comment), and the control
(the reference with every matrix product's operands in float8_e4m3, one step
below bfloat16, put in the program's place) has to break them.
"""

from __future__ import annotations

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
from daft_tpu.models import granite_hybrid as gh  # noqa: E402
from daft_tpu.models.serving import ContinuousBatcher, Request  # noqa: E402

TINY = "granite-hybrid-tiny"
#: |program log-probability - reference's| of a chosen token. Readings over seeds 0-4 and 2**31 + 5 (48 tokens
#: each): 4.3e-5 to 3.4e-4, the fp8 control 4.1e-4 to 7.8e-4; at width 64 one swapped expert (a router tie) moves
#: a logit by the whole limit, so the tests use seeds whose readings lie clear of it (0 and 3: 9.3e-5, 8.4e-5).
LOGPROB_GAP_MAX = 3e-4
#: |logits - reference's| after a chunked prefill, and chunked against whole. Largest readings 9e-5 and 6e-5.
LOGIT_GAP_MAX = 3e-4


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "granite_hybrid.py"))


def ref_config(rank: int = 0, size: int = 2) -> dict:
    """The tiny model as the reference reads a configuration file: sizes as run."""
    p = gh.TEST_SIZES[TINY]
    return dict(p, layer_types=list(p["layer_types"]), router_outputs=p["num_local_experts"],
                num_local_experts=p["num_local_experts"] // size, vocab_size=p["vocab_size"] // size,
                embedding_std=gh.EMBED_STD, options={"expert_shard": [rank, size], "vocab_shard": [rank, size]})


def program(seed: int, rank: int = 0, size: int = 2):
    cfg = gh.GraniteHybridConfig.from_name(TINY, expert_shard=(rank, size), vocab_shard=(rank, size))
    return gh.init_granite_params(cfg, seed)


def prompts(seed: int, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 128, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_draws_the_programs_weights(ref, seed):
    _, params = program(seed, rank=1)
    rcfg = ref_config(rank=1)
    experts, vocab = ref.share(rcfg)
    for i, layer in enumerate(params["layers"]):
        want = ref.layer_weights(rcfg, seed, i, experts)
        assert set(want) == set(layer)
        for name, w in want.items():
            assert layer[name].dtype == jnp.bfloat16  # no float32 copy of the tree
            assert float(jnp.max(jnp.abs(w - layer[name].astype(jnp.float32)))) == 0.0, (i, name)
    emb, final_norm = ref.embedding(rcfg, seed, vocab)
    assert float(jnp.max(jnp.abs(emb - params["embed"].astype(jnp.float32)))) == 0.0
    assert float(jnp.max(jnp.abs(final_norm - params["final_norm"].astype(jnp.float32)))) == 0.0


# (a) prefill then decode through the batcher, against the reference's full forward
def _served(seed, lengths=(5, 17, 33, 40, 9, 20), new=8, **kw):
    model, params = program(seed)
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=64, eos_id=None,
                          **dict(dict(prefill_chunk=16), **kw))
    reqs = [Request(tokens=t, max_new_tokens=new) for t in prompts(seed, lengths)]
    out = b.run(reqs)
    return reqs, out, b.last_logprobs


def _teacher_forced(ref, seed, reqs, out, precision="f32"):
    """The reference's log-probability of each chosen token and its regret, on the program's own tokens."""
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
    # greedy: the program chose the reference's argmax or a near tie (logits spread 0.001)
    assert max(float(r.max()) for r in regret) <= LOGPROB_GAP_MAX
    # the control, one precision step down, in the program's place: not within the limit
    low, _ = _teacher_forced(ref, seed, reqs, out, precision="fp8")
    gap_fp8 = max(float(np.max(np.abs(l - w))) for l, w in zip(low, want))
    assert gap_fp8 > 1.4 * LOGPROB_GAP_MAX, gap_fp8


# (b) a prompt prefilled in chunks with a ragged last chunk equals the same prompt in one piece
@pytest.mark.parametrize("length", [37, 48, 5])
def test_chunked_prefill_equals_whole_prefill_state_and_logits(ref, length):
    seed = 1
    model, params = program(seed)
    tokens = prompts(seed, [length])[0]

    def prefilled(chunk):
        state = model.init_state(2, 64)
        # slot 1 holds junk from an earlier request: a first chunk must not see it
        state = jax.tree_util.tree_map(lambda a: a.at[1].set(jnp.ones_like(a[1])), state)
        logits = None
        for c in range(-(-length // chunk)):
            part = np.zeros((1, chunk), np.int32)
            piece = tokens[c * chunk:(c + 1) * chunk]
            part[0, :len(piece)] = piece
            state, logits, _ = jax.jit(model.prefill)(
                params, state, part, np.array([1], np.int32), np.array([c * chunk], np.int32),
                np.array([len(piece)], np.int32))
        return state, np.asarray(logits[0])

    whole_state, whole = prefilled(64)
    chunked_state, chunked = prefilled(16)
    assert np.max(np.abs(whole - chunked)) <= LOGIT_GAP_MAX
    want = ref.forward(ref_config(), seed, tokens, logits_from=length - 1)[0]
    assert np.max(np.abs(chunked - want)) <= LOGIT_GAP_MAX
    for a, b, kind in zip(whole_state, chunked_state, model.cfg.layer_types):
        if kind == "mamba":  # recurrent state where the prompt ended, not where the padding ended
            assert float(jnp.max(jnp.abs(a["ssm"][1] - b["ssm"][1]))) <= 2e-2 * float(jnp.max(jnp.abs(a["ssm"][1])))
            assert float(jnp.max(jnp.abs(a["conv"][1].astype(jnp.float32) - b["conv"][1].astype(jnp.float32)))) <= 2e-2
        else:  # key/value rows of the prompt written, rows past its end left as they were
            rows = slice(0, length)
            assert float(jnp.max(jnp.abs(a["k"][1, rows].astype(jnp.float32)
                                         - b["k"][1, rows].astype(jnp.float32)))) <= 2e-2
            assert bool(jnp.all(b["k"][1, length:] == 1)) and bool(jnp.all(b["v"][1, length:] == 1))
        # slot 0 was no part of the call
        assert all(bool(jnp.all(x[0] == 0)) for x in jax.tree_util.tree_leaves(b))


# (c) the recurrence against the chunked scan
def test_recurrence_equals_chunked_scan():
    rng = np.random.default_rng(0)
    B, T, H, P, N = 2, 24, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(B, T, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (B, T, H)), jnp.float32)
    dt = dt.at[1, 19:].set(0.0)  # right padding: the state stays where row 1's prompt ended
    a = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32)
    y_chunked, s_chunked = gh.ssd_chunked(x, dt, a, b, c, s0, chunk=8)
    s, ys, s_at_19 = s0, [], None
    for t in range(T):
        if t == 19:
            s_at_19 = s
        y, s = gh.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], s)
        ys.append(y)
    y_steps = jnp.stack(ys, 1)
    # float32 throughout: the two orders of summation differ by rounding alone
    assert float(jnp.max(jnp.abs(y_chunked - y_steps))) <= 1e-4 * float(jnp.max(jnp.abs(y_steps)))
    assert float(jnp.max(jnp.abs(s_chunked - s))) <= 1e-4 * float(jnp.max(jnp.abs(s)))
    assert float(jnp.max(jnp.abs(s_chunked[1] - s_at_19[1]))) <= 1e-4 * float(jnp.max(jnp.abs(s)))


# (d) the shares add up
@pytest.mark.parametrize("layer", [0, 2])
def test_the_two_shares_expert_layers_add_up_to_the_uncut_layer(ref, layer):
    seed = 4
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.normal(size=(40, 64)), jnp.bfloat16)
    whole = ref.expert_layer(ref_config(0, 1), seed, layer, v.astype(jnp.float32), (0, 8))
    parts, counts = [], []
    for rank in (0, 1):
        model, params = program(seed, rank=rank)
        y, n = jax.jit(lambda p, v, m=model: gh._moe(m.cfg, p, v, jnp.ones((40,), bool)))(params["layers"][layer], v)
        parts.append(np.asarray(y))
        counts.append(n)
    shared = whole - ref.expert_layer(ref_config(0, 1), seed, layer, v.astype(jnp.float32), (0, 8), shared=False)
    # each share adds the shared expert whole: counted once
    total = parts[0] + parts[1] - shared
    assert np.max(np.abs(total - whole)) <= 2e-2 * np.max(np.abs(whole))  # bfloat16 products against float32
    # a share alone is not the layer, and the router ranked all eight experts in both
    assert np.max(np.abs(parts[0] - whole)) > 0.1 * np.max(np.abs(whole))
    assert int(counts[0]["assignments"]) == int(counts[1]["assignments"]) == 40 * 3
    assert int(counts[0]["held_assignments"]) + int(counts[1]["held_assignments"]) == 40 * 3
    # the reference's own shares add up exactly as well
    halves = [ref.expert_layer(ref_config(r, 2), seed, layer, v.astype(jnp.float32), (4 * r, 4), shared=False)
              for r in (0, 1)]
    assert np.max(np.abs(halves[0] + halves[1] + shared - whole)) <= 1e-5 * np.max(np.abs(whole))


# (e) slot isolation and copy_state with Mamba state
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
    base = prompts(2, [11])[0]
    reqs = [Request(tokens=base.copy(), max_new_tokens=6) for _ in range(4)] \
        + [Request(tokens=prompts(3, [9])[0], max_new_tokens=6)]
    b = ContinuousBatcher(model, params, num_slots=5, max_seq_len=64, eos_id=None, prefill_chunk=8)
    out = b.run(reqs)
    assert b._prefill._cache_size() == 1  # one executable for every chunk of every prompt
    assert out[0] == out[1] == out[2] == out[3] != out[4]
    from daft_tpu.profiling import recent_device_spans

    spans = [s for s in recent_device_spans() if s.name in ("serve.prefill", "serve.copy_state")][-5:]
    assert sorted(s.name for s in spans) == ["serve.copy_state"] * 3 + ["serve.prefill"] * 2
    # alone in a fresh batcher the copied prompt reads the same: the copy took Mamba state and conv tail along
    alone = ContinuousBatcher(model, params, num_slots=5, max_seq_len=64, eos_id=None, prefill_chunk=8)
    assert alone.run([Request(tokens=base.copy(), max_new_tokens=6)])[0] == out[3]


def test_serving_spans_say_which_grouped_product_each_program_traced():
    """``_grouped_mlp`` notes its path on the batcher's open span while the program
    traces; the batcher repeats it on the spans of calls that trace nothing. At the
    tiny widths, and on the CPU at any, that is XLA's ``ragged_dot``."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params = program(0)
    began = span_clock_ns()
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=64, eos_id=None, prefill_chunk=8)
    b.run([Request(tokens=t, max_new_tokens=3) for t in prompts(5, [5, 19, 7])])
    spans = [s for s in recent_device_spans() if s.start_ns >= began]
    prefills = [s for s in spans if s.name == "serve.prefill"]
    steps = [s for s in spans if s.name == "serve.decode_step"]
    assert len(prefills) >= 2 and len(steps) >= 3 and prefills[0].count["first"] == 1
    assert [s.count["moe"] for s in prefills + steps] == ["xla"] * (len(prefills) + len(steps))
    assert b._noted == {"serve.prefill": {"moe": "xla"}, "serve.decode_step": {"moe": "xla"}}
    assert all(s.count["moe.assignments"] >= s.count["moe.held_assignments"] for s in steps)  # the counts stay


# (f) prompt(..., logprobs=True) through a dataframe
def test_prompt_with_logprobs_through_a_dataframe(ref):
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.functions.ai import prompt
    from daft_tpu.profiling import recent_device_spans

    docs = [" ".join(f"w{(7 * i + j) % 50}" for j in range(5 + 6 * i)) for i in range(6)]
    df = daft_tpu.from_pydict({"id": list(range(6)), "doc": docs})
    cut = dict(num_hidden_layers=4, expert_shard=[0, 2], vocab_shard=[0, 2])
    expr = prompt(col("doc"), provider="flax_random", model=TINY, seed=9, max_new_tokens=5, ignore_eos=True,
                  logprobs=True, num_slots=4, max_prompt_tokens=48, **cut)
    out = df.with_column("answer", expr).to_pydict()
    assert [set(a) for a in out["answer"]] == [{"text", "token_ids", "logprobs"}] * 6
    for a in out["answer"]:
        assert len(a["token_ids"]) == len(a["logprobs"]) == 5
        assert all(0 <= t < 128 for t in a["token_ids"]) and all(np.isfinite(a["logprobs"]))
        assert a["text"] == " ".join(str(t) for t in a["token_ids"])
    # the path: UDFProject -> FlaxPrompter -> ContinuousBatcher -> the hybrid model, with its spans
    names = {s.name for s in recent_device_spans()}
    assert {"udf.call", "prompt.tokenize", "prompt.run", "serve.prefill", "serve.decode_step", "serve.fetch",
            "provider.init_params", "provider.place_params"} <= names
    inst = expr._expr.udf._instances[0]
    assert isinstance(inst.model, gh.GraniteHybridLM) and inst.params["embed"].dtype == jnp.bfloat16
    # and the answers are the reference's, teacher-forced on the hashed prompt
    tokens, lengths = inst.tokenizer.encode_batch(docs)
    rcfg = ref_config()
    for i in (0, 5):
        toks = np.asarray(out["answer"][i]["token_ids"])
        seq = np.concatenate([tokens[i, :lengths[i]], toks])
        lp = np.asarray(jax.nn.log_softmax(ref.forward(rcfg, 9, seq, logits_from=lengths[i] - 1)[:-1], -1))
        assert np.max(np.abs(lp[np.arange(5), toks] - out["answer"][i]["logprobs"])) <= LOGPROB_GAP_MAX
    # without logprobs: a string, as before
    plain = df.with_column("answer", prompt(col("doc"), provider="flax_random", model=TINY, seed=9, max_new_tokens=5,
                                            ignore_eos=True, num_slots=4, max_prompt_tokens=48, **cut)).to_pydict()
    assert all(isinstance(a, str) for a in plain["answer"])


# (g) an unknown model name with the cut's options raises
def test_an_unknown_name_with_the_cuts_options_is_an_error():
    from daft_tpu.ai.flax_provider import FlaxPrompter

    with pytest.raises(DaftValueError, match="granite-4.0-h-small"):
        FlaxPrompter("granite-4.0-h-smal", expert_shard=[0, 2])
    with pytest.raises(DaftValueError, match="expert_shard"):
        FlaxPrompter("tiny-lm", num_hidden_layers=2, expert_shard=[0, 2])
    with pytest.raises(DaftValueError, match="does not divide"):
        gh.GraniteHybridConfig.from_name(TINY, expert_shard=(0, 3))
    with pytest.raises(DaftValueError, match="unknown hybrid decoder"):
        gh.GraniteHybridConfig.from_name("granite")  # no substring rule
    # the exact name resolves to the published sizes
    cfg = gh.GraniteHybridConfig.from_name("granite-4.0-h-small", num_hidden_layers=10,
                                           expert_shard=(0, 2), vocab_shard=(0, 2))
    assert (cfg.hidden_size, cfg.held_experts, cfg.held_vocab, cfg.num_experts_per_tok) == (4096, 36, 50176, 10)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


# -- the attention core this decoder shares with ``models/olmo_hybrid.py`` (``decoders.attention_core``) -----
@pytest.mark.parametrize("kv_heads,queries", [(8, 4), (30, 1)])
def test_the_shared_attention_core_against_a_plain_softmax(kv_heads, queries):
    """Granite's 8 key/value heads x 4 queries and Olmo-Hybrid's 30 x 1: a chunk over the blocks held (the second
    chunk of each row: two blocks, a running softmax between them) and one token over every row held, each against
    a plain causal softmax in float32 over the same bfloat16 inputs; 2e-2 of outputs that spread ~0.3 is what the
    probabilities' rounding to bfloat16 before the weighted values costs (readings up to 6e-3)."""
    from daft_tpu.models import decoders

    B, T, S, hd, scale = 2, 16, 48, 32, 32 ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(kv_heads), 3)
    q = jax.random.normal(ks[0], (B, T, kv_heads, queries, hd)).astype(jnp.bfloat16)
    rows_k = jax.random.normal(ks[1], (B, S, kv_heads, hd)).astype(jnp.bfloat16)
    rows_v = jax.random.normal(ks[2], (B, S, kv_heads, hd)).astype(jnp.bfloat16)

    def plain(q, positions):
        sc = jnp.einsum("btgrd,bsgd->bgrts", q.astype(jnp.float32), rows_k.astype(jnp.float32)) * scale
        seen = jnp.arange(S)[None, None, :] <= positions[:, :, None]
        probs = jax.nn.softmax(jnp.where(seen[:, None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bgrts,bsgd->btgrd", probs, rows_v.astype(jnp.float32))

    positions = T + jnp.arange(T)[None, :] + jnp.zeros((B, 1), jnp.int32)
    got = decoders.attention_core(q, rows_k, rows_v, positions, scale, jnp.bfloat16)
    assert got.shape == (B, T, kv_heads, queries, hd) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - plain(q, positions)))) < 2e-2
    one = jnp.asarray([[40], [7]], jnp.int32)  # one token a row, rows at unlike depths
    got = decoders.attention_core(q[:, :1], rows_k, rows_v, one, scale, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(got - plain(q[:, :1], one)))) < 2e-2
