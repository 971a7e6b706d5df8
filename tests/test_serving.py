"""Continuous-batching LLM engine tests (reference: the vLLM streaming sink
src/daft-local-execution/src/streaming_sink/vllm.rs + daft/execution/vllm.py)."""

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

import daft_tpu
from daft_tpu.models.lm import DecoderLMConfig, generate, init_lm_params
from daft_tpu.models.serving import ContinuousBatcher, Request, generate_continuous, prefill_schedule

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import manifest  # noqa: E402


@pytest.fixture(scope="module")
def lm():
    cfg = DecoderLMConfig.tiny()
    return init_lm_params(cfg, seed=0)


@pytest.fixture(scope="module")
def lm32():
    """float32 weights: jit-vs-eager rounding cannot flip argmax ties, so
    continuous and static schedules must agree token-for-token."""
    import jax.numpy as jnp

    cfg = DecoderLMConfig(vocab_size=512, hidden=64, layers=2, heads=2,
                          max_seq_len=64, dtype=jnp.float32)
    return init_lm_params(cfg, seed=0)


def _mixed_requests(rng, n, vocab, max_range=(2, 40)):
    prompts = [rng.integers(3, vocab, rng.integers(4, 14)).astype(np.int32)
               for _ in range(n)]
    maxes = [int(m) for m in rng.integers(*max_range, n)]
    return prompts, maxes


def test_continuous_matches_static_greedy(lm32):
    """Greedy continuous output must equal static batched generation (f32:
    no bf16 tie-flipping; cache sizes matched so numerics align)."""
    import jax.numpy as jnp

    model, params = lm32
    rng = np.random.default_rng(0)
    P = 10
    max_new = model.cfg.max_seq_len - P  # static S == continuous S
    prompts = [rng.integers(3, model.cfg.vocab_size, P).astype(np.int32)
               for _ in range(6)]
    cont = generate_continuous(model, params, prompts, max_new, num_slots=3)
    padded = np.stack(prompts)
    static = np.asarray(generate(model, params, jnp.asarray(padded),
                                 jnp.full(6, P, np.int32), max_new))
    for c, s in zip(cont, static):
        s_trim = [int(t) for t in s]
        # static pads with 0 after EOS; continuous stops at EOS
        assert list(c) == s_trim[:len(c)]


def test_slot_isolation_under_shuffled_admission(lm):
    """Outputs are per-request deterministic regardless of admission order
    (same pool size -> identical jitted numerics; proves slots don't leak)."""
    model, params = lm
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, model.cfg.vocab_size, rng.integers(4, 12)).astype(np.int32)
               for _ in range(10)]
    a = generate_continuous(model, params, prompts, 8, num_slots=4)
    order = list(range(10))[::-1]
    b = generate_continuous(model, params, [prompts[i] for i in order], 8,
                            num_slots=4)
    for i, oi in enumerate(order):
        assert a[oi] == b[i], (i, oi)


def test_continuous_batching_throughput_gain(lm):
    """Mixed-length workload: slot refill must beat static batching by >1.5x
    in decode-step count (the device-time proxy: each step is one jitted
    forward of the full slot pool, identical cost in both schemes)."""
    model, params = lm
    rng = np.random.default_rng(1)
    n, slots = 48, 4
    prompts, maxes = _mixed_requests(rng, n, model.cfg.vocab_size, (2, 60))

    generate_continuous(model, params, prompts, maxes, num_slots=slots)
    cont_steps = generate_continuous.last_decode_steps

    # Static batching: fixed groups of `slots`, each group decodes for its
    # longest request (what the pre-continuous path did).
    static_steps = 0
    for i in range(0, n, slots):
        static_steps += max(maxes[i:i + slots])
    ratio = static_steps / cont_steps
    assert ratio > 1.5, (static_steps, cont_steps, ratio)


def test_prefix_routing_shares_prefills(lm):
    """Identical prompts admitted together reuse the cache via row copy:
    count real prefill computations through the bucketed prefill fns."""
    model, params = lm
    rng = np.random.default_rng(2)
    base = rng.integers(3, model.cfg.vocab_size, 8).astype(np.int32)
    reqs = [Request(tokens=base.copy(), max_new_tokens=6) for _ in range(6)]
    b = ContinuousBatcher(model, params, num_slots=6)
    calls = {"n": 0}
    orig = b._prefill_impl

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    b._prefill_impl = counting
    b._prefill_fns = {}  # rebuild jits over the counting fn
    out = b.run(reqs)
    assert all(o == out[0] for o in out)
    assert calls["n"] == 1, f"expected one shared prefill, got {calls['n']}"


def _fewest_calls(chunks, rows):
    return max(max(chunks), -(-sum(chunks) // rows))


def _check_schedule(chunks, rows):
    """Every prompt's chunks in order, at most one a call, at most ``rows`` a call, those with the most chunks
    left first, and as few calls as any schedule can have."""
    calls = prefill_schedule(chunks, rows)
    ran = [0] * len(chunks)
    for call in calls:
        assert 1 <= len(call) <= rows and len({i for i, _ in call}) == len(call)
        left = [n - r for n, r in zip(chunks, ran)]
        assert min(left[i] for i, _ in call) >= max([n for j, n in enumerate(left) if j not in dict(call)], default=0)
        for i, c in call:
            assert c == ran[i]
            ran[i] += 1
    assert ran == list(chunks) and len(calls) == _fewest_calls(chunks, rows)
    return calls


@pytest.mark.parametrize("traffic,slots,calls,padded_block_rows,mixed", [
    ("docs_lognormal_4k_out64", 16, [42], 2248, 40),          # LongCat's cell: one round (55 calls in groups of four)
    ("docs_lognormal_4k_out64", 8, [11, 31], 2100, 39),       # Olmo-Hybrid's: two rounds (13 + 42)
    ("docs_lognormal_1k_out64", 32, [14, 34], 516, 19),       # granite's: two rounds of like lengths (14 + 36)
], ids=["4k_16_slots", "4k_8_slots", "1k_32_slots"])
def test_schedule_packs_the_benchmarks_partitions(traffic, slots, calls, padded_block_rows, mixed):
    """The lengths every partition of a ``prompt`` cell holds (``benchmark/traffic/doc_pool.lengths``), admitted in
    order of length ``slots`` at a time and prefilled 4 x 512 tokens a call: a round runs max(its longest prompt's
    chunks, ceil(its chunks / 4)) calls, 42 / 42 / 48 a partition; host arithmetic, no model."""
    doc_pool = manifest.load_module(os.path.join(BENCH, "traffic", "doc_pool.py"))
    p = manifest.load_json(os.path.join(BENCH, "traffic", traffic + ".json"))
    lengths = sorted(doc_pool.lengths(p["length_tokens"], p["partition_rows"]).tolist())
    rounds = [[-(-n // 512) for n in lengths[i:i + slots]] for i in range(0, len(lengths), slots)]
    scheduled = [_check_schedule(r, 4) for r in rounds]
    assert [len(c) for c in scheduled] == calls == [_fewest_calls(r, 4) for r in rounds]
    assert 4 * sum(max(c for _, c in call) + 1 for r in scheduled for call in r) == padded_block_rows
    assert sum(len({c for _, c in call}) > 1 for r in scheduled for call in r) == mixed
    padded = 1 - sum(lengths) / (sum(calls) * 4 * 512)
    assert round(100 * padded, 2) == {42: 4.91, 48: 16.94}[sum(calls)]


@pytest.mark.parametrize("seed", range(6))
def test_schedule_has_the_fewest_calls_whatever_the_lengths(seed):
    rng = np.random.default_rng(seed)
    for _ in range(20):
        chunks = rng.integers(0, 33, rng.integers(1, 40)).tolist()
        if any(chunks):
            _check_schedule(chunks, int(rng.integers(1, 9)))


def test_schedule_of_like_lengths_is_one_call_a_chunk_and_of_no_chunks_no_call():
    assert prefill_schedule([2, 2, 2], 4) == [[(0, 0), (1, 0), (2, 0)], [(0, 1), (1, 1), (2, 1)]]
    assert prefill_schedule([0, 0], 4) == []


@pytest.mark.parametrize("lengths,slots,rows,calls,block_rows,padded,mixed", [
    ([4, 9, 21, 30], 4, 4, 8, 1 + 6 + 21 + 36, 4 * 36, 0),  # 1, 3, 6 and 8 chunks in calls of four: 64 of 144
    ([3, 4, 5], 4, 4, 2, 1 + 1 + 3, 4 * 3, 0),              # three prompts in four rows: the spare row attends nothing
    ([16, 16], 2, 2, 4, 10 + 10, 2 * 10, 0),                # like lengths: every visit holds a query
    # the same four prompts two a call: 9 calls for 18 chunks where groups of two ran 3 + 8; the deepest row of the
    # calls stands at blocks 0 to 6, then 2 (the shortest prompt beside the second's last chunk), then 7
    ([4, 9, 21, 30], 4, 2, 9, 1 + 6 + 21 + 36, 2 * (36 + 3), 6),
    ([30, 30, 3, 3, 3, 3], 6, 3, 8, 36 + 36 + 4, 3 * 36, 3),  # two long prompts take the four short ones along
], ids=["unlike_lengths", "a_spare_row", "like_lengths", "unlike_lengths_packed", "short_beside_long"])
def test_prefill_span_counts_the_block_rows_that_hold_a_query(lm, monkeypatch, lengths, slots, rows, calls, block_rows,
                                                              padded, mixed):
    """``serve.prefill`` of one round: ``block_rows`` sums, over the round's calls
    and the rows that still hold a query, the blocks of ``chunk`` positions
    attended (a row of c chunks: c (c + 1) / 2); ``padded_block_rows`` is what the
    calls span at static shape: rows a call x (the deepest row's block + 1), summed over
    the calls; ``mixed_calls`` the calls whose prompts stood at unlike depths."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params = lm
    rng = np.random.default_rng(4)
    monkeypatch.setattr(ContinuousBatcher, "PREFILL_TOKENS", 4 * rows)
    began = span_clock_ns()
    b = ContinuousBatcher(model, params, num_slots=slots, prefill_chunk=4)
    assert b.prefill_rows == rows
    b.run([Request(tokens=rng.integers(3, model.cfg.vocab_size, n).astype(np.int32), max_new_tokens=1)
           for n in lengths])
    spans = [s for s in recent_device_spans() if s.name == "serve.prefill" and s.start_ns >= began]
    assert len(spans) == 1
    count = spans[0].count
    chunks = [-(-n // 4) for n in lengths]
    assert count["block_rows"] == sum(c * (c + 1) // 2 for c in chunks)
    assert count["chunks"] == calls == _fewest_calls(chunks, rows) and count["padded_tokens"] == calls * rows * 4
    assert (count["rows"], count["tokens"], count["mixed_calls"]) == (len(lengths), sum(lengths), mixed)
    assert (count["block_rows"], count["padded_block_rows"], count["row_chunks"]) == (block_rows, padded, sum(chunks))
    assert count["block_rows"] <= count["padded_block_rows"]


def _float32_decoder(name):
    """-> (model, params, the prefill chunk its scan allows): the tiny decoders the suite builds, in float32."""
    import jax.numpy as jnp

    from daft_tpu.ai import flax_provider  # noqa: F401  (importing it fills the record of decoders)
    from daft_tpu.models import decoders

    if name == "lm":
        return (*init_lm_params(DecoderLMConfig(vocab_size=128, hidden=64, layers=2, heads=2, max_seq_len=80,
                                                dtype=jnp.float32), seed=0), 4)
    d = decoders.DECODERS[name]
    return (*d.init(dataclasses.replace(d.from_name(name), dtype=jnp.float32), 0), 8)


DECODER_NAMES = ["lm", "granite-hybrid-tiny", "longcat-flash-tiny", "olmo-hybrid-tiny", "pangu-ultra-moe-tiny"]


@pytest.mark.parametrize("name", DECODER_NAMES)
def test_prompts_packed_at_unlike_depths_read_as_each_alone(monkeypatch, name):
    """Prompts of 1, 3, 6 and 8 chunks and a twin of the third, two a call: rows of a call stand at unlike depths
    (and one call has a row to spare), the twin is copied after the round's last call, and every prompt's tokens
    and log-probabilities are what the same batcher gives it alone, where each call holds its one row."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params, T = _float32_decoder(name)
    monkeypatch.setattr(ContinuousBatcher, "PREFILL_TOKENS", 2 * T)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, model.vocab_size, n).astype(np.int32) for n in (T, 2 * T + 1, 6 * T - 3, 8 * T)]
    prompts.append(prompts[2].copy())

    def served(ps):
        b = ContinuousBatcher(model, params, num_slots=5, max_seq_len=8 * T + 8, eos_id=None, prefill_chunk=T)
        assert (b.prefill_rows, b.chunk) == (2, T)
        out = b.run([Request(tokens=p, max_new_tokens=5) for p in ps])
        return out, b.last_logprobs

    began = span_clock_ns()
    out, logprobs = served(prompts)
    spans = [s for s in recent_device_spans()
             if s.start_ns >= began and s.name in ("serve.prefill", "serve.copy_state")]
    assert [s.name for s in spans] == ["serve.prefill", "serve.copy_state"]
    assert (spans[0].count["rows"], spans[0].count["chunks"], spans[0].count["mixed_calls"]) == (4, 9, 6)
    for p, toks, lps in zip(prompts, out, logprobs):
        alone_toks, alone_lps = served([p])
        assert toks == alone_toks[0]
        np.testing.assert_allclose(lps, alone_lps[0], rtol=0, atol=1e-5)  # read equal to the last bit here


@pytest.mark.parametrize("name", DECODER_NAMES)
def test_a_row_of_length_0_leaves_a_slot_in_the_middle_of_its_prompt_bit_for_bit(name):
    """The models' contract the batcher's spare rows rest on, for a slot that no schedule names so today (a call
    with a row to spare holds every prompt that still has a chunk): slot 1 has run two of its prompt's three chunks
    when a call names it with ``lengths`` 0 beside slot 2's first chunk; its cache rows, recurrent and conv state
    and ``cur_logits`` stay as they were to the bit, and its third chunk then reads as in a batcher that never
    made that call."""
    import jax

    model, params, T = _float32_decoder(name)
    rng = np.random.default_rng(12)
    doc, other = (rng.integers(3, model.vocab_size, n).astype(np.int32) for n in (3 * T - 2, T))

    def chunk(b, slot, tokens, c, spare):
        part = tokens[c * T:(c + 1) * T]
        padded = np.zeros((2, T), np.int32)
        padded[0, :len(part)] = part
        b.state, b.cur_logits = b._prefill_fn()(
            b.params, b.state, b.cur_logits, padded, np.asarray([slot, spare], np.int32),
            np.asarray([c * T, 0], np.int32), np.asarray([len(part), 0], np.int32),
            np.asarray([(c + 1) * T >= len(tokens), False]))

    def of_slot(b, slot):
        return [np.asarray(a[slot]) for a in jax.tree_util.tree_leaves((b.state, b.cur_logits))]

    def batcher():
        b = ContinuousBatcher(model, params, num_slots=3, max_seq_len=3 * T + 8, eos_id=None, prefill_chunk=T)
        b.prefill_rows = 2
        return b

    b, plain = batcher(), batcher()
    for x in (b, plain):
        chunk(x, 1, doc, 0, spare=0)
        chunk(x, 1, doc, 1, spare=0)
    before = of_slot(b, 1)
    chunk(b, 2, other, 0, spare=1)  # names slot 1, in the middle of its prompt, with length 0
    for was, now in zip(before, of_slot(b, 1)):
        np.testing.assert_array_equal(was, now)
    chunk(b, 1, doc, 2, spare=0)
    chunk(plain, 1, doc, 2, spare=0)
    for want, got in zip(of_slot(plain, 1), of_slot(b, 1)):
        np.testing.assert_array_equal(want, got)
    assert np.abs(of_slot(b, 1)[-1]).max() > 0  # the prompt's last chunk left its logits


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rounds_are_admitted_in_order_of_length_whatever_the_hashes(lm, seed):
    """Eight prompts of unlike length on four slots, in an order and with tokens (so hashes) from the seed: the
    first round is the four shortest, the second the four longest, and a call's chunks follow its round's longest
    prompt; two identical prompts still meet in one round and share one prefill."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params = lm
    rng = np.random.default_rng(seed)
    lengths = [3, 5, 6, 8, 17, 18, 23, 23]
    twin = rng.integers(3, model.cfg.vocab_size, 23).astype(np.int32)
    prompts = [rng.integers(3, model.cfg.vocab_size, n).astype(np.int32) for n in lengths[:6]] + [twin, twin.copy()]
    began = span_clock_ns()
    b = ContinuousBatcher(model, params, num_slots=4, prefill_chunk=4)
    b.run([Request(tokens=prompts[i], max_new_tokens=2) for i in rng.permutation(8)])
    spans = [s.count for s in recent_device_spans() if s.name == "serve.prefill" and s.start_ns >= began]
    assert [(c["rows"], c["tokens"], c["chunks"]) for c in spans] == [(4, 3 + 5 + 6 + 8, 2), (3, 17 + 18 + 23, 6)]
    copies = [s for s in recent_device_spans() if s.name == "serve.copy_state" and s.start_ns >= began]
    assert len(copies) == 1


def test_llm_generate_through_engine():
    """llm_generate end-to-end over the continuous-batching prompter."""
    import daft_tpu.functions as F

    df = daft_tpu.from_pydict({
        "prompt": [f"tell me about topic {i % 3}" for i in range(9)]})
    out = df.with_column(
        "gen", F.llm_generate(daft_tpu.col("prompt"), provider="flax_random",
                              model="tiny", max_new_tokens=4)).to_pydict()
    assert len(out["gen"]) == 9
    assert all(isinstance(g, str) and g for g in out["gen"])
    # identical prompts -> identical generations (greedy + prefix routing)
    assert out["gen"][0] == out["gen"][3] == out["gen"][6]


def test_prompt_longer_than_cache_rejected(lm):
    model, params = lm
    import daft_tpu.errors as errors

    long_prompt = np.arange(model.cfg.max_seq_len + 10, dtype=np.int32) % 100 + 3
    with pytest.raises(errors.DaftValueError, match="cache capacity"):
        generate_continuous(model, params, [long_prompt], 4, num_slots=2)


def test_manual_tracer_spans():
    """Public manual-tracing API: nested spans parent correctly and export
    (reference: tracing::Instrument spans around operators)."""
    from daft_tpu.tracing import InMemorySpanExporter, Tracer

    exp = InMemorySpanExporter()
    tracer = Tracer(exp)
    with tracer.start_span("outer", {"k": 1}) as outer:
        with tracer.start_span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = exp.get_finished_spans()
    assert [s.name for s in spans] == ["inner", "outer"]
    assert spans[1].attributes["k"] == 1 and spans[1].end_ns >= spans[1].start_ns


# -- a decoder of one token a step beside the block step ------------------------------------------------------------
def _decode_before_blocks(self, params, state, cur_logits, positions, active, key):
    """``ContinuousBatcher._decode_impl`` as it was before a model could declare ``block_length`` (PR 42), verbatim."""
    import jax
    import jax.numpy as jnp

    if self.temperature <= 0.0:
        tok = jnp.argmax(cur_logits, axis=-1).astype(jnp.int32)
    else:
        tok = jax.random.categorical(
            key, cur_logits / self.temperature, axis=-1).astype(jnp.int32)
    tok = jnp.where(active, tok, 0)
    logprob = jnp.take_along_axis(jax.nn.log_softmax(cur_logits, axis=-1), tok[:, None], axis=-1)[:, 0]
    state, logits, counts = self.model.decode(params, state, tok, positions, active)
    return state, logits, {"tok": tok, "logprob": logprob, "counts": counts}


@pytest.mark.parametrize("name", ["lm", "granite-hybrid-tiny"])
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_one_token_decoders_decode_program_is_the_one_before_blocks(name, temperature):
    """The block step is another program under the same name: a decoder without ``block_length`` traces the decode
    program it traced before, argument for argument, and keeps its name and its next-token logits."""
    import functools

    import jax
    import jax.numpy as jnp

    model, params, _ = _float32_decoder(name)
    b = ContinuousBatcher(model, params, num_slots=3, max_seq_len=40, temperature=temperature)
    assert b.L is None and b.cur_logits.shape == (3, model.vocab_size)
    args = (params, b.state, b.cur_logits, jnp.asarray(b.positions), jnp.asarray(b.active), jax.random.PRNGKey(0))
    now = jax.make_jaxpr(b._decode_impl)(*args)
    before = jax.make_jaxpr(functools.partial(_decode_before_blocks, b))(*args)
    assert str(now) == str(before)
    text = b._decode.lower(*args).as_text()
    assert text.startswith("module @jit__decode_impl") and b._decode.lower(*args, None).as_text() == text


# -- what the batcher refuses at construction ------------------------------------------------------------------------
def _block_model(draft_length=None):
    from daft_tpu.models import sdar_moe

    class Drafting(sdar_moe.SdarMoeLM):
        pass

    Drafting.draft_length = draft_length
    model, params = sdar_moe.init_sdar_params(sdar_moe.SdarMoeConfig.from_name("sdar-moe-tiny"), 0)
    return Drafting(model.cfg), params


def test_a_block_model_is_refused_a_temperature_and_told_what_samples():
    from daft_tpu.errors import DaftValueError

    model, params = _block_model()
    with pytest.raises(DaftValueError, match="served greedily here .* need a decoder of one token a step or one that drafts"):
        ContinuousBatcher(model, params, num_slots=2, max_seq_len=40, temperature=0.7)
    assert ContinuousBatcher(model, params, num_slots=2, max_seq_len=40).L == 4


def test_a_model_that_drafts_and_generates_blocks_is_refused():
    from daft_tpu.errors import DaftValueError

    model, params = _block_model(draft_length=1)
    for temperature in (0.0, 1.0):
        with pytest.raises(DaftValueError, match="drafts tokens .draft_length. or generates a block a step .block_length., not both"):
            ContinuousBatcher(model, params, num_slots=2, max_seq_len=40, temperature=temperature)


def test_a_draft_of_more_than_one_token_is_refused():
    from daft_tpu.errors import DaftValueError
    from daft_tpu.models import pangu_ultra_moe as pg

    class TwoDrafts(pg.PanguUltraMoeLM):
        draft_length = 2

    with pytest.raises(DaftValueError, match="draft_length=2"):
        ContinuousBatcher(TwoDrafts(pg.PanguUltraMoeConfig.from_name("pangu-ultra-moe-tiny")), None, num_slots=2,
                          max_seq_len=40)
