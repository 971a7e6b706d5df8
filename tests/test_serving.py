"""Continuous-batching LLM engine tests (reference: the vLLM streaming sink
src/daft-local-execution/src/streaming_sink/vllm.rs + daft/execution/vllm.py)."""

import time

import numpy as np
import pytest

import daft_tpu
from daft_tpu.models.lm import DecoderLMConfig, generate, init_lm_params
from daft_tpu.models.serving import ContinuousBatcher, Request, generate_continuous


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


@pytest.mark.parametrize("lengths,slots,block_rows,padded", [
    ([4, 9, 21, 30], 4, 1 + 6 + 21 + 36, 4 * 36),    # one group of four rows of 1, 3, 6 and 8 chunks: 64 of 144
    ([3, 4, 5], 4, 1 + 1 + 3, 4 * 3),                # three prompts in four rows: the spare row attends nothing
    ([16, 16], 2, 10 + 10, 2 * 10),                  # like lengths: every visit holds a query
], ids=["unlike_lengths", "a_spare_row", "like_lengths"])
def test_prefill_span_counts_the_block_rows_that_hold_a_query(lm, lengths, slots, block_rows, padded):
    """``serve.prefill`` of one group: ``block_rows`` sums, over the group's calls
    and the rows that still hold a query, the blocks of ``chunk`` positions
    attended (a row of c chunks: c (c + 1) / 2); ``padded_block_rows`` is what the
    calls span at static shape: rows a call x chunks (chunks + 1) / 2 of the longest."""
    from daft_tpu.profiling import recent_device_spans
    from daft_tpu.tracing import span_clock_ns

    model, params = lm
    rng = np.random.default_rng(4)
    began = span_clock_ns()
    b = ContinuousBatcher(model, params, num_slots=slots, prefill_chunk=4)
    b.run([Request(tokens=rng.integers(3, model.cfg.vocab_size, n).astype(np.int32), max_new_tokens=1) for n in lengths])
    spans = [s for s in recent_device_spans() if s.name == "serve.prefill" and s.start_ns >= began]
    assert len(spans) == 1
    count = spans[0].count
    chunks = [-(-n // 4) for n in lengths]
    assert count["block_rows"] == sum(c * (c + 1) // 2 for c in chunks)
    assert count["padded_block_rows"] == b.prefill_rows * max(chunks) * (max(chunks) + 1) // 2
    assert (count["block_rows"], count["padded_block_rows"], count["row_chunks"]) == (block_rows, padded, sum(chunks))
    assert count["block_rows"] <= count["padded_block_rows"]


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
