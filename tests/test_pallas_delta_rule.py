"""``ops/pallas_delta_rule.py``: the chunked gated delta rule of a prefill call
as one Pallas kernel, run in interpret mode on the CPU against the reference's
token-by-token recurrence (``benchmark/reference/olmo_hybrid.py``'s
``delta_rule``) and against the XLA form it stands beside
(``models/olmo_hybrid.gated_delta_chunked``).

Tolerances. Kernel, XLA form and recurrence are the same arithmetic in another
order, all float32 after the inputs: ``DELTA_GAP_MAX`` (1e-4 of values that
spread ~1; readings up to 8e-6, the XLA form's own to the same digit). The
kernel against the XLA form on the same inputs: 2e-5 (readings up to 2e-6).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import types

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

from daft_tpu.models import olmo_hybrid as oh  # noqa: E402
from daft_tpu.models.serving import ContinuousBatcher, Request  # noqa: E402
from daft_tpu.ops import pallas_attention, pallas_delta_rule as pdr  # noqa: E402

#: The kernel against the recurrence: the limit the XLA form is held to (``tests/test_olmo_hybrid.py``).
DELTA_GAP_MAX = 1e-4
#: The kernel against the XLA form on the same inputs.
FORMS_GAP_MAX = 2e-5


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "olmo_hybrid.py"))


def delta_inputs(seed, B, T, H=4, dk=8, dv=16, beta_shift=0.0, log_decay=(-2.0, 2.0), dtype=jnp.float32):
    """As ``tests/test_olmo_hybrid.py`` draws them: q, k normalised, beta in (0, 2), a carried state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = oh._l2_normalised(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = oh._l2_normalised(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(log_decay[0] + log_decay[1] * jax.random.normal(ks[3], (B, T, H)))
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (B, T, H)) + beta_shift)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta, jax.random.normal(ks[5], (B, H, dv, dk))


def recurrence(ref, q, k, v, g, beta, s0):
    """The reference's token-by-token rule, a row at a time, on the values the kernel was handed."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    out = [ref.delta_rule(q[b], k[b], v[b], jnp.exp(g[b]), beta[b], s0[b]) for b in range(q.shape[0])]
    return jnp.stack([o for o, _ in out]), jnp.stack([s for _, s in out])


def fused(q, k, v, g, beta, s0, chunk):
    return pdr.gated_delta_fused(q, k, v, g, beta, s0, chunk=chunk, interpret=True)


def gaps(got, want):
    return float(jnp.max(jnp.abs(got[0] - want[0]))), float(jnp.max(jnp.abs(got[1] - want[1])))


@pytest.mark.parametrize("chunks", [1, 2, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_equals_the_recurrence(ref, chunks, dtype):
    """Over chunks a call, from a carried state that is not zero, with q, k, v as the program hands them over."""
    args = delta_inputs(0, B=2, T=16 * chunks, dtype=dtype)
    want_o, want_s = recurrence(ref, *args)
    got = fused(*args, 16)
    assert got[0].dtype == got[1].dtype == jnp.float32 and got[0].shape == want_o.shape and got[1].shape == want_s.shape
    assert max(gaps(got, (want_o, want_s))) < DELTA_GAP_MAX
    assert float(jnp.std(want_o)) > 0.1 and float(jnp.max(jnp.abs(want_s - args[5]))) > 0.1  # the state moved


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_solves_blocks_merge_up_to_the_chunk(ref, chunk):
    """One, two and four diagonal blocks of ``SOLVE_BLOCK`` steps a chunk: no, one and two levels of the merge."""
    args = delta_inputs(4, B=1, T=2 * chunk)
    assert max(gaps(fused(*args, chunk), recurrence(ref, *args))) < DELTA_GAP_MAX


@pytest.mark.parametrize("case", ["beta_near_2", "decay_near_1", "decay_near_0"])
def test_the_kernel_at_the_edges_of_its_range(ref, case):
    """beta near 2 (entries of ``A`` near 2 in magnitude: the solve's blocks and their merge carry them), decays near
    1 (nothing forgotten: the solve carries the whole chunk) and near 0 (exp(-60): gamma_i / gamma_j underflows to 0,
    never overflows), at a chunk whose solve merges twice."""
    kw = {"beta_near_2": dict(beta_shift=6.0), "decay_near_1": dict(log_decay=(-12.0, 0.5)),
          "decay_near_0": dict(log_decay=(4.0, 0.3))}[case]
    args = delta_inputs(1, B=1, T=128, **kw)
    if case == "beta_near_2":
        assert float(jnp.mean(args[4])) > 1.9
    got = fused(*args, 64)
    assert np.isfinite(np.asarray(got[0])).all() and np.isfinite(np.asarray(got[1])).all()
    assert max(gaps(got, recurrence(ref, *args))) < DELTA_GAP_MAX


@pytest.mark.parametrize("length", [1, 5, 16, 29, 0])
def test_a_length_that_ends_inside_a_chunk_leaves_the_rest_alone(ref, length):
    """Padding has g = 0 and beta = 0: the state after 32 steps of which ``length`` are valid is the state after
    ``length`` steps, whatever q, k and v hold behind them; a row that is all padding returns the state as it came,
    to the bit."""
    q, k, v, g, beta, s0 = delta_inputs(2, B=1, T=32)
    keep = (jnp.arange(32) < length)[None, :, None]
    got_o, got_s = fused(q, k, v, jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0), s0, 16)
    if length == 0:
        assert np.array_equal(np.asarray(got_s), np.asarray(s0))
        return
    want_o, want_s = recurrence(ref, q[:, :length], k[:, :length], v[:, :length], g[:, :length], beta[:, :length], s0)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < DELTA_GAP_MAX
    assert float(jnp.max(jnp.abs(got_o[:, :length] - want_o))) < DELTA_GAP_MAX


def test_rows_of_unlike_state_in_one_call_do_not_mix(ref):
    """Three rows of one call (the third all padding) against each row alone: every row's result and state are what
    the row gives by itself; the padded row's state is what came, to the bit."""
    q, k, v, g, beta, s0 = delta_inputs(3, B=3, T=32, H=6)
    g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)
    assert pdr._heads_a_step(16, 6, 8, 16) == 6 and pdr._heads_a_step(16, 5, 8, 16) == 0   # all of them (a block as wide as the array); in pairs
    o, s = fused(q, k, v, g, beta, s0, 16)
    for b in range(3):
        alone = fused(*(x[b:b + 1] for x in (q, k, v, g, beta, s0)), 16)
        assert max(gaps((o[b], s[b]), (alone[0][0], alone[1][0]))) < 1e-6   # the CPU's products block a larger call otherwise: not to the bit
    assert np.array_equal(np.asarray(s[2]), np.asarray(s0[2])) and not np.array_equal(np.asarray(s[0]), np.asarray(s[1]))
    assert max(gaps((o[:2], s[:2]), recurrence(ref, *(x[:2] for x in (q, k, v, g, beta, s0))))) < DELTA_GAP_MAX


@pytest.mark.parametrize("heads", [2, 16])
def test_the_published_head_sizes(ref, heads):
    """Keys of 96 (padded to a lane tile inside the call) and values of 192 (a head's columns start on a lane tile for
    every second head), bfloat16, chunks of 64, two chunks: two heads in one group, and sixteen in groups of four (two pairs a step)."""
    args = delta_inputs(5, B=1, T=128, H=heads, dk=96, dv=192, dtype=jnp.bfloat16)
    assert pdr._heads_a_step(64, heads, 96, 192) == {2: 2, 16: 4}[heads] and pdr._heads_a_step(64, 30, 96, 192) == pdr.MAX_HEADS
    got = fused(*args, 64)
    assert max(gaps(got, recurrence(ref, *args))) < DELTA_GAP_MAX
    assert max(gaps(got, oh.gated_delta_chunked(*args, 64))) < FORMS_GAP_MAX


@pytest.mark.parametrize("seed,dtype", [(6, jnp.float32), (7, jnp.bfloat16), (8, jnp.bfloat16)])
def test_the_kernel_equals_the_xla_form_on_the_same_inputs(seed, dtype):
    args = delta_inputs(seed, B=2, T=64, dtype=dtype)
    want = oh.gated_delta_chunked(*args, 16)
    assert max(gaps(fused(*args, 16), want)) < FORMS_GAP_MAX and float(jnp.std(want[0])) > 0.1


# -- which form a program takes ------------------------------------------------------------------
CELL = ((4, 512, 30, 96), (4, 512, 30, 192), jnp.bfloat16, 64)


def test_a_cpu_backend_takes_the_xla_form():
    assert jax.default_backend() == "cpu" and not pdr.delta_rule_applies(*CELL)


@pytest.mark.parametrize("case,applies", [
    ("the_cells_call", True), ("float32", False), ("steps_that_are_no_whole_chunks", False), ("a_chunk_of_8", False),
    ("a_chunk_of_48", False), ("a_chunk_of_32", False), ("a_chunk_of_128", False), ("the_tiny_decoder", False),
    ("the_tiny_decoder_at_chunks_of_64", True), ("an_odd_number_of_heads", False), ("a_state_past_the_budget", False)])
def test_on_a_tpu_the_rule_reads_dtype_steps_chunk_and_budget(monkeypatch, case, applies):
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    q, v, dtype, chunk = CELL
    args = {"the_cells_call": CELL, "float32": (q, v, jnp.float32, chunk), "steps_that_are_no_whole_chunks": ((4, 500, 30, 96), v, dtype, chunk),
            "a_chunk_of_8": (q, v, dtype, 8), "a_chunk_of_48": ((4, 480, 30, 96), v, dtype, 48), "a_chunk_of_32": (q, v, dtype, 32),
            "a_chunk_of_128": (q, v, dtype, 128), "the_tiny_decoder": ((4, 512, 4, 8), (4, 512, 4, 16), dtype, 8),
            "the_tiny_decoder_at_chunks_of_64": ((4, 512, 4, 8), (4, 512, 4, 16), dtype, 64),
            "an_odd_number_of_heads": ((4, 512, 15, 96), (4, 512, 15, 192), dtype, chunk),
            "a_state_past_the_budget": ((4, 512, 30, 2048), (4, 512, 30, 2048), dtype, chunk)}[case]
    assert pdr.delta_rule_applies(*args) is applies
    if case == "a_state_past_the_budget":
        assert pdr._step_bytes(64, 2048, 2048, 1) > pdr.VMEM_BUDGET and pdr._step_bytes(64, 96, 192, pdr.MAX_HEADS) < pdr.VMEM_BUDGET // 4


def test_a_call_the_rule_would_refuse_raises():
    args = delta_inputs(0, B=1, T=24)
    with pytest.raises(ValueError, match="whole chunks"):
        pdr.gated_delta_fused(*args, chunk=16, interpret=True)


# -- the model with the kernel (interpreted) and on XLA's form ---------------------------------------
@pytest.fixture
def fused_delta_rule(monkeypatch):
    """The backend rule answers this module as on a TPU, and the kernel it then selects runs interpreted (the
    attention's modules keep reading the CPU: its XLA path widens a block's rows for the CPU's products). ``calls``
    keeps the shape of q at each call traced."""
    calls = []
    kernel = pdr.gated_delta_fused

    def interpreted(q, *rest, chunk):
        calls.append(q.shape)
        return kernel(q, *rest, chunk=chunk, interpret=True)

    monkeypatch.setattr(pdr, "pallas_attention", types.SimpleNamespace(backend_is_tpu=lambda: True))
    monkeypatch.setattr(pdr, "gated_delta_fused", interpreted)
    return calls


def chunks_of_64():
    """The tiny decoder with the delta rule in chunks of 64 steps, the published model's: the chunk the kernel serves."""
    return oh.init_olmo_params(dataclasses.replace(oh.OlmoHybridConfig.from_name("olmo-hybrid-tiny"), linear_chunk_size=64), 0)


def test_the_batcher_with_the_kernel_chooses_what_the_xla_form_chooses(monkeypatch, fused_delta_rule):
    """Prompts of unlike lengths through prefill calls of 128 steps and decode steps: the same tokens and, to a
    bfloat16 step of the activations under logits that spread ~1, the same log-probabilities; the spans say which form each program traced."""
    model, params = chunks_of_64()
    reqs = lambda: [Request(tokens=(np.arange(2, 2 + n) * 7 % 251 + 2).astype(np.int32), max_new_tokens=4) for n in (270, 9, 133)]  # noqa: E731
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=280, eos_id=None, max_prompt_tokens=272, prefill_chunk=128)
    out = b.run(reqs())
    assert b._noted == {"serve.prefill": {"attn": "xla", "delta": "fused"}, "serve.decode_step": {"attn": "xla", "delta": "recurrent"}}
    assert fused_delta_rule == [(2, 128, 4, 8)] * 3                              # three linear layers, one trace of one program
    monkeypatch.setattr(pdr, "pallas_attention", pallas_attention)               # the CPU again
    x = ContinuousBatcher(model, params, num_slots=2, max_seq_len=280, eos_id=None, max_prompt_tokens=272, prefill_chunk=128)
    assert x.run(reqs()) == out and x._noted["serve.prefill"]["delta"] == "chunked" and len(fused_delta_rule) == 3
    worst = max(float(np.max(np.abs(np.asarray(a) - np.asarray(c)))) for a, c in zip(b.last_logprobs, x.last_logprobs))
    assert worst < 2e-2, worst  # the forms differ by 1e-7; where that flips a bfloat16 rounding of an activation, a log-probability moves by a step's worth (read: 0.005)


def test_at_the_tiny_decoders_own_chunk_the_program_keeps_the_xla_form(fused_delta_rule):
    """Two chunks of 8 steps do not fill the lanes: on a TPU too the tiny decoder traces ``chunked``."""
    model, params = oh.init_olmo_params(oh.OlmoHybridConfig.from_name("olmo-hybrid-tiny"), 0)
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=40, eos_id=None, max_prompt_tokens=32)
    b.run([Request(tokens=np.arange(2, 20).astype(np.int32), max_new_tokens=2)])
    assert b._noted["serve.prefill"]["delta"] == "chunked" and fused_delta_rule == []
