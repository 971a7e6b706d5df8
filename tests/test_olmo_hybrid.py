"""The Olmo-Hybrid decoder (``models/olmo_hybrid.py``: gated delta-rule linear
attention in three layers of four, full attention with QK-norm in the fourth,
no positions) against its plain reference (``benchmark/reference/olmo_hybrid.py``:
the recurrence token by token), through the continuous batcher and through
``prompt``, at a small size on the CPU: widths in the published ratios, four
layers l-l-l-f, chunks of 8 steps.

Tolerances. The chunked form against the token-by-token recurrence is the same
arithmetic in another order, all float32 after the inputs: 1e-4 of values that
spread ~1 (readings up to 4e-6). Program against reference: the program rounds
every product's operands to bfloat16 (2**-9 of each), the reference is float32
throughout, and at width 64 through four layers of post-normed sub-layers the
largest of 256 logits that spread ~1 reads 0.08 to 0.19 off over seeds 0-7 (the
fp8 control, one precision step down: 1.4 to 2.5); the limit sits at 0.3,
1.6x above the largest reading and 4.5x below the control's smallest.
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
from daft_tpu.models import decoders, olmo_hybrid as oh  # noqa: E402
from daft_tpu.models.serving import ContinuousBatcher, Request  # noqa: E402

TINY = "olmo-hybrid-tiny"
#: The largest |logit - reference's| after a chunked prefill and through decode steps (the module's head).
LOGIT_GAP_MAX = 0.3
#: |program log-probability - reference's| of a chosen token through the batcher: readings 0.02 to 0.12 over seeds 0-5.
LOGPROB_GAP_MAX = 0.3
#: The chunked delta rule against the recurrence.
DELTA_GAP_MAX = 1e-4


@pytest.fixture(scope="module")
def ref():
    return manifest.load_module(os.path.join(BENCH, "reference", "olmo_hybrid.py"))


def ref_config() -> dict:
    """The tiny model as the reference reads a configuration file."""
    p = oh.TEST_SIZES[TINY]
    return dict(p, layer_types=list(p["layer_types"]), embedding_std=oh.EMBED_STD)


def program(seed: int):
    return oh.init_olmo_params(oh.OlmoHybridConfig.from_name(TINY), seed)


def unfused(cfg, kind, layer):
    """A layer's tensors under the names ``tensor_specs`` draws them by."""
    p = dict(layer)
    qkv = p.pop("qkv")
    if kind == oh.LINEAR:
        p["q"], p["k"], p["v"] = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=1)
        p["b"], p["a"] = jnp.split(p.pop("ba"), 2, axis=1)
    else:
        p["q"], p["k"], p["v"] = jnp.split(qkv, 3, axis=1)
    return p


@pytest.mark.parametrize("seed", [0, 3])
def test_reference_draws_the_programs_weights(ref, seed):
    model, params = program(seed)
    cfg = model.cfg
    for i, (kind, layer) in enumerate(zip(cfg.layer_types, params["layers"])):
        want, got = ref.layer_weights(ref_config(), seed, i), unfused(cfg, kind, layer)
        assert set(want) == set(got)
        for name, w in want.items():
            assert got[name].dtype == (jnp.float32 if name in ("A_log", "dt_bias") else jnp.bfloat16)
            assert float(jnp.max(jnp.abs(w - got[name].astype(jnp.float32)))) == 0.0, (i, name)
    emb, final_norm, head = ref.embedding(ref_config(), seed)
    for name, w in (("embed", emb), ("final_norm", final_norm), ("head", head)):
        assert float(jnp.max(jnp.abs(w - params[name].astype(jnp.float32)))) == 0.0, name
    assert not np.array_equal(np.asarray(emb), np.asarray(head))  # untied


# -- the delta rule: chunks against the recurrence -----------------------------------------------
def delta_inputs(seed, B, T, H=3, dk=8, dv=16, beta_shift=0.0, log_decay=(-2.0, 2.0)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = oh._l2_normalised(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = oh._l2_normalised(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(log_decay[0] + log_decay[1] * jax.random.normal(ks[3], (B, T, H)))
    beta = 2 * jax.nn.sigmoid(3 * jax.random.normal(ks[4], (B, T, H)) + beta_shift)
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, dv, dk))


def recurrence(ref, q, k, v, g, beta, s0):
    """The reference's token-by-token rule, a row at a time."""
    out = [ref.delta_rule(q[b], k[b], v[b], jnp.exp(g[b]), beta[b], s0[b]) for b in range(q.shape[0])]
    return jnp.stack([o for o, _ in out]), jnp.stack([s for _, s in out])


@pytest.mark.parametrize("chunk", [4, 8, 16, 48])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_chunked_delta_rule_equals_the_recurrence(ref, chunk, dtype):
    """Over chunk sizes, from a carried state that is not zero, with q, k, v as the program hands them over."""
    q, k, v, g, beta, s0 = delta_inputs(0, B=2, T=48)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    want_o, want_s = recurrence(ref, *(x.astype(jnp.float32) for x in (q, k, v)), g, beta, s0)
    got_o, got_s = oh.gated_delta_chunked(q, k, v, g, beta, s0, chunk)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < DELTA_GAP_MAX and float(jnp.max(jnp.abs(got_s - want_s))) < DELTA_GAP_MAX
    assert float(jnp.std(want_o)) > 0.1 and float(jnp.max(jnp.abs(want_s - s0))) > 0.1  # the state moved


@pytest.mark.parametrize("case", ["beta_near_2", "decay_near_1", "decay_near_0"])
def test_the_chunked_delta_rule_at_the_edges_of_its_range(ref, case):
    """beta near 2 (the transition reflects along k: eigenvalue near -1), decays near 1 (nothing forgotten: the
    solve carries the whole chunk) and near 0 (exp(-60): gamma_i / gamma_j underflows to 0, never overflows)."""
    kw = {"beta_near_2": dict(beta_shift=6.0), "decay_near_1": dict(log_decay=(-12.0, 0.5)),
          "decay_near_0": dict(log_decay=(4.0, 0.3))}[case]
    q, k, v, g, beta, s0 = delta_inputs(1, B=1, T=32, **kw)
    if case == "beta_near_2":
        assert float(jnp.mean(beta)) > 1.9
    want_o, want_s = recurrence(ref, q, k, v, g, beta, s0)
    got_o, got_s = oh.gated_delta_chunked(q, k, v, g, beta, s0, 16)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    assert float(jnp.max(jnp.abs(got_o - want_o))) < DELTA_GAP_MAX and float(jnp.max(jnp.abs(got_s - want_s))) < DELTA_GAP_MAX


@pytest.mark.parametrize("length", [1, 5, 8, 13])
def test_a_length_that_ends_inside_a_chunk_leaves_the_rest_alone(ref, length):
    """Padding has g = 0 and beta = 0: the state after 16 steps of which ``length`` are valid is the state after
    ``length`` steps, whatever q, k and v hold behind them."""
    q, k, v, g, beta, s0 = delta_inputs(2, B=1, T=16)
    keep = (jnp.arange(16) < length)[None, :, None]
    got_o, got_s = oh.gated_delta_chunked(q, k, v, jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0), s0, 8)
    want_o, want_s = recurrence(ref, q[:, :length], k[:, :length], v[:, :length], g[:, :length], beta[:, :length], s0)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < DELTA_GAP_MAX
    assert float(jnp.max(jnp.abs(got_o[:, :length] - want_o))) < DELTA_GAP_MAX


def test_one_step_of_the_program_is_one_step_of_the_reference(ref):
    q, k, v, g, beta, s0 = delta_inputs(3, B=2, T=1)
    got_o, got_s = oh.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    want_o, want_s = recurrence(ref, q, k, v, g, beta, s0)
    assert float(jnp.max(jnp.abs(got_o - want_o[:, 0]))) < 1e-6 and float(jnp.max(jnp.abs(got_s - want_s))) < 1e-6
    # g = 0 and beta = 0 leave the state as it was (an inactive slot)
    _, same = oh.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], jnp.zeros_like(g[:, 0]), jnp.zeros_like(beta[:, 0]), s0)
    assert np.array_equal(np.asarray(same), np.asarray(s0))


# -- prefill in chunks, rows of unlike length in one call, then decode through the cache ---------
def run_prefill_then_decode(model, params, toks, lens, T, decode_row=0, steps=8):
    """Rows ``toks`` of lengths ``lens`` prefilled together in chunks of ``T`` into slots (2, 0) of three, then
    ``steps`` decode steps of row ``decode_row`` teacher-forced on its own next tokens. -> (logits after each
    row's prompt, logits after every decode step, state)."""
    B = len(lens)
    slots = jnp.asarray([2, 0][:B], jnp.int32)
    state = model.init_state(3, 64)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    after = {}
    for c in range(-(-max(lens) // T)):
        part = np.zeros((B, T), np.int32)
        here = np.clip(np.asarray(lens) - c * T, 0, T)
        for b in range(B):
            part[b, :here[b]] = toks[b][c * T:c * T + here[b]]
        state, logits, counts = prefill(params, state, part, slots, jnp.full((B,), c * T, jnp.int32),
                                        jnp.asarray(here, jnp.int32))
        assert counts == {}
        for b in range(B):
            if c * T < lens[b] <= (c + 1) * T:
                after[b] = np.asarray(logits[b])
    slot = int(slots[decode_row])
    active = jnp.arange(3) == slot
    got = []
    for i in range(lens[decode_row], lens[decode_row] + steps):
        state, logits, _ = decode(params, state, jnp.full((3,), toks[decode_row][i], jnp.int32),
                                  jnp.full((3,), i, jnp.int32), active)
        got.append(np.asarray(logits[slot]))
    return after, got, state


@pytest.mark.parametrize("seed,T", [(0, 8), (3, 16), (5, 24)])
def test_chunked_prefill_then_decode_agrees_with_the_references_logits(ref, seed, T):
    """Two rows of unlike length in one call (37 and 11 tokens: the short one ends in an early chunk, inside a
    chunk of the delta rule, and rides on with length 0), in several chunkings, then eight decode steps of the
    long one: every logit against the reference's one forward over the whole sequence."""
    model, params = program(seed)
    rng = np.random.default_rng(seed)
    toks = [rng.integers(2, 256, 45).astype(np.int32), rng.integers(2, 256, 11).astype(np.int32)]
    after, got, _ = run_prefill_then_decode(model, params, toks, [37, 11], T)
    want = [ref.forward(ref_config(), seed, t) for t in toks]
    gaps = [float(np.max(np.abs(after[0] - want[0][36]))), float(np.max(np.abs(after[1] - want[1][10])))]
    gaps += [float(np.max(np.abs(g - want[0][37 + j]))) for j, g in enumerate(got)]
    assert max(gaps) <= LOGIT_GAP_MAX, gaps
    assert float(np.std(want[0])) > 0.5  # logits spread ~1
    low = ref.forward(ref_config(), seed, toks[0], precision="fp8")  # the control, one precision step down
    assert float(np.max(np.abs(low[36:] - want[0][36:]))) > 2 * LOGIT_GAP_MAX


def test_a_row_without_a_prompt_leaves_its_slot_as_it_was():
    model, params = program(0)
    toks = [np.arange(2, 22).astype(np.int32), np.zeros(0, np.int32)]
    state0 = jax.tree_util.tree_map(lambda a: a + 1, model.init_state(3, 64))  # every slot holds something
    prefill = jax.jit(model.prefill)
    part = np.zeros((2, 8), np.int32)
    part[0] = toks[0][:8]
    state, _, _ = prefill(params, state0, part, jnp.asarray([2, 0], jnp.int32), jnp.zeros((2,), jnp.int32),
                          jnp.asarray([8, 0], jnp.int32))
    for new, old in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(state0)):
        assert np.array_equal(np.asarray(new[0]), np.asarray(old[0])) and np.array_equal(np.asarray(new[1]), np.asarray(old[1]))
        assert not np.array_equal(np.asarray(new[2]), np.asarray(old[2]))


def test_a_refilled_slot_starts_from_zero_state(ref):
    """A slot that held another prompt's recurrent state, conv tail and rows gives the new prompt the logits a
    fresh one gives, to the bit: the first chunk of a prompt zeroes what the slot held."""
    model, params = program(1)
    rng = np.random.default_rng(1)
    first, second = rng.integers(2, 256, 30).astype(np.int32), rng.integers(2, 256, 19).astype(np.int32)
    prefill = jax.jit(model.prefill)

    def fill(state, toks):
        for c in range(-(-len(toks) // 8)):
            part = np.zeros((1, 8), np.int32)
            here = min(8, len(toks) - c * 8)
            part[0, :here] = toks[c * 8:c * 8 + here]
            state, logits, _ = prefill(params, state, part, jnp.asarray([1], jnp.int32), jnp.full((1,), c * 8, jnp.int32),
                                       jnp.asarray([here], jnp.int32))
        return state, np.asarray(logits[0])

    used, _ = fill(model.init_state(2, 64), first)
    assert float(jnp.max(jnp.abs(used[0]["S"][1]))) > 0.01
    _, refilled = fill(used, second)
    _, fresh = fill(model.init_state(2, 64), second)
    assert np.array_equal(refilled, fresh)
    assert float(np.max(np.abs(fresh - ref.forward(ref_config(), 1, second)[-1]))) <= LOGIT_GAP_MAX


def test_copy_state_copies_both_kinds():
    model, _ = program(0)
    state = model.init_state(3, 16)
    state = [{k: jax.random.normal(jax.random.PRNGKey(i), a.shape).astype(a.dtype) for k, a in st.items()}
             for i, st in enumerate(state)]
    assert [sorted(st) for st in state] == [["S", "conv"]] * 3 + [["k", "v"]]
    copied = model.copy_state(state, 2, 0)
    for new, old in zip(jax.tree_util.tree_leaves(copied), jax.tree_util.tree_leaves(state)):
        assert np.array_equal(np.asarray(new[0]), np.asarray(old[2])) and not np.array_equal(np.asarray(old[0]), np.asarray(old[2]))
        assert np.array_equal(np.asarray(new[1:]), np.asarray(old[1:]))


def _kinds_by_leaf_names(state):
    """The rule the counts had before they were read from shapes: rows a token are the leaves a model names ``k``,
    ``v``, ``kv`` or ``ik`` (and a leaf under no name, the toy decoder's pairs); every other leaf is recurrent."""
    kinds = {"kv_bytes": 0, "recurrent_bytes": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        kinds["kv_bytes" if not names or names[-1] in ("k", "v", "kv", "ik") else "recurrent_bytes"] += leaf.nbytes
    return kinds


@pytest.mark.parametrize("decoder", sorted({n for n in decoders.DECODERS if n.endswith("-tiny")}) + ["toy"])
def test_slot_state_is_counted_in_two_kinds_by_the_names_of_its_leaves(decoder):
    """``serving.state_bytes_by_kind``: rows a position are the leaves that grow with the positions a slot holds,
    read from shapes, and that gives what the leaves' names gave, for every decoder on record and the toy. Positions
    that equal a width (16 here: olmo's head size and value head size) misfile nothing, and neither do positions
    that a model rounds up to a tile."""
    from daft_tpu.models.serving import state_bytes_by_kind

    if decoder == "toy":
        from daft_tpu.models.lm import DecoderLM, DecoderLMConfig

        model = DecoderLM(DecoderLMConfig.from_name("tiny"))
    else:
        d = decoders.DECODERS[decoder]
        model = d.model(d.from_name(decoder))
    for slots, positions in ((3, 16), (2, 100)):
        state = model.init_state(slots, positions)
        kinds = state_bytes_by_kind(model, slots, positions)
        assert kinds == _kinds_by_leaf_names(state)
        assert sum(kinds.values()) == sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    kinds = state_bytes_by_kind(model, 3, 16)
    if decoder == TINY:
        per_slot = 3 * (4 * 4 * 16 * 8 + 2 * 3 * 128)                      # three linear layers: S float32, conv tail
        assert kinds == {"kv_bytes": 3 * 2 * 4 * 16 * 16 * 2, "recurrent_bytes": 3 * per_slot}  # one attention layer
    elif decoder in ("granite-hybrid-tiny",):                               # ssm and conv beside k and v
        assert 0 < kinds["recurrent_bytes"] and kinds["kv_bytes"] > 0
    elif decoder == "pangu-ultra-moe-tiny":                                 # two kept hidden states beside the rows
        assert kinds["recurrent_bytes"] == 3 * 2 * 64 * 4 and kinds["kv_bytes"] > 0
    else:                                                                   # rows alone
        assert kinds["recurrent_bytes"] == 0 and kinds["kv_bytes"] > 0


# -- variants that leave mathematics out read not correct ----------------------------------------
@pytest.mark.parametrize("variant", ["beta_without_the_2", "no_qk_normalisation"])
def test_a_program_that_leaves_mathematics_out_is_outside_the_limit(ref, variant, monkeypatch):
    sound = oh.linear_inputs
    if variant == "beta_without_the_2":  # beta = sigmoid(.): the transition's eigenvalue stays in (0, 1)
        monkeypatch.setattr(oh, "linear_inputs", lambda cfg, *rest: sound(
            dataclasses.replace(cfg, linear_allow_neg_eigval=False), *rest))
    else:  # q and k as the conv and the SiLU leave them
        monkeypatch.setattr(oh, "_l2_normalised", lambda x: x)
    model, params = program(0)
    rng = np.random.default_rng(0)
    toks = [rng.integers(2, 256, 45).astype(np.int32)]
    after, got, _ = run_prefill_then_decode(model, params, toks, [37], 8)
    want = ref.forward(ref_config(), 0, toks[0])
    gaps = [float(np.max(np.abs(after[0] - want[36])))] + [float(np.max(np.abs(g - want[37 + j]))) for j, g in enumerate(got)]
    assert max(gaps) > LOGIT_GAP_MAX, (variant, gaps)


# -- the shared attention core -------------------------------------------------------------------
def test_the_batcher_logprobs_agree_with_the_references_full_forward(ref):
    model, params = program(4)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(2, 256, n).astype(np.int32) for n in (23, 9, 40, 17, 5)]
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=57, eos_id=None, max_prompt_tokens=48, prefill_chunk=16)
    out = b.run([Request(tokens=p, max_new_tokens=8) for p in prompts])
    assert b.chunk == 16 and b.chunk % model.prefill_multiple == 0
    worst = 0.0
    for p, ans, lps in zip(prompts, out, b.last_logprobs):
        logits = ref.forward(ref_config(), 4, np.concatenate([p, np.asarray(ans, np.int32)]), logits_from=len(p) - 1)
        lp = jax.nn.log_softmax(jnp.asarray(logits[:len(ans)]), axis=-1)
        worst = max(worst, float(np.max(np.abs(np.asarray(lp)[np.arange(len(ans)), ans] - np.asarray(lps)))))
    assert worst <= LOGPROB_GAP_MAX, worst


def test_serving_spans_say_which_form_of_the_delta_rule_each_program_traced():
    from daft_tpu import profiling

    model, params = program(0)
    b = ContinuousBatcher(model, params, num_slots=2, max_seq_len=40, eos_id=None, max_prompt_tokens=32)
    began = profiling.span_clock_ns()
    b.run([Request(tokens=np.arange(2, 12 + i).astype(np.int32), max_new_tokens=3) for i in range(3)])
    spans = [s for s in profiling.recent_device_spans() if s.start_ns >= began]
    pre = [s for s in spans if s.name == "serve.prefill"]
    dec = [s for s in spans if s.name == "serve.decode_step"]
    assert pre and dec and all(s.count["delta"] == "chunked" for s in pre) and all(s.count["delta"] == "recurrent" for s in dec)
    assert not any(k.startswith("moe.") for s in dec for k in s.count)  # no experts, no counts


def test_prompt_runs_the_decoder_through_the_normal_path(ref):
    import daft_tpu
    from daft_tpu import col, profiling
    from daft_tpu.functions import prompt

    docs = ["alpha beta gamma delta epsilon zeta", "one two three", "a b c d e f g h i j k l m n"]
    df = daft_tpu.from_pydict({"doc": docs})
    out = df.with_column("answer", prompt(col("doc"), provider="flax_random", model=TINY, num_slots=2,
                                          max_prompt_tokens=16, max_new_tokens=4, ignore_eos=True, logprobs=True,
                                          seed=2)).to_pydict()["answer"]
    assert len(out) == 3 and all(len(a["token_ids"]) == 4 for a in out)
    run = [s for s in profiling.recent_device_spans() if s.name == "prompt.run"][-1].count
    cfg = oh.OlmoHybridConfig.from_name(TINY)
    positions = 16 + 4 + 1
    kv = 2 * cfg.num_key_value_heads * cfg.head_dim * 2                     # one attention layer, bfloat16
    recurrent = 3 * (4 * cfg.linear_num_value_heads * cfg.linear_value_head_dim * cfg.linear_key_head_dim
                     + 2 * (cfg.linear_conv_kernel_dim - 1) * cfg.conv_dim)
    rows = -(-positions // oh.ROW_TILE) * oh.ROW_TILE                       # a slot's rows are held in whole tiles
    assert run["positions"] == positions and rows == 32 and run["kv_bytes"] == 2 * rows * kv
    assert run["recurrent_bytes"] == 2 * recurrent and run["state_bytes"] == run["kv_bytes"] + run["recurrent_bytes"]


def test_names_and_cuts_are_looked_up_in_one_record():
    assert decoders.DECODERS["Olmo-Hybrid-7B"] is decoders.DECODERS[TINY]
    assert decoders.DECODERS[TINY].cut_options == ("num_hidden_layers",) and "num_hidden_layers" in decoders.cut_options()
    cfg = oh.OlmoHybridConfig.from_name("Olmo-Hybrid-7B", num_hidden_layers=12)
    assert cfg.layer_types == ((oh.LINEAR,) * 3 + (oh.FULL,)) * 3 and cfg.hidden_size == 3840 and cfg.conv_dim == 11520
    assert len(oh.OlmoHybridConfig.from_name("Olmo-Hybrid-7B").layer_types) == 32
    for layers in (10, 3, 36, -4):  # no whole number of periods, or more than the model has
        with pytest.raises(DaftValueError, match="periods of 4"):
            oh.OlmoHybridConfig.from_name("Olmo-Hybrid-7B", num_hidden_layers=layers)
    with pytest.raises(DaftValueError, match="Olmo-Hybrid-7B"):
        oh.OlmoHybridConfig.from_name("Olmo-Hybrid")
    from daft_tpu.ai.flax_provider import FlaxPrompter

    with pytest.raises(DaftValueError, match="num_hidden_layers"):
        FlaxPrompter(TINY, expert_shard=(0, 2))  # another decoder's cut


# -- the kernels over the key/value rows: the chunk's write, and the attention where the rows lie ---
def test_the_cache_kernels_move_what_xlas_slices_move():
    """``ops/pallas_cache_blocks.py`` at the narrowest sizes its kernel serves (a head size of one lane tile, blocks
    of one step), interpreted: the chunk's valid rows land where XLA's slices put them and nowhere else (a row of
    length 0 and the tail of a row that ends inside the chunk keep what the slot held), for rows at unlike slots."""
    from daft_tpu.ops import pallas_cache_blocks as pcb

    slots_n, KV, S, hd, B, T = 5, 3, 3 * 128 + 7, 128, 3, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    cache = jax.random.normal(ks[0], (slots_n, KV, S, hd)).astype(jnp.bfloat16)
    new = jax.random.normal(ks[1], (B, T, KV, hd)).astype(jnp.bfloat16)
    slots, starts = jnp.asarray([4, 0, 2], jnp.int32), jnp.full((B,), 128, jnp.int32)
    lengths = jnp.asarray([128, 0, 37], jnp.int32)
    want = pcb.write_blocks_xla(cache, new, slots, starts, lengths)
    got = pcb.write_blocks_kernel(cache, new, slots, starts, lengths, interpret=True)
    assert got.dtype == want.dtype == jnp.bfloat16                                              # one dtype, either path
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert np.array_equal(np.asarray(got[0], np.float32), np.asarray(cache[0], np.float32))      # length 0: as it was
    assert not np.array_equal(np.asarray(got[4, :, 128:256], np.float32), np.asarray(cache[4, :, 128:256], np.float32))
    assert np.array_equal(np.asarray(got[2, :, 128 + 37:], np.float32), np.asarray(cache[2, :, 128 + 37:], np.float32))
    # which path a program takes is decided from the backend and the shapes: none of the tiny decoder's, here
    assert not pcb.kernels_apply(cache.shape, T) and not pcb.kernels_apply((4, 4, 64, 16), 16)


@pytest.mark.parametrize("slots", [8, 9, 16])
def test_the_cache_kernels_lower_for_a_described_v5e(slots, monkeypatch):
    """The kernels over the rows the model holds at the cell's sizes (30 heads x 16,449 positions x 128, blocks of
    512), lowered and compiled for a described v5e without the chip: the write aliases the cache, the attention
    kernel of a chunk and of a decode step is handed no copy of it, and the whole attention of a prefill call and of
    a decode step (the new rows' write, the kernel) holds no temporary of a cache's size with both caches aliased.
    A slot count of whole sublane tiles (8, the cell's; 16) is the case that copied: the device keeps rows that end
    inside a tile slots-minor there, which is why ``init_state`` holds whole tiles."""
    topologies = pytest.importorskip("jax.experimental.topologies")
    from jax.sharding import SingleDeviceSharding

    from daft_tpu.ops import pallas_attention, pallas_cache_attention as pca, pallas_cache_blocks as pcb

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no described v5e here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    of = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    cfg = oh.OlmoHybridConfig.from_name("Olmo-Hybrid-7B", num_hidden_layers=4)
    held = jax.eval_shape(lambda: oh.OlmoHybridLM(cfg).init_state(slots, 16449))[3]["k"]
    assert held.shape == (slots, 30, 16464, 128) and held.dtype == jnp.bfloat16
    rows = slots * 30 * 16464 * 128 * 2
    cache, new = of(held.shape, held.dtype), of((4, 512, 30, 128), jnp.bfloat16)
    ints = of((4,), jnp.int32)
    write = jax.jit(pcb.write_blocks_kernel, donate_argnums=(0,)).lower(cache, new, ints, ints, ints).compile()
    mem = write.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20 and mem.alias_size_in_bytes >= rows
    attend = lambda q, k, v, s, a, n: pca.cache_attention(q, k, v, s, a, n, scale=128 ** -0.5)  # noqa: E731
    for q, n in ((of((4, 512, 30, 1, 128), jnp.bfloat16), ints), (of((slots, 1, 30, 1, 128), jnp.bfloat16), of((slots,), jnp.int32))):
        assert jax.jit(attend).lower(q, cache, cache, n, n, n).compile().memory_analysis().temp_size_in_bytes < 64 << 20
    # the model's own two attentions, as the chip traces them
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    layer = jax.eval_shape(lambda: oh._init_layer(cfg, jax.random.PRNGKey(0), oh.FULL))
    p = jax.tree_util.tree_map(lambda x: of(x.shape, x.dtype), layer)
    st = {"k": cache, "v": cache}
    prefill = jax.jit(lambda p, u, st, s, a, n: oh._attn_prefill(cfg, p, u, st, s, a, n), donate_argnums=(2,))
    decode = jax.jit(lambda p, u, st, pos, act: oh._attn_decode(cfg, p, u, st, pos, act), donate_argnums=(2,))
    # (a prefill call's projections are 94 MB of float32 for its 2,048 tokens: its bound is an eighth of one cache)
    for compiled, most in ((prefill.lower(p, of((4, 512, 3840), jnp.bfloat16), st, ints, ints, ints).compile(), rows // 8),
                           (decode.lower(p, of((slots, 1, 3840), jnp.bfloat16), st, of((slots,), jnp.int32),
                                         of((slots,), jnp.bool_)).compile(), 64 << 20)):
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < most and mem.alias_size_in_bytes >= 2 * rows, (mem.temp_size_in_bytes, mem.alias_size_in_bytes)


# -- the model with the attention kernel (interpreted) and on XLA's path -------------------------
LANE_TILE = dict(num_attention_heads=2, num_key_value_heads=2, head_dim=128)   # the narrowest attention the kernel serves


@pytest.fixture
def fused_attention(monkeypatch):
    """The backend rule answers as on a TPU, and the kernels it then selects run
    interpreted. ``calls`` keeps the shape of q at each call traced."""
    from daft_tpu.ops import pallas_attention, pallas_cache_attention as pca, pallas_cache_blocks as pcb

    calls = []
    attend, write = pca.cache_attention, pcb.write_blocks_kernel

    def interpreted(q, *rest, scale):
        calls.append(q.shape)
        return attend(q, *rest, scale=scale, interpret=True)

    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: True)
    monkeypatch.setattr(pca, "cache_attention", interpreted)
    monkeypatch.setattr(pcb, "write_blocks_kernel", lambda *a: write(*a, interpret=True))
    return calls


def _prefill_then_decode(model, params, lengths, T, steps):
    """Rows of ``lengths`` tokens at slots 3, 2, 1 of four as calls of one chunk, then ``steps`` decode steps in
    which the slot of the first row is idle; -> (the logits after each call and step, the key/value rows)."""
    rng = np.random.default_rng(1)
    rows = len(lengths)
    chunks = -(-max(lengths) // T)
    tokens = rng.integers(2, 256, (rows, chunks * T)).astype(np.int32)
    state = model.init_state(rows + 1, 600)
    slots = jnp.arange(rows, 0, -1, dtype=jnp.int32)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    logits = []
    for c in range(chunks):
        here = np.clip(np.asarray(lengths) - c * T, 0, T).astype(np.int32)
        state, out, _ = prefill(params, state, tokens[:, c * T:(c + 1) * T], slots, jnp.full((rows,), c * T, jnp.int32), here)
        logits.append(np.asarray(out)[here > 0])
    positions = np.zeros((rows + 1,), np.int32)
    positions[np.asarray(slots)] = lengths
    active = np.asarray([False] + [True] * rows)
    active[slots[0]] = False
    for _ in range(steps):
        state, out, _ = decode(params, state, jnp.full((rows + 1,), 7, jnp.int32), jnp.asarray(positions), jnp.asarray(active))
        logits.append(np.asarray(out)[active])
        positions += active
    return logits, [np.asarray(st[n], np.float32) for st in state if "k" in st for n in ("k", "v")]


def test_prefill_and_decode_with_the_kernel_equal_xlas_path(monkeypatch, fused_attention):
    """Three rows of unlike lengths (one ends in the first chunk) through three
    calls of one chunk and four decode steps with an idle slot and an empty one:
    every logit and every slot's key/value rows agree across the two paths."""
    from daft_tpu.ops import pallas_attention, pallas_cache_attention as pca

    T = 128
    model, params = oh.init_olmo_params(dataclasses.replace(oh.OlmoHybridConfig.from_name(TINY), **LANE_TILE), 0)
    lengths = [3 * T, 60, T + 31]
    fused, fused_rows = _prefill_then_decode(model, params, lengths, T, steps=4)
    assert fused_attention == [(3, T, 2, 1, 128), (4, 1, 2, 1, 128)]                    # one trace a program
    assert not pca.cache_attention_applies((3, 8, 4, 1, 16), (4, 4, 64, 16), jnp.bfloat16)   # the tiny decoder's own widths
    fused_attention.clear()
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: False)
    xla, xla_rows = _prefill_then_decode(model, params, lengths, T, steps=4)
    assert fused_attention == [] and len(fused) == len(xla) == 3 + 4
    gap = max(float(np.max(np.abs(a - b))) for a, b in zip(fused, xla))
    assert gap <= 3e-2, gap  # bfloat16 rounding of the attention's result under logits that spread ~1
    assert min(float(np.std(x)) for x in xla) > 0.3
    for a, b in zip(fused_rows, xla_rows):
        assert np.max(np.abs(a - b)) <= 0.07  # one bfloat16 step of rows that spread ~1 through the layers before


def test_serving_spans_say_which_attention_each_program_traced(monkeypatch, fused_attention):
    from daft_tpu.ops import pallas_attention

    model, params = oh.init_olmo_params(dataclasses.replace(oh.OlmoHybridConfig.from_name(TINY), **LANE_TILE), 1)
    reqs = lambda: [Request(tokens=np.arange(2, 2 + n).astype(np.int32), max_new_tokens=3) for n in (300, 100, 140)]  # noqa: E731
    b = ContinuousBatcher(model, params, num_slots=4, max_seq_len=600, eos_id=None, prefill_chunk=128)
    out = b.run(reqs())
    assert b._noted == {"serve.prefill": {"attn": "fused", "delta": "chunked"},
                        "serve.decode_step": {"attn": "fused", "delta": "recurrent"}}
    from daft_tpu.profiling import newest_device_span

    count = newest_device_span("serve.prefill").count
    assert (count["attn"], count["block_rows"], count["padded_block_rows"]) == ("fused", 6 + 1 + 3, 4 * 6)
    assert newest_device_span("serve.decode_step").count["attn"] == "fused"
    # the same prompts on XLA's path choose the same tokens, and the spans say so
    monkeypatch.setattr(pallas_attention, "backend_is_tpu", lambda: False)
    x = ContinuousBatcher(model, params, num_slots=4, max_seq_len=600, eos_id=None, prefill_chunk=128)
    assert x.run(reqs()) == out
    assert x._noted["serve.prefill"]["attn"] == x._noted["serve.decode_step"]["attn"] == "xla"
    assert newest_device_span("serve.decode_step").count["attn"] == "xla"
