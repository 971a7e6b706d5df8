"""The ``prompt`` cell's benchmark files on the CPU: its traffic generator, its
entry, comparison and readers driven through ``run.py``'s own ``run_cell`` from
a manifest of its own (``data/rehearsal_prompt.json``: the tiny hybrid decoder
under the per-layer entries the real cell lists), and a fault planted where the
answers are produced. ``benchmark/rehearsal.json`` is not edited."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import compare, manifest  # noqa: E402

CELL = "granite_4_0_h_small_prompt.docs_lognormal_1k_out64"
TINY_CELL = "rehearsal_tiny_granite.rehearsal_docs"
REHEARSAL = os.path.join(DATA, "rehearsal_prompt.json")


@pytest.fixture(scope="module")
def bench_run():
    return manifest.load_module(os.path.join(BENCH, "run.py"))


@pytest.fixture(scope="module")
def real_cell():
    return manifest.resolve(CELL)


def test_the_configuration_states_the_published_sizes_and_the_cut(real_cell):
    cfg = real_cell.config
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if os.path.exists(catalog):  # every number of the catalog's config under the same key, but the three cut
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if json.loads(l)["name"] == cfg["model"])
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers", "num_local_experts", "vocab_size"}
        assert {k: cfg["published"][k] for k in differs} == {k: row["config"][k] for k in differs}
    from daft_tpu.models.granite_hybrid import GraniteHybridConfig

    o = cfg["options"]
    prog = GraniteHybridConfig.from_name(cfg["model"], o["num_hidden_layers"], o["expert_shard"], o["vocab_shard"])
    # the program's published sizes, kept as data, are the file's; the cut is the file's
    for key in ("hidden_size", "intermediate_size", "shared_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts_per_tok", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_expand", "mamba_chunk_size", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier", "logits_scaling", "rms_norm_eps"):
        assert getattr(prog, key) == cfg[key], key
    assert prog.num_local_experts == cfg["router_outputs"] == cfg["published"]["num_local_experts"] == 72
    assert (prog.held_experts, prog.held_vocab, prog.num_hidden_layers) == \
        (cfg["num_local_experts"], cfg["vocab_size"], cfg["num_hidden_layers"]) == (36, 50176, 10)
    assert list(prog.layer_types) == cfg["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert real_cell.reference.share(cfg) == ((0, 36), (0, 50176))


def test_every_partition_holds_the_same_lengths_whatever_the_seed(real_cell):
    gen, traffic = real_cell.generator, real_cell.traffic
    base = gen.lengths(traffic["length_tokens"], traffic["partition_rows"])
    assert (base.min(), base.max(), len(base)) == (189, 4096, 64)  # the lowest mid-point clears the clip at 128
    assert 1250 < base.mean() < 1330 and abs(np.median(base) - 1024) < 25  # ~82k prompt tokens a partition
    small = dict(traffic, pool_rows=128, lexicon_words=50)
    a, b = gen.documents(small, 3), gen.documents(small, 2 ** 31 + 5)
    words = lambda docs: [len(d.split()) for d in docs]  # noqa: E731
    assert a != b and words(a) != words(b)
    for docs in (a, b):
        for start in (0, 64):
            assert sorted(words(docs[start:start + 64])) == sorted(base.tolist())
    assert len(set(a)) == 128  # no document repeats, none shares a prefix worth routing


def test_the_counts_of_the_work_follow_the_shapes(real_cell):
    cfg, ref = real_cell.config, real_cell.reference
    # ISSUE 29's arithmetic: 1,626M parameters touched a token -> ~3.3 GFLOP in products, the scan and attention beside
    per_token = ref.step_flops(cfg, 1.0, 645.0, held_share=0.5)
    assert 3.3e9 < per_token < 3.8e9
    assert ref.ssd_scan_flops(cfg, 1.0) == 6 * 128 * 64 * 128
    assert ref.expert_matmul_flops(cfg, 1.0) == 6 * 4096 * 768
    # a decode step's experts are bound by their weights: 36 experts x 9.44M parameters x 2 bytes
    assert ref.expert_matmul_bytes(cfg, 160, 1, 36) == pytest.approx(36 * 9.44e6 * 2, rel=0.01)
    assert ref.ssd_scan_bytes(cfg, 32, 32) == pytest.approx(32 * 2 * 4.19e6, rel=0.02)  # the state, in and out


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_the_prompt_cell_rehearses_on_the_cpu_and_its_control_reads_not_correct(bench_run, seed, monkeypatch):
    from lib import program_spans

    # Beside five other test workers the host stalls between a wrapper and its span for longer than
    # the 100 us the chip's runs are held to (they read ~10 us); the control flow is what is tested here.
    monkeypatch.setattr(program_spans, "MAX_BRACKET_NS", 50_000_000)
    monkeypatch.setattr(program_spans, "WIDEN_NS", 50_000_000)
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=seed, seconds=0.5, trace_on=True, control=True)
    assert rec["correct"] is True and rec["failed"] == 0 and rec["attempted"] >= 16, rec["compared"]
    c = rec["compared"]
    assert c["answers_not_8_tokens"]["value"] == c["token_ids_outside_slice"]["value"] == 0
    assert c["ids_out_of_sequence"]["value"] == c["prompt_tokens_not_words"]["value"] == 0
    assert c["rows_compared"]["value"] == 8 and c["tokens_compared"]["value"] == 64
    assert rec["control"]["correct"] is False  # one precision step down, in the program's place
    assert rec["control"]["compared"]["logprob_gap"]["value"] > c["logprob_gap"]["limit"] > c["logprob_gap"]["value"]
    # the readers of the program's spans and counters find them; the device's need a device
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    assert {"prompt.tokenize_s_per_krow", "serve.slot_occupancy", "serve.padded_token_share",
            "moe.held_assignment_share", "moe.expert_load_max_over_mean", "lm.setup_init_s",
            "lm.setup_first_prefill_s"} <= set(m)
    assert not {"lm.step_mfu", "kernel.ssd_scan_roofline", "kernel.expert_matmul_roofline", "serve.decode_step_ms",
                "lm.mamba_ms_per_ktoken", "serve.host_exposed_s_per_krow"} & set(m)
    assert 0 < m["serve.slot_occupancy"] <= 100 and 0 <= m["serve.padded_token_share"] < 100
    assert 35 < m["moe.held_assignment_share"] < 65 and m["moe.expert_load_max_over_mean"] >= 1


def test_a_fault_in_the_decode_path_reads_not_correct(bench_run, monkeypatch):
    from daft_tpu.models import granite_hybrid

    sound = granite_hybrid.ssd_step

    def forgetful(x, dt, a, b, c, s):  # the recurrence reads its state and never writes it back
        y, _ = sound(x, dt, a, b, c, s)
        return y, s

    monkeypatch.setattr(granite_hybrid, "ssd_step", forgetful)
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=1, seconds=0.5, trace_on=False)
    assert rec["correct"] is False and not compare.verdict({"g": rec["compared"]["logprob_gap"]})


def test_the_entry_refuses_a_program_that_would_drop_the_options(monkeypatch):
    from daft_tpu.ai import flax_provider

    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    traffic = type("T", (), {"column": "doc", "df": None})()
    # an option the program's prompter does not take is refused by name, before the query is built
    with pytest.raises(SystemExit, match="prefill_chunk"):
        cell.entry.build(traffic, dict(cell.config, options=dict(cell.config["options"], prefill_chunk=16)), 1)
    # a program whose prompt hands its prompter no options (the parent of PR 29) is refused when the cell is resolved
    monkeypatch.delattr(flax_provider, "PROMPTER_OPTIONS")
    with pytest.raises(SystemExit, match="cannot run"):
        manifest.load_module(os.path.join(BENCH, "entries", "prompt_text.py"))


def test_the_entrys_shapes_are_those_the_prompter_runs():
    """``lowerables`` makes the two programs' arguments from the configuration alone; a run's own are the same."""
    import jax
    from daft_tpu.ai.flax_provider import FlaxPrompter

    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    got = cell.entry.lowerables(cell.config)
    assert set(got) == {"jit__prefill_impl", "jit__decode_impl"}
    inst = FlaxPrompter(cell.config["model"], **cell.config["options"])
    inst.prompt(["a b c d e", "f g h"])
    b = inst._batcher
    (params, state, logits, tokens, *_), (_, _, _, positions, _, key) = got["jit__prefill_impl"][1], got["jit__decode_impl"][1]
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert shapes((params, state, logits)) == shapes((b.params, b.state, b.cur_logits))
    assert tokens.shape == (b.prefill_rows, b.chunk) and positions.shape == (b.B,) and shapes(key) == shapes(b._key)
    # the default path is the measured one: chunks of 512 tokens, as many prompts a call as make 2,048
    from daft_tpu.models.serving import ContinuousBatcher
    assert (ContinuousBatcher.DEFAULT_CHUNK, ContinuousBatcher.PREFILL_TOKENS) == (512, 2048)
    real = manifest.resolve(CELL).config["options"]
    assert not {"prefill_chunk", "prefill_batch"} & set(real) and real["num_slots"] >= 4 and real["max_prompt_tokens"] >= 512


# -- device time by scope over two programs, on a trace made by hand ------------------------------
PREFILL_TEXT = """
HloModule jit__prefill_impl

%fused_in (p0: bf16[8,4]) -> f32[8,4] {
  %p0 = bf16[8,4]{1,0} parameter(0)
  ROOT %dot.1 = f32[8,4]{1,0} dot(%p0, %p0), metadata={op_name="jit(_prefill_impl)/layer_0/mamba/dot_general"}
}

%body (c: s32[]) -> s32[] {
  %c = s32[] parameter(0)
  ROOT %fusion.7 = f32[8,4]{1,0} fusion(%c), kind=kLoop, calls=%fused_in, metadata={op_name="jit(_prefill_impl)/layer_0/mamba/ssd_scan/mul"}
}

ENTRY %main (a: bf16[8,4]) -> f32[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %fusion.1 = f32[8,4]{1,0} fusion(%a), kind=kOutput, calls=%fused_in, metadata={op_name="jit(_prefill_impl)/layer_0/norm"}
  %while.2 = s32[] while(%a), condition=%body, body=%body, metadata={op_name="jit(_prefill_impl)/layer_0/mamba/ssd_scan/while"}
  %ragged-dot-none.3 = f32[8,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %copy.4 = f32[8,4]{1,0} copy(%fusion.1), metadata={op_name="jit(_prefill_impl)/layer_0/head/copy"}
}
"""


def test_device_time_by_scope_over_two_programs_on_a_hand_made_trace():
    from lib import lm_scopes, scopes

    texts = {lm_scopes.PREFILL: scopes.parse_hlo(PREFILL_TEXT), lm_scopes.DECODE: scopes.parse_hlo(PREFILL_TEXT)}
    ops = [[100, 10, "%fusion.1 = f32[8,4] fusion(...)"],            # a matmul fusion: its dot's scope, mamba
           [120, 50, "%while.2 = s32[] while(...)"],                 # the loop: listed beside its body, skipped
           [125, 40, "%fusion.7 = f32[8,4] fusion(...)"],            # the body's fusion: a dot again -> mamba
           [180, 30, "%ragged-dot-none.3 = f32[8,4] custom-call"],   # XLA's grouped product: experts, by name
           [215, 5, "%copy.4 = f32[8,4] copy(...)"],                 # head
           [300, 20, "%fusion.7 = f32[8,4] fusion(...)"],            # the same name inside the other program
           [400, 99, "%fusion.1 = f32[8,4] fusion(...)"]]            # outside every execution: no program's
    events = {"window": [0, 1000], "spans": {},
              "devices": {"/device:TPU:0": {"ops": ops, "modules": [[90, 140, "jit__prefill_impl(7)"],
                                                                     [290, 40, "jit__decode_impl(9)"],
                                                                     [950, 100, "jit__prefill_impl(7)"]]}}}
    got = lm_scopes._analyse(events, texts)
    assert got["coverage"] == 1.0
    assert got["ns"][lm_scopes.PREFILL] == dict(dict.fromkeys(lm_scopes.SCOPES + ("other",), 0.0),
                                                mamba=50.0, experts=30.0, head=5.0)
    assert got["ns"][lm_scopes.DECODE]["mamba"] == 20.0 and sum(got["ns"][lm_scopes.DECODE].values()) == 20.0
    assert lm_scopes.classify("jit(f)/layer_3/mamba/ssd_scan/dot_general") == "ssd_scan"
    assert lm_scopes.classify("jit(f)/layer_3/experts/sort") == "experts" and lm_scopes.classify(None) == "other"
    # whole executions in the window only: the third begins inside it and ends outside
    run = type("Run", (), {"events": events})()
    assert lm_scopes.programs(run) == {lm_scopes.PREFILL: [140.0], lm_scopes.DECODE: [40.0]}


@pytest.mark.parametrize("step_counts, want_decode", [
    # every program today: a step is one token for every active slot
    ([{"active": 3, "slots": 4}, {"active": 2, "slots": 4}], 5.0),
    # a decoder whose step is not one token a slot says what it processed: a block of 8 positions a slot in the first
    # step, of which the second commits what was left; ``active`` still counts slots
    ([{"active": 3, "slots": 4, "tokens": 24}, {"active": 2, "slots": 4, "tokens": 7}], 31.0),
    # an older program's spans beside a newer one's in one window: each step by what it carries
    ([{"active": 3, "slots": 4}, {"active": 2, "slots": 4, "tokens": 16}], 19.0)],
    ids=["active_where_no_tokens", "tokens_where_carried", "step_by_step"])
def test_a_decode_steps_processed_positions_are_its_tokens_counter_or_its_active_slots(step_counts, want_decode):
    from types import SimpleNamespace

    from lib import lm_scopes

    steps = [(200 + 100 * k, 280 + 100 * k, dict(c, **{"moe.assignments": 6, "moe.held_assignments": 3}), False)
             for k, c in enumerate(step_counts)]
    spans = {"serve.prefill": [(100, 190, {"tokens": 40, "padded_tokens": 64, "chunks": 2, "rows": 3, "row_chunks": 4}, False)],
             "serve.decode_step": steps + [(5000, 5080, {"active": 4, "slots": 4, "tokens": 99}, False)]}  # began after the window
    run = SimpleNamespace(events={"window": [0, 1000], "devices": {}, "spans": {}}, _lm_spans_done=True,
                          _program_spans=SimpleNamespace(offset_ns=0, spans=spans))
    n = lm_scopes.tokens(run)
    assert (n.prefill, n.padded, n.calls, n.rows, n.row_chunks) == (40.0, 64.0, 2.0, 3.0, 4.0)
    assert n.decode == want_decode and (n.active, n.slots, n.steps) == (5.0, 8.0, 2.0)
    assert (n.assignments, n.held) == (12.0, 6.0)
    # what is a thousand tokens processed follows the positions; the occupancy of the slots does not
    assert lm_scopes.per_ktoken_ms(run, 1e6 * (40 + want_decode)) == pytest.approx(1000.0)
    occupancy = manifest.load_module(os.path.join(BENCH, "metrics", "serve.slot_occupancy.py")).read
    assert occupancy(run) == pytest.approx(100.0 * 5 / 8)
