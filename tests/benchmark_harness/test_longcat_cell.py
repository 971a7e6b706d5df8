"""The LongCat-Flash ``prompt`` cell's benchmark files on the CPU: its
configuration against the catalog and the program, its traffic, the reference's
counts, its entry (``entries/prompt_decoder.py``: the decoder found through the
program's record) and readers driven through ``run.py``'s own ``run_cell`` from
a manifest of its own (``data/rehearsal_longcat.json``: the tiny decoder under
the per-layer entries the real cell lists), and a fault planted in the expert
branch. The manifest is checked by ``test_benchmark_harness.check_manifest`` and the
cell's entries by its ``check_prompt_cell``: by name, no place and no count."""

from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import compare, manifest  # noqa: E402

CELL = "longcat_flash_chat_prompt.docs_lognormal_4k_out64"
TINY_CELL = "rehearsal_tiny_longcat.rehearsal_docs"
REHEARSAL = os.path.join(DATA, "rehearsal_longcat.json")
CUT = {"num_layers", "n_routed_experts", "vocab_size"}


@pytest.fixture(scope="module")
def bench_run():
    return manifest.load_module(os.path.join(BENCH, "run.py"))


@pytest.fixture(scope="module")
def real_cell():
    return manifest.resolve(CELL)


def _wide_brackets(monkeypatch):
    """Beside five other test workers the host stalls between a wrapper and its span for longer than
    the 100 us the chip's runs are held to; the control flow is what is tested here."""
    from lib import program_spans

    monkeypatch.setattr(program_spans, "MAX_BRACKET_NS", 50_000_000)
    monkeypatch.setattr(program_spans, "WIDEN_NS", 50_000_000)


def test_the_configuration_states_the_published_sizes_and_the_cut(real_cell):
    cfg = real_cell.config
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if os.path.exists(catalog):  # every number of the catalog's config under the same key, but the three cut
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if json.loads(l)["name"] == cfg["model"])
        assert set(row["config"]) <= set(cfg)
        assert {k for k, v in row["config"].items() if cfg[k] != v} == CUT
        assert {k: cfg["published"][k] for k in CUT} == {k: row["config"][k] for k in CUT}
        assert row["source_url"] in cfg["source"]
    from daft_tpu.models.longcat_flash import LongcatFlashConfig

    o = cfg["options"]
    prog = LongcatFlashConfig.from_name(cfg["model"], o["num_layers"], o["expert_shard"], o["vocab_shard"])
    for key in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_attention_heads", "kv_lora_rank",
                "q_lora_rank", "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "mla_scale_q_lora",
                "mla_scale_kv_lora", "routed_scaling_factor", "zero_expert_num", "moe_topk", "rms_norm_eps",
                "rope_theta", "router_outputs"):
        assert getattr(prog, key) == cfg[key], key
    assert prog.n_routed_experts == cfg["published"]["n_routed_experts"] == cfg["router_outputs"] - cfg["zero_expert_num"]
    assert (prog.held_experts, prog.held_vocab, prog.num_layers) == \
        (cfg["n_routed_experts"], cfg["vocab_size"], cfg["num_layers"]) == (16, 16384, 4)
    assert real_cell.reference.share(cfg) == ((0, 16), (0, 16384))
    assert cfg["zero_expert_type"] == "identity" and cfg["scopes"][:2] == ["mla_proj", "mla_core"]
    for key in ("deployment", "assumed", "compare", "published"):
        assert cfg[key]
    assert {"lora_scales", "rope", "router", "router_bias", "head"} <= set(cfg["assumed"])
    assert cfg["compare"]["sample_rows"] == 4 and 0 < cfg["compare"]["logprob_gap_max"] < 1
    manifest_json = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in manifest_json["configs"] if c["name"] == "longcat_flash_chat_prompt")
    assert set(entry["reduced"]) == CUT and entry["source"] == "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json"


def test_every_partition_holds_the_same_lengths_whatever_the_seed(real_cell):
    gen, traffic = real_cell.generator, real_cell.traffic
    base = gen.lengths(traffic["length_tokens"], traffic["partition_rows"])
    assert (base.min(), base.max(), len(base), base.sum()) == (1112, 15088, 16, 81794)
    assert traffic["pool_rows"] == 256 and real_cell.config["batch_size"] == real_cell.config["options"]["num_slots"] == 16
    small = dict(traffic, pool_rows=32, lexicon_words=50)
    a, b = gen.documents(small, 3), gen.documents(small, 2 ** 31 + 5)
    words = lambda docs: [len(d.split()) for d in docs]  # noqa: E731
    assert a != b and words(a) != words(b)
    for docs in (a, b):
        for start in (0, 16):
            assert sorted(words(docs[start:start + 16])) == sorted(base.tolist())
    # a partition is one wave: sorted by length, four prompts a call, as many calls as the longest needs chunks
    chunks = [-(-int(max(g)) // 512) for g in np.sort(base).reshape(4, 4)]
    assert chunks == [5, 8, 12, 30] and max(base) <= real_cell.config["options"]["max_prompt_tokens"]


def test_the_counts_of_the_work_follow_the_shapes(real_cell):
    cfg, ref = real_cell.config, real_cell.reference
    # ISSUE 33's arithmetic: 638.9M parameters a layer outside the experts, 37.75M an expert
    per_token = ref.step_flops(cfg, 1.0, 0.0, held_share=0.0)
    assert per_token == pytest.approx(4 * 2 * 638.9e6, rel=0.002)
    assert ref.step_flops(cfg, 1.0, 0.0, held_share=1 / 48) - per_token == pytest.approx(4 * 0.25 * 2 * 37.75e6, rel=0.001)
    assert ref.expert_matmul_flops(cfg, 1.0) == 6 * 6144 * 2048
    # the least work of the core: 64 heads x (192 + 128) x 2 a causal pair
    assert ref.mla_core_flops(cfg, 1.0, 1.0) == 64 * 320 * 2
    # a decode step reads every held latent row once: 1,152 B a token an attention
    assert ref.mla_core_bytes(cfg, 0.0, 1.0) == 1152
    assert ref.mla_core_bytes(cfg, 16, 16 * 5000) == pytest.approx(16 * 5000 * 1152, rel=0.01)
    # a call that reaches all 16 held experts is bound by their weights: 16 x 37.75M x 2 B = 1.2 GB
    assert ref.expert_matmul_bytes(cfg, 512, 16) == pytest.approx(16 * 37.75e6 * 2, rel=0.02)
    assert ref.head_flops(cfg, 1.0) == 2 * 6144 * 16384


def test_the_manifest_holds_the_cells_entries_by_name_and_the_cell_resolves_from_a_copy(tmp_path):
    """Membership only (``test_benchmark_harness.check_prompt_cell`` has the rules): the accepted batcher's,
    prompter's and set-up's entries list this cell beside the other ``prompt`` cells, LongCat's own measurements
    list it alone, one of what it reports is an ``*mfu``, two are its kernels' rooflines. No place in a list and
    no count is held, so a later cell's entries can be appended."""
    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    harness.check_manifest(m)
    spec = harness.PROMPT_CELLS[CELL]
    assert spec["mfu"] == "lc.step_mfu" and spec["experts"] and spec["rooflines"] == {
        "kernel.mla_core_roofline", "kernel.scmoe_expert_matmul_roofline"}
    harness.check_prompt_cell(m, CELL, spec)
    # the ten readings the cell once took through wrappers of its own it now takes from the accepted entries
    listed = harness.listed_for(m, CELL)
    assert not [n for n in listed if n.startswith("lc.") and n not in spec["own"] | {spec["mfu"]}]
    assert {"serve.prefill_ms_per_ktoken", "serve.decode_step_ms", "moe.held_assignment_share", "lm.setup_init_s"} <= set(listed)
    # from a copy of the manifest elsewhere, every file of the cell is found beside the benchmark's own
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    harness.check_prompt_cell(m, CELL, spec, str(path))
    # the rehearsal manifest lists the same entries for the tiny cell
    r = manifest.load_json(REHEARSAL)
    harness.check_manifest(r, REHEARSAL)
    assert set(listed) <= harness.reported_by(r, TINY_CELL) <= harness.reported_by(m, CELL)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9])
def test_the_cell_rehearses_on_the_cpu_and_its_control_reads_not_correct(bench_run, seed, monkeypatch):
    _wide_brackets(monkeypatch)
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
    assert {"prompt.tokenize_s_per_krow", "serve.slot_occupancy", "serve.padded_token_share", "moe.held_assignment_share",
            "lc.zero_assignment_share", "lc.expert_load_max_over_mean", "lc.cache_bytes_per_token",
            "lm.setup_init_s", "lm.setup_first_prefill_s", "serve.fetch_arrays_per_step"} <= set(m)
    assert not {"lc.step_mfu", "kernel.mla_core_roofline", "kernel.scmoe_expert_matmul_roofline", "serve.decode_step_ms",
                "lc.mla_core_ms_per_ktoken", "lc.other_ms_per_ktoken", "serve.host_exposed_s_per_krow"} & set(m)
    # another decoder's own measurements list that decoder's cell: none of granite's, Olmo-Hybrid's or DeepSeek's here
    assert not any(k.startswith(("lm.", "oh.", "ds.")) and ".setup_" not in k for k in m) and "moe.expert_load_max_over_mean" not in m
    assert 0 < m["serve.slot_occupancy"] <= 100 and 0 <= m["serve.padded_token_share"] < 100
    assert m["lc.cache_bytes_per_token"] == 4 * 16 * 2  # four attentions x (8 + 8) values x 2 B
    assert 15 < m["lc.zero_assignment_share"] < 55 and 15 < m["moe.held_assignment_share"] < 55  # 4 and 4 of 12 outputs
    assert m["lc.expert_load_max_over_mean"] >= 1


def test_a_dropped_identity_part_reads_not_correct(bench_run, monkeypatch):
    import jax.numpy as jnp
    from daft_tpu.models import longcat_flash

    sound = longcat_flash._moe

    def without_identity(cfg, p, u, valid):  # the routed experts' part alone: the identity experts' is taken out again
        y, counts = sound(cfg, p, u, valid)
        idx, w = longcat_flash.route(cfg, p, u)
        zero = (idx >= cfg.n_routed_experts) & valid[:, None]
        return y - jnp.sum(jnp.where(zero, w, 0.0), axis=-1, keepdims=True) * u, counts

    monkeypatch.setattr(longcat_flash, "_moe", without_identity)
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=1, seconds=0.5, trace_on=False)
    assert rec["correct"] is False and not compare.verdict({"g": rec["compared"]["logprob_gap"]})


def test_a_program_without_the_kv_lora_scale_reads_not_correct(bench_run, monkeypatch):
    """``W_kvb`` is drawn over ``s_kv`` (``assumed.weights``), so a program that applies the scale leaves keys and
    values at the stream's scale; one that leaves it out is a third of that off, and the comparison says so."""
    import dataclasses
    from daft_tpu.models import longcat_flash

    sound = longcat_flash.mla_project
    monkeypatch.setattr(longcat_flash, "mla_project",
                        lambda cfg, *rest: sound(dataclasses.replace(cfg, mla_scale_kv_lora=False), *rest))
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    assert longcat_flash.LongcatFlashConfig.from_name(cell.config["model"]).kv_scale > 2
    rec = bench_run.run_cell(cell, seed=1, seconds=0.5, trace_on=False)
    assert rec["correct"] is False and not compare.verdict({"g": rec["compared"]["logprob_gap"]})


def test_the_entry_finds_the_decoder_through_the_programs_record():
    """``lowerables`` makes the two programs' arguments from the configuration alone; a run's own are the same."""
    import jax
    from daft_tpu.ai.flax_provider import FlaxPrompter

    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    text = manifest.load_module(os.path.join(BENCH, "entries", "prompt_text.py"))
    assert cell.entry.SPANS == text.SPANS and cell.entry.SPAN_ORDER == text.SPAN_ORDER
    got = cell.entry.lowerables(cell.config)
    assert set(got) == {"jit__prefill_impl", "jit__decode_impl"}
    inst = FlaxPrompter(cell.config["model"], **cell.config["options"])
    inst.prompt(["a b c d e", "f g h"])
    b = inst._batcher
    (params, state, logits, tokens, *_), (_, _, _, positions, _, key) = got["jit__prefill_impl"][1], got["jit__decode_impl"][1]
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert shapes((params, state, logits)) == shapes((b.params, b.state, b.cur_logits))
    assert tokens.shape == (b.prefill_rows, b.chunk) and positions.shape == (b.B,) and shapes(key) == shapes(b._key)
    # the same entry serves the other decoder on record, and says which names there are for one that is not
    granite = manifest.resolve("rehearsal_tiny_granite.rehearsal_docs", os.path.join(DATA, "rehearsal_prompt.json")).config
    assert set(cell.entry.lowerables(granite)) == set(got)
    with pytest.raises(SystemExit, match="longcat-flash-tiny"):
        cell.entry.lowerables(dict(cell.config, model="LongCat-Flash"))
    # an option the program's prompter does not take is refused by name, before the query is built
    traffic = type("T", (), {"column": "doc", "df": None})()
    with pytest.raises(SystemExit, match="prefill_chunk"):
        cell.entry.build(traffic, dict(cell.config, options=dict(cell.config["options"], prefill_chunk=16)), 1)
    real = manifest.resolve(CELL).config["options"]
    assert not {"prefill_chunk", "prefill_batch"} & set(real)


# -- device time by classes the caller names, on a trace made by hand ----------------------------
PREFILL_TEXT = """
HloModule jit__prefill_impl

%fused_in (p0: bf16[8,4]) -> f32[8,4] {
  %p0 = bf16[8,4]{1,0} parameter(0)
  ROOT %dot.1 = f32[8,4]{1,0} dot(%p0, %p0), metadata={op_name="jit(_prefill_impl)/layer_0/mla_core/dot_general"}
}

%body (c: s32[]) -> s32[] {
  %c = s32[] parameter(0)
  ROOT %fusion.7 = f32[8,4]{1,0} fusion(%c), kind=kLoop, calls=%fused_in, metadata={op_name="jit(_prefill_impl)/layer_0/mla_core/while/body/mul"}
}

ENTRY %main (a: bf16[8,4]) -> f32[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %fusion.1 = f32[8,4]{1,0} fusion(%a), kind=kOutput, calls=%fused_in, metadata={op_name="jit(_prefill_impl)/layer_0/add"}
  %while.2 = s32[] while(%a), condition=%body, body=%body, metadata={op_name="jit(_prefill_impl)/layer_0/mla_core/while"}
  %custom-call.3 = f32[8,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_prefill_impl)/layer_0/experts/jit(grouped_matmul)/pallas_call"}
  %copy.5 = f32[8,4]{1,0} copy(%fusion.1), metadata={op_name="jit(_prefill_impl)/layer_0/add"}
  ROOT %copy.4 = f32[8,4]{1,0} copy(%fusion.1), metadata={op_name="jit(_prefill_impl)/head/copy"}
}
"""


def test_device_time_by_the_callers_classes_on_a_hand_made_trace():
    from lib import decoder_scopes, lm_scopes, scopes

    classes = ("mla_proj", "mla_core", "experts", "head")
    texts = {lm_scopes.PREFILL: scopes.parse_hlo(PREFILL_TEXT), lm_scopes.DECODE: scopes.parse_hlo(PREFILL_TEXT)}
    ops = [[100, 10, "%fusion.1 = f32[8,4] fusion(...)"],            # a matmul fusion: its dot's scope, mla_core
           [120, 50, "%while.2 = s32[] while(...)"],                 # the loop: listed beside its body, skipped
           [125, 40, "%fusion.7 = f32[8,4] fusion(...)"],            # the body's fusion -> mla_core
           [180, 30, "%custom-call.3 = f32[8,4] custom-call"],       # the grouped product's kernel, by its scope
           [212, 2, "%copy.5 = f32[8,4] copy(...)"],                 # under no named scope: filed under other
           [215, 5, "%copy.4 = f32[8,4] copy(...)"],                 # head
           [300, 20, "%fusion.7 = f32[8,4] fusion(...)"],            # the same name inside the other program
           [400, 99, "%fusion.1 = f32[8,4] fusion(...)"]]            # outside every execution: no program's
    events = {"window": [0, 1000], "spans": {},
              "devices": {"/device:TPU:0": {"ops": ops, "modules": [[90, 140, "jit__prefill_impl(7)"],
                                                                     [290, 40, "jit__decode_impl(9)"]]}}}
    got = decoder_scopes._analyse(events, texts, classes)
    assert got["coverage"] == 1.0
    assert got["ns"][lm_scopes.PREFILL] == {"mla_proj": 0.0, "mla_core": 50.0, "experts": 30.0, "head": 5.0, "other": 2.0}
    assert got["ns"][lm_scopes.DECODE]["mla_core"] == 20.0 and sum(got["ns"][lm_scopes.DECODE].values()) == 20.0
    assert decoder_scopes.classify("jit(f)/layer_3/mla_core/while/body/dot_general", classes) == "mla_core"
    assert decoder_scopes.classify("jit(f)/layer_3/mamba/ssd_scan/dot", classes) == "other"  # another decoder's scopes
    assert decoder_scopes.classify(None, classes) == "other"
    # the innermost named scope wins, whatever the order the caller names them in
    assert decoder_scopes.classify("jit(f)/experts/mla_core/dot", ("mla_core", "experts")) == "mla_core"
