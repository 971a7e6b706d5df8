"""The DeepSeek-V3.2-Exp ``prompt`` cell's benchmark files on the CPU: its
configuration against the catalog and the program, its traffic, the reference's
counts, its readers driven through ``run.py``'s own ``run_cell`` from a manifest
of its own (``data/rehearsal_deepseek.json``: the tiny decoder under the
per-layer entries the real cell lists; not appended to
``benchmark/rehearsal.json``, which is a file the benchmark has), traced and
untraced, and a program without selection. What is held of the cell's entries is
held by name (``test_benchmark_harness.check_prompt_cell``): no place in a list
and no count."""

from __future__ import annotations

import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import compare, manifest  # noqa: E402

CELL = "deepseek_v3_2_exp_prompt.docs_lognormal_8k_out64"
TINY_CELL = "rehearsal_tiny_deepseek.rehearsal_docs"
REHEARSAL = os.path.join(DATA, "rehearsal_deepseek.json")
CUT = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"}


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


def _mine(m: dict, cell: str):
    return [p for p in m["per_layer"] if p.get("workloads") == [cell]]


def test_the_configuration_states_the_published_sizes_and_the_cut(real_cell):
    cfg = real_cell.config
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if os.path.exists(catalog):  # every number of the catalog's config under the same key, but the four cut
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if json.loads(l)["name"] == cfg["model"])
        assert set(row["config"]) <= set(cfg)
        assert {k for k, v in row["config"].items() if cfg[k] != v} == CUT
        assert {k: cfg["published"][k] for k in CUT} == {k: row["config"][k] for k in CUT}
        assert cfg["rope_scaling"] == row["config"]["rope_scaling"] and row["source_url"] in cfg["source"]
    from daft_tpu.models.deepseek_v32 import DeepseekV32Config

    o = cfg["options"]
    prog = DeepseekV32Config.from_name(cfg["model"], o["num_layers"], o["expert_shard"], o["vocab_shard"])
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "kv_lora_rank",
                "q_lora_rank", "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "index_n_heads", "index_head_dim",
                "index_topk", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor",
                "rms_norm_eps", "rope_theta", "num_hidden_layers", "first_k_dense_replace", "query_gain", "expert_gain"):
        assert getattr(prog, key) == cfg[key], key
    scaling = cfg["rope_scaling"]
    assert (prog.rope_factor, prog.original_max_position_embeddings, prog.beta_fast, prog.beta_slow, prog.mscale,
            prog.mscale_all_dim) == (scaling["factor"], scaling["original_max_position_embeddings"], scaling["beta_fast"],
                                     scaling["beta_slow"], scaling["mscale"], scaling["mscale_all_dim"])
    assert prog.n_routed_experts == cfg["published"]["n_routed_experts"] == cfg["router_outputs"] == 256
    assert (prog.held_experts, prog.held_vocab, prog.num_hidden_layers, prog.first_k_dense_replace) == \
        (cfg["n_routed_experts"], cfg["vocab_size"], cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (16, 16160, 5, 1)
    assert real_cell.reference.share(cfg) == ((0, 16), (0, 16160))
    assert cfg["scopes"] == ["mla_proj", "indexer", "select", "mla_core", "dense_mlp", "router", "experts", "head"]
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"] is True and cfg["num_nextn_predict_layers"] == 1
    for key in ("deployment", "assumed", "compare", "published"):
        assert cfg[key]
    assert {"indexer_rope", "indexer_precision", "ties", "yarn", "router", "mtp", "weights", "cache"} <= set(cfg["assumed"])
    assert cfg["compare"]["sample_rows"] == 4 and 0 < cfg["compare"]["logprob_gap_max"] and cfg["compare"]["readings"]
    entry = next(c for c in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["configs"] if c["name"] == "deepseek_v3_2_exp_prompt")
    assert set(entry["reduced"]) == CUT and entry["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json"
    assert len(cfg["source"]) <= 200


def test_every_partition_holds_the_same_lengths_and_a_quarter_of_its_pairs_are_selected(real_cell):
    import numpy as np

    gen, traffic = real_cell.generator, real_cell.traffic
    base = gen.lengths(traffic["length_tokens"], traffic["partition_rows"])
    assert base.tolist() == [2799, 4402, 5818, 7338, 9146, 11534, 15244, 23976] and base.sum() == 80257
    assert traffic["pool_rows"] == 128 and real_cell.config["batch_size"] == real_cell.config["options"]["num_slots"] == 8
    assert traffic["generator"] == "doc_pool" and traffic["source_rows_per_s"] == 4 and traffic["lexicon_words"] == 20000
    small = dict(traffic, pool_rows=16, lexicon_words=50)
    words = lambda docs: [len(d.split()) for d in docs]  # noqa: E731
    a, b = gen.documents(small, 3), gen.documents(small, 2 ** 31 + 5)
    assert a != b and words(a) != words(b) and sorted(words(a[:8])) == sorted(words(b[8:])) == base.tolist()
    # one wave of 8 slots: the longest document alone has 47 chunks, and the packed schedule has no more calls
    from daft_tpu.models.serving import prefill_schedule

    chunks = (-(-base // 512)).tolist()
    assert max(chunks) == 47 and len(prefill_schedule(chunks, 4)) == 47 and max(base) <= real_cell.config["options"]["max_prompt_tokens"]
    ref, cfg = real_cell.reference, real_cell.config
    pairs = int((base * (base + 1) // 2).sum())
    selected = sum(ref.selected_pairs(int(n), cfg["index_topk"]) for n in base)
    assert pairs == 569449107 and selected == 147597312 and 25.5 < 100 * selected / pairs < 26.5


def test_the_counts_of_the_work_follow_the_shapes(real_cell):
    cfg, ref = real_cell.config, real_cell.reference
    # ISSUE 40's arithmetic: a dense layer 597.4M parameters, an expert layer 951.6M of which 704.6M are its 16 held experts
    per_token = ref.step_flops(cfg, 1.0, 0.0, 0.0, held_share=0.0)
    assert per_token == pytest.approx(2 * (597.4e6 + 4 * (951.6e6 - 704.6e6)), rel=0.002)
    assert ref.step_flops(cfg, 1.0, 0.0, 0.0, held_share=1 / 16) - per_token == pytest.approx(4 * 0.5 * 2 * 44.04e6, rel=0.001)
    assert ref.step_flops(cfg, 0.0, 1.0, 0.0, 0.0) == 5 * ref.index_flops(cfg, 1.0) == 5 * 2 * 64 * 128
    assert ref.step_flops(cfg, 0.0, 0.0, 1.0, 0.0) == 5 * ref.mla_core_flops(cfg, 1.0) == 5 * 2 * 128 * 320
    assert ref.expert_matmul_flops(cfg, 1.0) == 6 * 7168 * 2048
    # a decode step reads each selected latent row and each held indexer key once: 1,152 B and 256 B a token
    assert ref.mla_core_bytes(cfg, 0.0, 1.0) == 1152 and ref.index_bytes(cfg, 0.0, 1.0) == 256
    assert ref.index_bytes(cfg, 1.0, 0.0) == 2 * 64 * 128 + 4 * 64
    # a call that reaches all 16 held experts is bound by their weights: 16 x 44.04M x 2 B = 1.41 GB
    assert ref.expert_matmul_bytes(cfg, 512, 16) == pytest.approx(16 * 44.04e6 * 2, rel=0.02)
    assert ref.head_flops(cfg, 1.0) == 2 * 7168 * 16160
    assert ref.selected_pairs(100, 2048) == 5050 and ref.selected_pairs(3000, 2048) == 2048 * 2049 / 2 + 952 * 2048
    assert [ref._padded_length(n, 32832) for n in (2799, 8208, 8209, 23976 + 64, 32832)] == [8208, 8208, 16416, 24624, 32832]


def test_the_manifest_holds_the_cells_entries_by_name_and_the_cell_resolves_from_a_copy(tmp_path):
    """Membership only (``test_benchmark_harness.check_prompt_cell`` has the rules): the accepted batcher's,
    prompter's, set-up's and expert entries list this cell beside the other ``prompt`` cells, PR 37's six among
    them, DeepSeek's own measurements list it alone, one of what it reports is an ``*mfu``, three are its kernels'
    rooflines, and one entry still reads through ``decoder_scopes.beside``. No place in a list and no count."""
    import shutil

    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    harness.check_manifest(m)
    spec = harness.PROMPT_CELLS[CELL]
    assert spec["mfu"] == "ds.step_mfu" and spec["experts"] and spec["beside"] == {"ds.cache_bytes_per_token"}
    assert spec["rooflines"] == {"kernel.dsa_index_roofline", "kernel.dsa_core_roofline", "kernel.ds_expert_matmul_roofline"}
    harness.check_prompt_cell(m, CELL, spec)
    listed = harness.listed_for(m, CELL)
    assert {p["name"] for p in _mine(m, CELL)} >= spec["own"] | spec["rooflines"] | {spec["mfu"]}
    assert {"serve.idle_ms_per_step", "serve.idle_unfiled_share", "serve.setup_first_decode_s",
            "moe.held_assignment_share"} <= set(listed)
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    harness.check_prompt_cell(m, CELL, spec, str(path))
    # the rehearsal manifest lists the same entries for the tiny cell, and is no part of benchmark/rehearsal.json
    rehearsal = manifest.load_json(REHEARSAL)
    harness.check_manifest(rehearsal, REHEARSAL)
    assert {p["name"] for p in _mine(rehearsal, TINY_CELL)} <= set(listed)  # a tiny manifest has the one cell
    assert spec["own"] | {spec["mfu"]} <= harness.reported_by(rehearsal, TINY_CELL) <= harness.reported_by(m, CELL)
    assert "deepseek" not in json.dumps(manifest.load_json(os.path.join(BENCH, "rehearsal.json")))


def test_the_wrappers_went_with_their_entries():
    """What stays true after the ``benchmark`` PR that listed the ``prompt`` cells in the accepted entries (PR 42),
    where ``test_the_files_the_benchmark_had_are_the_parents`` held what was true of PR 40 alone: no entry of the
    manifest or of a rehearsal manifest is one of the ten accepted readers under a decoder's prefix, no such file
    lies under ``benchmark/metrics/``, and of these decoders' files only one still calls ``beside``: for a reading that
    has no accepted entry to be listed in."""
    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    wrapped = {"prefill_ms_per_ktoken", "decode_step_ms", "prefill_share", "slot_occupancy", "padded_token_share",
               "host_exposed_s_per_krow", "tokenize_s_per_krow", "held_assignment_share", "setup_init_s",
               "setup_first_prefill_s"}
    is_wrapper = lambda name: name.split(".")[0] in ("lc", "oh", "ds") and name.split(".", 1)[1] in wrapped  # noqa: E731
    for path in harness.MANIFESTS:
        assert not [p["name"] for p in manifest.load_json(path)["per_layer"] if is_wrapper(p["name"])], path
    files = [f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".py")]
    assert not [f for f in files if is_wrapper(f)]
    # of the three decoders' own files one still reads through ``beside`` (a later cell may bring more of its own)
    through_beside = [f for f in files if f.split(".")[0] in ("lc", "oh", "ds")
                      and "beside(__file__" in open(os.path.join(BENCH, "metrics", f + ".py")).read()]
    assert through_beside == ["ds.cache_bytes_per_token"]


def _digests():
    out = {}
    for top in (BENCH, HERE):
        for d, dirs, files in os.walk(top):
            dirs[:] = [x for x in dirs if x not in ("out", "__pycache__")]
            for f in files:
                with open(os.path.join(d, f), "rb") as fh:
                    out[os.path.join(d, f)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.mark.parametrize("seed,trace_on", [(1, True), (2 ** 31 + 9, True), (1, False)], ids=["traced-1", "traced-large-seed", "untraced"])
def test_the_cell_rehearses_on_the_cpu_and_its_control_reads_not_correct(bench_run, seed, trace_on, monkeypatch):
    _wide_brackets(monkeypatch)
    before = _digests()
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=seed, seconds=0.5, trace_on=trace_on, control=True)
    assert _digests() == before  # a run writes under benchmark/out alone
    assert rec["correct"] is True and rec["failed"] == 0 and rec["attempted"] >= 16, rec["compared"]
    c = rec["compared"]
    assert c["answers_not_8_tokens"]["value"] == c["token_ids_outside_slice"]["value"] == 0
    assert c["ids_out_of_sequence"]["value"] == c["prompt_tokens_not_words"]["value"] == 0
    assert c["rows_compared"]["value"] == 8 and c["tokens_compared"]["value"] == 64
    assert rec["control"]["correct"] is False  # one precision step down, in the program's place
    assert rec["control"]["compared"]["logprob_gap"]["value"] > c["logprob_gap"]["limit"] > c["logprob_gap"]["value"]
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    if not trace_on:
        assert set(m) == {"rows_per_s_per_chip", "setup_s"}
        return
    # the readers of the program's spans and counters find them; the device's need a device
    assert {"ds.selected_pair_share", "ds.cache_bytes_per_token", "moe.held_assignment_share", "ds.expert_load_max_over_mean",
            "serve.slot_occupancy", "serve.padded_token_share", "prompt.tokenize_s_per_krow", "lm.setup_init_s",
            "lm.setup_first_prefill_s", "serve.fetch_arrays_per_step", "serve.setup_first_decode_s"} <= set(m)
    assert not {"ds.step_mfu", "kernel.dsa_index_roofline", "kernel.dsa_core_roofline", "kernel.ds_expert_matmul_roofline",
                "serve.decode_step_ms", "ds.select_ms_per_ktoken", "ds.other_ms_per_ktoken", "serve.host_exposed_s_per_krow",
                "serve.idle_ms_per_step", "serve.idle_unfiled_share"} & set(m)
    # another decoder's own measurements list that decoder's cell
    assert not any(k.startswith(("lm.", "lc.", "oh.")) and ".setup_" not in k for k in m) and "moe.expert_load_max_over_mean" not in m
    assert 0 < m["serve.slot_occupancy"] <= 100 and 0 <= m["serve.padded_token_share"] < 100
    # documents of 4-48 tokens under a top 32: the longest pass it, so fewer pairs are selected than there are, and most are
    assert 80 < m["ds.selected_pair_share"] < 100
    assert m["ds.cache_bytes_per_token"] == pytest.approx(3 * (16 + 16) * 2 * 128 / 57)  # 57 positions asked for, 128 held
    assert 25 < m["moe.held_assignment_share"] < 75 and m["ds.expert_load_max_over_mean"] >= 1  # 4 of 8 experts held
    # the counts hold their identities on every span of the run
    from daft_tpu.models import deepseek_v32 as ds
    from daft_tpu.profiling import recent_device_spans

    rounds = [s.count for s in recent_device_spans() if s.name == "serve.prefill" and "selected_pairs" in s.count]
    assert rounds and all(r["index_pairs"] == r["pairs"] >= r["selected_pairs"] > 0 for r in rounds)
    assert all(r["dsa"] == "masked" for r in rounds)
    assert ds.selected_pairs([20, 32], 32) == 20 * 21 // 2 + 32 * 33 // 2  # equal where no document passes index_topk


def test_a_program_without_selection_reads_not_correct(bench_run, monkeypatch):
    """Dense attention in the selection's place: the documents longer than ``index_topk`` (and every decode step
    after position 32) attend keys the reference has dropped, and the comparison says so."""
    import jax.numpy as jnp
    from daft_tpu.models import deepseek_v32 as ds

    monkeypatch.setattr(ds, "kth_threshold", lambda index, positions, k, reach=None: jnp.full(positions.shape, -jnp.inf))
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=1, seconds=0.5, trace_on=False)
    assert rec["correct"] is False and not compare.verdict({"g": rec["compared"]["logprob_gap"]})


def test_the_entry_finds_the_fourth_decoder_through_the_programs_record():
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    got = cell.entry.lowerables(cell.config)
    assert set(got) == {"jit__prefill_impl", "jit__decode_impl"}
    state = got["jit__prefill_impl"][1][1]
    assert [sorted(layer) for layer in state] == [["ik", "kv"]] * 3 and state[0]["ik"].shape == (4, 16, 128)
    with pytest.raises(SystemExit, match="deepseek-v32-tiny"):
        cell.entry.lowerables(dict(cell.config, model="DeepSeek-V3.2"))


# -- device time by this cell's classes, on a trace made by hand -------------------------------------
PREFILL_TEXT = """
HloModule jit__prefill_impl

%fused_in (p0: bf16[8,4]) -> f32[8,4] {
  %p0 = bf16[8,4]{1,0} parameter(0)
  ROOT %dot.1 = f32[8,4]{1,0} dot(%p0, %p0), metadata={op_name="jit(_prefill_impl)/layer_0/mla_proj/dot_general"}
}

%fused_count (p1: u32[8,4]) -> s32[8] {
  %p1 = u32[8,4]{1,0} parameter(0)
  ROOT %reduce.1 = s32[8]{0} reduce(%p1, %p1), dimensions={1}, to_apply=%fused_in, metadata={op_name="jit(_prefill_impl)/layer_0/select/while/body/reduce_sum"}
}

%body (c: s32[]) -> s32[] {
  %c = s32[] parameter(0)
  ROOT %fusion.7 = s32[8]{0} fusion(%c), kind=kLoop, calls=%fused_count, metadata={op_name="jit(_prefill_impl)/layer_0/select/while/body/reduce_sum"}
}

ENTRY %main (a: bf16[8,4]) -> f32[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %fusion.1 = f32[8,4]{1,0} fusion(%a), kind=kOutput, calls=%fused_in, metadata={op_name="jit(_prefill_impl)/layer_0/add"}
  %custom-call.2 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_prefill_impl)/layer_0/indexer/jit(index_scores)/pallas_call"}
  %while.3 = s32[] while(%a), condition=%body, body=%body, metadata={op_name="jit(_prefill_impl)/layer_0/select/while"}
  %custom-call.4 = f32[8,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(_prefill_impl)/layer_0/mla_core/jit(mla_prefill_attention)/pallas_call"}
  ROOT %copy.5 = f32[8,4]{1,0} copy(%fusion.1), metadata={op_name="jit(_prefill_impl)/layer_0/add"}
}
"""


def test_device_time_by_the_cells_classes_on_a_hand_made_trace(real_cell):
    """The classes' sum is the busy time of the two programs' operations: the loop of the selection is listed by
    the trace beside its body and skipped, the two kernels are filed by their scopes, what has no scope is ``other``."""
    from lib import decoder_scopes, lm_scopes, scopes

    classes = real_cell.config["scopes"]
    texts = {lm_scopes.PREFILL: scopes.parse_hlo(PREFILL_TEXT), lm_scopes.DECODE: scopes.parse_hlo(PREFILL_TEXT)}
    ops = [[100, 10, "%fusion.1 = f32[8,4] fusion(...)"],            # the projection's matmul fusion -> mla_proj
           [112, 20, "%custom-call.2 = f32[8,8] custom-call"],       # the index kernel -> indexer
           [135, 64, "%while.3 = s32[] while(...)"],                 # the search's loop: listed beside its body, skipped
           [136, 30, "%fusion.7 = s32[8] fusion(...)"],              # a pass of the search -> select
           [168, 30, "%fusion.7 = s32[8] fusion(...)"],
           [200, 40, "%custom-call.4 = f32[8,4] custom-call"],       # the attention kernel -> mla_core
           [241, 3, "%copy.5 = f32[8,4] copy(...)"]]                 # under no named scope -> other
    events = {"window": [0, 1000], "spans": {},
              "devices": {"/device:TPU:0": {"ops": ops, "modules": [[90, 160, "jit__prefill_impl(7)"]]}}}
    got = decoder_scopes._analyse(events, texts, classes)
    ns = got["ns"][lm_scopes.PREFILL]
    assert got["coverage"] == 1.0
    assert (ns["mla_proj"], ns["indexer"], ns["select"], ns["mla_core"], ns["other"]) == (10.0, 20.0, 60.0, 40.0, 3.0)
    assert sum(ns.values()) == sum(dur for _, dur, name in ops if "while" not in name)  # the classes' sum is the busy time
