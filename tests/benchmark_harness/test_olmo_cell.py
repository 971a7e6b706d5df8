"""The Olmo-Hybrid ``prompt`` cell's benchmark files on the CPU: its
configuration against the catalog and the program, the reference's counts
against hand arithmetic, its entries in ``BENCHMARK.json`` and in a rehearsal
manifest of its own (``data/rehearsal_olmo.json``: the tiny decoder under
``configs/rehearsal_tiny_olmo.json``), both checked by
``test_benchmark_harness.check_manifest`` as they stand, the tiny cell driven
through ``run.py``'s own ``run_cell`` with every value that needs no device
read, and the fp8 control and a program without the 2 in beta reading not
correct there. What is held of the cell's entries is held by name
(``check_prompt_cell``): no place in a list and no count."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import compare, manifest  # noqa: E402

CELL = "olmo_hybrid_7b_prompt.docs_lognormal_4k_out64"
TINY_CELL = "rehearsal_tiny_olmo.rehearsal_docs"
REHEARSAL = os.path.join(HERE, "data", "rehearsal_olmo.json")
#: Olmo-Hybrid's own measurements: one ``*mfu``, two rooflines, seven classes of device time, two counters. The
#: batcher's, the tokenizer's and the set-up's readings are the accepted entries', which list this cell beside the
#: other ``prompt`` cells since PR 42 (the nine ``oh.*`` wrappers of the same readers went).
OWN = {"oh.step_mfu", "kernel.delta_rule_roofline", "kernel.full_attn_core_roofline",
       "oh.lin_proj_ms_per_ktoken", "oh.delta_rule_ms_per_ktoken", "oh.attn_proj_ms_per_ktoken",
       "oh.attn_core_ms_per_ktoken", "oh.mlp_ms_per_ktoken", "oh.head_ms_per_ktoken", "oh.other_ms_per_ktoken",
       "oh.kv_bytes_per_token", "oh.recurrent_mb_per_slot"}
#: What a run without a device trace reads of the cell's entries: the program's spans and counters.
ON_THE_CPU = {"oh.kv_bytes_per_token", "oh.recurrent_mb_per_slot", "serve.slot_occupancy", "serve.padded_token_share",
              "prompt.tokenize_s_per_krow", "lm.setup_init_s", "lm.setup_first_prefill_s",
              "serve.dispatch_host_ms_per_step", "serve.fetch_arrays_per_step", "serve.setup_first_decode_s"}


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
    if os.path.exists(catalog):  # every key of the catalog's config under the same name, but the one cut
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if json.loads(l)["name"] == cfg["model"])
        assert set(row["config"]) <= set(cfg)
        assert {k for k, v in row["config"].items() if cfg[k] != v} == {"num_hidden_layers"}
        assert cfg["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] == 32
        assert row["source_url"] in cfg["source"]
    from daft_tpu.models.olmo_hybrid import OlmoHybridConfig

    prog = OlmoHybridConfig.from_name(cfg["model"], cfg["options"]["num_hidden_layers"])
    for key in ("vocab_size", "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "rms_norm_eps", "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval", "num_hidden_layers"):
        assert getattr(prog, key) == cfg[key], key
    assert (cfg["hidden_size"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["vocab_size"]) == (3840, 30, 96, 192, 4, 30, 128, 11008, 100352)
    assert list(prog.layer_types) == cfg["layer_types"][:12] == real_cell.reference.layer_types(cfg) and len(cfg["layer_types"]) == 32
    assert cfg["rope_parameters"] == {"rope_theta": None} and cfg["tie_word_embeddings"] is False
    assert cfg["scopes"] == ["lin_proj", "delta_rule", "attn_proj", "attn_core", "mlp", "head"]
    for key in ("deployment", "assumed", "compare", "published"):
        assert cfg[key]
    assert {"head_dim", "qk_norm", "norm_placement", "positions", "linear_attention", "weights", "num_slots",
            "max_prompt_tokens"} <= set(cfg["assumed"])
    assert cfg["compare"]["sample_rows"] == 4 and 0 < cfg["compare"]["logprob_gap_max"] < 1
    o = cfg["options"]
    assert (o["max_prompt_tokens"], o["max_new_tokens"], o["ignore_eos"], o["logprobs"], cfg["batch_size"]) == (16384, 64, True, True, 16)
    entry = next(c for c in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["configs"] if c["name"] == "olmo_hybrid_7b_prompt")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"


def test_the_counts_of_the_work_follow_the_shapes(real_cell):
    cfg, ref = real_cell.config, real_cell.reference
    # ISSUE 35's arithmetic: 215.6M parameters a linear layer, 185.8M an attention layer, 126.8M of each the MLP
    assert ref.layer_parameters(cfg, "linear_attention") == pytest.approx(215.6e6, rel=0.001)
    assert ref.layer_parameters(cfg, "full_attention") == pytest.approx(185.8e6, rel=0.001)
    assert 3 * 3840 * 11008 == pytest.approx(126.8e6, rel=0.001)
    whole = 8 * (3 * ref.layer_parameters(cfg, "linear_attention") + ref.layer_parameters(cfg, "full_attention")) + 2 * 100352 * 3840
    cut = 3 * (3 * ref.layer_parameters(cfg, "linear_attention") + ref.layer_parameters(cfg, "full_attention")) + 2 * 100352 * 3840
    assert whole == pytest.approx(7431e6, rel=0.001) and cut == pytest.approx(3268e6, rel=0.001)
    assert ref.layer_types(cfg).count("linear_attention") == 9 and ref.layer_types(cfg).count("full_attention") == 3
    # a token's keys and values in one attention layer, and a sequence's recurrent state in one linear layer
    assert ref.kv_bytes_per_token(cfg) == 2 * 30 * 128 * 2 == 15360
    assert ref.state_bytes(cfg) == 30 * 192 * 96 * 4 == 2211840
    # a token without its attention and its recurrence: twice the matrix parameters of the layers that run
    per_token = ref.step_flops(cfg, 1.0, 0.0) - 9 * ref.delta_rule_flops(cfg, 1.0)
    assert per_token == pytest.approx(2 * (9 * 215.6e6 + 3 * 185.8e6), rel=0.002)
    # the recurrence, token by token: 7 operations an element of the state; q, k, v, o, beta, g once a token
    assert ref.delta_rule_flops(cfg, 1.0) == 7 * 30 * 192 * 96
    assert ref.delta_rule_bytes(cfg, 1.0, 0.0) == 2 * (2 * 2880 + 2 * 5760) + 8 * 30
    assert ref.delta_rule_bytes(cfg, 0.0, 1.0) == 2 * 2211840
    # attention's core: a score and a weighted value over 30 heads of 128 a causal pair; each held row once a call
    assert ref.attn_core_flops(cfg, 1.0) == 4 * 30 * 128
    assert ref.attn_core_bytes(cfg, 0.0, 1.0) == 15360 and ref.attn_core_bytes(cfg, 1.0, 0.0) == 2 * 2 * 3840
    assert ref.step_flops(cfg, 1.0, 1000.0) - ref.step_flops(cfg, 1.0, 0.0) == pytest.approx(3 * 1000 * 4 * 30 * 128)
    assert ref.head_flops(cfg, 1.0) == 2 * 3840 * 100352


def test_the_manifests_hold_the_cells_entries_by_name_and_the_cell_resolves_from_a_copy(tmp_path):
    """Membership only (``test_benchmark_harness.check_prompt_cell`` has the rules): the accepted batcher's,
    prompter's and set-up's entries list this cell beside the other ``prompt`` cells (the expert counter does not:
    no experts), Olmo-Hybrid's own measurements list it alone, one of what it reports is an ``*mfu``, two are its
    kernels' rooflines. Where the entries, the cell or its configuration stand in their lists, and how many there
    are, is held nowhere, so a later cell's can be appended."""
    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    harness.check_manifest(m)
    spec = harness.PROMPT_CELLS[CELL]
    assert spec["own"] | spec["rooflines"] | {spec["mfu"]} == OWN and spec["mfu"] == "oh.step_mfu" and not spec["experts"]
    harness.check_prompt_cell(m, CELL, spec)
    listed = harness.listed_for(m, CELL)
    assert OWN <= set(listed) and "moe.held_assignment_share" not in listed
    assert not [n for n in listed if n.startswith("oh.") and n.split(".", 1)[1] in (  # the nine wrappers went
        "prefill_ms_per_ktoken", "decode_step_ms", "prefill_share", "slot_occupancy", "padded_token_share",
        "host_exposed_s_per_krow", "tokenize_s_per_krow", "setup_init_s", "setup_first_prefill_s")]
    (config,) = [c for c in m["configs"] if c["name"] == "olmo_hybrid_7b_prompt"]
    (workload,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert workload["config"] == config["name"] and workload["chips"] == 1 and workload["traffic"] == "docs_lognormal_4k_out64"
    # from a copy of the manifest elsewhere, every file of the cell is found beside the benchmark's own
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    harness.check_prompt_cell(m, CELL, spec, str(path))
    # the cell's own rehearsal manifest lists the same entries for the tiny cell, and the benchmark's is as it was
    r = manifest.load_json(REHEARSAL)
    harness.check_manifest(r, REHEARSAL)
    assert OWN <= harness.reported_by(r, TINY_CELL) <= harness.reported_by(m, CELL)
    assert all(dict(listed[n], workloads=[TINY_CELL]) == e for n, e in harness.listed_for(r, TINY_CELL).items())
    assert [w["name"] for w in r["workloads"]] == [TINY_CELL]
    assert not [w for w in manifest.load_json(os.path.join(BENCH, "rehearsal.json"))["workloads"] if "olmo" in w["name"]]


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
    # every value that needs no device is read; those that need one are left out, not null
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    assert {k for k in m if k.startswith(("oh.", "kernel.", "lm.", "serve.", "prompt."))} == ON_THE_CPU
    assert not any(k.startswith(("lc.", "ds.", "moe.")) for k in m)  # other decoders' own entries, the expert counters
    assert 0 < m["serve.slot_occupancy"] <= 100 and 0 <= m["serve.padded_token_share"] < 100
    # one attention layer, 4 heads of 16, a slot's rows held in whole tiles of 16: 64 for the 57 positions asked for
    assert m["oh.kv_bytes_per_token"] == pytest.approx(1 * 2 * 4 * 16 * 2 * 64 / 57)
    assert m["oh.recurrent_mb_per_slot"] == pytest.approx(3 * (4 * 4 * 16 * 8 + 2 * 3 * 128) / 1e6)  # three linear layers


def test_a_program_without_the_2_in_beta_reads_not_correct(bench_run, monkeypatch):
    """``linear_allow_neg_eigval``: beta = 2 sigmoid(.). A program that leaves the 2 out writes half of every
    update into the state, and the comparison says so."""
    import dataclasses

    from daft_tpu.models import olmo_hybrid

    sound = olmo_hybrid.linear_inputs
    monkeypatch.setattr(olmo_hybrid, "linear_inputs", lambda cfg, *rest: sound(
        dataclasses.replace(cfg, linear_allow_neg_eigval=False), *rest))
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=1, seconds=0.5, trace_on=False)
    assert rec["correct"] is False and not compare.verdict({"g": rec["compared"]["logprob_gap"]})


def test_the_entry_finds_the_decoder_through_the_programs_record():
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
    assert b.chunk % 8 == 0  # whole chunks of the tiny decoder's delta rule
    real = manifest.resolve(CELL).config
    with pytest.raises(SystemExit, match="olmo-hybrid-tiny"):
        cell.entry.lowerables(dict(cell.config, model="Olmo-Hybrid"))
    # a depth that is no whole number of periods is the program's to refuse, before anything is drawn
    from daft_tpu.errors import DaftValueError

    with pytest.raises(DaftValueError, match="periods of 4"):
        cell.entry.lowerables(dict(real, options=dict(real["options"], num_hidden_layers=10)))
