"""The openPangu-Ultra-MoE-718B ``prompt`` cell's benchmark files on the CPU: its
configuration against the catalog and the program, the reference's counts, its
readers driven through ``run.py``'s own ``run_cell`` from a manifest of its own
(``data/rehearsal_pangu.json``: the tiny decoder under the per-layer entries the
real cell lists; not appended to ``benchmark/rehearsal.json``, which is a file
the benchmark has), traced and untraced, ``spec_logprob_gap`` end to end with its
control, and a sampler at fault. What is held of the cell's entries is held by
name (``test_benchmark_harness.check_prompt_cell``): no place in a list and no count."""

from __future__ import annotations

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

CELL = "pangu_ultra_moe_718b_prompt.chat_lognormal_512_gen256"
TINY_CELL = "rehearsal_tiny_pangu.rehearsal_docs"
REHEARSAL = os.path.join(DATA, "rehearsal_pangu.json")
CUT = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"}
#: What ``check_prompt_cell`` holds of this cell: the accepted batcher, prompter, set-up and expert readers list it
#: (the default ``shared``), and its decoder's own entries list it alone.
SPEC = {"mfu": "pg.step_mfu", "rooflines": {"kernel.pg_mla_core_roofline"}, "experts": True, "entries": {"prompt_speculative"},
        "beside": {"pg.cache_bytes_per_token"},
        "own": {"pg.spec_step_hbm_share", "pg.draft_accept_share", "pg.mtp_ms_per_ktoken", "pg.sample_ms_per_ktoken",
                "pg.mla_proj_ms_per_ktoken", "pg.mla_core_ms_per_ktoken", "pg.dense_mlp_ms_per_ktoken",
                "pg.experts_ms_per_ktoken", "pg.head_ms_per_ktoken", "pg.other_ms_per_ktoken", "pg.cache_bytes_per_token"}}


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
    from daft_tpu.models import pangu_ultra_moe as pg

    # every published number under the key of the source's config.json, but the cut
    published = {k: v for k, v in pg.PUBLISHED[cfg["model"]].items() if k != "expert_gain"}
    assert {k for k, v in published.items() if cfg[k] != v} == CUT
    assert {"attention_bias", "hidden_act", "max_position_embeddings", "model_type", "norm_topk_prob",
            "num_key_value_heads", "sandwich_norm", "tie_word_embeddings"} <= set(cfg)

    o = cfg["options"]
    prog = pg.PanguUltraMoeConfig.from_name(cfg["model"], o["num_layers"], o["expert_shard"], o["vocab_shard"])
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers", "first_k_dense_replace",
                "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
                "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps", "rope_theta",
                "num_nextn_predict_layers", "expert_gain"):
        assert getattr(prog, key) == cfg[key], key
    assert (prog.held_experts, prog.held_vocab, prog.n_routed_experts) == (cfg["n_routed_experts"], cfg["vocab_size"], 256)
    assert cfg["published"] == {**{k: pg.PUBLISHED[cfg["model"]][k] for k in CUT}, "note": cfg["published"]["note"]}
    assert cfg["router_outputs"] == 256 and cfg["sandwich_norm"] is True and cfg["tie_word_embeddings"] is False
    assert o["temperature"] == 1.0 and o["logprobs"] and o["ignore_eos"]
    assert cfg["scopes"] == ["mtp", "sample", "mla_proj", "mla_core", "dense_mlp", "router", "experts", "head"]
    for key in ("deployment", "assumed", "compare", "published"):
        assert cfg[key]
    assert {"memory", "sandwich_norm", "router", "mtp", "mtp_hidden", "speculation", "weights"} <= set(cfg["assumed"])
    assert cfg["compare"]["sample_rows"] == 8 and cfg["compare"]["readings"]
    entry = next(c for c in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["configs"]
                 if c["name"] == "pangu_ultra_moe_718b_prompt")
    assert set(entry["reduced"]) == CUT
    assert entry["source"] == "https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json"


def test_the_counts_of_the_work_follow_the_shapes(real_cell):
    """The deployment's arithmetic of the cut: 6,037.9M parameters; a speculative step of 64 slots reads ~12.4 GB."""
    cfg, ref = real_cell.config, real_cell.reference
    d, fe = 7680, 2048
    mla = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 128 * 128 * 7680
    assert ref._attention_params(cfg) == mla == 196_575_232
    # every held weight but the embedding, all 16 experts of the four expert layers and the MTP block's reached
    weights = ref.held_weight_bytes(cfg, 5 * 16)
    assert 11.6e9 < weights < 11.9e9
    assert ref.spec_step_bytes(cfg, 5 * 16, 64 * 700, 64) - weights == 2 * 576 * (5 * 64 * 700 + 64 * 700 - 128)
    # a token through the main layers: projections twice their parameters, the dense MLP, 4 expert layers' shared
    # expert, router and one held eighth (16 of 256 experts x top 8 = half an expert a token)
    per_token = 5 * 2 * mla + 6 * d * 18432 + 4 * (6 * d * fe + 2 * d * 256 + 0.5 * 6 * d * fe)
    assert ref.step_flops(cfg, 1.0, 0.0, 16 / 256) == per_token
    assert ref.step_flops(cfg, 0.0, 1.0, 0.0) == 5 * ref.mla_core_flops(cfg, 1.0) == 5 * 2 * 128 * 320
    assert ref.mtp_flops(cfg, 1.0, 0.0, 16 / 256) == 4 * d * d + 2 * mla + 6 * d * fe + 2 * d * 256 + 0.5 * 6 * d * fe
    assert ref.mtp_rows_flops(cfg, 1.0) == 4 * d * d + 2 * d * 576
    assert ref.head_flops(cfg, 1.0) == 2 * d * 19200


def test_the_manifest_holds_the_cells_entries_by_name_and_the_cell_resolves_from_a_copy(tmp_path):
    import shutil

    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    harness.check_manifest(m)
    harness.check_prompt_cell(m, CELL, SPEC)
    listed = harness.listed_for(m, CELL)
    mine = SPEC["own"] | SPEC["rooflines"] | {SPEC["mfu"]}  # this PR's entries, each listing this cell alone
    assert set(listed) == mine | harness.FOR_EVERY_PROMPT_CELL | harness.FOR_EVERY_CELL_WITH_EXPERTS
    path = tmp_path / "BENCHMARK.json"
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    harness.check_prompt_cell(m, CELL, SPEC, str(path))
    for other, spec in harness.PROMPT_CELLS.items():  # the accepted cells' entries hold as they did
        harness.check_prompt_cell(m, other, spec)
    rehearsal = manifest.load_json(REHEARSAL)
    harness.check_manifest(rehearsal, REHEARSAL)
    assert SPEC["own"] | {SPEC["mfu"]} <= harness.reported_by(rehearsal, TINY_CELL)
    assert {p["name"] for p in rehearsal["per_layer"]} == harness.reported_by(m, CELL)
    assert rehearsal["end_to_end"] == m["end_to_end"]
    assert "pangu" not in json.dumps(manifest.load_json(os.path.join(BENCH, "rehearsal.json")))


@pytest.mark.parametrize("seed,trace_on", [(1, True), (2 ** 31 + 9, False)], ids=["traced-1", "untraced-large-seed"])
def test_the_cell_rehearses_on_the_cpu_and_its_control_reads_not_correct(bench_run, seed, trace_on, monkeypatch):
    _wide_brackets(monkeypatch)
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=seed, seconds=0.5, trace_on=trace_on, control=True)
    assert rec["correct"] is True and rec["failed"] == 0 and rec["attempted"] >= 16, rec["compared"]
    c = rec["compared"]
    assert c["answers_not_24_tokens"]["value"] == c["token_ids_outside_slice"]["value"] == 0
    assert c["ids_out_of_sequence"]["value"] == c["prompt_tokens_not_words"]["value"] == 0
    assert c["corrections_not_from_residual"]["value"] == 0
    assert c["rows_compared"]["value"] == 8 and c["tokens_compared"]["value"] == 8 * 24 and c["drafts_compared"]["value"] >= 8 * 12
    assert 0 < c["draft_accept_share"]["value"] < 1
    assert rec["control"]["correct"] is False  # one precision step down, in the program's place
    for name in ("logprob_gap", "draft_logprob_gap"):
        assert rec["control"]["compared"][name]["value"] > c[name]["limit"] > c[name]["value"], name
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    if not trace_on:
        assert set(m) == {"rows_per_s_per_chip", "setup_s"}
        return
    # the readers of the program's spans and counters find them; the device's need a device
    assert {"pg.draft_accept_share", "pg.cache_bytes_per_token", "moe.held_assignment_share", "serve.slot_occupancy",
            "serve.padded_token_share", "prompt.tokenize_s_per_krow", "lm.setup_init_s", "lm.setup_first_prefill_s",
            "serve.fetch_arrays_per_step", "serve.setup_first_decode_s"} <= set(m)
    assert 0 < m["pg.draft_accept_share"] < 100 and 40 < m["moe.held_assignment_share"] < 60  # half the experts held
    # a latent row of 8 + 8 values in bfloat16 over 3 layers and the MTP block, in whole tiles of 128 positions
    assert m["pg.cache_bytes_per_token"] == pytest.approx(4 * 16 * 2 * 128 / 73)
    assert not {"pg.step_mfu", "pg.spec_step_hbm_share", "pg.mtp_ms_per_ktoken", "pg.other_ms_per_ktoken",
                "serve.decode_step_ms", "serve.idle_ms_per_step"} & set(m)
    # another decoder's own measurements list that decoder's cell
    assert not any(k.startswith(("lm.", "lc.", "oh.", "ds.", "sd.")) and ".setup_" not in k for k in m)


def test_a_sampler_that_accepts_every_draft_reads_not_correct(bench_run, monkeypatch):
    """A rejection sampler that accepts every draft emits tokens drawn from the drafting block's q at half the
    positions: their log p under the reference is lower than its entropy says, and ``sampled_nll_excess`` says so
    where the two gaps, which read each emitted token's own log p, do not."""
    from daft_tpu.models import serving

    sound = serving.rejection_sample
    monkeypatch.setattr(serving, "rejection_sample", lambda key, d, p, q, t: (d >= 0, sound(key, d, p, q, t)[1]))
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    cell.config["compare"] = dict(cell.config["compare"], sample_rows=16)
    rec = bench_run.run_cell(cell, seed=3, seconds=0.5, trace_on=False)
    c = rec["compared"]
    assert rec["correct"] is False and not compare.verdict({"x": c["sampled_nll_excess"]})
    assert compare.verdict({"g": c["logprob_gap"], "d": c["draft_logprob_gap"]})
    assert c["draft_accept_share"]["value"] == 1.0


def test_a_correction_drawn_from_p_reads_not_correct(bench_run, monkeypatch):
    """A rejection sampler whose correction is drawn from p instead of max(0, p - q) emits too many of p's likely
    tokens by about ``sampled_nll_excess``'s noise at this size (-0.04 to 0.33 over seven seeds), but some of its
    corrections are tokens where q exceeds p, which max(0, p - q) never draws: ``corrections_not_from_residual``
    counts them (6 to 11 of ~45 corrections over the same seeds; the program's own sampler 0 on each)."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.models import serving

    sound = serving.rejection_sample

    def from_p(key, d, p, q, t):
        correction = jax.random.categorical(jax.random.fold_in(key, 7), p / t, axis=-1).astype(jnp.int32)
        return sound(key, d, p, q, t)[0], correction

    monkeypatch.setattr(serving, "rejection_sample", from_p)
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=3, seconds=0.5, trace_on=False)
    c = rec["compared"]
    assert rec["correct"] is False and c["corrections_not_from_residual"]["value"] > 0
    assert compare.verdict({"g": c["logprob_gap"], "d": c["draft_logprob_gap"]})
    assert 0 < c["draft_accept_share"]["value"] < 1


def test_the_entry_finds_the_drafting_decoder_through_the_programs_record():
    cell = manifest.resolve(TINY_CELL, REHEARSAL)
    got = cell.entry.lowerables(cell.config)
    assert set(got) == {"jit__prefill_impl", "jit__decode_impl"}
    _, state, logits, *_, block, spec = got["jit__decode_impl"][1]
    assert [layer["kv"].shape for layer in state["layers"]] == [(4, 16, 128)] * 3 and state["mtp"]["kv"].shape == (4, 16, 128)
    assert block is None and [s.shape for s in spec] == [(4, 2), (4,), (4,)] and logits.shape == (4, 128)
    # a decoder of one token a step, a block decoder, or none at all (a program without the record's drafting decoder)
    for model in ("granite-hybrid-tiny", "sdar-moe-tiny", "openPangu-Ultra-MoE-72B"):
        with pytest.raises(SystemExit, match="drafts its own"):
            cell.entry.build(None, dict(cell.config, model=model), 1)


def test_the_device_readers_on_hand_made_spans_and_times(real_cell, monkeypatch):
    """``pg.step_mfu``, ``pg.spec_step_hbm_share`` and ``kernel.pg_mla_core_roofline`` from counters and device times
    made by hand (a CPU run has no device trace): 171 speculative steps of 64 slots at 20 ms (each must read ~12.4 GB:
    76% of 819 GB/s) and a prefill partition of 2.5 s: the step's share of the HBM under 100%, the whole step's share
    of the peak a few %; the main attention's core 2.34 s of it (41.1 ms a thousand tokens), which could take ~20 ms a
    layer (5.8 in the prefill, where the MXU bounds it; 14 in the steps, where HBM does): ~4% of its roofline."""
    from types import SimpleNamespace

    from lib import decoder_scopes, lm_scopes, program_spans

    step = {"active": 64, "slots": 64, "drafted": 64, "accepted": 30, "tokens": 94, "forwards": 2,
            "verify_positions": 128, "keys": 64 * 770, "moe.experts_reached": 5 * 16,
            "moe.held_assignments": 5 * 128 * 8 // 16, "moe.assignments": 5 * 128 * 8}
    steps = [(0, 1, step, None)] * 171
    prefill = [(0, 1, {"pairs": 14e6, "tokens": 40866, "rows": 64, "row_chunks": 115}, None)]
    monkeypatch.setattr(lm_scopes, "aligned", lambda run: True)
    monkeypatch.setattr(program_spans, "in_window", lambda run, name, whole=True: (
        steps if name == "serve.decode_step" else prefill if name == "serve.prefill" else []))
    monkeypatch.setattr(decoder_scopes, "class_ns", lambda run, classes, *wanted, program=None: (
        2.34e9 if wanted == ("mla_core",) else None))
    monkeypatch.setattr(lm_scopes, "tokens", lambda run: SimpleNamespace(
        prefill=40866.0, decode=171 * 94.0, calls=28.0, rows=64.0, held=171 * 320.0, assignments=171 * 5120.0))
    monkeypatch.setattr(lm_scopes, "programs", lambda run: {lm_scopes.PREFILL: [2.5e9], lm_scopes.DECODE: [20e6] * 171})
    monkeypatch.setattr(decoder_scopes, "counters", lambda run, span, *keys: (
        {"pairs": 14e6, "mtp_positions": 40802.0} if span == "serve.prefill" else
        {k: float(sum(s[2][k] for s in steps)) for k in keys}))
    run = SimpleNamespace(cell=real_cell, device_kind="TPU v5 lite")
    read = {n: manifest.load_module(os.path.join(BENCH, "metrics", n + ".py")).read
            for n in ("pg.step_mfu", "pg.spec_step_hbm_share", "pg.draft_accept_share", "kernel.pg_mla_core_roofline")}
    assert read["pg.draft_accept_share"](run) == pytest.approx(100 * 30 / 64)
    assert 60 < read["pg.spec_step_hbm_share"](run) < 100
    assert 1 < read["pg.step_mfu"](run) < 40
    prefill_s = max(81920 * 14e6 / 197e12, 2 * 576 * 40866 * (115 / 64 + 1) / 2 / 819e9)
    step_s = max(81920 * (2 * 171 * 64 * 770 - 171 * 64) / 197e12, (1152 * 171 * 64 * 770 + 81920 * 171 * 128) / 819e9)
    assert read["kernel.pg_mla_core_roofline"](run) == pytest.approx(100 * 5 * (prefill_s + step_s) / 2.34)
    assert 3 < read["kernel.pg_mla_core_roofline"](run) < 6
