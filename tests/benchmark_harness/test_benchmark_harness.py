"""Tests of the benchmark harness (``benchmark/``): the yardstick's arithmetic,
the resolver, the comparison and its control, and the run's control flow on the
CPU at a tiny size. One file, so that the one test that describes a TPU
topology stays with its fixture (see the on-chip-measurement guide)."""

from __future__ import annotations

import gzip
import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import compare, manifest, peaks, trace, window  # noqa: E402

SPAN_ORDER = ["udf", "preprocess", "provider", "pad", "stage"]
REHEARSAL = os.path.join(BENCH, "rehearsal.json")
RAW_CELL = "rehearsal_tiny_clip.rehearsal_raw"
JPEG_CELL = "rehearsal_tiny_clip.rehearsal_jpeg_parquet"


@pytest.fixture(scope="module")
def bench_run():
    return manifest.load_module(os.path.join(BENCH, "run.py"))


@pytest.fixture(scope="module")
def manifest_json():
    return manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))


# -- trace reduction -----------------------------------------------------------
@pytest.fixture(scope="module")
def chip_events():
    with gzip.open(os.path.join(DATA, "trace_l14_jpeg_two_partitions.json.gz")) as f:
        return json.load(f)


def test_trace_reduction_on_recorded_chip_trace(chip_events):
    ev = chip_events
    # three whole ViT-L/14 forwards at B=512 in 7.96 s of a preprocess-bound run
    assert trace.window_s(ev) == pytest.approx(7.9605, abs=1e-3)
    assert trace.step_ms(ev) == pytest.approx(891.70, abs=0.05)
    assert trace.busy_s(ev) == pytest.approx(3 * 0.8917, rel=2e-3)
    assert trace.idle_share(ev) == pytest.approx(0.664, abs=2e-3)
    gaps = dict(trace.idle_gaps(ev, SPAN_ORDER))
    # the device waits for PIL: nearly all idle time lies under the preprocess span
    assert gaps["preprocess"] == pytest.approx(5.0955, abs=1e-3)
    assert list(gaps)[0] == "preprocess" and gaps["provider"] < 0.2
    idle_s = trace.window_s(ev) - trace.busy_s(ev)
    assert sum(gaps.values()) == pytest.approx(idle_s, rel=1e-6)
    assert trace.self_s(ev, "preprocess", SPAN_ORDER) == pytest.approx(5.0955, abs=1e-3)
    assert 0.15 < trace.uncovered_by_device_s(ev, "provider") < 0.2
    ops = trace.device_ops(ev)
    assert len(ops) == 10 and ops[0][1] >= ops[1][1] > 0
    assert sum(s for _, s in ops) <= trace.busy_s(ev) * 1.001


@pytest.mark.parametrize("xs, ys, want_sub, want_and", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)], [(2, 3), (5, 7)]),
    ([(0, 4), (6, 9)], [(3, 7)], [(0, 3), (7, 9)], [(3, 4), (6, 7)]),
    ([(0, 4)], [], [(0, 4)], []),
    ([(1, 2)], [(0, 5)], [], [(1, 2)]),
])
def test_interval_arithmetic(xs, ys, want_sub, want_and):
    assert trace.subtract(xs, ys) == want_sub
    assert trace.intersect(xs, ys) == want_and
    assert trace.merge(xs + ys) == trace.merge(want_sub + ys)
    assert trace.total(want_sub) + trace.total(want_and) == trace.total(xs)


def test_op_kind_groups_instances():
    a = "%fusion.12 = bf16[512,257,1024]{2,1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kLoop"
    b = "%fusion.97 = bf16[512,257,1024]{2,1,0:T(8,128)(2,1)} fusion(%p4), kind=kLoop"
    assert trace.op_kind(a) == trace.op_kind(b) == "fusion bf16[512,257,1024]"
    assert trace.op_kind("copy.3") == "copy"


# -- operations from shapes, and the peaks ---------------------------------------
@pytest.mark.parametrize("config, xla_gflop_per_row", [
    ("clip_vit_l14_image", 162.8), ("clip_vit_b16_image", 35.4)])
def test_flops_from_shapes_against_xla_count(config, xla_gflop_per_row):
    cell = manifest.resolve(config + ".predecoded_224")
    mine = cell.reference.forward_flops_per_row(cell.config) / 1e9
    assert mine <= xla_gflop_per_row  # XLA also counts elementwise work
    assert mine == pytest.approx(xla_gflop_per_row, rel=0.03)


@pytest.mark.parametrize("config, heads_t_d, flops_a_layer, bytes_a_layer, bound, expected_share", [
    # 4 x heads x T^2 x d_head and 2 x T x 4 x hidden, times the batch; PERF.md section 5's core times
    ("clip_vit_l14_image", (16, 257, 64), 138_514_792_448, 1_077_936_128, "bytes", 38.42),
    ("clip_vit_b16_image", (12, 197, 64), 122_082_557_952, 1_239_416_832, "bytes", 59.00)])
def test_attention_core_counts_against_hand_arithmetic(config, heads_t_d, flops_a_layer, bytes_a_layer, bound,
                                                       expected_share, monkeypatch):
    from lib import scopes

    cell = manifest.resolve(config + ".predecoded_224")
    cfg, ref, (h, t, d) = cell.config, cell.reference, heads_t_d
    batch = cfg["batch_size"]
    assert ref.attention_core_flops_per_row(cfg) == 4 * h * t * t * d
    assert ref.attention_core_bytes_per_row(cfg) == 2 * t * 4 * h * d
    assert ref.attention_core_flops_per_row(cfg) * batch == flops_a_layer
    assert ref.attention_core_bytes_per_row(cfg) * batch == bytes_a_layer
    # the two products are 4% of the whole forward's count
    assert cfg["num_hidden_layers"] * ref.attention_core_flops_per_row(cfg) \
        == pytest.approx(0.04 * ref.forward_flops_per_row(cfg), rel=0.02)
    by_flops, by_bytes = flops_a_layer / 197e12, bytes_a_layer / 819e9
    assert (by_bytes > by_flops) == (bound == "bytes")
    # the reader on a hand-made table of classes: the core's PR 27 time a step gives the issue's expected share
    core_ms = {"clip_vit_l14_image": 82.22, "clip_vit_b16_image": 30.78}[config]
    read = manifest.load_module(os.path.join(BENCH, "metrics", "kernel.attn_core_roofline.py")).read
    table = {"mlp": 1.0, "attn_proj": 1.0, "attn_core": core_ms, "layernorm": 1.0, "other": 1.0}
    monkeypatch.setattr(scopes, "classes", lambda run: table)
    run = SimpleNamespace(cell=cell, n_devices=1, device_kind="TPU v5 lite")
    want = 100 * cfg["num_hidden_layers"] * max(by_flops, by_bytes) / (core_ms / 1e3)
    assert read(run) == pytest.approx(want, rel=1e-12) and want == pytest.approx(expected_share, abs=0.01)
    run.n_devices = 4  # a batch over four chips: a quarter of the rows a device, the same share at a quarter of the time
    table["attn_core"] = core_ms / 4
    assert read(run) == pytest.approx(want, rel=1e-12)
    table["attn_core"] = 0.0  # no operation under the scope (a forward that names its core otherwise): silent, not 0
    assert read(run) is None
    monkeypatch.setattr(scopes, "classes", lambda run: None)  # no device traced, or the text did not match
    assert read(run) is None
    monkeypatch.setattr(scopes, "classes", lambda run: {"attn_core": core_ms})
    with pytest.raises(KeyError):  # a chip whose peaks are not on record is an error, never a default
        read(SimpleNamespace(cell=cell, n_devices=1, device_kind="TPU v9 imaginary"))


def test_peaks_table_refuses_unknown_kind():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "bf16_flops_per_s")


# -- window arithmetic -------------------------------------------------------------
def _arrivals(gaps, rows=512, t0=30.0):
    out, t = [], t0
    for g in gaps:
        t += g
        out.append((t, rows))
    return out


@pytest.mark.parametrize("gaps, seconds, want_open, want_close, want_rows, want_rate", [
    # first partition slow (compile), then steady: opens at the third, closes at the first arrival >= 4 s later
    ([20.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0], 4.0, 2, 6, 4 * 512, 512.0),
    # warm-up gaps disagree until they settle; the edge partition counts whole, the time runs to its arrival
    ([20.0, 3.0, 1.5, 1.0, 1.0, 1.0, 1.0, 1.0], 2.5, 4, 7, 3 * 512, 512.0),
    # a 3 s stall inside the window lowers the rate; nothing is dropped
    ([20.0, 1.0, 1.0, 1.0, 4.0, 1.0, 1.0, 1.0], 6.0, 2, 5, 3 * 512, 3 * 512 / 6.0),
])
def test_window_arithmetic(gaps, seconds, want_open, want_close, want_rows, want_rate):
    arrivals, seen, open_index, close_index = _arrivals(gaps), [], None, None
    for a in arrivals:
        seen.append(a)
        if open_index is None:
            if window.warmed_up(seen):
                open_index = len(seen) - 1
        elif window.closes(seen, open_index, seconds):
            close_index = len(seen) - 1
            break
    assert (open_index, close_index) == (want_open, want_close)
    w = window.measure(seen, open_index, close_index)
    assert w.rows == want_rows and w.partitions == want_close - want_open
    assert w.rows_per_s == pytest.approx(want_rate)
    assert w.longest_gap_s == pytest.approx(max(gaps[want_open + 1:want_close + 1]))


def test_partition_file_round_trip(tmp_path):
    arrivals = _arrivals([20.0, 1.0, 1.0, 1.0, 1.0])
    path = tmp_path / "cell-1-0.jsonl"
    with open(path, "w") as f:
        for i, (t, rows) in enumerate(arrivals):
            rec = {"i": i, "t": t, "rows": rows}
            if i in (2, 4):
                rec["mark"] = "open" if i == 2 else "close"
            f.write(json.dumps(rec) + "\n")
    got, o, c = window.read_partition_file(str(path))
    assert (got, o, c) == (arrivals, 2, 4)
    assert window.measure(got, o, c).rows_per_s == pytest.approx(512.0)


# -- the manifest and the resolver ----------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_KEY = re.compile(r"(hidden|intermediate|latent|state|projection|head).*(size|dim)|_dim$|_rank$")


def _is_mfu(name: str) -> bool:
    """``mfu`` as a part of the name of its own: ``model.step_mfu``, ``mfu.train``; not ``flops_util``."""
    return "mfu" in re.split(r"[._\-]", name)


def check_manifest(m: dict, path: str = None, root: str = ROOT) -> None:
    """What every manifest of this benchmark has to satisfy, with every cell resolved
    from ``path`` (None: the repo's own) and the files under ``root``."""
    bench_dir = os.path.join(root, "benchmark")
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert all(os.path.isdir(os.path.join(root, p)) for p in m["paths"])
    cells = len(m["workloads"])
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, cells // 4)
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == cells
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"]) and os.path.isfile(os.path.join(root, c["file"]))
        assert not any(WIDTH_KEY.search(k) for k in c["reduced"]), c["reduced"]
        published = manifest.load_json(os.path.join(root, c["file"])).get("published", {})
        assert set(c["reduced"]) == set(published) - {"note"}
    end_to_end = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in end_to_end and all(0 < e["bound"] <= 0.1 for e in m["end_to_end"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(x["unit"]) for k in ("end_to_end", "per_layer") for x in m[k])
    cell_names = {w["name"] for w in m["workloads"]}
    # the contract's most, stated here and nowhere else: no test pins a count or a place below it
    assert len(m["per_layer"]) <= 128, f"per_layer holds {len(m['per_layer'])} entries: the contract's most is 128"
    for p in m["per_layer"]:
        assert p["moves"] in end_to_end and 0 < len(p["layer"]) <= 200 and "\n" not in p["layer"]
        assert p["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if "workloads" in p:  # the cells in which the reader finds something to read
            assert p["workloads"] and set(p["workloads"]) <= cell_names, f"workloads of {p['name']}"
        if "roofline" in p["name"]:  # a kernel's, by name and by source: no whole step borrows the word
            assert p["name"].startswith("kernel.") and p["unit"] == "%" and p["source"] == "device_trace" \
                and "workloads" in p, f"roofline {p['name']}"
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        applies = [p["name"] for p in m["per_layer"] if "workloads" not in p or w["name"] in p["workloads"]]
        # every cell brings its whole step's share of the peak, and brings one
        assert sum(_is_mfu(n) for n in applies) == 1, f"mfu metrics of {w['name']}"
        cell = manifest.resolve(w["name"], path, bench_dir)  # every file of the cell is found and loads
        assert callable(cell.generator.build) and callable(cell.entry.build)
        assert callable(cell.comparison.compare)  # what the reference offers is the comparison's business
        assert [x["name"] for x in cell.end_to_end] == list(end_to_end)
        assert [x["name"] for x in cell.per_layer] == applies  # the entries that apply, and no count pinned
        assert all(callable(x["read"]) for x in cell.end_to_end + cell.per_layer)


def test_manifest_is_consistent(manifest_json):
    check_manifest(manifest_json)


def _planted(m: dict, fault: str) -> dict:
    m = json.loads(json.dumps(m))
    by_name = {p["name"]: p for p in m["per_layer"]}
    if fault == "roofline_outside_kernel":  # a whole step under a kernel's word
        by_name["model.step_mfu"]["name"] = "model.forward_roofline"
    elif fault == "roofline_for_every_cell":
        del by_name["kernel.attn_core_roofline"]["workloads"]
    elif fault == "two_mfu_in_a_cell":
        m["per_layer"].append(dict(by_name["model.step_mfu"], name="model.attn_core_mfu",
                                   workloads=["clip_vit_b16_image.predecoded_224"]))
    elif fault == "no_mfu_in_a_cell":
        by_name["model.step_mfu"]["workloads"].remove("clip_vit_l14_image.jpeg_parquet_laion")
    elif fault == "workloads_names_no_cell":
        by_name["engine.pull_s_per_krow"]["workloads"].append("clip_vit_h14_image.predecoded_224")
    elif fault == "workloads_empty":
        by_name["engine.pull_s_per_krow"]["workloads"] = []
    elif fault == "one_entry_too_many":  # copies of an entry every cell reports, up to one past the contract's most
        spare = by_name["device.idle_share"]
        m["per_layer"] += [dict(spare, name=f"device.idle_share.{k}") for k in range(129 - len(m["per_layer"]))]
    return m


@pytest.mark.parametrize("fault, says", [
    ("roofline_outside_kernel", "roofline model.forward_roofline"),
    ("roofline_for_every_cell", "roofline kernel.attn_core_roofline"),
    ("two_mfu_in_a_cell", "mfu metrics of clip_vit_b16_image.predecoded_224"),
    ("no_mfu_in_a_cell", "mfu metrics of clip_vit_l14_image.jpeg_parquet_laion"),
    ("workloads_names_no_cell", "workloads of engine.pull_s_per_krow"),
    ("workloads_empty", "workloads of engine.pull_s_per_krow"),
    ("one_entry_too_many", "per_layer holds 129 entries: the contract's most is 128")])
def test_a_planted_manifest_is_refused(manifest_json, tmp_path, fault, says):
    planted = _planted(manifest_json, fault)
    assert planted != manifest_json
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(planted))
    with pytest.raises(AssertionError, match=says):
        check_manifest(planted, str(path))


# -- what a ``prompt`` cell's entries have to hold, by name -----------------------------------------------
GRANITE_CELL = "granite_4_0_h_small_prompt.docs_lognormal_1k_out64"
LONGCAT_CELL = "longcat_flash_chat_prompt.docs_lognormal_4k_out64"
OLMO_CELL = "olmo_hybrid_7b_prompt.docs_lognormal_4k_out64"
DEEPSEEK_CELL = "deepseek_v3_2_exp_prompt.docs_lognormal_8k_out64"
#: One entry a reader: the batcher's, the prompter's and the set-up's readings read the same spans whatever the
#: decoder, so every ``prompt`` cell is listed in these (the expert counter in the cells whose decoder has experts).
FOR_EVERY_PROMPT_CELL = frozenset({
    "serve.host_exposed_s_per_krow", "serve.prefill_ms_per_ktoken", "serve.decode_step_ms", "serve.prefill_share",
    "serve.slot_occupancy", "serve.padded_token_share", "prompt.tokenize_s_per_krow", "lm.setup_init_s",
    "lm.setup_first_prefill_s", "serve.idle_ms_per_step", "serve.dispatch_host_ms_per_step",
    "serve.fetch_arrays_per_step", "prompt.idle_outside_run_s_per_krow", "serve.idle_unfiled_share",
    "serve.setup_first_decode_s"})
FOR_EVERY_CELL_WITH_EXPERTS = frozenset({"moe.held_assignment_share"})
#: cell -> its decoder's own measurements: the one ``*mfu``, its kernels' rooflines, its scope classes and counters
#: (``own``), and which of its entries read through ``decoder_scopes.beside`` (none but one, since PR 42). A further
#: decoder's cell brings a dictionary like these in its own test file and hands it to ``check_prompt_cell``.
PROMPT_CELLS = {
    GRANITE_CELL: {
        "mfu": "lm.step_mfu", "rooflines": {"kernel.ssd_scan_roofline", "kernel.expert_matmul_roofline"},
        "experts": True, "beside": set(), "entries": {"prompt_text"},
        "own": {"lm.mamba_ms_per_ktoken", "lm.experts_ms_per_ktoken", "lm.attn_ms_per_ktoken", "lm.head_ms_per_ktoken",
                "lm.other_ms_per_ktoken", "moe.expert_load_max_over_mean"}},
    LONGCAT_CELL: {
        "mfu": "lc.step_mfu", "rooflines": {"kernel.mla_core_roofline", "kernel.scmoe_expert_matmul_roofline"},
        "experts": True, "beside": set(), "entries": {"prompt_decoder"},
        "own": {"lc.mla_proj_ms_per_ktoken", "lc.mla_core_ms_per_ktoken", "lc.dense_mlp_ms_per_ktoken",
                "lc.experts_ms_per_ktoken", "lc.head_ms_per_ktoken", "lc.other_ms_per_ktoken",
                "lc.zero_assignment_share", "lc.expert_load_max_over_mean", "lc.cache_bytes_per_token"}},
    OLMO_CELL: {
        "mfu": "oh.step_mfu", "rooflines": {"kernel.delta_rule_roofline", "kernel.full_attn_core_roofline"},
        "experts": False, "beside": set(), "entries": {"prompt_decoder"},
        "own": {"oh.lin_proj_ms_per_ktoken", "oh.delta_rule_ms_per_ktoken", "oh.attn_proj_ms_per_ktoken",
                "oh.attn_core_ms_per_ktoken", "oh.mlp_ms_per_ktoken", "oh.head_ms_per_ktoken", "oh.other_ms_per_ktoken",
                "oh.kv_bytes_per_token", "oh.recurrent_mb_per_slot"}},
    DEEPSEEK_CELL: {
        "mfu": "ds.step_mfu", "rooflines": {"kernel.dsa_index_roofline", "kernel.dsa_core_roofline",
                                            "kernel.ds_expert_matmul_roofline"},
        "experts": True, "beside": {"ds.cache_bytes_per_token"}, "entries": {"prompt_decoder"},
        "own": {"ds.mla_proj_ms_per_ktoken", "ds.indexer_ms_per_ktoken", "ds.select_ms_per_ktoken",
                "ds.mla_core_ms_per_ktoken", "ds.dense_mlp_ms_per_ktoken", "ds.experts_ms_per_ktoken",
                "ds.head_ms_per_ktoken", "ds.other_ms_per_ktoken", "ds.selected_pair_share", "ds.cache_bytes_per_token",
                "ds.expert_load_max_over_mean"}},
}


def listed_for(m: dict, cell: str) -> dict:
    """name -> entry, of the per-layer entries whose ``workloads`` names ``cell``."""
    return {p["name"]: p for p in m["per_layer"] if cell in p.get("workloads", ())}


def reported_by(m: dict, cell: str) -> set:
    """The names a traced run of ``cell`` reports: the entries without a list and those that list it."""
    return {p["name"] for p in m["per_layer"] if "workloads" not in p or cell in p["workloads"]}


def check_prompt_cell(m: dict, cell: str, spec: dict, path: str = None, root: str = ROOT) -> None:
    """What the manifest ``m`` (at ``path``; None: the repo's own) has to hold of one ``prompt`` cell, all of it
    membership: these names list this cell, exactly one of what it reports is an ``*mfu``, these are its rooflines.
    Nothing here depends on where an entry, a cell or a configuration stands in its list, or on how many there are,
    so it holds as it is of a manifest to which a later cell's configuration, workload and entries were appended."""
    bench_dir = os.path.join(root, "benchmark")
    (workload,) = [w for w in m["workloads"] if w["name"] == cell]
    (config,) = [c for c in m["configs"] if c["name"] == workload["config"]]
    assert workload["chips"] == 1 and config["file"] == f"benchmark/configs/{config['name']}.json"
    listed, reported = listed_for(m, cell), reported_by(m, cell)
    # a cell appended between two ``benchmark`` PRs is in no accepted entry's list yet and says so (``"shared": set()``)
    shared = spec.get("shared", FOR_EVERY_PROMPT_CELL | (FOR_EVERY_CELL_WITH_EXPERTS if spec["experts"] else frozenset()))
    mine = spec["own"] | spec["rooflines"] | {spec["mfu"]}
    assert shared | mine <= set(listed), sorted((shared | mine) - set(listed))
    if not spec["experts"]:
        assert not FOR_EVERY_CELL_WITH_EXPERTS & set(listed)
    # the decoder's own measurements are of this decoder: they list this cell and no other
    assert all(listed[n]["workloads"] == [cell] for n in mine)
    # the whole step's share of the peak: one, and this decoder's; its kernels' rooflines among what it reports
    # (a later PR that writes a kernel for this decoder appends that kernel's)
    assert [n for n in reported if _is_mfu(n)] == [spec["mfu"]]
    assert spec["rooflines"] <= {n for n in reported if "roofline" in n}
    assert all((listed[n]["unit"], listed[n]["source"]) == ("%", "device_trace") for n in spec["rooflines"])
    for n in shared | mine:
        e = listed[n]
        assert os.path.isfile(os.path.join(bench_dir, "metrics", n + ".py")), n
        assert e["moves"] == ("setup_s" if ".setup_" in n else "rows_per_s_per_chip"), n
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}, n
    # one entry a reader: of what is named here, only these read through another entry's file
    through_beside = {n for n in shared | mine
                      if "beside(__file__" in open(os.path.join(bench_dir, "metrics", n + ".py")).read()}
    assert through_beside == spec["beside"]
    # every file of the cell is found, and the cell reports at least what is named here and what every cell reports
    resolved = manifest.resolve(cell, path, bench_dir)
    assert resolved.chips == 1 and resolved.config["entry"] in spec["entries"] and resolved.traffic["generator"] == "doc_pool"
    assert {x["name"] for x in resolved.per_layer} == reported >= shared | mine | {p["name"] for p in m["per_layer"] if "workloads" not in p}


#: big ``prompt`` cell -> the rehearsal manifest under ``data/`` whose one tiny cell stands for it on the CPU
TINY_MANIFEST_OF = {GRANITE_CELL: "rehearsal_prompt.json", LONGCAT_CELL: "rehearsal_longcat.json",
                    OLMO_CELL: "rehearsal_olmo.json", DEEPSEEK_CELL: "rehearsal_deepseek.json"}
#: Every manifest there is: the one the chip runs, the tiny CLIP cells', and whatever rehearsal manifest lies under
#: ``data/`` (found, not listed: a later cell's PR adds its own file there and edits nothing here).
MANIFESTS = [os.path.join(ROOT, "BENCHMARK.json"), REHEARSAL] + sorted(
    os.path.join(DATA, f) for f in os.listdir(DATA) if f.startswith("rehearsal_") and f.endswith(".json"))


def test_every_entry_has_its_reader_file_and_every_reader_file_an_entry():
    """What stays true of every PR, ``benchmark`` PRs among them: every per-layer and end-to-end entry of
    ``BENCHMARK.json`` and of the rehearsal manifests has its file under ``benchmark/metrics/``, and no file lies
    there that none of them names (a reader whose entry went goes with it)."""
    named = {}
    for path in MANIFESTS:
        m = manifest.load_json(path)
        for e in m["end_to_end"] + m["per_layer"]:
            named.setdefault(e["name"], []).append(os.path.relpath(path, ROOT))
    files = {f[:-len(".py")] for f in os.listdir(os.path.join(BENCH, "metrics")) if f.endswith(".py")}
    assert not set(named) - files, {n: named[n] for n in set(named) - files}
    assert not files - set(named), sorted(files - set(named))


def _reported_entries(m: dict, cell: str) -> set:
    """What ``cell`` reports, each entry without its ``workloads`` list (and whether it had one)."""
    return {(json.dumps({k: v for k, v in e.items() if k != "workloads"}, sort_keys=True), "workloads" in e)
            for e in m["per_layer"] if "workloads" not in e or cell in e["workloads"]}


@pytest.mark.parametrize("cell", sorted(TINY_MANIFEST_OF))
def test_a_tiny_prompt_cell_reports_what_its_big_cell_reports(cell, manifest_json):
    """The four rehearsal manifests of ``data/``: each tiny ``prompt`` cell reports entries its big cell reports,
    under the same names, entry for entry but for the cell's name in the ``workloads`` lists: every one that reads
    the same spans whatever the decoder, and the decoder's own as ``PROMPT_CELLS`` names them (an entry that a later
    PR appends for the big cell is rehearsed from a manifest that PR brings)."""
    path = os.path.join(DATA, TINY_MANIFEST_OF[cell])
    r = manifest.load_json(path)
    check_manifest(r, path)
    (tiny,) = [w["name"] for w in r["workloads"]]
    assert _reported_entries(r, tiny) <= _reported_entries(manifest_json, cell)
    spec = PROMPT_CELLS[cell]
    assert reported_by(r, tiny) >= FOR_EVERY_PROMPT_CELL | spec["own"] | spec["rooflines"] | {spec["mfu"]} | (
        FOR_EVERY_CELL_WITH_EXPERTS if spec["experts"] else frozenset())
    assert all(e.get("workloads", [tiny]) == [tiny] for e in r["per_layer"])
    assert r["end_to_end"] == manifest_json["end_to_end"]


#: A later PR's files, of another kind than what is here: a query that is no dataframe, answers that
#: are no embeddings, a comparison that is exact. Nothing below is named in any file of the benchmark.
DUMMY_FILES = {
    "configs/dummy_model.json": json.dumps(
        {"entry": "dummy_entry", "reference": "dummy_reference", "comparison": "dummy_exact", "batch_size": 4}),
    "traffic/dummy_mix.json": json.dumps({"generator": "dummy_generator", "rows": 28}),
    "traffic/dummy_generator.py": """
import types, numpy as np
def build(traffic, config, seed, workdir, seconds):
    pool = np.random.default_rng(seed).integers(0, 100, (traffic["rows"], 3))
    return types.SimpleNamespace(pool=pool, bytes_written=0)
""",
    "entries/dummy_entry.py": """
import time, numpy as np
SPANS, SPAN_ORDER = [], ["udf"]
class Query:
    def __init__(self, pool, batch, fault):
        self.pool, self.batch, self.fault = pool, batch, fault
    def iter_partitions(self):
        n = len(self.pool)
        for k in range(10 ** 6):
            ids = np.arange(k * self.batch, (k + 1) * self.batch) % n
            time.sleep(0.02)
            yield ids, self.pool[ids].sum(1) + self.fault
def exec_config(config): return {}
def build(traffic, config, seed): return Query(traffic.pool, config["batch_size"], config.get("fault", 0)), None
def udf_of(handle): return None
def take(part): return part
def n_devices(handle): return 1
def release(handle): pass
""",
    "reference/dummy_reference.py": "def answers(rows):\n    return [int(sum(r)) for r in rows]\n",
    "comparisons/dummy_exact.py": """
import numpy as np
def compare(cell, seed, pool, id_stream, window_parts, control=False):
    want = np.array(cell.reference.answers(pool))
    wrong = sum(int(np.count_nonzero(a != want[i])) for i, a in window_parts)
    return {"numbers": {"answers_wrong": {"value": wrong, "limit": 0}}, "failed": wrong}
""",
    "metrics/dummy.rows_seen.py": "def read(run):\n    return float(run.window.rows)\n",
    # its own whole-step share and its own kernel's roofline: silent where no device was traced
    "metrics/dummy.step_mfu.py": """
from lib import trace
def read(run):
    return 50.0 if trace.has_device(run.events) else None
""",
    "metrics/kernel.dummy_roofline.py": """
from lib import trace
def read(run):
    return 25.0 if trace.has_device(run.events) else None
""",
}


def test_a_cell_added_as_files_only_is_resolved_and_run(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {str(f.relative_to(bench)): f.read_bytes() for f in bench.rglob("*") if f.is_file()}
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for rel, text in DUMMY_FILES.items():
        (bench / rel).write_text(text)
    # ... and its entries in the manifest
    m["configs"].append({"name": "dummy_model", "source": "none", "file": "benchmark/configs/dummy_model.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "dummy_model.dummy_mix", "config": "dummy_model", "traffic": "dummy_mix",
                           "chips": 1, "why": "test"})
    mine = {"layer": "dummy", "moves": "rows_per_s_per_chip", "workloads": ["dummy_model.dummy_mix"]}
    m["per_layer"] += [
        dict(mine, name="dummy.rows_seen", unit="rows", better="higher", source="program_counter"),
        dict(mine, name="dummy.step_mfu", unit="%", better="higher", source="device_trace"),
        dict(mine, name="kernel.dummy_roofline", unit="%", better="higher", source="device_trace")]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    for p in m["paths"]:
        (tmp_path / p).mkdir(parents=True, exist_ok=True)
    check_manifest(m, str(path), str(tmp_path))  # appended entries, each listing one cell, pass
    cell = manifest.resolve("dummy_model.dummy_mix", str(path), str(bench))
    assert cell.config["batch_size"] == 4 and cell.traffic["rows"] == 28
    # the cell reports what every cell reports and its own three, not the embed-only accepted ones
    unlisted = {p["name"] for p in m["per_layer"] if "workloads" not in p}
    assert {x["name"] for x in cell.per_layer} == unlisted | {"dummy.rows_seen", "dummy.step_mfu", "kernel.dummy_roofline"}
    assert not {"model.step_mfu", "preprocess.s_per_krow", "provider.host_s_per_krow", "model.step_ms"} & unlisted
    # the new metrics list their cell, so no other cell has to report them
    for w in m["workloads"]:
        if w["name"] != "dummy_model.dummy_mix":
            other = manifest.resolve(w["name"], str(path), str(bench))
            assert not {"dummy.rows_seen", "dummy.step_mfu", "kernel.dummy_roofline"} & {x["name"] for x in other.per_layer}
    # the copy's own run.py drives the new cell from set-up to verdict: end-to-end, then traced
    run = manifest.load_module(str(bench / "run.py"))
    rec = run.run_cell(cell, seed=2 ** 31 + 5, seconds=0.3, trace_on=False)
    assert rec["correct"] is True and rec["failed"] == 0 and rec["attempted"] >= 4 * 15
    assert set(rec["metrics"]) == {"rows_per_s_per_chip", "setup_s"}
    assert 150 < rec["metrics"]["rows_per_s_per_chip"]["value"] <= 4 / 0.02
    assert list(rec)[-1] == "compared" and rec["compared"]["answers_wrong"] == {"value": 0, "limit": 0}
    traced = run.run_cell(cell, seed=7, seconds=0.3, trace_on=True)
    assert traced["metrics"]["dummy.rows_seen"]["value"] == traced["attempted"]
    # no device ran: a share of a peak or of a roofline is left out, not 0
    assert not {"dummy.step_mfu", "kernel.dummy_roofline", "device.idle_share"} & set(traced["metrics"])
    cell.config["fault"] = 1  # an answer altered where it is produced
    rec = run.run_cell(cell, seed=7, seconds=0.3, trace_on=False)
    assert rec["correct"] is False and rec["failed"] == rec["attempted"] > 0
    # no file that was there has changed
    after = {str(f.relative_to(bench)): f.read_bytes() for f in bench.rglob("*")
             if f.is_file() and "__pycache__" not in f.parts and "out" not in f.relative_to(bench).parts[:1]}
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == set(DUMMY_FILES)


#: A further decoder behind ``prompt``, as the next ``model_config`` PR brings it between two ``benchmark`` PRs: a
#: configuration for the entry that is here (``prompt_decoder``; the tiny Olmo-Hybrid decoder stands in for the
#: new one), a traffic file for the generator that is here, and entries of its own: one ``*mfu``, one kernel's
#: roofline, and a batcher's reading taken through ``decoder_scopes.beside`` because the accepted entry cannot
#: list the cell yet. Data and one-line readers; no file that is here is edited.
APPENDED_CELL = "appended_decoder_prompt.appended_docs"
APPENDED_READERS = {
    "metrics/ap.step_mfu.py": "from lib import trace\ndef read(run):\n    return 40.0 if trace.has_device(run.events) else None\n",
    "metrics/kernel.appended_core_roofline.py":
        "from lib import trace\ndef read(run):\n    return 30.0 if trace.has_device(run.events) else None\n",
    "metrics/ap.tokenize_s_per_krow.py":
        "from lib import decoder_scopes\n\nread = decoder_scopes.beside(__file__, \"prompt.tokenize_s_per_krow\")\n",
}


def test_a_decoders_cell_appended_as_files_only_leaves_the_four_prompt_cells_as_they_are(tmp_path, monkeypatch):
    """The criterion the next ``model_config`` PR depends on: an eighth cell behind ``prompt_decoder`` is appended to
    a copy of the benchmark (configuration, traffic, three entries; no file that is here edited), the enlarged
    manifest passes ``check_manifest``, the cell resolves and runs traced on the CPU with its wrapper reading the
    accepted reader's number, and what is held of the four ``prompt`` cells holds against the copy word for word."""
    from lib import program_spans

    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {str(f.relative_to(bench)): f.read_bytes() for f in bench.rglob("*") if f.is_file()}
    files = dict(APPENDED_READERS)
    files["configs/appended_decoder_prompt.json"] = (bench / "configs" / "rehearsal_tiny_olmo.json").read_text()
    files["traffic/appended_docs.json"] = (bench / "traffic" / "rehearsal_docs.json").read_text()
    for rel, text in files.items():
        (bench / rel).write_text(text)
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    m["configs"].append({"name": "appended_decoder_prompt", "source": "none", "reduced": [], "why": "test",
                         "file": "benchmark/configs/appended_decoder_prompt.json"})
    m["workloads"].append({"name": APPENDED_CELL, "config": "appended_decoder_prompt", "traffic": "appended_docs",
                           "chips": 1, "why": "test"})
    mine = {"better": "higher", "source": "device_trace", "layer": "decoder forward (appended)",
            "moves": "rows_per_s_per_chip", "workloads": [APPENDED_CELL]}
    m["per_layer"] += [dict(mine, name="ap.step_mfu", unit="%"), dict(mine, name="kernel.appended_core_roofline", unit="%"),
                       dict(mine, name="ap.tokenize_s_per_krow", unit="s/krow", better="lower", source="program_span",
                            layer="prompter and tokenizer (ai/flax_provider.py FlaxPrompter)")]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    for p in m["paths"]:
        (tmp_path / p).mkdir(parents=True, exist_ok=True)
    check_manifest(m, str(path), str(tmp_path))
    # what is held of the four prompt cells holds against the copy as against the repo's own; and of the new cell,
    # which no accepted entry lists yet, what its own dictionary says
    for cell, spec in PROMPT_CELLS.items():
        check_prompt_cell(m, cell, spec, str(path), str(tmp_path))
    check_prompt_cell(m, APPENDED_CELL, {
        "mfu": "ap.step_mfu", "rooflines": {"kernel.appended_core_roofline"}, "experts": False, "shared": set(),
        "own": {"ap.tokenize_s_per_krow"}, "beside": {"ap.tokenize_s_per_krow"}, "entries": {"prompt_decoder"}},
        str(path), str(tmp_path))
    # the copy's own run.py drives the new cell, traced: the wrapper reads what the accepted reader reads, the
    # shares of a peak need a device and are left out
    monkeypatch.setattr(program_spans, "MAX_BRACKET_NS", 50_000_000)  # beside five other test workers: see
    monkeypatch.setattr(program_spans, "WIDEN_NS", 50_000_000)        # test_prompt_cell.py
    cell = manifest.resolve(APPENDED_CELL, str(path), str(bench))
    run = manifest.load_module(str(bench / "run.py"))
    rec = run.run_cell(cell, seed=2 ** 31 + 41, seconds=0.5, trace_on=True)
    assert rec["correct"] is True and rec["failed"] == 0, rec["compared"]
    assert rec["metrics"]["ap.tokenize_s_per_krow"]["value"] > 0
    assert not {"ap.step_mfu", "kernel.appended_core_roofline"} & set(rec["metrics"])
    # no file that was there has changed
    after = {str(f.relative_to(bench)): f.read_bytes() for f in bench.rglob("*")
             if f.is_file() and "__pycache__" not in f.parts and "out" not in f.relative_to(bench).parts[:1]}
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == set(files)


def test_jpeg_sizes_do_not_depend_on_the_seed():
    gen = manifest.load_module(os.path.join(BENCH, "traffic", "image_pool.py"))
    p = manifest.load_json(os.path.join(BENCH, "traffic", "rehearsal_jpeg_parquet.json"))["jpeg"]
    from PIL import Image
    import io

    def sizes(seed):
        return [Image.open(io.BytesIO(b)).size for b in gen.jpeg_pool(p, 32, seed, threads=2)]

    a, b = sizes(3), sizes(2 ** 31 + 5)
    assert a != b and sorted(a) == sorted(b)  # the same multiset of sizes, in another order
    assert set(a) <= {tuple(s[:2]) for s in p["sizes"]} and len(set(a)) > 1
    assert gen.jpeg_pool(p, 8, 3, threads=1) == gen.jpeg_pool(p, 8, 3, threads=3)
    # the cell's own traffic: every file 256x256, the mean file the published 25 KB
    laion = manifest.load_json(os.path.join(BENCH, "traffic", "jpeg_parquet_laion.json"))["jpeg"]
    pool = gen.jpeg_pool(laion, 48, 11, threads=2)
    assert {Image.open(io.BytesIO(f)).size for f in pool} == {(256, 256)}
    assert np.mean([len(f) for f in pool]) == pytest.approx(25_000, rel=0.04)


# -- the run itself, on the CPU -----------------------------------------------------------
def _run(args, env_extra):
    # one device and one compute thread: the child must not crowd the other test workers
    env = dict(os.environ, **env_extra,
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1")
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py")] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(manifest_json):
    cell = manifest_json["workloads"][0]["name"]
    p = _run(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_rehearsal_prints_its_record_and_no_result_line():
    args = ["--workload", JPEG_CELL, "--seed", str(2 ** 31 + 77), "--seconds", "1", "--trace", "1", "--rehearse-cpu"]
    refused = _run(args, {"JAX_PLATFORMS": ""})
    assert refused.returncode == 2 and refused.stdout.strip() == ""
    p = _run(args, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    with pytest.raises(ValueError):
        json.loads(lines[-1])  # no contract line
    rec = json.loads(lines[-2])
    assert rec["rehearsal"] is True and rec["correct"] is True and rec["device"]["platform"] == "cpu"
    assert list(rec)[-2] == "compared" and "embedding_gap" in rec["compared"]
    assert "compared embedding_gap" in p.stderr and p.stderr.strip().endswith("correct: True")
    # per-layer metrics a CPU trace cannot give are left out, never reported as 0
    assert "model.step_mfu" not in rec["metrics"] and "preprocess.s_per_krow" in rec["metrics"]
    part = os.path.join(BENCH, "out", f"{JPEG_CELL}-{2 ** 31 + 77}-1.jsonl")
    arrivals, o, c = window.read_partition_file(part)
    # a streaming query: partitions arrived one by one, long before the source (60 s of rows) was read
    assert len(arrivals) > 4 and c > o >= 2 and arrivals[-1][0] > arrivals[0][0]
    assert window.measure(arrivals, o, c).rows == rec["attempted"]


def _broken(kind):
    """A ``_chunked_forward`` with one fault planted where the answers are produced."""
    from daft_tpu.ai import flax_provider

    sound = flax_provider._chunked_forward

    def faulty(fwd, params, arr, *a, **kw):
        if kind == "answers_shifted":  # every answer is its neighbour's
            return np.roll(sound(fwd, params, arr, *a, **kw), 1, axis=0)
        if kind == "half_the_batch_left_out":  # the forward sees half, the rest comes back empty
            out = np.array(sound(fwd, params, arr, *a, **kw))
            out[len(out) // 2:] = 0.0
            return out
        return sound(fwd, params, arr, *a, **kw)

    return faulty


@pytest.mark.parametrize("fault, want_correct", [
    ("none", True), ("answers_shifted", False), ("half_the_batch_left_out", False)])
def test_a_fault_in_the_timed_path_reads_not_correct(bench_run, monkeypatch, fault, want_correct):
    from daft_tpu.ai import flax_provider

    monkeypatch.setattr(flax_provider, "_chunked_forward", _broken(fault))
    cell = manifest.resolve(RAW_CELL, REHEARSAL)
    rec = bench_run.run_cell(cell, seed=2 ** 31 + 13, seconds=0.5, trace_on=False)
    assert rec["correct"] is want_correct, rec["compared"]
    if fault == "half_the_batch_left_out":
        assert rec["failed"] > 0 and rec["compared"]["rows_not_unit_norm"]["value"] > 0
    if fault == "none":
        assert rec["metrics"]["rows_per_s_per_chip"]["value"] > 0 and rec["attempted"] > 0


def test_rows_out_of_sequence_are_counted():
    ids = [np.arange(0, 16), np.arange(16, 32), np.arange(48, 64), np.arange(0, 16)]
    assert compare.out_of_sequence(ids[:2] + [np.arange(32, 48)] + ids[2:], 64) == 0
    assert compare.out_of_sequence(ids, 64) == 1  # one partition went missing


# -- the reference and its control ---------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cell = manifest.resolve(RAW_CELL, REHEARSAL)
    rng = np.random.default_rng(0)
    size = cell.config["image_size"]
    return cell, rng.integers(0, 256, (24, size, size, 3), dtype=np.uint8)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_reference_draws_the_programs_weights_and_agrees_with_it(tiny, seed):
    import jax

    from daft_tpu.ai.flax_provider import FlaxCLIPImageEmbedder
    from daft_tpu.models.clip import CLIPConfig, init_clip_params

    cell, pixels = tiny
    ref = cell.reference
    _, params = init_clip_params(CLIPConfig.from_name(cell.config["model"]), seed)
    theirs = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(params["params"]["vision"])}
    mine = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(ref.make_weights(cell.config, seed))}
    assert set(mine) == set(theirs)
    assert max(float(np.max(np.abs(np.asarray(mine[k]) - np.asarray(theirs[k])))) for k in mine) < 1e-7
    served = FlaxCLIPImageEmbedder(cell.config["model"], seed=seed, batch_size=8).embed_image(pixels)
    want = ref.embed(cell.config, seed, pixels, block_rows=8)
    gap = float(np.max(np.linalg.norm(served - want, axis=1)))
    assert gap <= cell.config["compare"]["embedding_gap_max"] / 2, gap  # bf16 compute against float32


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_control_one_precision_down_reads_not_correct(tiny, seed):
    cell, pixels = tiny
    ref = cell.reference
    pool = pixels.reshape(len(pixels), -1)
    ids = np.arange(len(pool))

    def verdict(delivered, control=False):
        out = cell.comparison.compare(cell, seed, pool, [ids], [(ids, delivered)], control=control)
        numbers = out["control" if control else "numbers"]
        return compare.verdict(numbers), numbers["embedding_gap"]["value"]

    sound = ref.embed(cell.config, seed, pixels, block_rows=8)
    ok, gap = verdict(sound)
    assert ok and gap < 1e-5
    ok, gap_fp8 = verdict(ref.embed(cell.config, seed, pixels, precision="fp8", block_rows=8))
    assert not ok and gap_fp8 > 1.5 * cell.config["compare"]["embedding_gap_max"]
    # the same control as the chip runs judge it: put in the place of sound answers by the comparison itself
    ok, gap_in_place = verdict(sound, control=True)
    assert not ok and gap_in_place > 1.5 * cell.config["compare"]["embedding_gap_max"]


# -- compiled for the chip, without the chip ------------------------------------------------
@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.slow  # half a CPU-minute on every core: crowds the timing fences of tier-1; run with -m slow
def test_b16_forward_compiles_for_v5e_and_fills_a_quarter_of_the_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    tool = manifest.load_module(os.path.join(BENCH, "tools", "compile_for_v5e.py"))
    cfg = dict(manifest.load_json(os.path.join(BENCH, "configs", "clip_vit_b16_image.json")),
               name="clip_vit_b16_image")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        got = tool.analyse(cfg, topo=topo)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
    assert got["xla_gflop_per_row"] == pytest.approx(35.4, rel=0.01)
    assert got["shape_count_gflop_per_row"] == pytest.approx(got["xla_gflop_per_row"], rel=0.03)
    # parameters (text tower included) + temporaries: the chip read 4.75 GB, 28% of its 16.9 GB
    assert 4.2 < got["argument_gb"] + got["temp_gb"] < 5.0
