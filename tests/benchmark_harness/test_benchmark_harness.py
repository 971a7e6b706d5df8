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


def test_manifest_is_consistent(manifest_json):
    m = manifest_json
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in m["paths"])
    cells = len(m["workloads"])
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, cells // 4)
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == cells
    for c in m["configs"]:
        assert any(c["file"].startswith(p + "/") for p in m["paths"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert not any(WIDTH_KEY.search(k) for k in c["reduced"]), c["reduced"]
        published = manifest.load_json(os.path.join(ROOT, c["file"])).get("published", {})
        assert set(c["reduced"]) == set(published) - {"note"}
    end_to_end = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in end_to_end and all(0 < e["bound"] <= 0.1 for e in m["end_to_end"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(x["unit"]) for k in ("end_to_end", "per_layer") for x in m[k])
    for p in m["per_layer"]:
        assert p["moves"] in end_to_end and 0 < len(p["layer"]) <= 200 and "\n" not in p["layer"]
        assert p["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "roofline" not in p["name"]  # no whole-step number under a kernel's name
    assert any("mfu" in p["name"].split(".")[-1].split("_") for p in m["per_layer"])
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = manifest.resolve(w["name"])  # every file of the cell is found and loads
        assert callable(cell.generator.build) and callable(cell.entry.build) and callable(cell.reference.embed)
        assert callable(cell.comparison.compare)
        assert [x["name"] for x in cell.end_to_end] == list(end_to_end)
        assert len(cell.per_layer) == len(m["per_layer"]) == 12
        assert all(callable(x["read"]) for x in cell.end_to_end + cell.per_layer)


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
    m["per_layer"].append({"name": "dummy.rows_seen", "unit": "rows", "better": "higher", "source": "program_counter",
                           "layer": "dummy", "moves": "rows_per_s_per_chip", "workloads": ["dummy_model.dummy_mix"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    cell = manifest.resolve("dummy_model.dummy_mix", str(path), str(bench))
    assert cell.config["batch_size"] == 4 and cell.traffic["rows"] == 28
    # the new metric lists its cell, so no other cell has to report it
    other = manifest.resolve(m["workloads"][0]["name"], str(path), str(bench))
    assert "dummy.rows_seen" not in [x["name"] for x in other.per_layer]
    # the copy's own run.py drives the new cell from set-up to verdict: end-to-end, then traced
    run = manifest.load_module(str(bench / "run.py"))
    rec = run.run_cell(cell, seed=2 ** 31 + 5, seconds=0.3, trace_on=False)
    assert rec["correct"] is True and rec["failed"] == 0 and rec["attempted"] >= 4 * 15
    assert set(rec["metrics"]) == {"rows_per_s_per_chip", "setup_s"}
    assert 150 < rec["metrics"]["rows_per_s_per_chip"]["value"] <= 4 / 0.02
    assert list(rec)[-1] == "compared" and rec["compared"]["answers_wrong"] == {"value": 0, "limit": 0}
    traced = run.run_cell(cell, seed=7, seconds=0.3, trace_on=True)
    assert traced["metrics"]["dummy.rows_seen"]["value"] == traced["attempted"]
    assert "model.step_mfu" not in traced["metrics"]  # no device ran: left out, not 0
    cell.config["fault"] = 1  # an answer altered where it is produced
    rec = run.run_cell(cell, seed=7, seconds=0.3, trace_on=False)
    assert rec["correct"] is False and rec["failed"] == rec["attempted"] > 0
    # no file that was there has changed
    after = {str(f.relative_to(bench)): f.read_bytes() for f in bench.rglob("*")
             if f.is_file() and "__pycache__" not in f.parts and "out" not in f.relative_to(bench).parts[:1]}
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == set(DUMMY_FILES)


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
