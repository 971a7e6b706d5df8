"""Tests of the readers of the program's own spans (``benchmark/lib/program_spans.py``,
``benchmark/lib/scopes.py``, the sixteen readers and their entries in
``benchmark/program_span_metrics.json``). CPU only; nothing here asserts a time."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import manifest, program_spans, scopes  # noqa: E402

REHEARSAL = os.path.join(BENCH, "rehearsal.json")
JPEG_CELL = "rehearsal_tiny_clip.rehearsal_jpeg_parquet"
ENTRIES = manifest.load_json(os.path.join(BENCH, "program_span_metrics.json"))["per_layer"]
MS = 1_000_000


def _span(name, start, end, **count):
    return SimpleNamespace(name=name, start_ns=start, end_ns=end, count=count, error=False)


def _synthetic(offset_ns: int, batches: int = 6, stray: int = 0):
    """Wrapper spans on the trace's clock and the program's spans ``offset_ns``
    later on theirs, nested as the code nests them; ``stray`` program batches
    more at the end that no wrapper saw."""
    wrappers = {"provider": [], "stage": [], "pad": []}
    program = []
    for k in range(batches + stray):
        t = k * 100 * MS + k * k * 137_000  # no two batches the same distance apart
        if k < batches:
            wrappers["provider"].append([t, t + 90 * MS])
            wrappers["stage"].append([t + 2 * MS + 2_000, t + 7 * MS - 1_000])
        o = offset_ns
        program += [_span("provider.forward", t + 3_000 + o, t + 90 * MS - 2_000 + o, rows=8),
                    _span("provider.pad", t + 1 * MS + o, t + 2 * MS - 5_000 + o, rows=6, padded_rows=8),
                    _span("provider.stage", t + 2 * MS + o, t + 7 * MS + o, bytes=64),
                    _span("provider.dispatch", t + 7 * MS + 1_000 + o, t + 9 * MS + o),
                    _span("provider.fetch", t + 9 * MS + 1_000 + o, t + 89 * MS + o, bytes=32)]
    return program, wrappers, [0, (batches + 1) * 100 * MS]


def _run(program, wrappers, window, device_ops=None, trace_rows=48):
    devices = {"/device:TPU:0": {"ops": device_ops, "modules": []}} if device_ops is not None else {}
    return SimpleNamespace(events={"window": window, "devices": devices, "spans": wrappers},
                           trace_rows=trace_rows)


# -- the clock ------------------------------------------------------------------------
@pytest.mark.parametrize("stray", [0, 1])
def test_clock_is_matched_through_a_planted_offset(stray):
    planted = 3 * MS + 2 ** 60  # the program's clock is wall-anchored: near 2**61 in 2026
    program, wrappers, window = _synthetic(planted, stray=stray)
    clock = program_spans.match_clock(program, wrappers, window)
    # the stage wrapper opens 2 us after its program span (lo = planted - 2 us), the forward span
    # 3 us after its wrapper (hi = planted + 3 us): the middle is taken, and the bracket is 5 us
    assert clock.bracket_ns == 5_000 and clock.pairs == 12
    assert clock.offset_ns - planted == 500
    assert isinstance(clock.offset_ns, int)


def test_clock_refuses_a_span_outside_its_wrapper(monkeypatch, capsys):
    program, wrappers, window = _synthetic(3 * MS)
    wrappers["stage"][2][1] += 200_000  # this wrapper ends 0.2 ms after its program span
    with pytest.raises(program_spans.ClockMismatch, match="'stage' wrapper"):
        program_spans.match_clock(program, wrappers, window)
    program, wrappers, window = _synthetic(3 * MS)
    for w in wrappers["provider"]:
        w[0] -= 300_000  # every forward opens 0.3 ms into its wrapper: no bracket
    with pytest.raises(program_spans.ClockMismatch, match="bracket"):
        program_spans.match_clock(program, wrappers, window)
    with pytest.raises(program_spans.ClockMismatch, match="no pair"):
        program_spans.match_clock(program, {"provider": wrappers["provider"]}, window)
    # through a reader: nothing is read, and standard error says why
    monkeypatch.setattr(program_spans, "ring", lambda: program)
    run = _run(program, wrappers, window)
    assert program_spans.aligned(run) is None and program_spans.in_window(run, "provider.pad") == []
    assert "no program span is read" in capsys.readouterr().err


# -- exposure ---------------------------------------------------------------------------
def test_exposed_time_on_hand_made_device_intervals(monkeypatch):
    program, wrappers, window = _synthetic(5 * MS, batches=2)
    monkeypatch.setattr(program_spans, "ring", lambda: program)
    # the device works from 8 ms to 85 ms of each batch: stage (2-7 ms) lies bare, the first
    # millisecond of dispatch (7-9 ms) too, and the last 4 of the fetch (9-89 ms)
    ops = [[k * 100 * MS + k * k * 137_000 + 8 * MS, 77 * MS, "%fusion.1 = f32[8]{0} fusion()"] for k in range(2)]
    run = _run(program, wrappers, window, ops, trace_rows=16)
    assert program_spans.exposed_s(run, "provider.stage") == pytest.approx(2 * 5e-3, abs=1e-5)
    assert program_spans.exposed_s(run, "provider.dispatch") == pytest.approx(2 * 1e-3, abs=1e-5)
    assert program_spans.exposed_s(run, "provider.fetch") == pytest.approx(2 * 4e-3, abs=1e-5)
    assert program_spans.span_s(run, "provider.fetch") == pytest.approx(2 * 80e-3, abs=1e-5)
    split = program_spans.exposed_split_s(run, "provider.fetch")  # the device starts 1 ms into the fetch
    assert split["after_last_op"] == pytest.approx(2 * 4e-3, abs=1e-5) and split["between_ops"] == 0
    assert split["before_first_op"] == pytest.approx(0, abs=1e-5) and split["no_op"] == 0
    run.events["window"] = [50 * MS, 150 * MS]  # a window that cuts both fetches: times are clipped, counters are not
    assert program_spans.span_s(run, "provider.fetch") == pytest.approx((89 - 50) * 1e-3 + (150 - 109.137) * 1e-3, abs=1e-5)
    assert program_spans.counter_sum(run, "provider.pad", "padded_rows") == 8  # one pad began in it
    run.events["window"] = window
    assert program_spans.per_krow(run, 0.016) == pytest.approx(1.0)
    assert program_spans.counter_sum(run, "provider.pad", "padded_rows") == 16
    assert program_spans.counter_sum(run, "provider.pad", "no_such") is None
    no_device = _run(program, wrappers, window, None)
    assert program_spans.exposed_s(no_device, "provider.stage") is None
    assert program_spans.span_s(no_device, "provider.stage") == pytest.approx(2 * 5e-3, abs=1e-5)


def test_set_up_spans_are_the_newest_before_the_window(monkeypatch):
    program, wrappers, window = _synthetic(0, batches=4)
    early = [_span("provider.init_params", -900 * MS, -400 * MS, param_bytes=1),
             _span("provider.forward", -300 * MS, -100 * MS, first=1, rows=8),
             _span("provider.forward", -90 * MS, -10 * MS, rows=8)]
    monkeypatch.setattr(program_spans, "ring", lambda: early + program)
    run = _run(program, wrappers, window)
    assert program_spans.setup_span_s(run, "provider.init_params") == pytest.approx(0.5)
    assert program_spans.setup_span_s(run, "provider.forward", first=1) == pytest.approx(0.2)
    assert program_spans.setup_span_s(run, "provider.forward") == pytest.approx(0.08)
    assert program_spans.setup_span_s(run, "provider.place_params") is None


# -- scopes -----------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_hlo():
    import jax

    cell = manifest.resolve(JPEG_CELL, REHEARSAL)
    fn, shapes = cell.entry.lowerable(cell.config)
    return scopes.parse_hlo(jax.jit(fn).lower(*shapes).compile().as_text())


def test_every_instruction_of_the_tiny_forward_is_classed(tiny_hlo):
    (entry,) = [c for c in tiny_hlo["computations"] if c.startswith("main")]
    names = tiny_hlo["computations"][entry]
    assert len(names) > 40
    by_class = {}
    for name in names:
        scope, found = scopes.scopes_of(tiny_hlo, name)
        assert scope is None or scope in found or tiny_hlo["instructions"][name]["scope"] == scope
        by_class.setdefault(scopes.classify(scope), []).append(tiny_hlo["instructions"][name]["opcode"])
    assert set(by_class) == set(scopes.CLASSES)
    # two blocks: fc1 and fc2, qkv and out, the two products of the attention core
    matmuls = {c: sum(op in scopes.MATMULS for op in ops) for c, ops in by_class.items()}
    assert (matmuls["mlp"], matmuls["attn_proj"], matmuls["attn_core"]) == (4, 4, 4)
    assert matmuls["layernorm"] == 0 and matmuls["other"] >= 1  # patch embedding, projection


@pytest.mark.parametrize("scope, want", [
    ("jit(fwd)/CLIPModel.encode_image/vision/block_3/mlp/fc1/dot_general", "mlp"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_3/mlp/tanh", "mlp"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_0/attn/qkv/add", "attn_proj"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_0/attn/out/dot_general", "attn_proj"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_0/attn/attn_core/vmap()/exp", "attn_core"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_0/attn/vmap(BTNH,BSNH->BNTS)/dot_general", "attn_core"),  # cached before the scope
    ("jit(fwd)/CLIPModel.encode_image/vision/block_0/attn/split", "other"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_9/ln2/rsqrt", "layernorm"),
    ("jit(fwd)/CLIPModel.encode_image/vision/ln_pre/sub", "layernorm"),
    ("jit(fwd)/CLIPModel.encode_image/text/ln_final/mul", "layernorm"),
    ("jit(fwd)/CLIPModel.encode_image/vision/pixel_norm/div", "other"),
    ("jit(fwd)/CLIPModel.encode_image/vision/block_1/add", "other"),
    (None, "other"),
])
def test_classes_by_scope_path(scope, want):
    assert scopes.classify(scope) == want


PLANTED = '''HloModule jit_fwd, is_scheduled=true

%fused_computation.7 (p0: bf16[8,5,64], p1: bf16[64,256]) -> bf16[8,5,256] {
  %p0 = bf16[8,5,64]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[64,256]{1,0} parameter(1)
  %convolution.3 = bf16[8,5,256]{2,1,0} convolution(%p0, %p1), dim_labels=0bf_io0->0bf, metadata={op_name="jit(fwd)/m/vision/block_0/mlp/fc1/dot_general" source_file="x.py" source_line=3}
  ROOT %multiply.9 = bf16[8,5,256]{2,1,0} multiply(%convolution.3, %convolution.3), metadata={op_name="jit(fwd)/m/vision/block_0/ln2/mul"}
}

%fused_computation.8 (p0: f32[8,5,64]) -> f32[8,5] {
  %p0 = f32[8,5,64]{2,1,0} parameter(0)
  ROOT %reduce.2 = f32[8,5]{1,0} reduce(%p0), dimensions={2}, metadata={op_name="jit(fwd)/m/vision/block_0/ln2/reduce_sum"}
}

ENTRY %main.42 (a: bf16[8,5,64], b: bf16[64,256], c: f32[8,5,64]) -> f32[8,5] {
  %a = bf16[8,5,64]{2,1,0} parameter(0)
  %b = bf16[64,256]{1,0} parameter(1)
  %c = f32[8,5,64]{2,1,0} parameter(2)
  %fusion.7 = bf16[8,5,256]{2,1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(fwd)/m/vision/block_0/ln2/mul"}
  %copy.4 = bf16[8,5,256]{1,2,0} copy(%fusion.7), metadata={op_name="jit(fwd)/m/vision/block_0/attn/transpose"}
  ROOT %fusion.8 = f32[8,5]{1,0} fusion(%c), kind=kInput, calls=%fused_computation.8
}
'''


def _planted_events(unknown_ns=0):
    ops = []
    for k in range(3):  # three whole steps of 10 ms: the fusion with the matmul 6, the copy 1, the reduce 3
        t = k * 20 * MS
        ops += [[t, 6 * MS, "%fusion.7 = bf16[8,5,256]{2,1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput"],
                [t + 6 * MS, 1 * MS, "%copy.4 = bf16[8,5,256]{1,2,0} copy(%fusion.7)"],
                [t + 7 * MS, 3 * MS - unknown_ns, "%fusion.8 = f32[8,5]{1,0} fusion(%c), kind=kInput"]]
        if unknown_ns:
            ops.append([t + 10 * MS - unknown_ns, unknown_ns, "%fusion.99 = f32[8]{0} fusion(%z)"])
    modules = [[k * 20 * MS, 10 * MS, f"jit_fwd({k})"] for k in range(3)] + [[55 * MS, 1 * MS, "jit_other(1)"]]
    return {"window": [0, 60 * MS], "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "spans": {}}


def test_a_fusion_with_a_matmul_belongs_to_the_matmuls_scope():
    hlo = scopes.parse_hlo(PLANTED)
    assert set(hlo["computations"]) == {"fused_computation.7", "fused_computation.8", "main.42"}
    assert hlo["instructions"]["fusion.7"]["opcode"] == "fusion" and hlo["instructions"]["copy.4"]["opcode"] == "copy"
    scope, found = scopes.scopes_of(hlo, "fusion.7")  # its own op_name says ln2; the convolution inside says fc1
    assert scope.endswith("mlp/fc1/dot_general") and len(found) == 2
    assert scopes.classify(scope) == "mlp"
    scope, _ = scopes.scopes_of(hlo, "fusion.8")  # no op_name of its own: its root's
    assert scopes.classify(scope) == "layernorm"
    got = scopes.analyse(_planted_events(), hlo)
    assert got["steps"] == 3 and got["coverage"] == 1.0
    assert got["classes"] == {"mlp": 6.0, "attn_proj": 0.0, "attn_core": 0.0, "layernorm": 3.0, "other": 1.0}
    assert sum(got["classes"].values()) == pytest.approx(10.0)
    top = got["ops"]["fusion bf16[8,5,256]"]
    assert top["ms"] == 6.0 and top["class"] == "mlp" and top["scopes"] == ["block_*/ln2", "block_*/mlp/fc1"]
    assert scopes.event_instruction("copy.3") == "copy.3"


def test_under_99_percent_coverage_nothing_is_read(monkeypatch, capsys):
    hlo_text = PLANTED
    monkeypatch.setattr(scopes, "forward_text", lambda run: hlo_text)
    run = SimpleNamespace(events=_planted_events(unknown_ns=200_000))  # 2% of each step is not in the text
    assert scopes.classes(run) is None and scopes.table(run) is None
    assert "did not reproduce" in capsys.readouterr().err and run.scopes_coverage == pytest.approx(0.98)
    run = SimpleNamespace(events=_planted_events(unknown_ns=50_000))  # 0.5%: read, the stranger under other
    got = scopes.classes(run)
    assert got["other"] == pytest.approx(1.05) and sum(got.values()) == pytest.approx(10.0)
    assert [r["kind"] for r in scopes.table(run)][:2] == ["fusion bf16[8,5,256]", "fusion f32[8,5]"]
    assert scopes.classes(SimpleNamespace(events=None)) is None  # no trace: no compile either


# -- the sixteen readers and their entries -------------------------------------------------
def test_the_entries_name_files_accepted_layers_and_real_cells():
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    accepted_layers = {p["layer"] for p in m["per_layer"]}
    cells = {w["name"] for w in m["workloads"]}
    end_to_end = {e["name"] for e in m["end_to_end"]}
    names = [e["name"] for e in ENTRIES]
    assert len(names) == len(set(names)) == 16
    assert not set(names) & {p["name"] for p in m["per_layer"]}
    for e in ENTRIES:
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.isfile(os.path.join(BENCH, "metrics", e["name"] + ".py")), e["name"]
        assert e["layer"] in accepted_layers and e["moves"] in end_to_end
        assert e["workloads"] and set(e["workloads"]) <= cells
        assert e["source"] in ("device_trace", "program_span", "program_counter") and e["better"] == "lower"
        assert "roofline" not in e["name"]
    jpeg_only = {e["name"] for e in ENTRIES if len(e["workloads"]) == 1}
    assert jpeg_only == {"preprocess.decode_s_per_krow", "preprocess.resize_s_per_krow", "preprocess.slowest_row_ms"}
    # appended to the manifest they make sixteen more per-layer metrics in the JPEG cell, thirteen in the others
    for cell, more in (("clip_vit_l14_image.jpeg_parquet_laion", 16), ("clip_vit_b16_image.predecoded_224", 13)):
        assert len({e["name"] for e in ENTRIES if cell in e["workloads"]}) == more


@pytest.mark.parametrize("name", [e["name"] for e in ENTRIES])
def test_each_reader_returns_none_without_spans_or_device(name, monkeypatch):
    read = manifest.load_module(os.path.join(BENCH, "metrics", name + ".py")).read
    cell = SimpleNamespace(entry=None, config={})
    no_trace = SimpleNamespace(events=None, trace_rows=0, cell=cell)
    assert read(no_trace) is None
    # a traced run of a program that has no ring (the parent commit), and one whose ring is empty
    cpu_trace = {"window": [0, 10 * MS], "devices": {}, "spans": {"provider": [[0, MS]], "stage": [[0, MS]]}}
    for ring in (None, []):
        monkeypatch.setattr(program_spans, "ring", lambda ring=ring: ring)
        assert read(SimpleNamespace(events=cpu_trace, trace_rows=64, cell=cell)) is None


def test_the_readers_read_a_traced_rehearsal_end_to_end():
    """``tools/program_spans.py --rehearse-cpu`` on the tiny JPEG cell: the merged manifest drives ``run.py``'s
    own ``run_cell``; every reader that needs no device reports, the device's are left out, the accepted
    metrics are there. In a child with one compute thread, so that it does not crowd the other test workers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "tools", "program_spans.py"), "--rehearse-cpu",
                        "--workload", JPEG_CELL, "--seed", str(2 ** 31 + 21), "--seconds", "0.5"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    got = rec["metrics"]
    assert rec["correct"] is True and "_run" not in got and "preprocess.s_per_krow" in got
    for name in ("engine.pull_s_per_krow", "preprocess.decode_s_per_krow", "preprocess.resize_s_per_krow",
                 "preprocess.slowest_row_ms", "provider.padded_row_share",
                 "setup.init_s", "setup.place_s", "setup.first_forward_s"):
        assert got[name]["value"] >= 0, name
    assert not any(n.startswith("model.") and n.endswith("_ms") and n != "model.step_ms" for n in got)
    assert "provider.fetch_exposed_s_per_krow" not in got  # no device plane in a CPU trace
    # decode + resize are within what the wrapper from outside saw of preprocess
    parts = got["preprocess.decode_s_per_krow"]["value"] + got["preprocess.resize_s_per_krow"]["value"]
    assert 0 < parts <= got["preprocess.s_per_krow"]["value"] * 1.05
    assert got["provider.padded_row_share"]["value"] == 50.0  # batches of 8 rows in buckets of 16
    (line,) = [l for l in p.stderr.splitlines() if l.startswith("program_spans: ")]
    info = json.loads(line[len("program_spans: "):])
    assert 0 <= info["clock"]["bracket_ns"] <= program_spans.MAX_BRACKET_NS and info["clock"]["pairs"] >= 4
    assert set(info["preprocess_s_per_krow"]) == {"decode", "resize", "copy"}
