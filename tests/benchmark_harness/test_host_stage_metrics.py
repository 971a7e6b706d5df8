"""PR 30's two readers (``engine.host_stage_wait_s_per_krow``, ``engine.host_stage_ready_share``) on
hand-made spans, their entries in ``BENCHMARK.json``, and a traced JPEG rehearsal with the host stage
running ahead (the CPU backend keeps the serial loop, so the descriptor is told that it runs beside
the host): every reader of the program's spans still finds its spans, now that they come from four
threads. CPU only; nothing here asserts a time. No file the benchmark already had is edited:
``benchmark/rehearsal.json`` is read, given the two entries in memory and written under ``tmp_path``."""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import manifest, program_spans  # noqa: E402

NEW = ["engine.host_stage_wait_s_per_krow", "engine.host_stage_ready_share"]
EMBED_CELLS = ["clip_vit_l14_image.predecoded_224", "clip_vit_b16_image.predecoded_224",
               "clip_vit_l14_image.jpeg_parquet_laion"]
TINY_CELLS = ["rehearsal_tiny_clip.rehearsal_raw", "rehearsal_tiny_clip.rehearsal_jpeg_parquet"]
MS = 1_000_000


def _read(name):
    return manifest.load_module(os.path.join(BENCH, "metrics", name + ".py")).read


def _span(name, start, end, **count):
    return SimpleNamespace(name=name, start_ns=start, end_ns=end, count=count, error=False)


def _pipelined(offset_ns: int, batches: int = 6, late=()):
    """Spans as the operator writes them with the host stage ahead: batch k's transfer (``provider.pad``,
    ``provider.stage`` with its wrapper inside) runs while batch k-1 is in its forward; the operator waits
    1 ms for a morsel that is ready and 30 ms for one that is not (``late``)."""
    wrappers = {"provider": [], "stage": []}
    program = []
    o, t = offset_ns, 5 * MS  # the window opens 5 ms before the first wait
    for k in range(batches):
        wait = 30 * MS if k in late else 1 * MS
        program.append(_span("udf.wait", t + o, t + wait + o, ready=int(k not in late), rows=8))
        t += wait
        wrappers["provider"].append([t, t + 90 * MS])
        program += [_span("provider.forward", t + 3_000 + o, t + 90 * MS - 2_000 + o, rows=8, staged=1),
                    _span("provider.dispatch", t + 1 * MS + o, t + 2 * MS + o),
                    _span("provider.fetch", t + 2 * MS + o, t + 89 * MS + o, bytes=32)]
        # the next batch's transfer, on its own thread, 10 ms into this forward
        wrappers["stage"].append([t + 10 * MS + 2_000, t + 15 * MS - 1_000])
        program += [_span("provider.pad", t + 9 * MS + o, t + 10 * MS - 5_000 + o, rows=8, padded_rows=8),
                    _span("provider.stage", t + 10 * MS + o, t + 15 * MS + o, bytes=64)]
        t += 90 * MS
    program.append(_span("udf.wait", t + o, t + 1 * MS + o, ready=1))  # the end of the stream: no rows
    return program, wrappers, [0, t + 50 * MS]


def _run(program, wrappers, window, trace_rows=48):
    return SimpleNamespace(events={"window": window, "devices": {}, "spans": wrappers}, trace_rows=trace_rows)


# -- the readers on hand-made spans ------------------------------------------------------
@pytest.mark.parametrize("late, want_ready, want_wait_ms", [((), 100.0, 6 * 1 + 1), ((0, 3), 100.0 * 4 / 6, 4 * 1 + 2 * 30 + 1)])
def test_the_two_readers_on_hand_made_spans(monkeypatch, late, want_ready, want_wait_ms):
    planted = 7 * MS + 2 ** 60
    program, wrappers, window = _pipelined(planted, late=late)
    monkeypatch.setattr(program_spans, "ring", lambda: program)
    run = _run(program, wrappers, window)
    assert program_spans.aligned(run).pairs == 12  # the clocks match though stage and forward overlap
    assert _read("engine.host_stage_ready_share")(run) == pytest.approx(want_ready)  # the wait that found the end is no morsel
    assert _read("engine.host_stage_wait_s_per_krow")(run) == pytest.approx(1000 * want_wait_ms * 1e-3 / 48, abs=1e-4)


def test_the_wait_is_clipped_to_the_window_and_a_morsel_is_counted_where_its_wait_began(monkeypatch):
    program, wrappers, window = _pipelined(5 * MS, late=(0,))
    monkeypatch.setattr(program_spans, "ring", lambda: program)
    run = _run(program, wrappers, [25 * MS, window[1]], trace_rows=40)  # opens 20 ms into the first, late, wait
    assert _read("engine.host_stage_wait_s_per_krow")(run) == pytest.approx(1000 * (10 + 5 * 1 + 1) * 1e-3 / 40, abs=1e-4)
    assert _read("engine.host_stage_ready_share")(run) == 100.0  # that wait began before the window


@pytest.mark.parametrize("name", NEW)
def test_each_reader_returns_none_where_the_program_has_no_such_span(name, monkeypatch):
    read = _read(name)
    assert read(SimpleNamespace(events=None, trace_rows=0)) is None  # no trace
    cpu_trace = {"window": [0, 10 * MS], "devices": {}, "spans": {"provider": [[0, MS]], "stage": [[0, MS]]}}
    for ring in (None, []):  # the parent commit's program: no ring, or one without these spans
        monkeypatch.setattr(program_spans, "ring", lambda ring=ring: ring)
        assert read(SimpleNamespace(events=cpu_trace, trace_rows=64)) is None
    # a program whose UDF declares no host stage writes every other span and no ``udf.wait``
    program, wrappers, window = _pipelined(3 * MS)
    serial = [s for s in program if s.name != "udf.wait"]
    monkeypatch.setattr(program_spans, "ring", lambda: serial)
    assert read(_run(serial, wrappers, window)) is None


# -- the entries -----------------------------------------------------------------------------
def test_the_two_entries_list_the_embed_cells_and_no_prompt_cell():
    """Membership, no place and no count: wherever the two stand among however many entries, they list the three
    embed cells, those cells report them, and a cell whose UDF has no host stage does not."""
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {p["name"]: p for p in m["per_layer"]}
    wait, ready = (by_name[n] for n in NEW)
    for e in (wait, ready):
        assert set(e["workloads"]) == set(EMBED_CELLS) and e["moves"] == "rows_per_s_per_chip"
        assert e["layer"] == "UDF operator and source (execution/executor.py, scan)"
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.isfile(os.path.join(BENCH, "metrics", e["name"] + ".py"))
    assert (wait["unit"], wait["better"], wait["source"]) == ("s/krow", "lower", "program_span")
    assert (ready["unit"], ready["better"], ready["source"]) == ("%", "higher", "program_counter")
    for w in m["workloads"]:  # the embed cells resolve with them; the prompt cells, whose UDF has no host stage, without
        reported = {p["name"] for p in manifest.resolve(w["name"]).per_layer}
        assert set(NEW) <= reported if w["name"] in EMBED_CELLS else not set(NEW) & reported, w["name"]


# -- a traced rehearsal with the host stage ahead ---------------------------------------------
@pytest.fixture
def rehearsal_with_the_entries(tmp_path):
    m = manifest.load_json(os.path.join(BENCH, "rehearsal.json"))
    real = {p["name"]: p for p in manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    m["per_layer"] += [dict(real[name], workloads=TINY_CELLS) for name in NEW]
    path = tmp_path / "rehearsal.json"
    path.write_text(json.dumps(m))
    return str(path)


@pytest.mark.parametrize("cell_name", TINY_CELLS)
def test_a_traced_rehearsal_with_the_host_stage_ahead_reads_every_program_span_metric(
        cell_name, rehearsal_with_the_entries, monkeypatch, capsys):
    from daft_tpu.ai.flax_provider import _FlaxDescriptor
    from daft_tpu.profiling import recent_device_spans

    monkeypatch.setattr(_FlaxDescriptor, "runs_beside_host", lambda self: True)
    # Beside five other test workers the host stalls between a wrapper and its span for longer than
    # the 100 us the chip's runs are held to; the control flow is what is tested here.
    monkeypatch.setattr(program_spans, "MAX_BRACKET_NS", 50_000_000)
    monkeypatch.setattr(program_spans, "WIDEN_NS", 50_000_000)
    run = manifest.load_module(os.path.join(BENCH, "run.py"))
    cell = manifest.resolve(cell_name, rehearsal_with_the_entries)
    before = {s.span_id for s in recent_device_spans()}
    rec = run.run_cell(cell, seed=2 ** 31 + 29, seconds=0.5, trace_on=True)
    assert rec["correct"] is True and rec["failed"] == 0, rec["compared"]
    assert "no program span is read" not in capsys.readouterr().err  # the clocks matched
    got = {k: v["value"] for k, v in rec["metrics"].items()}
    jpeg = cell_name.endswith("jpeg_parquet")
    want = {"engine.outside_provider_share", "engine.pull_s_per_krow", "preprocess.s_per_krow",
            "provider.padded_row_share", "setup.init_s", "setup.place_s", "setup.first_forward_s"} | set(NEW)
    if jpeg:
        want |= {"preprocess.decode_s_per_krow", "preprocess.resize_s_per_krow", "preprocess.slowest_row_ms"}
    assert want <= set(got), sorted(want - set(got))
    assert 0 <= got["engine.host_stage_ready_share"] <= 100 and got["engine.host_stage_wait_s_per_krow"] >= 0
    assert got["provider.padded_row_share"] == 50.0  # the same 16 rows in buckets of 32
    # the run went through the host stage: its spans are in the ring, the forwards took staged batches
    mine = [s for s in recent_device_spans() if s.span_id not in before]
    forwards = [s for s in mine if s.name == "provider.forward"]
    assert forwards and all(s.count.get("staged") == 1 for s in forwards)
    assert len([s for s in mine if s.name == "udf.host_stage"]) >= len(forwards)
    # and when it ended nothing of the operator's was left running
    import threading

    assert not [t.name for t in threading.enumerate() if t.name.startswith(("daft-udf-host", "daft-udf-transfer"))]
