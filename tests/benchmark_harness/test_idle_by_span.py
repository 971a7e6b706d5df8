"""``lib/idle_by_span.py`` and PR 37's nine entries on the CPU: the leaves'
idle seconds on a trace and a ring made by hand, the closure of a step's four
parts and of the window, a ring on two threads, an older program's ring; the
entries in ``BENCHMARK.json`` and their readers; what is held of each of the
four ``prompt`` cells' entries, by name; and the four tiny ``prompt`` cells
driven through ``run.py``'s own ``run_cell`` from their rehearsal manifests
(``data/rehearsal_*.json``, which list the nine since PR 42): what needs no
device reads a number, what needs one is left out."""

from __future__ import annotations

import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import idle_by_span, manifest, program_spans  # noqa: E402

PROMPT_CELLS = ["granite_4_0_h_small_prompt.docs_lognormal_1k_out64", "longcat_flash_chat_prompt.docs_lognormal_4k_out64",
                "olmo_hybrid_7b_prompt.docs_lognormal_4k_out64", "deepseek_v3_2_exp_prompt.docs_lognormal_8k_out64"]
#: The six that list the ``prompt`` cells (all four since PR 42), then the three every cell reports.
IDLE = ["serve.idle_ms_per_step", "serve.dispatch_host_ms_per_step", "serve.fetch_arrays_per_step",
        "prompt.idle_outside_run_s_per_krow", "serve.idle_unfiled_share", "serve.setup_first_decode_s"]
LOG = ["setup.compile_s", "setup.cache_load_s", "setup.trace_lower_s"]
#: Of the nine, what a run without a device reads: the program's spans, counters and log.
ON_THE_CPU = {"serve.dispatch_host_ms_per_step", "serve.fetch_arrays_per_step", "serve.setup_first_decode_s"} | set(LOG)
#: tiny cell -> (its rehearsal manifest, the arrays a decode step of that decoder fetches: tok, logprob, its counts)
TINY = {"rehearsal_tiny_granite.rehearsal_docs": ("rehearsal_prompt.json", 5),
        "rehearsal_tiny_longcat.rehearsal_docs": ("rehearsal_longcat.json", 7),
        "rehearsal_tiny_olmo.rehearsal_docs": ("rehearsal_olmo.json", 2),
        "rehearsal_tiny_deepseek.rehearsal_docs": ("rehearsal_deepseek.json", 6)}


# -- a ring and a trace made by hand -----------------------------------------------
OFFSET = 1_000_000  # the ring's clock is this far ahead of the trace's


def _span(name, start, end, span_id, parent=0, thread=1, **count):
    return SimpleNamespace(name=name, start_ns=start + OFFSET, end_ns=end + OFFSET, span_id=span_id, parent=parent,
                           thread=thread, count=count, error=False)


def _ring(dispatch=True):
    """One ``prompt.run`` over [100, 900] of a window [0, 1000]: a prefill round, a state copy, two decode steps
    (each: loop top 10, dispatch 20, a gap of 2 before the fetch, fetch 40, bookkeeping 8), 12 that no serving span
    covers; the tokenizer before it; on another thread a decode step of some other batcher."""
    spans = [_span("prompt.tokenize", 40, 100, 1), _span("prompt.run", 100, 900, 2),
             _span("serve.prefill", 105, 400, 3, parent=2, rows=2), _span("serve.copy_state", 400, 410, 4, parent=2)]
    for k, start in enumerate((410, 500)):
        step = 10 + 10 * k
        spans.append(_span("serve.decode_step", start, start + 80, step, parent=2, active=2, slots=2))
        if dispatch:
            spans.append(_span("serve.dispatch", start + 10, start + 30, step + 1, parent=step))
        spans.append(_span("serve.fetch", start + 32, start + 72, step + 2, parent=step, arrays=5))
    spans.append(_span("serve.decode_step", 600, 700, 90, thread=2, active=1, slots=1))
    spans.append(_span("serve.dispatch", 610, 620, 91, parent=90, thread=2))
    spans.append(_span("serve.fetch", 620, 690, 92, parent=90, thread=2, arrays=9))
    return spans


#: The device ran over [0, 60], [110, 395], [405, 425], [470, 505], [560, 1000]: idle 50 + 10 + 45 + 55 = 160.
OPS = [[0, 60, "%fusion.1"], [110, 285, "%fusion.2"], [405, 20, "%fusion.3"], [470, 35, "%fusion.4"], [560, 440, "%fusion.5"]]


def _events(devices=True):
    return {"window": [0, 1000], "spans": {},
            "devices": {"/device:TPU:0": {"ops": OPS, "modules": []}} if devices else {}}


def test_the_leaves_idle_seconds_are_the_planted_ones():
    got = idle_by_span.table(_ring(), OFFSET, _events())
    ns = {k: round(v * 1e9, 6) for k, v in got["idle_s"].items()}
    # step one [410, 490], the device idle over [425, 470]: 17 of the dispatch's part [420, 442] (the call and the gap
    # before the fetch), 28 of the fetch [442, 482]; step two [500, 580], idle over [505, 560]: 5 of the loop top
    # [500, 510], the dispatch's 22, 28 of the fetch [532, 572]; the prefill round and the copy [105, 410]: 5 + 10
    assert ns == {"step.loop_top": 5.0, "step.dispatch": 17.0 + 22.0, "step.fetch": 28.0 + 28.0, "step.bookkeep": 0.0,
                  "prefill": 5.0 + 10.0, "unfiled": 5.0, "outside_run": 40.0}
    assert round(got["idle_total_s"] * 1e9, 6) == 160.0 == sum(ns.values())  # the window's idle time, by construction
    assert {k: round(v * 1e9, 6) for k, v in got["host_s"].items()} == {
        "step.loop_top": 20.0, "step.dispatch": 44.0, "step.fetch": 80.0, "step.bookkeep": 16.0, "prefill": 305.0,
        "unfiled": 5.0 + 10.0 + 320.0, "outside_run": 200.0}
    assert got["steps"] == 2 and got["runs"] == 1 and got["fetch_arrays"] == 10
    assert got["dispatch_host_ms"] == [32e-6, 32e-6]  # a step's start to its fetch's start


def test_the_four_parts_of_a_step_sum_to_the_steps_idle_time(monkeypatch):
    ring, events = _ring(), _events()
    got = idle_by_span.table(ring, OFFSET, events)
    run = SimpleNamespace(_idle_by_span=got, trace_rows=4)
    parts = [1e3 * got["idle_s"][p] / got["steps"] for p in idle_by_span.STEP_PARTS]
    assert sum(parts) == pytest.approx(idle_by_span.step_idle_ms(run)) == pytest.approx(100e-6 / 2)
    assert idle_by_span.outside_run_idle_s_per_krow(run) == pytest.approx(1000 * 40e-9 / 4)
    assert idle_by_span.dispatch_host_ms(run) == 32e-6
    # steps that overlap each other are no partition of the loop: the closure says so instead of a wrong number
    ring.append(_span("serve.decode_step", 440, 520, 50, parent=2))
    ring += [_span("serve.dispatch", 450, 460, 51, parent=50), _span("serve.fetch", 460, 510, 52, parent=50)]
    monkeypatch.setattr(idle_by_span, "CLOSURE_NS", 1.0)
    with pytest.raises(ValueError, match="do not nest"):
        idle_by_span.table(ring, OFFSET, events)


@pytest.mark.parametrize("off_by", [-4, 3])
def test_a_clock_a_little_off_moves_the_parts_of_a_step_and_not_the_steps_sum(off_by):
    """The trace's device clock can stand off its host clock by a fraction of a step's gap (section 6 of PERF.md,
    PR 37): the idle time then lands in other parts of the step, which is why the entries read the step's sum."""
    true, off = (idle_by_span.table(_ring(), o, _events()) for o in (OFFSET, OFFSET + off_by))
    steps = lambda got: sum(got["idle_s"][p] for p in idle_by_span.STEP_PARTS)  # noqa: E731
    assert steps(off) == pytest.approx(steps(true)) == pytest.approx(100e-9)
    assert [off["idle_s"][p] for p in idle_by_span.STEP_PARTS] != [true["idle_s"][p] for p in idle_by_span.STEP_PARTS]
    # what lies outside ``prompt.run`` moves by the error itself at the one edge where the device idles: 40 of 50
    assert abs(off["idle_s"]["outside_run"] - true["idle_s"]["outside_run"]) * 1e9 == pytest.approx(abs(off_by))


def test_unfiled_catches_an_instant_no_span_covers_and_a_step_cut_short():
    ring = _ring()
    got = idle_by_span.table(ring, OFFSET, _events())
    assert got["idle_s"]["unfiled"] == pytest.approx(5e-9)  # [100, 105], before the prefill round opened
    cut = [s for s in ring if s.span_id != 12]  # the first step lost its fetch (an exception in the dispatch)
    got = idle_by_span.table(cut, OFFSET, _events())
    assert got["steps"] == 1 and got["idle_s"]["unfiled"] == pytest.approx((5 + 45) * 1e-9)
    assert sum(got["idle_s"].values()) == pytest.approx(got["idle_total_s"]) == pytest.approx(160e-9)


def test_a_ring_on_two_threads_files_only_the_thread_that_holds_prompt_run():
    got = idle_by_span.table(_ring(), OFFSET, _events())
    # the other thread's step [600, 700] lies where the device ran and under prompt.run's [100, 900]: it is neither
    # a step of the count nor a leaf; its time stays prompt.run's own
    assert got["steps"] == 2 and got["host_s"]["step.fetch"] == pytest.approx(80e-9)
    moved = [SimpleNamespace(**dict(vars(s), thread=3 - s.thread)) for s in _ring()]  # the threads swapped
    assert idle_by_span.table(moved, OFFSET, _events())["host_s"] == got["host_s"]
    assert idle_by_span.table([s for s in _ring() if s.thread == 2], OFFSET, _events()) is None  # no prompt.run at all


def test_without_a_device_the_host_seconds_stand_and_the_idle_ones_are_none():
    got = idle_by_span.table(_ring(), OFFSET, _events(devices=False))
    assert got["idle_s"] is None and got["idle_total_s"] is None and got["host_s"]["prefill"] == pytest.approx(305e-9)
    run = SimpleNamespace(_idle_by_span=got, trace_rows=4)
    assert idle_by_span.step_idle_ms(run) is None and idle_by_span.outside_run_idle_s_per_krow(run) is None
    assert idle_by_span.dispatch_host_ms(run) == 32e-6


def test_an_older_programs_ring_reads_nothing():
    """The parent opens no ``serve.dispatch`` and keeps no compile log: every reader returns None and none raises."""
    assert idle_by_span.table(_ring(dispatch=False), OFFSET, _events()) is None
    run = SimpleNamespace(events=None, span_order=["udf", "prompter", "batcher"], trace_rows=4)
    for name in IDLE + LOG:
        assert manifest.load_module(os.path.join(BENCH, "metrics", name + ".py")).read(run) is None, name


def test_the_log_is_placed_by_the_rings_offset_and_cut_at_the_windows_opening(monkeypatch):
    log = [(OFFSET - 50, "trace", 0.5, "provider.init_params"), (OFFSET - 40, "lower", 0.25, "provider.init_params"),
           (OFFSET - 30, "compile", 4.0, "serve.prefill"), (OFFSET - 20, "cache_load", 2.0, "serve.dispatch"),
           (OFFSET + 0, "trace", 0.125, ""), (OFFSET + 500, "compile", 64.0, "serve.dispatch")]  # the last in the window
    monkeypatch.setattr(idle_by_span, "compile_log", lambda: log)
    run = SimpleNamespace(events=_events(), span_order=["udf", "prompter", "batcher"],
                          _lm_spans_done=True, _program_spans=SimpleNamespace(offset_ns=OFFSET, spans={}))
    read = lambda name: manifest.load_module(os.path.join(BENCH, "metrics", name + ".py")).read(run)  # noqa: E731
    assert (read("setup.compile_s"), read("setup.cache_load_s"), read("setup.trace_lower_s")) == (4.0, 2.0, 0.875)
    # an embed cell's wrappers: the other matcher's result, from the same attribute
    embed = SimpleNamespace(events=_events(), span_order=["udf", "preprocess", "provider", "stage"],
                            _program_spans=SimpleNamespace(offset_ns=OFFSET - 35, spans={}))
    assert idle_by_span.setup_log_s(embed, "compile") == 0.0 and idle_by_span.setup_log_s(embed, "trace", "lower") == 0.75
    # a program without the log, or clocks that were not matched: nothing, and no 0
    unmatched = SimpleNamespace(events=_events(), span_order=["udf", "prompter", "batcher"], _lm_spans_done=True,
                                _program_spans=None)
    assert idle_by_span.setup_log_s(unmatched, "compile") is None
    monkeypatch.setattr(idle_by_span, "compile_log", lambda: None)
    assert read("setup.compile_s") is None


def test_the_tools_table_reads_the_same_from_a_kept_file(tmp_path, capsys):
    tool = manifest.load_module(os.path.join(BENCH, "tools", "idle_by_span.py"))
    data = {"workload": "a.cell", "offset_ns": OFFSET, "events": _events(), "spans": [vars(s) for s in _ring()],
            "compile_log": [[OFFSET - 30, "compile", 4.0, "serve.prefill"], [OFFSET + 500, "compile", 64.0, "serve.dispatch"]]}
    path = tmp_path / "kept.idle_by_span.json"
    path.write_text(json.dumps(data))
    assert tool.main(["--from", str(path)]) == 0
    out = capsys.readouterr().out
    assert "2 decode steps" in out and "serve.fetch" in out and "outside prompt.run" in out
    assert "compile log before the window: 1 entries" in out and "compile log after it opened: 1 entries" in out


# -- the entries --------------------------------------------------------------------
def _manifest():
    return manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_the_nine_entries_resolve_in_the_cells_that_list_them():
    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    m = _manifest()
    harness.check_manifest(m)
    by = {p["name"]: p for p in m["per_layer"]}
    assert set(IDLE + LOG) <= set(by)
    mine = [by[n] for n in IDLE + LOG]
    assert all(p["better"] == "lower" for p in mine)
    assert all(set(PROMPT_CELLS) <= set(by[n]["workloads"]) for n in IDLE) and not any("workloads" in by[n] for n in LOG)
    assert not any("mfu" in p["name"] or "roofline" in p["name"] for p in mine)
    assert {n for n in IDLE + LOG if by[n]["moves"] == "setup_s"} == {"serve.setup_first_decode_s"} | set(LOG)
    assert {by[n]["layer"] for n in LOG + ["serve.setup_first_decode_s"]} == {"process start and model set-up"}
    assert by["prompt.idle_outside_run_s_per_krow"]["layer"] == "prompter and tokenizer (ai/flax_provider.py FlaxPrompter)"
    assert {by[n]["layer"] for n in IDLE[:3] + ["serve.idle_unfiled_share"]} == {"continuous batcher (models/serving.py)"}
    assert {by[n]["source"] for n in LOG + ["serve.fetch_arrays_per_step"]} == {"program_counter"}
    assert {by[n]["source"] for n in ("serve.dispatch_host_ms_per_step", "serve.setup_first_decode_s")} == {"program_span"}
    assert {by[n]["source"] for n in set(IDLE) - ON_THE_CPU} == {"device_trace"}
    for p in mine:
        assert os.path.isfile(os.path.join(BENCH, "metrics", p["name"] + ".py"))
    # a cell that an idle entry lists reports all nine; any other cell (the embed cells) the log's three alone
    for w in m["workloads"]:
        reported = {x["name"] for x in manifest.resolve(w["name"]).per_layer}
        if w["name"] in by[IDLE[0]]["workloads"]:
            assert set(IDLE + LOG) <= reported, w["name"]
        else:
            assert set(LOG) <= reported and not set(IDLE) & reported, w["name"]


@pytest.mark.parametrize("where", ["the_repos_own", "a_copy_elsewhere"])
@pytest.mark.parametrize("cell", PROMPT_CELLS)
def test_a_prompt_cells_entries_are_held_by_name(cell, where, tmp_path):
    """One test over the four ``prompt`` cells (it was three: ``test_the_three_prompt_cells_keep_their_own_entries``
    and this file's two copies of ``test_longcat_cell.py``'s and ``test_olmo_cell.py``'s manifest tests, which
    ``tests/conftest.py`` marked for their pins): what ``test_benchmark_harness.check_prompt_cell`` holds of a
    cell, membership alone, against the repo's manifest and against a copy of it elsewhere, beside which every file
    of the cell is found; and the nine of this file among what the cell reports."""
    harness = manifest.load_module(os.path.join(HERE, "test_benchmark_harness.py"))
    m = _manifest()
    path = None
    if where == "a_copy_elsewhere":
        path = str(tmp_path / "BENCHMARK.json")
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
    harness.check_prompt_cell(m, cell, harness.PROMPT_CELLS[cell], path)
    listed = harness.listed_for(m, cell)
    alone = {n for n, p in listed.items() if p["workloads"] == [cell]}
    beside = {n for n, p in listed.items() if len(p["workloads"]) > 1}
    # the decoder's own measurements list the cell alone, one ``*mfu`` among them; what reads the same spans whatever
    # the decoder lists it beside the other prompt cells, PR 37's six among them
    spec = harness.PROMPT_CELLS[cell]
    assert alone >= spec["own"] | spec["rooflines"] | {spec["mfu"]} and sum("mfu" in n for n in alone) == 1
    assert set(IDLE) <= beside and all(set(PROMPT_CELLS) <= set(listed[n]["workloads"]) for n in IDLE)
    resolved = manifest.resolve(cell, path)
    assert alone | beside | set(LOG) <= {x["name"] for x in resolved.per_layer}
    assert resolved.chips == 1 and resolved.traffic["generator"] == "doc_pool"


# -- the tiny cells, driven ---------------------------------------------------------
@pytest.fixture(scope="module")
def bench_run():
    return manifest.load_module(os.path.join(BENCH, "run.py"))


@pytest.mark.parametrize("tiny", sorted(TINY))
def test_a_tiny_prompt_cell_reads_what_needs_no_device(bench_run, tiny, monkeypatch):
    monkeypatch.setattr(program_spans, "MAX_BRACKET_NS", 50_000_000)  # beside five other test workers: see
    monkeypatch.setattr(program_spans, "WIDEN_NS", 50_000_000)        # test_prompt_cell.py
    file, arrays = TINY[tiny]
    cell = manifest.resolve(tiny, os.path.join(HERE, "data", file))  # the rehearsal manifests list the nine since PR 42
    assert set(IDLE + LOG) <= {x["name"] for x in cell.per_layer}
    loaded = sum(s for _, kind, s, _ in idle_by_span.compile_log() if kind == "cache_load")  # by this process's other tests
    rec = bench_run.run_cell(cell, seed=2 ** 31 + 37, seconds=0.5, trace_on=True)
    assert rec["correct"] is True and rec["failed"] == 0, rec["compared"]
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    assert set(IDLE + LOG) & set(m) == ON_THE_CPU  # the device's are left out, not null and not 0
    assert m["serve.fetch_arrays_per_step"] == arrays
    assert 0 < m["serve.dispatch_host_ms_per_step"] < 10_000 and m["serve.setup_first_decode_s"] > 0
    # the process compiled its programs before the window and traced them; a CPU process keeps no compile cache
    assert m["setup.compile_s"] > 0 and m["setup.trace_lower_s"] > 0 and 0 <= m["setup.cache_load_s"] <= loaded
    assert rec["metrics"]["device.compiles_in_window"]["value"] == 0
