"""One test of this directory cannot hold once ``BENCHMARK.json`` gains a
per-layer entry, and its file is the benchmark's, which a PR that may only
append does not edit: ``test_host_stage_metrics.py`` pins ``per_layer`` at 51
entries with PR 30's two standing last, and new entries go at the end (PR 33
appends 22). It is marked here, by its whole id and strictly: the day a
``benchmark`` PR unpins it, it passes, the strict marker fails, and this file
goes (PERF.md section 7 (r)). What it holds of the two entries themselves is held
by ``test_longcat_cell.py::test_the_host_stage_entries_list_the_embed_cells_alone``.
No other test is to be named here."""

import pytest

PINNED_AT_51 = ("tests/benchmark_harness/test_host_stage_metrics.py"
                "::test_the_entries_list_the_three_embed_cells_and_stand_last")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid == PINNED_AT_51:
            item.add_marker(pytest.mark.xfail(
                reason="pins BENCHMARK.json's per_layer at 51 entries; entries are appended", strict=True,
                raises=AssertionError))
