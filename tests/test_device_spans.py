"""The recorder of the device path (``profiling.device_span``): the ring, the
span tree of an ``embed_image`` query from the UDF operator down to dispatch and
fetch, the counters, the spans under ``collect(profile=True)``, and the two
scopes the model adds to the ones Flax writes. CPU, tiny CLIP; nothing here
asserts a time."""

from __future__ import annotations

import contextlib
import io
import re
import sys
import threading

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col, profiling
from daft_tpu.functions.ai import embed_image
from daft_tpu.profiling import device_span, recent_device_spans

ROWS, MORSEL, BATCH = 20, 10, 4
CHUNK_SPANS = ("provider.pad", "provider.stage", "provider.dispatch", "provider.fetch")


def _jpegs(n: int, seed: int = 0):
    from PIL import Image

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        buf = io.BytesIO()
        Image.fromarray(rng.integers(0, 255, (40, 48, 3), dtype=np.uint8)).save(buf, format="JPEG")
        out.append(buf.getvalue())
    return out


def _since(mark: int):
    """Spans recorded since ``mark`` = the id of the newest span before."""
    return [s for s in recent_device_spans() if s.span_id > mark]


def _mark() -> int:
    with device_span("test.mark") as sp:
        pass
    return sp.span_id


def _run_query(form: str = "host", profile: bool = False, seed: int = 1):
    """One ``embed_image`` query over ROWS JPEGs. ``form`` is how a batch reaches
    ``_chunked_forward``: ``host``, a morsel of MORSEL rows as a host array that
    the call chunks, pads and stages itself (the CPU backend's loop), or
    ``staged``, one device batch a morsel that the operator's host stage has put
    on the device already (the loop beside an accelerator: the descriptor is
    told that it runs beside the host, as ``tests/test_udf_host_stage.py`` does)."""
    from daft_tpu.ai.flax_provider import _FlaxDescriptor

    df = daft_tpu.from_pydict({"id": list(range(ROWS)), "jpg": _jpegs(ROWS)})
    expr = embed_image(col("jpg"), provider="flax_random", model="tiny", batch_size=BATCH, seed=seed)
    with pytest.MonkeyPatch.context() as patch:
        if form == "staged":
            patch.setattr(_FlaxDescriptor, "runs_beside_host", lambda self: True)
        mark = _mark()
        with daft_tpu.execution_config_ctx(default_morsel_size=MORSEL, result_cache_enabled=False):
            out = df.with_column("emb", expr).select("id", "emb").collect(profile=profile)
    assert sum(len(p) for p in out.iter_partitions()) == ROWS  # the collected result, no second query
    return _since(mark), out


@pytest.fixture(scope="module", params=["host", "staged"])
def query_spans(request):
    spans, _ = _run_query(request.param)
    return request.param, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _morsels(form: str) -> int:
    return ROWS // (MORSEL if form == "host" else BATCH)  # staged: one device batch a morsel


# -- the span tree of one query ----------------------------------------------------
def test_span_tree_names_and_parentage(query_spans):
    form, spans = query_spans
    by_id = {s.span_id: s for s in spans}
    calls = _named(spans, "udf.call")
    assert len(calls) == _morsels(form)  # one udf.call per morsel
    assert all(s.parent == 0 for s in calls + _named(spans, "udf.pull"))
    # whoever prepares a morsel is the root of its preprocessing, pad and stage
    preparer = "udf.call" if form == "host" else "udf.host_stage"
    for name, parent in (("image.preprocess", preparer), ("provider.forward", "udf.call")):
        mine = _named(spans, name)
        assert len(mine) == len(calls)
        assert all(by_id[s.parent].name == parent for s in mine)
    for name in ("provider.dispatch", "provider.fetch"):
        assert all(by_id[s.parent].name == "provider.forward" for s in _named(spans, name))
    for name in ("provider.pad", "provider.stage"):
        if form == "host":
            assert all(by_id[s.parent].name == "provider.forward" for s in _named(spans, name))
        else:  # the operator's transfer thread ran them, ahead of the call
            assert all(s.parent == 0 for s in _named(spans, name))
    # a child lies within its parent, on one thread, and the ring is in closing order
    for s in spans:
        if s.parent:
            p = by_id[s.parent]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns and p.thread == s.thread
    for thread in {s.thread for s in spans}:  # one thread in the host form
        ends = [s.end_ns for s in spans if s.thread == thread]
        assert ends == sorted(ends)
    assert len({s.query_id for s in spans}) == 1 and spans[0].query_id  # one query, one id


def test_one_pad_stage_dispatch_fetch_per_chunk_whoever_staged_it(query_spans):
    form, spans = query_spans
    forwards = _named(spans, "provider.forward")
    chunks = sum(f.count["chunks"] for f in forwards)
    assert chunks == _morsels(form) * (-(-MORSEL // BATCH) if form == "host" else 1)
    for name in CHUNK_SPANS:
        assert len(_named(spans, name)) == chunks
    assert not any("mode" in f.count for f in forwards)
    first = forwards[0]
    order = [s.name for s in spans if s.parent == first.span_id]  # in closing order
    if form == "host":  # chunk 1 is staged while chunk 0 computes, then chunk 0 is fetched
        assert order[:6] == ["provider.pad", "provider.stage", "provider.dispatch",
                             "provider.pad", "provider.stage", "provider.fetch"]
        assert not any("staged" in f.count for f in forwards)
    else:  # the chunk was on the device before the call: nothing is padded or staged below it
        assert order == ["provider.dispatch", "provider.fetch"]
        assert all(f.count["staged"] == 1 for f in forwards)
        staged_by = {s.thread for s in _named(spans, "provider.pad") + _named(spans, "provider.stage")}
        assert len(staged_by) == 1 and staged_by.isdisjoint(f.thread for f in forwards)


def test_pull_spans_count_the_rows_handed_on(query_spans):
    _, spans = query_spans
    pulls = _named(spans, "udf.pull")
    assert sum(p.count.get("rows", 0) for p in pulls) == ROWS
    assert "rows" not in pulls[-1].count  # the pull that found the child exhausted


# -- counters -----------------------------------------------------------------------
def test_rows_sum_to_the_rows_delivered_and_padding_is_counted(query_spans):
    import jax

    form, spans = query_spans
    for name in ("udf.call", "image.preprocess", "provider.forward", "provider.pad"):
        assert sum(s.count["rows"] for s in _named(spans, name)) == ROWS, name
    pads = _named(spans, "provider.pad")
    # host: 10 rows in chunks of 4: 4, 4 and a ragged 2; staged: a morsel is one chunk of 4;
    # each padded to the bucket (8, or the mesh's multiple)
    assert sorted(p.count["rows"] for p in pads) == ([2, 2, 4, 4, 4, 4] if form == "host" else [4] * 5)
    assert all(p.count["padded_rows"] >= 8 and p.count["padded_rows"] % 8 == 0 for p in pads)
    for f in _named(spans, "provider.forward"):
        assert f.count["n_devices"] in (1, len(jax.devices()))
    image_bytes = 8 * 32 * 32 * 3
    assert all(s.count["bytes"] == image_bytes * (p.count["padded_rows"] // 8)
               for s, p in zip(_named(spans, "provider.stage"), pads))
    assert all(s.count["bytes"] > 0 for s in _named(spans, "provider.fetch"))


def test_preprocess_splits_decode_resize_and_copy(query_spans):
    _, spans = query_spans
    for s in _named(spans, "image.preprocess"):
        c = s.count
        assert c["path"] == "encoded"
        parts = c["decode_ns"] + c["resize_ns"] + c["copy_ns"]
        assert 0 < parts <= s.end_ns - s.start_ns
        assert min(c["decode_ns"], c["resize_ns"], c["copy_ns"]) > 0
        assert parts / c["rows"] <= c["slowest_row_ns"] <= parts  # at least the mean row


@pytest.mark.parametrize("path", ["tensor", "image"])
def test_preprocess_names_its_path(path):
    from daft_tpu.datatype import DataType
    from daft_tpu.functions.ai import _images_to_numpy

    rng = np.random.default_rng(0)
    if path == "tensor":
        series = daft_tpu.Series.from_numpy(
            rng.integers(0, 255, (3, 32 * 32 * 3), dtype=np.uint8), "img", DataType.image("RGB", 32, 32))
    else:
        decoded = daft_tpu.from_pydict({"jpg": _jpegs(3)}).select(col("jpg").image.decode().alias("img")).collect()
        series = next(decoded.iter_partitions()).combined().get_column("img")
    mark = _mark()
    assert _images_to_numpy(series, 32).shape == (3, 32, 32, 3)
    (sp,) = _named(_since(mark), "image.preprocess")
    assert sp.count["path"] == path and sp.count["rows"] == 3
    assert ("decode_ns" in sp.count) == (path == "image")


def test_set_up_spans_appear_once_per_instance():
    from daft_tpu.ai.flax_provider import FlaxCLIPImageEmbedder

    imgs = np.random.default_rng(2).integers(0, 255, (6, 32, 32, 3), dtype=np.uint8)
    mark = _mark()
    for _ in range(2):  # two instances, three calls each
        emb = FlaxCLIPImageEmbedder("tiny", batch_size=4)
        for _ in range(3):
            emb.embed_image(imgs)
    spans = _since(mark)
    inits, places = _named(spans, "provider.init_params"), _named(spans, "provider.place_params")
    assert len(inits) == len(places) == 2
    assert inits[0].count["param_bytes"] == places[0].count["param_bytes"] > 1_000_000
    assert places[0].count["n_devices"] >= 1
    forwards = _named(spans, "provider.forward")
    assert [f.count.get("first", 0) for f in forwards] == [1, 0, 0, 1, 0, 0]
    assert profiling.newest_device_span("provider.forward") is forwards[-1]


# -- the ring -----------------------------------------------------------------------
def test_ring_is_bounded_and_oldest_first():
    for i in range(profiling.DEVICE_SPAN_RING + 50):
        with device_span("test.fill", i=i):
            pass
    ring = recent_device_spans()
    assert len(ring) == profiling.DEVICE_SPAN_RING
    assert [s.count["i"] for s in ring[-3:]] == [profiling.DEVICE_SPAN_RING + 47 + k for k in range(3)]
    assert ring[0].count["i"] == 50  # the oldest 50 fell out
    ring.clear()  # a copy: the recorder's own ring is untouched
    assert len(recent_device_spans()) == profiling.DEVICE_SPAN_RING


def test_ring_is_safe_under_two_threads():
    n, errors = 4000, []

    def worker(tag):
        try:
            for i in range(n):
                with device_span("test.outer", tag=tag, i=i) as outer:
                    with device_span("test.inner", tag=tag) as inner:
                        pass
                    assert inner.parent == outer.span_id
        except Exception as e:  # noqa: BLE001 -- reported below
            errors.append(e)

    def reader(stop):
        try:
            while not stop.is_set():
                recent_device_spans()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    stop = threading.Event()
    threads = [threading.Thread(target=worker, args=(t,)) for t in ("a", "b")]
    watcher = threading.Thread(target=reader, args=(stop,))
    try:
        mark = _mark()
        watcher.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        watcher.join(timeout=10)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads + [watcher])
    assert len(recent_device_spans()) == profiling.DEVICE_SPAN_RING  # 16,000 spans went through
    spans = [s for s in recent_device_spans() if s.span_id > mark and s.name.startswith("test.")]
    by_id = {s.span_id: s for s in spans}
    inners = [s for s in spans if s.name == "test.inner" and s.parent in by_id]
    assert inners and all(by_id[s.parent].count["tag"] == s.count["tag"] and
                          by_id[s.parent].thread == s.thread for s in inners)
    assert len({s.span_id for s in spans}) == len(spans)


def test_a_span_that_raises_is_recorded_with_error():
    mark = _mark()
    with pytest.raises(ZeroDivisionError):
        with device_span("test.outer"):
            with device_span("test.raises", rows=3):
                1 / 0
    inner, outer = _since(mark)
    assert (inner.name, inner.error, inner.count) == ("test.raises", True, {"rows": 3})
    assert outer.error and inner.parent == outer.span_id
    with device_span("test.after") as after:  # the thread's stack was unwound
        pass
    assert after.parent == 0 and not after.error
    assert inner.as_dict()["error"] is True and "_open" not in inner.as_dict()


def test_span_clock_offset_places_the_ring_on_the_wall_clock():
    import time

    with device_span("test.clock") as sp:
        wall = time.time_ns()
    assert sp.start_ns <= wall - profiling.span_clock_offset_ns() + 5_000_000
    assert abs(profiling.span_clock_offset_ns()) < 60e9  # wall anchor at import, drift since


# -- under a profiled query ---------------------------------------------------------
def test_under_collect_profile_the_spans_hang_below_the_udf_operator():
    spans, out = _run_query(profile=True, seed=3)
    trace = out.query_profile.to_chrome_trace()
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (op,) = by_name["daft.op.UDFProject"]
    for name in ("udf.pull", "udf.call", "image.preprocess", "provider.forward") + CHUNK_SPANS:
        assert len(by_name[name]) == len(_named(spans, name)), name
        for e in by_name[name]:  # on the operator's lane, inside its span
            assert (e["pid"], e["tid"]) == (op["pid"], op["tid"])
            assert op["ts"] - 1 <= e["ts"] and e["ts"] + e["dur"] <= op["ts"] + op["dur"] + 1
    # parentage as span ids: a call is the operator's child, a chunk's spans the forward's
    wires = {s.span_id: s for s in out.query_profile.spans()}
    op_id = next(s.span_id for s in wires.values() if s.name == "daft.op.UDFProject")
    calls = [s for s in wires.values() if s.name == "udf.call"]
    assert calls and all(s.parent_id == op_id for s in calls)
    stage = next(s for s in wires.values() if s.name == "provider.stage")
    assert wires[stage.parent_id].name == "provider.forward"
    assert wires[wires[stage.parent_id].parent_id].name == "udf.call"
    assert stage.attributes["bytes"] > 0 and stage.attributes["operator"] == "UDFProject"
    # the operator table still counts operators only
    assert {r["operator"] for r in out.query_profile.operator_table()} >= {"UDFProject"}
    assert not any(r["operator"].startswith("provider") for r in out.query_profile.operator_table())


def test_unprofiled_query_emits_nothing_but_fills_the_ring():
    spans, out = _run_query(profile=False, seed=4)
    assert out.query_profile is None and _named(spans, "provider.dispatch")


# -- the scopes the model adds -------------------------------------------------------
def _compiled_forward_text(monkeypatch, without=()):
    import jax
    import jax.numpy as jnp

    from daft_tpu.models.clip import CLIPConfig, CLIPModel

    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext() if name in without else real(name))
    cfg = CLIPConfig.tiny()
    model = CLIPModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((2, 32, 32, 3), jnp.uint8), jnp.zeros((2, 16), jnp.int32))

    def fwd(p, pixels):
        return model.apply(p, pixels, method=model.encode_image)

    pixels = jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.uint8)
    return jax.jit(fwd).lower(params, pixels).compile().as_text()


def test_named_scopes_change_op_name_only(monkeypatch):
    with_scopes = _compiled_forward_text(monkeypatch)
    without = _compiled_forward_text(monkeypatch, without=("attn_core", "pixel_norm"))
    assert "/attn/attn_core/" in with_scopes and "/vision/pixel_norm/" in with_scopes
    assert "attn_core" not in without and "pixel_norm" not in without

    def instructions(text):
        rows = [re.sub(r", metadata=\{[^}]*\}", "", line) for line in text.splitlines() if " = " in line]
        assert len(rows) > 50
        return rows

    assert instructions(with_scopes) == instructions(without)
    # every module of the block is named by Flax without our help
    for scope in ("block_1/ln1/", "block_1/attn/qkv/", "block_1/attn/out/", "block_1/mlp/fc1/", "ln_post/"):
        assert f"/vision/{scope}" in without


# -- the decode loop's spans (PR 37) ------------------------------------------------
SERVING = ("serve.decode_step", "serve.prefill", "serve.copy_state")


@pytest.fixture(scope="module")
def two_waves():
    """``FlaxPrompter.prompt`` over six prompts (two of them alike) on two slots, twice: the spans of each call."""
    from daft_tpu.ai.flax_provider import FlaxPrompter

    inst = FlaxPrompter("tiny", max_new_tokens=3, num_slots=2, ignore_eos=True)
    docs = ["a b", "e f g h i j", "k l m", "a b", "m n o p q", "r s t u v w x"]  # the two alike are admitted together
    calls = []
    for _ in range(2):
        mark = _mark()
        inst.prompt(docs)
        calls.append(_since(mark))
    return calls


def _under(spans, parent):
    return sorted((s for s in spans if s.parent == parent.span_id), key=lambda s: s.start_ns)


def test_from_the_first_decode_step_to_the_last_the_loop_lies_in_one_serving_span(two_waves):
    for spans in two_waves:
        (run,) = _named(spans, "prompt.run")
        top = _under(spans, run)
        steps = _named(top, "serve.decode_step")
        assert len(steps) == run.count["decode_steps"] >= 6 and len(_named(top, "serve.prefill")) >= 3
        assert _named(top, "serve.copy_state")  # the two prompts alike shared a prefill
        # nothing else hangs below ``prompt.run``, no two of them overlap, and all are on its thread
        assert {s.name for s in top} == set(SERVING) and {s.thread for s in top} == {run.thread}
        assert all(a.end_ns <= b.start_ns for a, b in zip(top, top[1:]))
        assert run.start_ns <= top[0].start_ns and top[-1].end_ns <= run.end_ns and top[-1].name == "serve.decode_step"


def test_each_decode_step_has_one_dispatch_and_one_fetch_in_that_order(two_waves):
    for spans in two_waves:
        for step in _named(spans, "serve.decode_step"):
            dispatch, fetch = _under(spans, step)
            assert (dispatch.name, fetch.name) == ("serve.dispatch", "serve.fetch")
            assert step.start_ns <= dispatch.start_ns <= dispatch.end_ns <= fetch.start_ns <= fetch.end_ns <= step.end_ns
            assert fetch.count == {"arrays": 2}  # tok and logprob: counted on the first step, a constant after it
            assert step.count["slots"] == 2 and 1 <= step.count["active"] <= 2


def test_first_is_on_the_batchers_first_decode_step_alone(two_waves):
    firsts = [[s.count.get("first", 0) for s in _named(spans, "serve.decode_step")] for spans in two_waves]
    assert firsts[0][0] == 1 and not any(firsts[0][1:]) and not any(firsts[1])
    # that step traced and compiled the decode program, under its dispatch: the compile log's counters say so
    dispatch = _named(two_waves[0], "serve.dispatch")
    assert dispatch[0].count["compiles"] >= 1 and dispatch[0].count["compile_s"] > 0 and dispatch[0].count["trace_s"] > 0
    assert not any("compile_s" in s.count for s in dispatch[1:] + _named(two_waves[1], "serve.dispatch"))


def test_the_steps_bookkeeping_and_key_split_run_inside_the_step(monkeypatch):
    """One span is one turn of the loop: the key split before the dispatch and the retiring of finished slots after
    the fetch happen under ``serve.decode_step``, admission's part on the host under no serving span."""
    import jax

    from daft_tpu.models.lm import DecoderLMConfig, init_lm_params
    from daft_tpu.models.serving import ContinuousBatcher, Request

    seen = []

    def noting(what, fn):
        def wrapped(*a, **kw):
            seen.append((what, [s.name for s in (profiling.open_device_span(n) for n in SERVING + ("serve.dispatch", "serve.fetch")) if s]))
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(jax.random, "split", noting("split", jax.random.split))
    monkeypatch.setattr(ContinuousBatcher, "_retire", noting("retire", ContinuousBatcher._retire))
    monkeypatch.setattr(ContinuousBatcher, "_admit_host", noting("admit", ContinuousBatcher._admit_host))
    model, params = init_lm_params(DecoderLMConfig.tiny(), seed=0)
    b = ContinuousBatcher(model, params, num_slots=2, eos_id=None)
    b.run([Request(tokens=np.arange(3, 3 + n, dtype=np.int32), max_new_tokens=2) for n in (4, 6, 5)])
    by = {what: [open_ for w, open_ in seen if w == what] for what in ("split", "retire", "admit")}
    assert len(by["split"]) == b.decode_steps == 4 and len(by["retire"]) == 3 and len(by["admit"]) == 3
    assert by["split"] == [["serve.decode_step"]] * 4 and by["retire"] == [["serve.decode_step"]] * 3
    assert by["admit"] == [[]] * 3


# -- the compile log (PR 37) --------------------------------------------------------
def _logged_since(t_ns: int):
    """By time and not by place: the log is the process's, bounded, and other tests of this worker fill it."""
    return [row for row in profiling.recent_compiles() if row[0] >= t_ns]


_NEW_SHAPE_SCRIPT = """
import jax
import jax.numpy as jnp
import pytest

from daft_tpu import profiling
from daft_tpu.profiling import device_span


def _logged_since(t_ns):
    return [row for row in profiling.recent_compiles() if row[0] >= t_ns]


fn = jax.jit(lambda a: (a * 3 + 1).sum())
with device_span("test.outer") as outer:
    with device_span("test.compiles") as sp:
        fn(jnp.ones((37,)))
mine = _logged_since(outer.start_ns)
assert {kind for _, kind, _, _ in mine} == {"trace", "lower", "compile"}, mine
assert {name for _, _, _, name in mine} == {"test.compiles"}, mine  # the innermost open span, not its parent
assert all(sp.start_ns <= t <= sp.end_ns and seconds > 0 for t, _, seconds, _ in mine), mine
for kind, key in (("trace", "trace_s"), ("lower", "lower_s"), ("compile", "compile_s")):
    assert sp.count[key] == pytest.approx(sum(s for _, k, s, _ in mine if k == kind)), (key, sp.count, mine)
assert sp.count["compiles"] == sum(k == "compile" for _, k, _, _ in mine) >= 1, (sp.count, mine)
assert "cache_loads" not in sp.count and not outer.count, (sp.count, outer.count)
# the same shape again compiles nothing and logs nothing; a new shape does, outside any span under no name
with device_span("test.again") as again:
    fn(jnp.ones((37,)))
assert not _logged_since(again.start_ns) and not again.count, (_logged_since(again.start_ns), again.count)
fn(jnp.ones((41,)))
assert {name for _, _, _, name in _logged_since(again.end_ns)} == {""}, _logged_since(again.end_ns)
print("ok")
"""


def test_a_new_shape_is_logged_with_the_open_spans_name_and_counted_on_it():
    """In a process of its own: the log is the process's, read by time, and what other tests of a worker leave
    running (a thread that traces, a stream's feeder still inside a partition) logs into the same window."""
    import os
    import subprocess

    p = subprocess.run([sys.executable, "-c", _NEW_SHAPE_SCRIPT], capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == "ok"


def test_a_load_from_the_compile_cache_is_logged_as_a_load_and_not_as_a_compile():
    """JAX times the backend around its persistent cache too: a hit raises the retrieval's event and then the
    backend's, and the log keeps the first alone. (Processes held to the CPU keep no cache, so the events are raised
    by hand, through ``jax.monitoring`` as JAX raises them.)"""
    import jax.monitoring as monitoring

    with device_span("test.loads") as sp:
        monitoring.record_event_duration_secs("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        monitoring.record_event_duration_secs("/jax/core/compile/backend_compile_duration", 0.26, fun_name="f")
        monitoring.record_event_duration_secs("/jax/core/compile/backend_compile_duration", 1.5, fun_name="g")
        monitoring.record_event_duration_secs("/jax/compilation_cache/compile_time_saved_sec", 9.0)  # not a stage
    assert [(kind, seconds, name) for _, kind, seconds, name in _logged_since(sp.start_ns)] == [
        ("cache_load", 0.25, "test.loads"), ("compile", 1.5, "test.loads")]
    assert sp.count == {"cache_load_s": 0.25, "cache_loads": 1, "compile_s": 1.5, "compiles": 1}
    assert len(profiling.recent_compiles()) <= profiling.COMPILE_LOG


def test_the_listener_registers_once_however_often_profiling_is_imported_or_reloaded():
    """In a process of its own: a reload here would empty the ring under the other tests."""
    import os
    import subprocess

    script = """
import importlib
import jax.monitoring as monitoring
from daft_tpu import profiling
import daft_tpu.profiling
for _ in range(3):
    profiling = importlib.reload(profiling)
with profiling.device_span("once") as sp:
    monitoring.record_event_duration_secs("/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="f")
print(len(profiling.recent_compiles()), sp.count)
"""
    p = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == "1 {'trace_s': 0.5}"
