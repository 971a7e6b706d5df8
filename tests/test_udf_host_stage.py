"""The UDF operator runs a UDF's host stage ahead of its device stage
(``execution/executor._host_stage_ahead``, ``Udf.host_stage``): order, overlap,
the bound on what is staged ahead, failures, abandonment, cancellation, the
degenerate case, and ``embed_image`` over the tiny CLIP. CPU; what has to happen
is waited for as an event, nothing sleeps and nothing asserts a time. The CPU
backend itself takes the serial loop (``Descriptor.runs_beside_host``), so the
CLIP tests tell the descriptor that it runs beside the host."""

from __future__ import annotations

import gc
import io
import threading
import time
import weakref

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.datatype import DataType
from daft_tpu.errors import DaftCancelledError, DaftExecutionError
from daft_tpu.execution.executor import Executor
from daft_tpu.profiling import device_span, recent_device_spans
from daft_tpu.series import Series
from daft_tpu.udf import Udf

BATCH = 4
WAIT_S = 30.0  # every wait for an event that has to come; a test that needs it all has failed
OPERATOR_THREADS = ("daft-udf-host", "daft-udf-transfer", "daft-feed-UDFHostStage")


class Staged:
    """What the fake transfer hands on: stands for a batch on the device."""

    def __init__(self, values):
        self.values = values


class StagedUdf(Udf):
    """A batch UDF over an int column with a host stage: ``host`` squares the
    rows, ``transfer`` wraps them, ``fn`` adds one. ``hooks`` maps a stage's name
    to a callable run at its start with the morsel's first id; ``log`` holds
    ``(stage, first id, thread)`` in the order the stages began."""

    def __init__(self, hooks=None, cpus=None, host_stage=True):
        self.hooks, self.log, self.lock, self.staged = hooks or {}, [], threading.Lock(), []

        def fn(series, prepared=None):
            first = series.to_pylist()[0]
            self._began("device", first)
            values = [v * v for v in series.to_pylist()] if prepared is None else prepared.values
            return Series.from_pylist([v + 1 for v in values], "out", DataType.int64())

        super().__init__(fn, DataType.int64(), batch=True, name="staged", batch_size=BATCH, cpus=cpus)
        if host_stage:
            self.host_stage, self.transfer = self._host, self._transfer

    def _began(self, stage, first):
        with self.lock:
            self.log.append((stage, first, threading.get_ident()))
        if stage in self.hooks:
            self.hooks[stage](first)

    def _host(self, series):
        values = series.to_pylist()
        self._began("host", values[0])
        return [v * v for v in values]

    def _transfer(self, batch):
        self._began("transfer", int(round(batch[0] ** 0.5)))
        staged = Staged(batch)
        self.staged.append(weakref.ref(staged))
        return staged

    def began(self, stage):
        with self.lock:
            return [first for s, first, _ in self.log if s == stage]


def _query(udf, rows):
    df = daft_tpu.from_pydict({"id": list(range(rows))})
    return df.with_column("out", udf(col("id"))).select("id", "out")


#: ``min_morsel_size`` 1: the projection above the operator hands each morsel on as it comes.
CONFIG = {"result_cache_enabled": False, "min_morsel_size": 1}


def _run(udf, rows):
    with daft_tpu.execution_config_ctx(**CONFIG):
        return _query(udf, rows).collect().to_pydict()


def _operator_threads():
    return [t.name for t in threading.enumerate() if t.name.startswith(OPERATOR_THREADS)]


def _wait_until(cond) -> bool:
    deadline = time.monotonic() + WAIT_S
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


def _since(mark):
    return [s for s in recent_device_spans() if s.span_id > mark]


def _mark():
    with device_span("test.mark") as sp:
        pass
    return sp.span_id


# -- order and results ----------------------------------------------------------------
@pytest.mark.parametrize("cpus", [None, 1])
def test_results_and_order_equal_the_serial_loops(cpus):
    rows = 10 * BATCH + 3  # a ragged last morsel
    ahead, serial = StagedUdf(cpus=cpus), StagedUdf(host_stage=False)
    got, want = _run(ahead, rows), _run(serial, rows)
    assert got == want == {"id": list(range(rows)), "out": [i * i + 1 for i in range(rows)]}
    # one device batch a morsel, each stage once a morsel, the device stage in input order on one thread
    firsts = list(range(0, rows, BATCH))
    assert ahead.began("device") == ahead.began("transfer") == firsts and sorted(ahead.began("host")) == firsts
    threads = {stage: {t for s, _, t in ahead.log if s == stage} for stage in ("host", "transfer", "device")}
    assert len(threads["device"]) == len(threads["transfer"]) == 1
    assert not threads["host"] & (threads["device"] | threads["transfer"])
    assert len(threads["host"]) <= (cpus or Executor.HOST_STAGE_WORKERS)
    assert serial.began("host") == [] and not _operator_threads()


def test_a_udf_without_a_host_stage_runs_on_the_operators_thread_and_records_no_host_stage_span():
    udf = StagedUdf(host_stage=False)
    mark = _mark()
    _run(udf, 3 * BATCH)
    names = {s.name for s in _since(mark)}
    assert {"udf.pull", "udf.call"} <= names and not {"udf.host_stage", "udf.wait"} & names
    operator = {s.thread for s in _since(mark) if s.name == "udf.pull"}
    assert len(operator) == 1 and {t for _, _, t in udf.log} == operator  # pulled and called on one thread
    # a UDF of sixteen device batches a morsel, as before: one call here
    assert udf.began("device") == [0]


def test_spans_and_the_ready_counter():
    released = threading.Event()
    # the first device stage waits until the third morsel's transfer has begun: the second wait finds it ready
    udf = StagedUdf(hooks={"transfer": lambda first: first == 2 * BATCH and released.set(),
                           "device": lambda first: first == 0 and released.wait(WAIT_S)})
    mark = _mark()
    _run(udf, 5 * BATCH)
    spans = _since(mark)
    hosts = [s for s in spans if s.name == "udf.host_stage"]
    waits = [s for s in spans if s.name == "udf.wait"]
    assert [s.count["rows"] for s in sorted(hosts, key=lambda s: s.start_ns)] == [BATCH] * 5
    delivered = [s for s in waits if "rows" in s.count]
    assert len(delivered) == 5 and len(waits) == 6 and "rows" not in waits[-1].count  # the last found the end
    assert delivered[1].count["ready"] == 1 and all(s.count["ready"] in (0, 1) for s in waits)
    assert all(s.parent == 0 for s in hosts + waits)
    operator = {t for s, _, t in udf.log if s == "device"}
    assert {s.thread for s in waits} == operator and not operator & {s.thread for s in hosts}


# -- overlap and the bound ------------------------------------------------------------
def test_the_host_stage_of_the_next_morsel_begins_before_this_ones_device_stage_ends():
    began, overlapped = threading.Event(), []
    udf = StagedUdf(hooks={"host": lambda first: first == BATCH and began.set(),
                           "device": lambda first: first == 0 and overlapped.append(began.wait(WAIT_S))})
    _run(udf, 4 * BATCH)
    assert overlapped == [True]  # a serial loop would have waited the whole WAIT_S and read False


def test_never_more_than_the_bound_staged_ahead():
    bound = Executor.STAGED_AHEAD
    finished, reached, beyond = [], threading.Event(), threading.Event()
    violations = []

    def on_transfer(first):
        k = first // BATCH + 1  # the k-th transfer may begin only once device stage k - bound - 1 has ended
        if k - bound - 1 > len(finished):
            violations.append((k, len(finished)))
        if k == bound + 1:
            reached.set()
        if k == bound + 2:
            beyond.set()

    def on_device(first):
        if first == 0:  # holds the first morsel on the "chip" until the pipeline is as far ahead as it gets
            assert reached.wait(WAIT_S)
            violations.extend([("beyond", 0)] * beyond.wait(0.3))  # and no further: the one wait for nothing

    udf = StagedUdf(hooks={"transfer": on_transfer, "device": on_device})
    real_fn = udf.fn

    def fn(series, prepared=None):
        out = real_fn(series, prepared=prepared)
        finished.append(series.to_pylist()[0])
        return out

    udf.fn = fn
    _run(udf, 12 * BATCH)
    assert not violations and len(finished) == 12 and beyond.is_set()


# -- failure, abandonment, cancellation -------------------------------------------------
def test_a_failure_in_a_host_stage_surfaces_in_its_morsels_place_with_its_cause():
    boom = ValueError("row 12 is no image")

    def on_host(first):
        if first == 3 * BATCH:
            raise boom

    udf = StagedUdf(hooks={"host": on_host})
    got = []
    with daft_tpu.execution_config_ctx(**CONFIG):
        with pytest.raises(DaftExecutionError, match="host stage") as err:
            for part in _query(udf, 8 * BATCH).iter_partitions():
                got += part.to_pydict()["id"]
    assert got == list(range(3 * BATCH))  # the morsels before it arrived, none after
    assert err.value.__cause__ is boom  # the error itself, as a failing call is reported
    assert udf.began("device") == [0, BATCH, 2 * BATCH] and not _operator_threads()


def test_a_failure_in_the_transfer_surfaces_the_same_way():
    def on_transfer(first):
        if first == BATCH:
            raise MemoryError("no room on the device")

    udf = StagedUdf(hooks={"transfer": on_transfer})
    with daft_tpu.execution_config_ctx(**CONFIG):
        with pytest.raises(DaftExecutionError) as err:
            _query(udf, 6 * BATCH).collect()
    assert isinstance(err.value.__cause__, MemoryError) and udf.began("device") == [0]
    assert _wait_until(lambda: not _operator_threads())


def test_an_abandoned_consumer_leaves_no_thread_and_no_staged_batch():
    ahead_of_it = threading.Event()
    udf = StagedUdf(hooks={"transfer": lambda first: first == 2 * BATCH and ahead_of_it.set()})
    with daft_tpu.execution_config_ctx(**CONFIG):
        it = _query(udf, 200 * BATCH).iter_partitions()
        first = next(it)
        assert ahead_of_it.wait(WAIT_S) and _operator_threads()  # batches lie staged, workers run
        it.close()
    assert first.to_pydict()["id"] == list(range(BATCH))
    # The operator's close joins the pool and the transfer's thread; it runs with the query's own close, or,
    # where the stage above was pulling at that moment, as soon as that stage's feeder lets the operator go.
    assert _wait_until(lambda: not _operator_threads())
    # the stage above had pulled some morsels ahead of the consumer; the source was not run to its end
    assert len(udf.began("device")) < 50 and len(udf.began("host")) < 100
    gc.collect()
    assert udf.staged and not [ref for ref in udf.staged if ref() is not None]


def test_limit_releases_it_too():
    udf = StagedUdf()
    with daft_tpu.execution_config_ctx(**CONFIG):
        out = _query(udf, 100 * BATCH).limit(BATCH + 1).collect().to_pydict()
    assert out["out"] == [i * i + 1 for i in range(BATCH + 1)]
    assert _wait_until(lambda: not _operator_threads()) and len(udf.began("device")) < 50


def test_the_cancel_token_stops_it():
    from daft_tpu.cancellation import current_token

    def on_device(first):
        if first == BATCH:
            current_token().cancel("enough")

    udf = StagedUdf(hooks={"device": on_device})
    with daft_tpu.execution_config_ctx(**CONFIG):
        with pytest.raises(DaftCancelledError, match="enough"):
            _query(udf, 100 * BATCH).collect()
    assert udf.began("device") == [0, BATCH]  # observed at the next morsel, with morsels prepared ahead
    assert _wait_until(lambda: not _operator_threads())


# -- embed_image over the tiny CLIP ------------------------------------------------------
ROWS, MORSELS = 18, 5  # four morsels of BATCH and a ragged one


def _jpegs(n: int, seed: int = 0):
    from PIL import Image

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        buf = io.BytesIO()
        Image.fromarray(rng.integers(0, 255, (40, 48, 3), dtype=np.uint8)).save(buf, format="JPEG")
        out.append(buf.getvalue())
    return out


@pytest.fixture
def beside_host(monkeypatch):
    """The Flax descriptor says that its instances run beside the host, as on a TPU."""
    from daft_tpu.ai.flax_provider import _FlaxDescriptor

    monkeypatch.setattr(_FlaxDescriptor, "runs_beside_host", lambda self: True)


def _embed(profile=False, seed=1):
    from daft_tpu.functions.ai import embed_image

    rows = _jpegs(ROWS)
    rows[5] = rows[17] = None  # nulls embed as the zero image, as in the serial loop
    df = daft_tpu.from_pydict({"id": list(range(ROWS)), "jpg": rows})
    expr = embed_image(col("jpg"), provider="flax_random", model="tiny", batch_size=BATCH, seed=seed)
    mark = _mark()
    with daft_tpu.execution_config_ctx(default_morsel_size=BATCH, result_cache_enabled=False):
        out = df.with_column("emb", expr).select("id", "emb").collect(profile=profile)
    return _since(mark), out


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_on_the_cpu_backend_embed_image_keeps_the_serial_loop():
    spans, _ = _embed()
    assert len(_named(spans, "udf.call")) == MORSELS and not _named(spans, "udf.host_stage")
    by_id = {s.span_id: s for s in spans}
    assert all(by_id[s.parent].name == "provider.forward" for s in _named(spans, "provider.stage"))
    assert not any(f.count.get("staged") for f in _named(spans, "provider.forward"))


def test_embed_image_ahead_equals_the_serial_loop_and_its_spans_say_who_ran_what(monkeypatch):
    from daft_tpu.ai.flax_provider import _FlaxDescriptor

    _, serial = _embed()
    monkeypatch.setattr(_FlaxDescriptor, "runs_beside_host", lambda self: True)
    spans, ahead = _embed()
    a, b = ahead.to_pydict(), serial.to_pydict()
    assert a["id"] == b["id"] == list(range(ROWS))
    np.testing.assert_allclose(np.asarray(a["emb"]), np.asarray(b["emb"]), atol=1e-6)
    assert np.allclose(np.asarray(a["emb"])[5], np.asarray(a["emb"])[17])  # both the zero image's embedding
    by_id = {s.span_id: s for s in spans}
    calls, hosts = _named(spans, "udf.call"), _named(spans, "udf.host_stage")
    assert len(calls) == len(hosts) == len(_named(spans, "image.preprocess")) == MORSELS
    assert all(s.parent == 0 for s in calls + hosts + _named(spans, "udf.wait") + _named(spans, "udf.pull"))
    assert all(by_id[s.parent].name == "udf.host_stage" for s in _named(spans, "image.preprocess"))
    assert all(by_id[s.parent].name == "udf.call" for s in _named(spans, "provider.forward"))
    # the transfer runs on a thread of its own, ahead of the forward that reads it
    stages, pads = _named(spans, "provider.stage"), _named(spans, "provider.pad")
    assert len(stages) == len(pads) == MORSELS and all(s.parent == 0 for s in stages + pads)
    assert sorted(p.count["rows"] for p in pads) == [2, 4, 4, 4, 4]
    for name in ("provider.dispatch", "provider.fetch"):
        assert len(_named(spans, name)) == MORSELS
        assert all(by_id[s.parent].name == "provider.forward" for s in _named(spans, name))
    forwards = _named(spans, "provider.forward")
    assert {(f.count["chunks"], f.count.get("staged")) for f in forwards} == {(1, 1)}
    operator = {s.thread for s in calls}
    transfer = {s.thread for s in stages}
    assert len(operator) == len(transfer) == 1 and not operator & transfer
    assert not {s.thread for s in hosts} & (operator | transfer)
    # in order: stage k began before stage k+1, and before its own dispatch
    starts = [s.start_ns for s in stages]
    assert starts == sorted(starts)
    assert all(s.end_ns <= d.start_ns for s, d in zip(stages, _named(spans, "provider.dispatch")))
    for s in spans:  # a child lies within its parent, on its parent's thread
        if s.parent:
            p = by_id[s.parent]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns and p.thread == s.thread


def test_the_next_morsels_preprocess_starts_before_this_ones_fetch_ends(beside_host, monkeypatch):
    from daft_tpu.ai import flax_provider
    from daft_tpu.functions import ai

    second_began, real_images, real_embed = threading.Event(), ai._images_to_numpy, \
        flax_provider.FlaxCLIPImageEmbedder.embed_image
    calls = []

    def images(series, size):
        calls.append(len(series))
        if len(calls) == 2:
            second_began.set()
        return real_images(series, size)

    def embed(self, batch):  # the first forward is not dispatched until the second morsel is being decoded
        assert second_began.wait(WAIT_S)
        return real_embed(self, batch)

    monkeypatch.setattr(ai, "_images_to_numpy", images)
    monkeypatch.setattr(flax_provider.FlaxCLIPImageEmbedder, "embed_image", embed)
    spans, _ = _embed(seed=2)
    by_start = lambda name: sorted(_named(spans, name), key=lambda s: s.start_ns)  # noqa: E731
    assert by_start("image.preprocess")[1].start_ns < by_start("provider.fetch")[0].end_ns
    assert by_start("udf.host_stage")[1].start_ns < by_start("provider.fetch")[0].end_ns


def test_under_collect_profile_every_threads_spans_hang_below_the_udf_operator(beside_host):
    spans, out = _embed(profile=True, seed=3)
    events = [e for e in out.query_profile.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (op,) = by_name["daft.op.UDFProject"]
    for name in ("udf.pull", "udf.host_stage", "udf.wait", "udf.call", "image.preprocess", "provider.forward",
                 "provider.pad", "provider.stage", "provider.dispatch", "provider.fetch"):
        assert len(by_name[name]) == len(_named(spans, name)) > 0, name
        for e in by_name[name]:  # on the operator's lane, inside its span, whichever thread ran it
            assert (e["pid"], e["tid"]) == (op["pid"], op["tid"]), name
            assert op["ts"] - 1 <= e["ts"] and e["ts"] + e["dur"] <= op["ts"] + op["dur"] + 1, name
    wires = {s.span_id: s for s in out.query_profile.spans()}
    op_id = next(s.span_id for s in wires.values() if s.name == "daft.op.UDFProject")
    for name in ("udf.pull", "udf.host_stage", "udf.wait", "udf.call", "provider.stage"):
        mine = [s for s in wires.values() if s.name == name]
        assert mine and all(s.parent_id == op_id and s.attributes["operator"] == "UDFProject" for s in mine), name


def test_classify_image_runs_ahead_too(beside_host):
    from daft_tpu.functions.ai import classify_image

    df = daft_tpu.from_pydict({"jpg": _jpegs(6)})
    expr = classify_image(col("jpg"), ["cat", "dog"], provider="flax_random", model="tiny", batch_size=BATCH)
    mark = _mark()
    with daft_tpu.execution_config_ctx(result_cache_enabled=False):
        out = df.with_column("label", expr).select("label").collect().to_pydict()
    assert set(out["label"]) <= {"cat", "dog"} and len(out["label"]) == 6
    spans = _since(mark)
    assert len(_named(spans, "udf.host_stage")) == 2
    image_forwards = [f for f in _named(spans, "provider.forward") if f.count.get("staged")]
    assert sorted(f.count["rows"] for f in image_forwards) == [2, 4]  # the labels' text forward staged nothing ahead
