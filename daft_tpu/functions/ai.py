"""AI expression functions: embed_text / embed_image / classify_* / prompt.

Reference: daft/functions/ai/__init__.py (embed_text:72, embed_image:157,
classify_text:250, classify_image:329, prompt:430) — each resolves a provider,
gets a protocol descriptor, and wraps it into a stateful batch UDF whose
replicas the executor schedules onto accelerator slots. Here the slots are
TPU chips and the models are jitted Flax forwards (daft_tpu/ai/flax_provider).
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Any, List, Optional, Sequence, Union

import numpy as np

from daft_tpu.ai.provider import load_provider
from daft_tpu.datatype import DataType, TypeId
from daft_tpu.errors import DaftExecutionError, DaftTypeError
from daft_tpu.expressions.expression import Expression
from daft_tpu.profiling import device_span
from daft_tpu.series import Series
from daft_tpu.udf import Udf


class _ProtocolUdf(Udf):
    """Batch UDF over a lazily-instantiated protocol implementation.

    The instance (model params in HBM) is created once per worker process on
    first batch — the actor-pool replica pattern (reference:
    daft/ai/_expressions.py + @daft.cls wrapping in functions/ai).

    ``host`` and ``transfer`` (both ``(inst, x)``) are the UDF's host stage
    (``Udf.host_stage``): ``call`` then also takes what ``transfer`` returned
    and starts from it.

    ``release()`` gives the instances' HBM back. A stream that its consumer
    abandoned (a ``limit``, a closed ``iter_partitions()``) leaves its last
    morsel running on the next stage's feeder thread (``pipeline.run_stage``
    does not wait for it), and that call holds the instance until it returns:
    ``release`` waits for the calls in flight, then drops the instances.
    """

    def __init__(self, descriptor, call, return_dtype: DataType, name: str,
                 host=None, transfer=None):
        self._descriptor = descriptor
        self._call = call
        self._host = host
        self._transfer = transfer
        self._instances = {}
        self._instance_lock = threading.Lock()
        self._idle = threading.Condition()  # guards the two below
        self._calls = 0          # calls of ``fn`` in flight
        self._released = False
        udf_opts = descriptor.get_udf_options()

        def fn(*series, prepared=None):
            # Device-batch chunking lives inside the protocol impls (they
            # chunk to their device batch and async-dispatch all chunks so
            # transfers overlap compute); here we just hand over the morsel,
            # or what the host stage made of it. The instance is a local of
            # ``_run`` alone: gone before the call counts as ended.
            with self._in_flight():
                return self._run(series, prepared)

        fn.__name__ = name
        super().__init__(
            fn, return_dtype, batch=True, name=name,
            max_concurrency=udf_opts.max_concurrency,
            cpus=udf_opts.cpus, tpus=udf_opts.tpus,
            memory_bytes=udf_opts.memory_bytes,
            batch_size=udf_opts.batch_size, use_process=udf_opts.use_process,
            chips_per_replica=udf_opts.chips_per_replica,
        )

    @property
    def host_stage(self):
        if self._host is None or not self._descriptor.runs_beside_host():
            return None
        return lambda *series: self._host(self._get_instance(), *series)

    def transfer(self, batch):
        return self._transfer(self._get_instance(), batch)

    def _run(self, series, prepared):
        inst = self._get_instance()
        if prepared is None:
            return self._call(inst, *series)
        return self._call(inst, *series, prepared)

    @contextlib.contextmanager
    def _in_flight(self):
        with self._idle:
            self._calls += 1
        try:
            yield
        finally:
            with self._idle:
                self._calls -= 1
                self._idle.notify_all()

    def release(self, wait_s: Optional[float] = None) -> bool:
        """Drop the instances this process holds (parameters and serving state
        in HBM) once the calls in flight have ended, waiting up to ``wait_s``
        for them (None: until they end). From then on the UDF makes no
        instance: a call that comes later (the abandoned stream's feeder may
        start one more) is an error, not a second set of parameters. -> whether
        the instances were dropped."""
        with self._idle:
            self._released = True
            if not self._idle.wait_for(lambda: self._calls == 0, wait_s):
                return False
            self._instances.clear()
        # An instance is not freed by its last reference alone: a batcher's
        # jitted programs are bound methods that refer back to it.
        gc.collect()
        return True

    def _get_instance(self):
        # One model instance PER REPLICA SLOT: with chips_per_replica the
        # executor runs each morsel inside a replica_scope, and the instance
        # created there holds its params on that slot's mesh slice.
        from daft_tpu.parallel.replica import replica_id

        rid = replica_id()
        inst = self._instances.get(rid)
        if inst is None:
            if self._released:
                raise DaftExecutionError(f"the UDF {self.name!r} was released: it makes no further instance")
            with self._instance_lock:
                inst = self._instances.get(rid)
                if inst is None:
                    inst = self._instances[rid] = self._descriptor.instantiate()
        return inst

    def __getstate__(self):
        # Cross-process shipping: drop the lock and the live model instances —
        # each worker process re-instantiates (params must live in ITS HBM).
        state = self.__dict__.copy()
        state["_instances"] = {}
        state["_calls"] = 0
        state["_released"] = False
        state.pop("_instance_lock", None)
        state.pop("_idle", None)
        return state

    def __setstate__(self, state):
        import threading

        self.__dict__.update(state)
        self._instances = {}
        self._instance_lock = threading.Lock()
        self._idle = threading.Condition()


class _RowClock:
    """The tail of a PIL loop's row (resize, copy into the batch) and where the
    loop's time goes, for the ``image.preprocess`` span: per row,
    bytes to an RGB image (``decode_ns``), the resize (``resize_ns``) and the
    copy into the batch (``copy_ns``), summed over rows, and the slowest row
    (``slowest_row_ns``). Three clock reads a row: a row starts where the last
    one ended, so the sums leave out only what precedes the loop."""

    __slots__ = ("decode_ns", "resize_ns", "copy_ns", "slowest_row_ns", "_t", "_filter")

    def __init__(self):
        from PIL import Image as PILImage

        self.decode_ns = self.resize_ns = self.copy_ns = self.slowest_row_ns = 0
        self._filter = PILImage.BILINEAR
        self._t = time.perf_counter_ns()

    def resize_into(self, out: np.ndarray, i: int, img) -> None:
        """The row's decoded RGB image, resized to the batch's size and copied
        into ``out[i]``; the time since the last row ended was its decode."""
        decoded_ns = time.perf_counter_ns()
        img = img.resize(out.shape[1:3][::-1], self._filter)
        resized_ns = time.perf_counter_ns()
        out[i] = np.asarray(img)
        now = time.perf_counter_ns()
        self.decode_ns += decoded_ns - self._t
        self.resize_ns += resized_ns - decoded_ns
        self.copy_ns += now - resized_ns
        self.slowest_row_ns = max(self.slowest_row_ns, now - self._t)
        self._t = now

    def counters(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__[:4]}


def _images_to_numpy(series: Series, size: int) -> np.ndarray:
    """Convert an image-bearing Series to a dense (B, size, size, 3) uint8
    batch. Fixed-shape columns are zero-copy reshapes; variable-shape images
    host-resize (PIL) first — matching the reference's preprocessing
    transform step. The whole call is the span ``image.preprocess``."""
    dt = series.dtype
    with device_span("image.preprocess", rows=len(series), path="tensor") as sp:
        if dt.id == TypeId.FIXED_SHAPE_IMAGE:
            vals, _ = series.to_numpy_masked()
            h, w, c = dt.shape
            if (h, w) != (size, size) or c != 3:
                vals = _host_resize_batch(vals, size)
            return np.ascontiguousarray(vals)
        if dt.id in (TypeId.FIXED_SHAPE_TENSOR, TypeId.EMBEDDING, TypeId.FIXED_SIZE_LIST):
            vals, _ = series.to_numpy_masked()
            if vals.ndim == 2 and vals.shape[1] == size * size * 3:
                return vals.reshape(-1, size, size, 3).astype(np.uint8)
            if vals.ndim == 4:
                return vals.astype(np.uint8)
            raise DaftTypeError(f"Cannot interpret {dt!r} as {size}x{size}x3 images")
        if dt.id == TypeId.IMAGE:
            from PIL import Image as PILImage

            from daft_tpu.datatype import ImageMode

            sp.count["path"] = "image"
            out = np.zeros((len(series), size, size, 3), dtype=np.uint8)
            rows = series.to_arrow().to_pylist()
            clock = _RowClock()
            for i, row in enumerate(rows):
                if row is None:
                    continue
                m = ImageMode(row["mode"])
                arr = np.frombuffer(row["data"], dtype=m.pixel_dtype.to_numpy()).reshape(
                    row["height"], row["width"], row["channel"]
                )
                img = PILImage.fromarray(arr.squeeze(-1) if arr.shape[2] == 1 else arr).convert("RGB")
                clock.resize_into(out, i, img)
            sp.count.update(clock.counters())
            return out
        if dt.is_binary():
            # Encoded images: decode+resize on host.
            from PIL import Image as PILImage
            import io

            sp.count["path"] = "encoded"
            out = np.zeros((len(series), size, size, 3), dtype=np.uint8)
            rows = series.to_pylist()
            clock = _RowClock()
            for i, raw in enumerate(rows):
                if raw is None:
                    continue
                clock.resize_into(out, i, PILImage.open(io.BytesIO(raw)).convert("RGB"))
            sp.count.update(clock.counters())
            return out
    raise DaftTypeError(f"embed_image expects an image column, got {dt!r}")


def _host_resize_batch(vals: np.ndarray, size: int) -> np.ndarray:
    from PIL import Image as PILImage

    out = np.zeros((vals.shape[0], size, size, 3), dtype=np.uint8)
    for i in range(vals.shape[0]):
        arr = vals[i]
        img = PILImage.fromarray(arr.squeeze(-1) if arr.shape[-1] == 1 else arr[..., :3])
        out[i] = np.asarray(img.convert("RGB").resize((size, size), PILImage.BILINEAR))
    return out


def _image_batch(inst, series: Series) -> np.ndarray:
    """Host stage of the image UDFs: the column as the dense uint8 batch the
    model takes (decode, resize, copy)."""
    cfg = getattr(getattr(inst, "image_embedder", inst), "cfg", None)
    return _images_to_numpy(series, cfg.image_size if cfg is not None else 224)


def _stage_images(inst, batch: np.ndarray):
    """Its transfer: chunked, padded and put on the device where the instance
    can take a batch in that form (``stage_images``); else the host batch."""
    stage = getattr(inst, "stage_images", None)
    return batch if stage is None else stage(batch)


def embed_text(text: Expression, *, provider: Union[str, object, None] = None,
               model: Optional[str] = None, **options) -> Expression:
    """Embed a string column (reference: daft/functions/ai/__init__.py:72)."""
    p = load_provider(provider)
    desc = p.get_text_embedder(model, **options)
    dims = desc.get_dimensions() or 384
    dtype = DataType.embedding(DataType.float32(), dims)

    def call(inst, series: Series) -> Series:
        embs = inst.embed_text(series.to_pylist())
        return Series.from_numpy(embs, "embedding", dtype)

    return _ProtocolUdf(desc, call, dtype, "embed_text")(text)


def embed_image(image: Expression, *, provider: Union[str, object, None] = None,
                model: Optional[str] = None, **options) -> Expression:
    """Embed an image column (reference: daft/functions/ai/__init__.py:157).

    Accepts FixedShapeImage (zero-copy to HBM), variable Image, raw encoded
    bytes, or a uint8 tensor column.
    """
    p = load_provider(provider)
    desc = p.get_image_embedder(model, **options)
    dims = desc.get_dimensions() or 768
    dtype = DataType.embedding(DataType.float32(), dims)

    def call(inst, series: Series, batch=None) -> Series:
        embs = inst.embed_image(_image_batch(inst, series) if batch is None else batch)
        return Series.from_numpy(embs, "embedding", dtype)

    return _ProtocolUdf(desc, call, dtype, "embed_image", host=_image_batch, transfer=_stage_images)(image)


def classify_text(text: Expression, labels: Sequence[str], *,
                  provider: Union[str, object, None] = None,
                  model: Optional[str] = None, **options) -> Expression:
    p = load_provider(provider)
    desc = p.get_text_classifier(model, **options)
    labels = list(labels)

    def call(inst, series: Series) -> Series:
        out = inst.classify_text(series.to_pylist(), labels)
        return Series.from_pylist(out, "label", DataType.string())

    return _ProtocolUdf(desc, call, DataType.string(), "classify_text")(text)


def classify_image(image: Expression, labels: Sequence[str], *,
                   provider: Union[str, object, None] = None,
                   model: Optional[str] = None, **options) -> Expression:
    p = load_provider(provider)
    desc = p.get_image_classifier(model, **options)
    labels = list(labels)

    def call(inst, series: Series, batch=None) -> Series:
        out = inst.classify_image(_image_batch(inst, series) if batch is None else batch, labels)
        return Series.from_pylist(out, "label", DataType.string())

    return _ProtocolUdf(desc, call, DataType.string(), "classify_image",
                        host=_image_batch, transfer=_stage_images)(image)


def prompt(text: Expression, *, provider: Union[str, object, None] = None,
           model: Optional[str] = None, **options) -> Expression:
    """Generate text per row (reference: daft/functions/ai/__init__.py:430).

    With ``logprobs=True`` the column is a struct ``{text, token_ids:
    list[int32], logprobs: list[float32]}``: the chosen tokens and each one's
    log-probability, so that a comparison can hold the answer against logits."""
    p = load_provider(provider)
    desc = p.get_prompter(model, **options)
    if not options.get("logprobs"):
        def call(inst, series: Series) -> Series:
            out = inst.prompt(series.to_pylist())
            return Series.from_pylist(out, "response", DataType.string())

        return _ProtocolUdf(desc, call, DataType.string(), "prompt")(text)

    dtype = DataType.struct({"text": DataType.string(), "token_ids": DataType.list(DataType.int32()),
                             "logprobs": DataType.list(DataType.float32())})

    def call_logprobs(inst, series: Series) -> Series:
        import pyarrow as pa

        texts, ids, logprobs = inst.prompt(series.to_pylist())
        offsets = np.concatenate([[0], np.cumsum([len(r) for r in ids])]).astype(np.int32)

        def lists(rows, np_dtype):
            flat = np.concatenate(rows) if rows else np.zeros(0, np_dtype)
            return pa.ListArray.from_arrays(pa.array(offsets), pa.array(flat.astype(np_dtype)))

        arr = pa.StructArray.from_arrays(
            [pa.array(texts, pa.string()), lists(ids, np.int32), lists(logprobs, np.float32)],
            names=["text", "token_ids", "logprobs"])
        return Series.from_arrow(arr, "response", dtype)

    return _ProtocolUdf(desc, call_logprobs, dtype, "prompt")(text)


def llm_generate(text: Expression, *, model: Optional[str] = None,
                 provider: Union[str, object, None] = None, **options) -> Expression:
    """Batched LLM generation (reference: daft/functions/llm.py llm_generate
    → vLLM; here the continuous-batching DecoderLM sink)."""
    return prompt(text, provider=provider, model=model, **options)
