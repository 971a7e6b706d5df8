"""Flax/TPU provider: protocol implementations over daft_tpu.models.

This is the engine's north-star path (reference analogue:
daft/ai/transformers/* — torch CUDA): CLIP image/text towers, MiniLM sentence
encoder and a decoder LM, all served as jitted XLA computations with

* **float32 params resident in HBM, bf16 compute** — initialised once per
  worker process; the models cast activations and matmul operands to bf16,
* **batch-shape bucketing** — inputs pad to power-of-two buckets so jax.jit
  recompiles O(log batch) times, never per morsel (SURVEY.md §7 hard part (f)),
* **uint8 device staging** — images ship to HBM as uint8 NHWC (a quarter of
  the bytes of f32) and are normalised on device,
* **one staging policy** — ``_chunked_forward`` dispatches the forward of one
  device batch, has the next batch on the device while that one computes, and
  only then fetches: the next batch is either staged already by a caller that
  ran ahead (``stage_chunks``: the UDF operator's transfer thread) or padded
  and staged by the call itself at that point,
* **zero-egress weights** — random init by default; ``weights_path`` loads a
  local checkpoint when present.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from daft_tpu.ai.protocols import (
    Descriptor,
    ImageClassifierDescriptor,
    ImageEmbedderDescriptor,
    PrompterDescriptor,
    TextClassifierDescriptor,
    TextEmbedderDescriptor,
    UDFOptions,
)
from daft_tpu.ai.provider import Provider
from daft_tpu.device import setup_compile_cache
from daft_tpu.errors import DaftValueError
from daft_tpu.models import decoders
from daft_tpu.profiling import device_span
from daft_tpu.utils.tokenizer import HashingTokenizer

setup_compile_cache()

_BUCKETS = (8, 32, 128, 256, 512, 1024)


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return ((n + _BUCKETS[-1] - 1) // _BUCKETS[-1]) * _BUCKETS[-1]


def _pad_batch(arr: np.ndarray, to: int) -> np.ndarray:
    if arr.shape[0] == to:
        return arr
    pad = [(0, to - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


def _initialised(init, *args):
    """``init(*args)`` -> (model, params) as the span ``provider.init_params``.
    The init program is dispatched, not waited for: the wait falls to whoever
    reads the parameters first (the instance's first forward)."""
    with device_span("provider.init_params") as sp:
        model, params = init(*args)
        sp.count["param_bytes"] = _tree_bytes(params)
    return model, params


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def load_checkpoint(path: str, params):
    """Delegate to the single loader in models/checkpoint.py (orbax dir,
    .msgpack, or .npz)."""
    from daft_tpu.models.checkpoint import load_params

    return load_params(path, params)


def _load_clip(model_name: str, weights_path: str):
    """CLIP weights from a local HF checkpoint dir (torch -> flax
    conversion, models/convert.py) or a flax-native file/orbax dir."""
    from daft_tpu.models.clip import CLIPConfig, load_params
    from daft_tpu.models.convert import is_hf_checkpoint_dir

    if is_hf_checkpoint_dir(weights_path):
        from daft_tpu.models.convert import load_hf_checkpoint

        kind, model, params = load_hf_checkpoint(weights_path, dtype=jnp.bfloat16)
        if kind != "clip":
            raise DaftValueError(
                f"CLIP embedder expects a clip checkpoint, got {kind!r}")
        return model, params
    return load_params(weights_path, CLIPConfig.from_name(model_name))


#: Default device batch of the image embedder.
DEFAULT_BATCH = 128

#: Jitted forwards that ``_chunked_forward`` has run: an instance's first call
#: traces, loads or compiles its executable and runs it, and is set-up. The
#: value is the attention path its newest trace took (``fused`` | ``xla``;
#: None for a model that notes none).
_FORWARDS_RUN = weakref.WeakKeyDictionary()


class StagedChunks(list):
    """``[(rows, device batch), ...]``: a batch that ``stage_chunks`` has already
    chunked, padded and put on the device, in the order it will run."""


def stage_chunks(arr: np.ndarray, max_batch: int, stage=None, pad_mult: int = 1) -> StagedChunks:
    """The host half of ``_chunked_forward``, for a caller that runs it ahead
    of the forward (the UDF operator's host stage): every chunk of ``arr``
    padded and staged as ``_staged_chunks`` does it, all of them now."""
    return StagedChunks(_staged_chunks(arr, max_batch, stage, pad_mult))


def _staged_chunks(arr: np.ndarray, max_batch: int, stage, pad_mult: int):
    """Yields ``(rows, device batch)`` for each chunk of at most ``max_batch``
    rows of ``arr``, in order: padded to its bucket (span ``provider.pad``) and
    handed to ``stage`` (``jax.device_put`` if None; span ``provider.stage``
    around the call) when it is asked for, not before."""
    stage = stage or jax.device_put
    for start in range(0, arr.shape[0], max_batch):
        chunk = arr[start:start + max_batch]
        b = _bucket(len(chunk))
        if b % pad_mult:  # dp-sharded batches must divide the dp axis
            b = ((b + pad_mult - 1) // pad_mult) * pad_mult
        with device_span("provider.pad", rows=len(chunk), padded_rows=b):
            padded = _pad_batch(chunk, b)
        with device_span("provider.stage", bytes=padded.nbytes):
            on_device = stage(padded)
        yield len(chunk), on_device


def _chunked_forward(fwd, params, arr, max_batch: int, out_dim: int,
                     stage=None, pad_mult: int = 1) -> np.ndarray:
    """Chunk to max_batch and run the forwards as a depth-1 pipeline: dispatch
    the forward of chunk i, have chunk i+1 on the device while it computes,
    then fetch chunk i. Never more than one forward is queued ahead of the
    fetch.

    ``arr`` is a host array, whose chunks the call pads and stages itself
    (``_staged_chunks``), each when the pipeline comes to it, or
    ``StagedChunks``: the chunks are on the device already (``stage_chunks``,
    run while an earlier forward computed), nothing is padded or staged here,
    and the span counts ``staged`` = 1.

    The call is the span ``provider.forward`` (``attn`` says which attention
    path the forward took); each chunk's pad, stage, dispatch and fetch are
    spans of their own below it (profiling.py), pad and stage below whoever
    ran ``stage_chunks`` where that was not this call.
    """
    prestaged = isinstance(arr, StagedChunks)
    n = sum(cn for cn, _ in arr) if prestaged else arr.shape[0]
    if n == 0:
        return np.zeros((0, out_dim), dtype=np.float32)
    with device_span("provider.forward", rows=n) as sp:
        if fwd not in _FORWARDS_RUN:
            _FORWARDS_RUN[fwd] = None
            sp.count["first"] = 1
        if prestaged:
            sp.count["staged"] = 1
            staged = iter(arr)
        else:
            staged = _staged_chunks(arr, max_batch, stage, pad_mult)
        # n_devices: the devices the parameters occupy — what a per-chip rate
        # divides by, whatever else the host can see.
        leaf = jax.tree_util.tree_leaves(params)[0]
        sp.count["n_devices"] = len(leaf.sharding.device_set)

        outs = []
        nxt = next(staged)  # n > 0: there is a first chunk
        while nxt is not None:
            cn, on_device = nxt
            with device_span("provider.dispatch"):
                f = fwd(params, on_device)  # async dispatch
            nxt = next(staged, None)  # chunk i+1 is staged while chunk i computes
            with device_span("provider.fetch") as fetch:
                out = np.asarray(f)  # forces + fetches the chunk
                fetch.count["bytes"] = out.nbytes
            outs.append(out[:cn])
        sp.count["chunks"] = len(outs)
        # The model notes the attention path on this span while a forward
        # traces (layers.MultiHeadAttention); a call that traces nothing
        # repeats what the newest trace chose.
        attn = _FORWARDS_RUN[fwd] = sp.count.get("attn") or _FORWARDS_RUN[fwd]
        if attn:
            sp.count["attn"] = attn
    return np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


class _FlaxModelBase:
    """Holds params on device; one instance per replica slot (with
    ``chips_per_replica`` each instance owns an ICI mesh slice of the
    devices its process holds)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.mesh = None
        self._param_specs = None

    def setup_mesh(self, mesh_axes: Optional[Dict[str, int]] = None):
        """Build this replica's mesh over its device slot.

        ``mesh_axes`` e.g. ``{"dp": 2, "tp": 4}`` (-1 absorbs the rest);
        default is pure data parallel over the replica's chips. Single-chip
        replicas stay mesh-less (plain jit).
        """
        from daft_tpu.parallel.replica import replica_devices

        devs = replica_devices()
        if len(devs) <= 1 and not mesh_axes:
            return None
        from daft_tpu.parallel.mesh import make_mesh

        self.mesh = make_mesh(dict(mesh_axes or {"dp": -1}), devices=devs)
        return self.mesh

    def place_params(self, params):
        """Shard params onto the mesh (tp rules when a "tp" axis exists,
        replicated otherwise); plain device_put without a mesh."""
        n_devices = 1 if self.mesh is None else self.mesh.size
        with device_span("provider.place_params", n_devices=n_devices,
                         param_bytes=_tree_bytes(params)):
            # Not waited for: the transfers end inside the first forward.
            if self.mesh is None:
                return jax.device_put(params)
            from jax.sharding import NamedSharding, PartitionSpec as P

            from daft_tpu.parallel.mesh import DEFAULT_TP_RULES, match_partition_rules

            if "tp" in self.mesh.axis_names:
                specs = match_partition_rules(DEFAULT_TP_RULES, params, self.mesh)
            else:
                specs = jax.tree_util.tree_map(lambda _: P(), params)
            self._param_specs = specs
            return jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                params, specs)

    def stage_batch(self, arr):
        """Put one padded host batch onto the device(s): dp-sharded along
        axis 0 when a mesh with a "dp" axis exists."""
        if self.mesh is None or "dp" not in self.mesh.axis_names:
            return jax.device_put(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P("dp", *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def batch_multiple(self) -> int:
        """Padded batches must divide evenly across the dp axis."""
        if self.mesh is None or "dp" not in self.mesh.axis_names:
            return 1
        return int(self.mesh.shape["dp"])


class FlaxCLIPImageEmbedder(_FlaxModelBase):
    def __init__(self, model_name: str, weights_path: Optional[str] = None,
                 seed: int = 0, batch_size: Optional[int] = None,
                 mesh_axes: Optional[Dict[str, int]] = None):
        super().__init__()
        from daft_tpu.models.clip import CLIPConfig, init_clip_params, load_params

        self.max_batch = int(batch_size or DEFAULT_BATCH)
        # Multi-chip replica: params shard over this replica's mesh slice
        # (tp rules when requested, replicated for pure dp) and batches
        # dp-shard along axis 0; single-chip replicas keep plain jit.
        self.setup_mesh(mesh_axes)
        if weights_path:
            self.model, params = _load_clip(model_name, weights_path)
            self.cfg = self.model.cfg
        else:
            self.cfg = CLIPConfig.from_name(model_name)
            self.model, params = _initialised(init_clip_params, self.cfg, seed)
        self.params = self.place_params(params)
        # GSPMD does not partition a pallas_call: under a mesh the model is
        # told, and its attention takes XLA's path (same parameters).
        model = self.model = self.model.clone(partitioned=self.mesh is not None)

        def fwd(p, pixels):
            emb = model.apply(p, pixels, method=model.encode_image)
            return emb / jnp.linalg.norm(emb, axis=-1, keepdims=True).clip(1e-6)

        self._fwd = jax.jit(fwd)

    @property
    def dimensions(self) -> int:
        return self.cfg.embed_dim

    def _nhwc(self, images: np.ndarray) -> np.ndarray:
        if images.ndim == 2:
            return images.reshape(images.shape[0], self.cfg.image_size, self.cfg.image_size, 3)
        return images

    def stage_images(self, images: np.ndarray) -> StagedChunks:
        """The transfer of ``embed_image``'s input, for a caller that issues it
        while an earlier batch is on the chip: images as ``embed_image`` takes
        them, chunked to ``max_batch``, padded and put on the device(s)."""
        return stage_chunks(self._nhwc(images), self.max_batch, self.stage_batch, self.batch_multiple())

    def embed_image(self, images) -> np.ndarray:
        """images: (B, H, W, 3) uint8 (or flat (B, H*W*3)), or what
        ``stage_images`` made of them. Returns (B, D) f32.

        Chunks to ``max_batch`` and runs the forwards as ``_chunked_forward``
        does.
        """
        if not isinstance(images, StagedChunks):
            images = self._nhwc(images)
        return _chunked_forward(self._fwd, self.params, images, self.max_batch,
                                self.cfg.embed_dim, stage=self.stage_batch,
                                pad_mult=self.batch_multiple())


class FlaxCLIPTextEmbedder(_FlaxModelBase):
    max_batch = 512

    def __init__(self, model_name: str, weights_path: Optional[str] = None, seed: int = 0):
        super().__init__()
        from daft_tpu.models.clip import CLIPConfig, init_clip_params, load_params

        tokenizer = None
        if weights_path:
            self.model, params = _load_clip(model_name, weights_path)
            self.cfg = self.model.cfg
            from daft_tpu.models.convert import is_hf_checkpoint_dir
            from daft_tpu.utils.tokenizer import tokenizer_from_dir

            if is_hf_checkpoint_dir(weights_path):
                tokenizer = tokenizer_from_dir(weights_path,
                                               self.cfg.context_length)
                if tokenizer is None:
                    # A converted CLIP pools at the checkpoint vocab's eos
                    # position; hashing ids essentially never hit it, so a
                    # missing tokenizer silently degenerates every embedding.
                    raise DaftValueError(
                        f"HF CLIP checkpoint {weights_path!r} has no "
                        f"tokenizer files (vocab.json + merges.txt); they "
                        f"are required for text embedding")
        else:
            self.cfg = CLIPConfig.from_name(model_name)
            self.model, params = _initialised(init_clip_params, self.cfg, seed)
        self.params = jax.device_put(params)
        self.tokenizer = tokenizer or HashingTokenizer(
            self.cfg.vocab_size, self.cfg.context_length)
        model = self.model

        @jax.jit
        def fwd(p, tokens):
            emb = model.apply(p, tokens, method=model.encode_text)
            return emb / jnp.linalg.norm(emb, axis=-1, keepdims=True).clip(1e-6)

        self._fwd = fwd

    @property
    def dimensions(self) -> int:
        return self.cfg.embed_dim

    def embed_text(self, texts: Sequence[Optional[str]]) -> np.ndarray:
        tokens, _ = self.tokenizer.encode_batch(texts)
        return _chunked_forward(self._fwd, self.params, tokens, self.max_batch,
                                self.cfg.embed_dim)


class FlaxMiniLMTextEmbedder(_FlaxModelBase):
    max_batch = 512

    def __init__(self, model_name: str, weights_path: Optional[str] = None,
                 seed: int = 0, dtype=None):
        super().__init__()
        from daft_tpu.models.convert import is_hf_checkpoint_dir
        from daft_tpu.models.minilm import MiniLMConfig, init_minilm_params

        if weights_path and is_hf_checkpoint_dir(weights_path):
            # Local HF checkpoint: checkpoint-faithful BertEncoder + the
            # checkpoint's own WordPiece vocab — embed_text then matches the
            # torch provider numerically (reference:
            # daft/ai/transformers text embedder; tests/test_convert.py).
            from daft_tpu.models.convert import load_hf_checkpoint
            from daft_tpu.utils.tokenizer import tokenizer_from_dir

            kind, self.model, params = load_hf_checkpoint(
                weights_path, dtype=dtype or jnp.bfloat16)
            if kind != "bert":
                raise DaftValueError(
                    f"text_embedder expects a bert checkpoint, got {kind!r}")
            self.cfg = self.model.cfg
            # Sequences must fit the checkpoint's learned position table.
            max_len = min(256, self.cfg.max_position)
            tok = tokenizer_from_dir(weights_path, max_length=max_len)
            if tok is None:
                # Hashed ids through a TRAINED embedding table are finite
                # but semantically garbage — same contract as the CLIP path.
                raise DaftValueError(
                    f"HF BERT checkpoint {weights_path!r} has no tokenizer "
                    f"files (vocab.txt); they are required for text embedding")
            self.tokenizer = tok
        else:
            self.cfg = MiniLMConfig.from_name(model_name)
            self.model, params = _initialised(init_minilm_params, self.cfg, seed)
            if weights_path:
                params = load_checkpoint(weights_path, params)
            self.tokenizer = HashingTokenizer(self.cfg.vocab_size,
                                              self.cfg.max_length)
        self.params = jax.device_put(params)
        model = self.model
        self._fwd = jax.jit(model.apply)

    @property
    def dimensions(self) -> int:
        return self.cfg.embed_dim

    def embed_text(self, texts: Sequence[Optional[str]]) -> np.ndarray:
        tokens, _ = self.tokenizer.encode_batch(texts)
        return _chunked_forward(self._fwd, self.params, tokens, self.max_batch,
                                self.cfg.embed_dim)


class FlaxCLIPClassifier(_FlaxModelBase):
    """Zero-shot classification: cosine similarity between image/text
    embeddings and label-text embeddings."""

    def __init__(self, model_name: str, weights_path: Optional[str] = None, seed: int = 0):
        super().__init__()
        self.image_embedder = FlaxCLIPImageEmbedder(model_name, weights_path, seed=seed)
        self.text_embedder = FlaxCLIPTextEmbedder(model_name, weights_path, seed=seed)
        self._label_cache: Dict[tuple, np.ndarray] = {}

    def _label_embs(self, labels: Sequence[str]) -> np.ndarray:
        key = tuple(labels)
        if key not in self._label_cache:
            self._label_cache[key] = self.text_embedder.embed_text(
                [f"a photo of a {l}" for l in labels]
            )
        return self._label_cache[key]

    def stage_images(self, images: np.ndarray) -> StagedChunks:
        return self.image_embedder.stage_images(images)

    def classify_image(self, images, labels: Sequence[str]) -> List[str]:
        img = self.image_embedder.embed_image(images)
        lab = self._label_embs(labels)
        sims = img @ lab.T
        idx = sims.argmax(axis=1)
        return [labels[i] for i in idx]

    def classify_text(self, texts: Sequence[Optional[str]], labels: Sequence[str]) -> List[str]:
        emb = self.text_embedder.embed_text(texts)
        key = ("__text__",) + tuple(labels)
        if key not in self._label_cache:
            self._label_cache[key] = self.text_embedder.embed_text(list(labels))
        lab = self._label_cache[key]
        sims = emb @ lab.T
        idx = sims.argmax(axis=1)
        return [labels[i] for i in idx]


#: Options of ``prompt`` that cut a published decoder to one chip's share: every decoder's on record.
CUT_OPTIONS = decoders.cut_options()
#: Options of ``prompt`` that reach ``FlaxPrompter``; any other is the engine's or is dropped.
PROMPTER_OPTIONS = ("weights_path", "seed", "max_new_tokens", "temperature", "num_slots", "max_prompt_tokens",
                    "ignore_eos", "logprobs") + CUT_OPTIONS


class FlaxPrompter(_FlaxModelBase):
    """``prompt`` / ``llm_generate`` over a decoder and the continuous batcher.

    ``model_name`` is looked up exactly in the record of published decoders
    (``models/decoders.DECODERS``); such a decoder takes its own cut's options
    (e.g. ``num_hidden_layers`` or ``num_layers``, ``expert_shard``,
    ``vocab_shard``: one chip's share of a stated deployment). Any other name is
    ``DecoderLMConfig.from_name``'s, and a cut's options with such a name are an
    error that lists the names on record. ``num_slots`` decode slots, prompts cut
    to ``max_prompt_tokens`` hashed tokens, ``ignore_eos`` for a fixed answer
    length, ``logprobs`` for answers a comparison can hold
    against logits (with a decoder that generates a block a step, also the
    pass that fixed each token, and with a decoder that drafts, the drafts:
    where each stood in the answer, its id and its log-probability under the
    drafting block)."""

    def __init__(self, model_name: str, weights_path: Optional[str] = None,
                 max_new_tokens: int = 32, temperature: float = 0.0, seed: int = 0,
                 num_slots: int = 8, max_prompt_tokens: Optional[int] = None,
                 ignore_eos: bool = False, logprobs: bool = False, **cut):
        super().__init__()
        unknown = set(cut) - set(CUT_OPTIONS)
        if unknown:
            raise DaftValueError(f"prompt does not know the options {sorted(unknown)}")
        decoder = decoders.DECODERS.get(model_name)
        if decoder is not None:
            foreign = set(cut) - set(decoder.cut_options)
            if foreign:
                raise DaftValueError(f"{model_name!r} is cut by {list(decoder.cut_options)}, not by {sorted(foreign)}")
            self.cfg = decoder.from_name(model_name, **cut)
            self.model, params = _initialised(decoder.init, self.cfg, seed)
            self.prompt_len = int(max_prompt_tokens or 128)
            self.max_seq_len = self.prompt_len + max_new_tokens + 1
        elif cut:
            raise DaftValueError(
                f"{sorted(cut)} cut a published model to one chip's share, and {model_name!r} is none of "
                f"{sorted(decoders.DECODERS)}")
        else:
            from daft_tpu.models.lm import DecoderLMConfig, init_lm_params

            self.cfg = DecoderLMConfig.from_name(model_name)
            self.model, params = _initialised(init_lm_params, self.cfg, seed)
            self.max_seq_len = self.cfg.max_seq_len
            self.prompt_len = min(int(max_prompt_tokens or min(self.max_seq_len // 2, 128)), self.max_seq_len - 2)
        if weights_path:
            params = load_checkpoint(weights_path, params)
        self.params = self.place_params(params)
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.num_slots = int(num_slots)
        self.eos_id = None if ignore_eos else 2
        self.logprobs = bool(logprobs)
        self.tokenizer = HashingTokenizer(self.model.vocab_size, self.prompt_len)
        self._batcher = None  # lazy ContinuousBatcher (persistent slots and state)
        self._batcher_lock = threading.Lock()  # batcher state is stateful

    def prompt(self, prompts: Sequence[Optional[str]]):
        """Continuous-batching generation with prefix routing (reference:
        the vLLM streaming sink; see daft_tpu/models/serving.py). -> one
        string of ids a row, or with ``logprobs`` ``(strings, token ids,
        log-probabilities)``, the last two as lists of arrays, and for a model
        that generates a block a step a fourth, the pass that fixed each token,
        and for a model that drafts three more: each row's drafts' places in its
        answer, their ids and their log-probabilities under the drafting block."""
        from daft_tpu.models.serving import ContinuousBatcher, Request

        with device_span("prompt.tokenize", rows=len(prompts)) as sp:
            tokens, lengths = self.tokenizer.encode_batch(prompts)
            lengths = np.maximum(lengths, 1)
            sp.count["tokens"] = int(lengths.sum())
        reqs = [Request(tokens=np.asarray(tokens[i][:lengths[i]], np.int32),
                        max_new_tokens=self.max_new_tokens)
                for i in range(len(prompts))]
        with self._batcher_lock:  # slot state is shared; runs serialize
            if self._batcher is None:
                self._batcher = ContinuousBatcher(
                    self.model, self.params, num_slots=self.num_slots, max_seq_len=self.max_seq_len,
                    temperature=self.temperature, eos_id=self.eos_id, max_prompt_tokens=self.prompt_len)
            b = self._batcher
            with device_span("prompt.run", rows=len(reqs), slots=b.B, positions=b.positions_held,
                             state_bytes=sum(b.state_bytes.values()), **b.state_bytes) as sp:
                out = b.run(reqs)
                sp.count["decode_steps"] = b.decode_steps
            logprobs, steps, drafts = b.last_logprobs, b.last_unmask_steps, b.last_drafts
        if not self.logprobs:
            return [" ".join(str(t) for t in row if t != 0) for row in out]
        answers = ([" ".join(str(t) for t in row) for row in out],
                   [np.asarray(row, np.int32) for row in out],
                   [np.asarray(row, np.float32) for row in logprobs])
        if b.L:
            answers += ([np.asarray(row, np.int32) for row in steps],)
        if self.model.draft_length:
            rows = [np.asarray(row, np.float64).reshape(-1, 3) for row in drafts]
            answers += tuple([r[:, k].astype(dtype) for r in rows] for k, dtype in ((0, np.int32), (1, np.int32), (2, np.float32)))
        return answers


# ---------------------------------------------------------------------- #
# Descriptors                                                             #
# ---------------------------------------------------------------------- #
class _FlaxDescriptor(Descriptor):
    def __init__(self, kind: str, model: str, options: Dict[str, Any]):
        self.kind = kind
        self.model = model
        self.options = dict(options)

    def get_provider(self) -> str:
        return "flax"

    def get_model(self) -> str:
        return self.model

    def get_options(self) -> Dict[str, Any]:
        return dict(self.options)

    def get_udf_options(self) -> UDFOptions:
        # Pure: called when the expression is built, in a process that may
        # only be planning — it must not touch a device.
        bs = self.options.get("batch_size")
        return UDFOptions(
            batch_size=bs if bs is not None else 256,
            max_concurrency=self.options.get("max_concurrency", 1),
            tpus=self.options.get("tpus", 1.0),
            chips_per_replica=self.options.get("chips_per_replica"),
        )

    def yields_blocks(self) -> bool:
        """A prompter over a decoder on record that generates a block a step: its answers carry ``unmask_steps``.
        Read from the record when the expression is built: nothing is drawn or placed."""
        decoder = decoders.DECODERS.get(self.model)
        return self.kind == "prompter" and decoder is not None and decoder.blocks

    def drafts(self) -> bool:
        """A prompter over a decoder on record that drafts its own tokens: its answers with ``logprobs`` carry the
        drafts. Read from the record when the expression is built: nothing is drawn or placed."""
        decoder = decoders.DECODERS.get(self.model)
        return self.kind == "prompter" and decoder is not None and decoder.drafts

    def runs_beside_host(self) -> bool:
        # On the CPU backend the forward takes the cores a host stage would.
        return jax.default_backend() != "cpu"

    def get_dimensions(self) -> Optional[int]:
        from daft_tpu.models.clip import CLIPConfig
        from daft_tpu.models.minilm import MiniLMConfig

        wp = self.options.get("weights_path")
        if wp:
            # A local HF checkpoint defines its own dims — the name-derived
            # config does not apply (tiny fixture checkpoints etc).
            from daft_tpu.models.convert import hf_config, is_hf_checkpoint_dir

            if is_hf_checkpoint_dir(wp):
                d = hf_config(wp)
                if d.get("model_type") == "clip":
                    return d.get("projection_dim", 512)
                if "hidden_size" in d:
                    return d["hidden_size"]
        if self.kind == "image_embedder":
            return CLIPConfig.from_name(self.model).embed_dim
        if self.kind == "text_embedder":
            if "clip" in self.model.lower() or "vit" in self.model.lower():
                return CLIPConfig.from_name(self.model).embed_dim
            return MiniLMConfig.from_name(self.model).embed_dim
        return None

    def instantiate(self):
        opts = {k: v for k, v in self.options.items()
                if k in ("weights_path", "seed", "max_new_tokens", "temperature")}
        if self.kind == "image_embedder":
            kw = {k: v for k, v in opts.items() if k in ("weights_path", "seed")}
            kw["batch_size"] = self.options.get("batch_size")
            kw["mesh_axes"] = self.options.get("mesh_axes")
            return FlaxCLIPImageEmbedder(self.model, **kw)
        if self.kind == "text_embedder":
            if "clip" in self.model.lower() or "vit" in self.model.lower():
                return FlaxCLIPTextEmbedder(self.model, **{k: v for k, v in opts.items() if k in ("weights_path", "seed")})
            return FlaxMiniLMTextEmbedder(self.model, **{k: v for k, v in opts.items() if k in ("weights_path", "seed")})
        if self.kind in ("image_classifier", "text_classifier"):
            return FlaxCLIPClassifier(self.model, **{k: v for k, v in opts.items() if k in ("weights_path", "seed")})
        if self.kind == "prompter":
            return FlaxPrompter(self.model, **{k: self.options[k] for k in PROMPTER_OPTIONS if k in self.options})
        raise DaftValueError(self.kind)


class FlaxProvider(Provider):
    name = "flax"

    DEFAULT_IMAGE_MODEL = "ViT-L/14"
    DEFAULT_TEXT_MODEL = "all-MiniLM-L6-v2"
    DEFAULT_LM = "default-lm"

    def __init__(self, random_init: bool = False, **options):
        self.random_init = random_init
        self.options = options

    def _opts(self, options: Dict[str, Any]) -> Dict[str, Any]:
        merged = {**self.options, **options}
        if self.random_init:
            merged.pop("weights_path", None)
        return merged

    def get_image_embedder(self, model: Optional[str] = None, **options) -> _FlaxDescriptor:
        return _FlaxDescriptor("image_embedder", model or self.DEFAULT_IMAGE_MODEL, self._opts(options))

    def get_text_embedder(self, model: Optional[str] = None, **options) -> _FlaxDescriptor:
        return _FlaxDescriptor("text_embedder", model or self.DEFAULT_TEXT_MODEL, self._opts(options))

    def get_image_classifier(self, model: Optional[str] = None, **options) -> _FlaxDescriptor:
        return _FlaxDescriptor("image_classifier", model or "ViT-B/32", self._opts(options))

    def get_text_classifier(self, model: Optional[str] = None, **options) -> _FlaxDescriptor:
        return _FlaxDescriptor("text_classifier", model or "ViT-B/32", self._opts(options))

    def get_prompter(self, model: Optional[str] = None, **options) -> _FlaxDescriptor:
        return _FlaxDescriptor("prompter", model or self.DEFAULT_LM, self._opts(options))
