"""AI protocols + descriptors.

Reference: daft/ai/protocols.py:15-60 — TextEmbedder / ImageEmbedder /
TextClassifier / ImageClassifier / Prompter protocols, each paired with a
Descriptor that carries instantiation options and UDF scheduling options
(batch size, concurrency, accelerator ask). On TPU the accelerator ask is
chips (``tpus``) instead of the reference's ``gpus``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from daft_tpu.datatype import DataType


@dataclass
class UDFOptions:
    """Scheduling options the descriptor hands to the UDF operator
    (reference: get_udf_options, daft/ai/transformers/protocols/image_embedder.py:45-50)."""

    batch_size: int = 256
    max_concurrency: int = 1
    tpus: float = 1.0
    cpus: Optional[float] = None
    memory_bytes: Optional[int] = None
    use_process: bool = False
    # >1: each replica owns an ICI mesh slice of this many chips and the
    # provider shards its params/batches over it (parallel/replica.py) — the
    # TPU generalisation of the reference's gpus_per_actor.
    chips_per_replica: Optional[int] = None


@runtime_checkable
class TextEmbedder(Protocol):
    def embed_text(self, texts: Sequence[Optional[str]]) -> np.ndarray: ...


@runtime_checkable
class ImageEmbedder(Protocol):
    def embed_image(self, images: np.ndarray) -> np.ndarray: ...


@runtime_checkable
class TextClassifier(Protocol):
    def classify_text(self, texts: Sequence[Optional[str]], labels: Sequence[str]) -> List[str]: ...


@runtime_checkable
class ImageClassifier(Protocol):
    def classify_image(self, images: np.ndarray, labels: Sequence[str]) -> List[str]: ...


@runtime_checkable
class Prompter(Protocol):
    def prompt(self, prompts: Sequence[Optional[str]]) -> List[str]: ...


class Descriptor:
    """Serializable recipe for instantiating a protocol implementation inside
    a UDF worker (possibly on another host)."""

    def get_provider(self) -> str:
        raise NotImplementedError

    def get_model(self) -> str:
        raise NotImplementedError

    def get_options(self) -> Dict[str, Any]:
        return {}

    def get_udf_options(self) -> UDFOptions:
        return UDFOptions()

    def get_dimensions(self) -> Optional[int]:
        """Embedding dimensionality, when known statically."""
        return None

    def yields_blocks(self) -> bool:
        """True for a prompter whose model generates a block a step: its answers
        carry the pass that fixed each token. Read when the expression is built."""
        return False

    def drafts(self) -> bool:
        """True for a prompter whose model drafts its own tokens and verifies
        them: its answers with log-probabilities carry the drafts. Read when the
        expression is built."""
        return False

    def runs_beside_host(self) -> bool:
        """True where instances compute on a processor other than the host's
        CPU, so that the host's work for the next batch can run while this one
        computes (``Udf.host_stage``). Asked when a query runs, never while it
        is planned."""
        return True

    def instantiate(self):
        raise NotImplementedError


class TextEmbedderDescriptor(Descriptor):
    protocol = "text_embedder"


class ImageEmbedderDescriptor(Descriptor):
    protocol = "image_embedder"


class TextClassifierDescriptor(Descriptor):
    protocol = "text_classifier"


class ImageClassifierDescriptor(Descriptor):
    protocol = "image_classifier"


class PrompterDescriptor(Descriptor):
    protocol = "prompter"
