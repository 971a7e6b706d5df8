"""Device selection: which process gets which chip, and where compiled
programs are kept.

A TPU chip belongs to one process at a time. A process that has initialised
a JAX backend holds every chip it can see, and a child that needs one then
fails or hangs. So the decisions that involve the device are made here, in
one place, and none of them initialises a backend except :func:`require_tpu`:

* :func:`setup_compile_cache` — the one function that places JAX's persistent
  compilation cache; every module that jits calls it before its first jit.
* :func:`child_device_env` — the environment an out-of-process worker is
  started with, so it knows its device before it imports JAX.
* :func:`require_tpu` — what a measurement path calls first: a TPU or an error,
  never a CPU run under a device metric's name.
"""

from __future__ import annotations

import glob
import logging
import os
from typing import Dict, Optional

from daft_tpu.config import daft_env

_log = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and no
    directory is set in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it is
    built from the checkout's location alone and never moves between runs.

    A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, CPU-only
    workers) gets no cache from here. The cache is for the chip's compiles
    of seconds to minutes; XLA:CPU logs a machine-feature mismatch on every
    hit even on the machine that wrote the entry, and warns of SIGILL when a
    copied tree brings entries from another CPU.
    """
    import jax

    if (daft_env("JAX_PLATFORMS") != "cpu"
            and not daft_env("JAX_COMPILATION_CACHE_DIR")):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def describe_devices() -> Dict[str, object]:
    """Initialise the backend and describe it as JAX reports it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def require_tpu() -> Dict[str, object]:
    """:func:`describe_devices`, raising unless the backend is a TPU."""
    device = describe_devices()
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"a TPU is required, JAX found platform={device['platform']!r} "
            f"(JAX_PLATFORMS={daft_env('JAX_PLATFORMS')!r})")
    return device


def local_chip_count() -> int:
    """TPU chips on this host, counted from their device files so that the
    caller does not have to initialise a backend (and so take the chips)."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def child_device_env(index: Optional[int]) -> Dict[str, str]:
    """The device decision for out-of-process worker ``index`` of this host,
    as environment overrides applied before the child imports JAX: chip
    ``index`` while chips remain, CPU for every other child (``index=None``
    included). A driver that is itself held to CPU holds its children there.
    """
    if (index is None or daft_env("JAX_PLATFORMS") == "cpu"
            or index >= local_chip_count()):
        return {"JAX_PLATFORMS": "cpu"}
    return {"TPU_VISIBLE_CHIPS": str(index),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def enter_child() -> None:
    """First call of an out-of-process worker: log the device decision its
    parent made (:func:`child_device_env`) and place the compile cache."""
    if daft_env("JAX_PLATFORMS") == "cpu":
        _log.info("worker pid %d: JAX_PLATFORMS=cpu, no chip assigned",
                  os.getpid())
    else:
        _log.info("worker pid %d: TPU chip %s", os.getpid(),
                  daft_env("TPU_VISIBLE_CHIPS", "unassigned (all visible)"))
    setup_compile_cache()
