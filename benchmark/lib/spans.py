"""Host spans on the profiler's clock, taken from outside the program: the
traced run wraps the functions an entry names (and the UDF's callable), and each
wrapper notes ``time.time_ns()`` on the way in and out. That is the clock the
profiler stamps its own events with, and the trace states its start on it
(``profile_start_time``), so spans and device operations line up to microseconds.

Why not ``jax.profiler.TraceAnnotation``: it needs the profiler's host tracer,
and with that on, XLA's host-side linearisation of every staged uint8 batch
writes some 900,000 ``Transpose`` events: a 186 MB trace for three forwards, 17-27 s
to stop it, and forwards slowed up to 2.8x (chip runs, PR 25). With the host
tracer off the same trace is 3 MB and the forwards run at their untraced speed.
"""

from __future__ import annotations

import functools
import importlib
import time
from contextlib import contextmanager
from typing import Dict, List


class Recorder:
    """Spans by name, as [start_ns, end_ns] on the ``time.time_ns()`` clock."""

    def __init__(self):
        self.spans: Dict[str, List[List[int]]] = {}

    def wrap(self, fn, name: str):
        rows = self.spans.setdefault(name, [])

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            t0 = time.time_ns()
            try:
                return fn(*a, **kw)
            finally:
                rows.append([t0, time.time_ns()])

        return wrapped


@contextmanager
def installed(spans, udf=None):
    """``spans``: [(module, attribute path, span name)]. ``udf``: an object whose
    ``fn`` is the UDF's callable, wrapped as the span ``udf``. Yields the recorder."""
    rec, undo = Recorder(), []
    try:
        for module, path, name in spans:
            owner = importlib.import_module(module)
            *parents, attr = path.split(".")
            for part in parents:
                owner = getattr(owner, part)
            original = getattr(owner, attr)
            setattr(owner, attr, rec.wrap(original, name))
            undo.append((owner, attr, original))
        if udf is not None:
            undo.append((udf, "fn", udf.fn))
            udf.fn = rec.wrap(udf.fn, "udf")
        yield rec
    finally:
        for owner, attr, original in reversed(undo):
            setattr(owner, attr, original)
