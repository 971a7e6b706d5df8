"""From the profiler's ``.xplane.pb`` to numbers.

Two steps, so that the second can be checked against a small recorded trace:
``extract`` reads the file (with nothing but JAX) into plain lists, and the
functions below reduce those lists. All times are nanoseconds since the trace
began. Device operations come from the trace; host spans and the traced window
come from ``lib/spans.py`` on the ``time.time_ns()`` clock and are moved onto
the trace's by its ``profile_start_time``.

    events = {"window": [t0, t1],                       # the traced window
              "devices": {plane: {"ops": [[start, dur, name], ...],      # line "XLA Ops"
                                  "modules": [[start, dur, name], ...]}},  # line "XLA Modules"
              "spans": {name: [[start, end], ...]}}      # host spans
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def profiler_options():
    """Device operations only: no Python tracer and no host tracer (see ``lib/spans.py``)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def extract(xplane_path: str, window_ns: Sequence[int], spans_ns: Dict[str, list]) -> dict:
    """``window_ns`` and ``spans_ns`` are on the ``time.time_ns()`` clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices: Dict[str, dict] = {}
    start = None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {_OPS_LINE: "ops", _MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.start_ns, e.duration_ns, e.name] for e in line.events]
        elif plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    if start is None:
        raise ValueError(f"{xplane_path} does not state its profile_start_time")
    return {"window": [t - start for t in window_ns], "devices": devices,
            "spans": {n: sorted([a - start, b - start] for a, b in v) for n, v in spans_ns.items()}}


# -- interval arithmetic ------------------------------------------------------
def merge(intervals: Iterable[Sequence[float]]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted((float(a), float(b)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Sequence[float]]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """Of two merged lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """The parts of merged ``xs`` that merged ``ys`` does not cover."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# -- reductions ---------------------------------------------------------------
def has_device(events) -> bool:
    """False for no trace and for a trace without a device plane (the CPU rehearsal)."""
    return bool(events and events["devices"])


def _clip(events: dict, intervals: Iterable[Sequence[float]]) -> List[Interval]:
    return intersect(merge(intervals), [tuple(events["window"])])


def window_s(events: dict) -> float:
    return (events["window"][1] - events["window"][0]) / 1e9


def busy(events: dict) -> Dict[str, List[Interval]]:
    """Per device: the union of the intervals in which an operation ran, inside the window."""
    return {d: _clip(events, ((s, s + dur) for s, dur, _ in v["ops"]))
            for d, v in events["devices"].items()}


def busy_s(events: dict) -> float:
    """Seconds in which an operation ran on the device, averaged over the devices."""
    per = [total(iv) / 1e9 for iv in busy(events).values()]
    if not per or max(per) <= 0:
        raise ValueError("no device operation in the traced window")
    return sum(per) / len(per)


def idle_share(events: dict) -> float:
    return 1.0 - busy_s(events) / window_s(events)


def span(events: dict, name: str) -> List[Interval]:
    return _clip(events, events["spans"].get(name, []))


def exclusive(events: dict, order: Sequence[str]) -> Dict[str, List[Interval]]:
    """Each instant of the window under the innermost span that covers it:
    ``order`` lists spans outermost first, and a later span takes its time out
    of every earlier one. What no span covers is ``outside_any_span``."""
    out, covered = {}, []
    for name in reversed(order):
        mine = span(events, name)
        out[name] = subtract(mine, covered)
        covered = merge(covered + mine)
    out["outside_any_span"] = subtract([tuple(events["window"])], covered)
    return out


def self_s(events: dict, name: str, order: Sequence[str]) -> float:
    """Self time of span ``name``: its duration less what later spans of ``order`` cover."""
    return total(exclusive(events, order)[name]) / 1e9


def uncovered_by_device_s(events: dict, name: str) -> float:
    """Seconds of span ``name`` during which no device ran an operation."""
    any_busy = merge(iv for ivs in busy(events).values() for iv in ivs)
    return total(subtract(span(events, name), any_busy)) / 1e9


def idle_gaps(events: dict, order: Sequence[str]) -> List[List]:
    """[[span, seconds], ...]: the device's idle time by what the host was doing, longest first."""
    any_busy = merge(iv for ivs in busy(events).values() for iv in ivs)
    idle = subtract([tuple(events["window"])], any_busy)
    rows = [[n, total(intersect(iv, idle)) / 1e9] for n, iv in exclusive(events, order).items()]
    return sorted((r for r in rows if r[1] > 0), key=lambda r: -r[1])[:10]


def step_ms(events: dict) -> float:
    """Median device time of one execution of the program that took most of the
    device's time in the window (the jitted forward), whole executions only."""
    by_name: Dict[str, List[float]] = {}
    t0, t1 = events["window"]
    for v in events["devices"].values():
        for s, dur, name in v["modules"]:
            if s >= t0 and s + dur <= t1:
                by_name.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(dur)
    if not by_name:
        raise ValueError("no whole program execution in the traced window")
    durs = max(by_name.values(), key=sum)
    return statistics.median(durs) / 1e6


def op_kind(name: str) -> str:
    """'%fusion.12 = bf16[512,257,1024]{2,1,0:T(8,128)} fusion(...)' -> 'fusion bf16[512,257,1024]':
    instances of one kind and output shape are counted together."""
    m = re.match(r"%?([\w\-]+?)(?:\.\d+)* = (.*)", name)
    if not m:
        return re.sub(r"[.\d]+$", "", name.lstrip("%")) or name
    shape = re.sub(r"\{[^}]*\}", "", m.group(2)).split(" ")[0]
    return f"{m.group(1)} {shape}"[:80]


def device_ops(events: dict) -> List[List]:
    """[[operation, seconds], ...]: the ten operations with most device time,
    instances of one fusion counted together, averaged over devices."""
    sums: Dict[str, float] = {}
    t0, t1 = events["window"]
    for v in events["devices"].values():
        for s, dur, name in v["ops"]:
            if s + dur > t0 and s < t1:
                key = op_kind(name)
                sums[key] = sums.get(key, 0.0) + dur / 1e9
    n = max(1, len(events["devices"]))
    return [[k, v / n] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:10]]
