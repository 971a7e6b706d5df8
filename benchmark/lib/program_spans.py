"""The program's own device-path spans on the trace's clock.

``daft_tpu/profiling.py`` keeps every span of the device path (``udf.pull``,
``image.preprocess``, ``provider.stage``, ...) in a ring, on its own clock
(``span_clock_ns``). The trace's events, and the host spans that ``lib/spans.py``
takes from outside, are nanoseconds since the trace began. Two pairs of a
wrapper and a program span nest in known directions, and bracket the offset
between the clocks:

    program ``provider.stage``  is opened around the call the ``stage`` wrapper sits in
    wrapper ``provider``        is opened around the call that opens ``provider.forward``

so ``lo`` = median(program stage start - wrapper stage start) is at most the
offset and ``hi`` = median(program forward start - wrapper provider start) at
least. The middle is taken and then checked: every wrapper and span of those
pairs has to nest as the code nests them, to ``WIDEN_NS``. Where the program has
no ring (an older program), the run no trace or no wrappers, or the check fails,
``aligned`` returns None and every reader built on it returns None.
"""

from __future__ import annotations

import statistics
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

from lib import trace

#: (program span, wrapper span of ``lib/spans.py``): the program's span is outside the wrapper.
PROGRAM_OUTSIDE = ("provider.stage", "stage")
#: (program span, wrapper span): the program's span is inside the wrapper.
PROGRAM_INSIDE = ("provider.forward", "provider")
#: After the shift each pair has to nest to this many nanoseconds.
WIDEN_NS = 50_000
#: ``hi - lo`` above this is no bracket.
MAX_BRACKET_NS = 100_000


class ClockMismatch(ValueError):
    """The two clocks could not be matched; the message says why."""


def ring() -> Optional[list]:
    """The program's finished device-path spans, oldest first; None where the
    program has no such recorder."""
    try:
        from daft_tpu import profiling

        return profiling.recent_device_spans()
    except (ImportError, AttributeError):
        return None


#: The ring may end with spans of a call that the wrappers never saw (a partition
#: computed ahead while the run was winding up): so many are tried as left over.
MAX_TRAILING = 3


def _pairs(program: Sequence, wrappers: Sequence[Sequence[float]], window: Sequence[float]):
    """Program spans and wrappers of one kind, paired newest with newest (the
    ring may have dropped its oldest, and may hold older runs' spans), and only
    the pairs whose wrapper begins in the traced window. Of the pairings that
    leave 0 to ``MAX_TRAILING`` newest program spans over, the one whose starts
    differ most evenly is the same calls."""
    best = None
    for trailing in range(MAX_TRAILING + 1):
        prog = program[:len(program) - trailing]
        n = min(len(prog), len(wrappers))
        pairs = [(p, w) for p, w in zip(prog[len(prog) - n:], wrappers[len(wrappers) - n:])
                 if window[0] <= w[0] <= window[1]]
        if pairs:
            gaps = [p.start_ns - w[0] for p, w in pairs]
            if best is None or max(gaps) - min(gaps) < best[0]:
                best = (max(gaps) - min(gaps), pairs)
    return best[1] if best else []


def match_clock(spans: Sequence, wrappers: Dict[str, list], window: Sequence[float]) -> SimpleNamespace:
    """-> ``offset_ns`` (program clock minus trace clock), ``bracket_ns`` (``hi - lo``)
    and the count of pairs used. Raises ``ClockMismatch``."""
    by_name: Dict[str, list] = {}
    for sp in sorted(spans, key=lambda s: s.start_ns):
        by_name.setdefault(sp.name, []).append(sp)
    outside = _pairs(by_name.get(PROGRAM_OUTSIDE[0], []), sorted(wrappers.get(PROGRAM_OUTSIDE[1], [])), window)
    inside = _pairs(by_name.get(PROGRAM_INSIDE[0], []), sorted(wrappers.get(PROGRAM_INSIDE[1], [])), window)
    if not outside or not inside:
        raise ClockMismatch(f"no pair of a wrapper and a program span in the traced window "
                            f"({len(outside)} of {PROGRAM_OUTSIDE}, {len(inside)} of {PROGRAM_INSIDE})")
    # median_low: an element, so whole nanoseconds (times here are near 2**61)
    lo = statistics.median_low(int(p.start_ns - w[0]) for p, w in outside)
    hi = statistics.median_low(int(p.start_ns - w[0]) for p, w in inside)
    if not -WIDEN_NS <= hi - lo <= MAX_BRACKET_NS:
        raise ClockMismatch(f"the pairs bracket the offset to {hi - lo} ns "
                            f"(limit {MAX_BRACKET_NS}): they are not the same calls")
    offset = (lo + hi) // 2
    for p, w in outside:  # wrapper within its program span
        if w[0] < p.start_ns - offset - WIDEN_NS or w[1] > p.end_ns - offset + WIDEN_NS:
            raise ClockMismatch(f"a {PROGRAM_OUTSIDE[1]!r} wrapper [{w[0]:.0f}, {w[1]:.0f}] lies outside its "
                                f"{PROGRAM_OUTSIDE[0]!r} span [{p.start_ns - offset:.0f}, {p.end_ns - offset:.0f}]")
    for p, w in inside:  # program span within its wrapper
        if p.start_ns - offset < w[0] - WIDEN_NS or p.end_ns - offset > w[1] + WIDEN_NS:
            raise ClockMismatch(f"a {PROGRAM_INSIDE[0]!r} span [{p.start_ns - offset:.0f}, {p.end_ns - offset:.0f}] "
                                f"lies outside its {PROGRAM_INSIDE[1]!r} wrapper [{w[0]:.0f}, {w[1]:.0f}]")
    return SimpleNamespace(offset_ns=offset, bracket_ns=hi - lo, pairs=len(outside) + len(inside))


def aligned(run) -> Optional[SimpleNamespace]:
    """The ring's spans on the trace's clock: ``spans`` maps a name to
    ``[(start, end, counters, error)]`` in nanoseconds since the trace began,
    oldest first; ``offset_ns``, ``bracket_ns`` and ``pairs`` say how the clocks
    were matched. Computed once a run; None where it cannot be (the reason goes
    to standard error, once)."""
    if not hasattr(run, "_program_spans"):
        run._program_spans = _aligned(run)
    return run._program_spans


def _aligned(run) -> Optional[SimpleNamespace]:
    spans = ring()
    if run.events is None or not spans:
        return None
    try:
        clock = match_clock(spans, run.events["spans"], run.events["window"])
    except ClockMismatch as e:
        print(f"program_spans: no program span is read in this run: {e}", file=sys.stderr)
        return None
    by_name: Dict[str, list] = {}
    for sp in sorted(spans, key=lambda s: s.start_ns):
        by_name.setdefault(sp.name, []).append(
            (sp.start_ns - clock.offset_ns, sp.end_ns - clock.offset_ns, sp.count, sp.error))
    clock.spans = by_name
    return clock


def in_window(run, name: str, whole: bool = True) -> List[tuple]:
    """The aligned spans called ``name`` of the traced window. ``whole``: those
    that began in it, each counted once (for counters; a run computes one
    partition ahead, so as many began in the window as arrived in it). Else every
    span that overlaps it (for times, which the callers clip to the window)."""
    a = aligned(run)
    if a is None:
        return []
    t0, t1 = run.events["window"]
    if whole:
        return [s for s in a.spans.get(name, []) if t0 <= s[0] <= t1]
    return [s for s in a.spans.get(name, []) if s[0] < t1 and s[1] > t0]


def counter_sum(run, name: str, key: str) -> Optional[float]:
    """A counter summed over the spans called ``name`` that began in the window;
    None where no such span carries it."""
    values = [s[2][key] for s in in_window(run, name) if key in s[2]]
    return float(sum(values)) if values else None


def _clipped(run, name: str) -> List[tuple]:
    """The window's part of the spans called ``name``, as merged intervals."""
    mine = in_window(run, name, whole=False)
    if not mine:
        return []
    return trace.intersect(trace.merge((a, b) for a, b, _, _ in mine), [tuple(run.events["window"])])


def _any_busy(run) -> Optional[List[tuple]]:
    """The intervals in which any device ran an operation; None without a device."""
    if not trace.has_device(run.events):
        return None
    return trace.merge(iv for ivs in trace.busy(run.events).values() for iv in ivs)


def span_s(run, name: str) -> Optional[float]:
    """Seconds of the traced window under spans called ``name``."""
    mine = _clipped(run, name)
    return trace.total(mine) / 1e9 if mine else None


def exposed_s(run, name: str) -> Optional[float]:
    """Seconds of the traced window under spans called ``name`` during which no
    device ran an operation."""
    mine, any_busy = _clipped(run, name), _any_busy(run)
    if not mine or any_busy is None:
        return None
    return trace.total(trace.subtract(mine, any_busy)) / 1e9


def exposed_split_s(run, name: str) -> Optional[dict]:
    """``exposed_s`` by where in the span the device sat idle: before the first
    operation that ran under it, between operations, after the last. Under
    ``provider.fetch``: the wait for the input to reach the chip, gaps inside
    the forward, and the copy out with the host's wake-up."""
    mine, any_busy = _clipped(run, name), _any_busy(run)
    if not mine or any_busy is None:
        return None
    out = {"before_first_op": 0.0, "between_ops": 0.0, "after_last_op": 0.0, "no_op": 0.0}
    for a, b in mine:
        ran = trace.intersect([(a, b)], any_busy)
        if not ran:
            out["no_op"] += (b - a) / 1e9
            continue
        out["before_first_op"] += (ran[0][0] - a) / 1e9
        out["after_last_op"] += (b - ran[-1][1]) / 1e9
        out["between_ops"] += trace.total(trace.subtract([(ran[0][0], ran[-1][1])], ran)) / 1e9
    return out


def per_krow(run, seconds: Optional[float]) -> Optional[float]:
    if seconds is None or not run.trace_rows:
        return None
    return 1000.0 * seconds / run.trace_rows


def setup_span_s(run, name: str, **where) -> Optional[float]:
    """Seconds of the newest span called ``name`` (whose counters hold ``where``)
    that ended before the traced window opened."""
    a = aligned(run)
    if a is None:
        return None
    t0 = run.events["window"][0]
    before = [s for s in a.spans.get(name, [])
              if s[1] <= t0 and all(s[2].get(k) == v for k, v in where.items())]
    return (before[-1][1] - before[-1][0]) / 1e9 if before else None
