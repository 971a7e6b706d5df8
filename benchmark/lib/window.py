"""Window arithmetic over partition arrivals: ``[(seconds, rows), ...]`` in the
order the consumer took them. All the work over all the time: the rate is the
rows of every partition that arrived in (open, close] over close - open, where
both ends are arrivals, so no partition is counted by halves."""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

Arrival = Tuple[float, int]

#: Two consecutive gaps agree when they differ by at most this share of the larger.
GAPS_AGREE = 0.10
#: Warm-up gives up waiting for two agreeing gaps after this many partitions.
WARMUP_MAX_PARTITIONS = 8


def warmed_up(arrivals: Sequence[Arrival]) -> bool:
    """True once the newest arrival may open the window: at least two warm-up
    partitions before it, and its gap agrees with the gap before."""
    n = len(arrivals)
    if n < 3:
        return False
    if n >= WARMUP_MAX_PARTITIONS:
        return True
    g1 = arrivals[-2][0] - arrivals[-3][0]
    g2 = arrivals[-1][0] - arrivals[-2][0]
    return abs(g2 - g1) <= GAPS_AGREE * max(g1, g2)


def closes(arrivals: Sequence[Arrival], open_index: int, seconds: float) -> bool:
    """True when the newest arrival is the first at or after ``seconds`` past the opening."""
    return arrivals[-1][0] - arrivals[open_index][0] >= seconds


@dataclass
class Window:
    open_index: int
    close_index: int
    open_t: float
    close_t: float
    rows: int
    partitions: int
    longest_gap_s: float

    @property
    def seconds(self) -> float:
        return self.close_t - self.open_t

    @property
    def rows_per_s(self) -> float:
        return self.rows / self.seconds


def measure(arrivals: Sequence[Arrival], open_index: int,
            close_index: Optional[int] = None) -> Window:
    """The window from arrival ``open_index`` to ``close_index`` (default: the last)."""
    if close_index is None:
        close_index = len(arrivals) - 1
    if not 0 <= open_index < close_index < len(arrivals):
        raise ValueError(f"no window between arrivals {open_index} and {close_index} of {len(arrivals)}")
    inside = arrivals[open_index + 1:close_index + 1]
    times = [arrivals[open_index][0]] + [t for t, _ in inside]
    return Window(open_index, close_index, times[0], times[-1],
                  sum(r for _, r in inside), len(inside),
                  max(b - a for a, b in zip(times, times[1:])))


def read_partition_file(path: str) -> Tuple[List[Arrival], int, int]:
    """A run's partition file -> (arrivals, open_index, close_index)."""
    import json

    arrivals, marks = [], {}
    with open(path) as f:
        for i, line in enumerate(f):
            rec = json.loads(line)
            arrivals.append((rec["t"], rec["rows"]))
            if "mark" in rec:
                marks[rec["mark"]] = i
    return arrivals, marks["open"], marks["close"]
