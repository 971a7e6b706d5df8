"""What one ``profiling.device_span`` costs on this host: nanoseconds a span
(open with one counter, add one counter, close into the ring), the least and
the median of 15 rounds of 100,000. Host code only; the budget is 3,000.

    python3 benchmark/tools/device_span_cost.py
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def measure(rounds: int = 15, spans: int = 100_000) -> dict:
    from daft_tpu.profiling import device_span

    per_span = []
    for _ in range(rounds):
        t0 = time.perf_counter_ns()
        for _ in range(spans):
            with device_span("cost", rows=1) as sp:
                sp.count["bytes"] = 2
        per_span.append((time.perf_counter_ns() - t0) / spans)
    return {"device_span_ns": {"min": min(per_span), "median": statistics.median(per_span)},
            "rounds": rounds, "spans_a_round": spans}


if __name__ == "__main__":
    print(json.dumps(measure()))
