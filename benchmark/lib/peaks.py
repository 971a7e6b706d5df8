"""The table of chip peaks, keyed by ``device_kind``. An unknown kind is an error."""

from __future__ import annotations

import json
import os


def peak(device_kind: str, key: str) -> float:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}; "
                       f"add it to benchmark/lib/peaks.json with its source")
    return float(table[device_kind][key])
