"""What every comparison shares: the verdict over the numbers compared, the
sample of pool rows drawn from the seed, and the count of rows out of sequence.
The comparison itself is the file ``comparisons/<name>.py`` that the cell's
configuration names under ``comparison``."""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def sample_ids(pool, k: int, seed: int) -> np.ndarray:
    n = len(pool)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    ids = rng.choice(n, size=min(k, n), replace=False)
    if not isinstance(pool, np.ndarray):  # encoded rows: keep the largest in
        ids[0] = int(np.argmax([len(r) for r in pool]))
    return np.unique(ids)


def out_of_sequence(id_stream: Sequence[np.ndarray], pool_rows: int) -> int:
    ids = np.concatenate(id_stream)
    return int(np.count_nonzero((ids[1:] - ids[:-1]) % pool_rows != 1)
               + np.count_nonzero((ids < 0) | (ids >= pool_rows)))


def verdict(numbers: Dict[str, Dict[str, float]]) -> bool:
    """Correct when every number that has a limit is finite and within it."""
    return all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
               for n in numbers.values() if n["limit"] is not None)
