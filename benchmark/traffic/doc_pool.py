"""Text traffic: a pool of distinct documents drawn from the seed, served as an
in-memory source that outlasts the run by repeating the pool by reference.

Parameters (a traffic file's keys):

    column            the string column's name
    pool_rows         distinct documents in the pool (a multiple of partition_rows)
    partition_rows    rows of one source partition (= the UDF's batch)
    length_tokens     document lengths in words (one token each to a hashing
                      tokenizer): {"distribution": "lognormal", "median", "sigma",
                      "min", "max"}: the ``partition_rows`` quantile mid-points
                      of the distribution, clipped
    lexicon_words     distinct words ("w0" .. "w<n-1>") the documents draw from
    source_rows_per_s the source holds this many rows for every second of run

Every partition holds the same multiset of lengths (the quantile mid-points), in
an order drawn from the seed and with words drawn from the seed: the seed changes
the content and the order, not the work, and every partition costs the same.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Traffic:
    df: object
    column: str
    pool: List[str]
    pool_rows: int
    bytes_written: int = 0


def lengths(p: dict, n: int) -> np.ndarray:
    """The ``n`` quantile mid-points of the length distribution, clipped: the same for every seed."""
    if p["distribution"] != "lognormal":
        raise ValueError(f"unknown length distribution {p['distribution']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(p["median"] * np.exp(p["sigma"] * z)), p["min"], p["max"]).astype(np.int64)


def documents(traffic: dict, seed: int) -> List[str]:
    rng = np.random.default_rng(seed)
    per, n = traffic["partition_rows"], traffic["pool_rows"]
    if n % per:
        raise ValueError("pool_rows is no multiple of partition_rows")
    lexicon = np.array([f"w{i}" for i in range(traffic["lexicon_words"])])
    base = lengths(traffic["length_tokens"], per)
    docs = []
    for _ in range(n // per):
        for length in rng.permutation(base):
            docs.append(" ".join(lexicon[rng.integers(0, len(lexicon), int(length))]))
    return docs


def build(traffic: dict, config: dict, seed: int, workdir: str, seconds: float) -> Traffic:
    import daft_tpu
    from daft_tpu.dataframe.dataframe import DataFrame
    from daft_tpu.logical.builder import LogicalPlanBuilder
    from daft_tpu.micropartition import MicroPartition

    per, n, column = traffic["partition_rows"], traffic["pool_rows"], traffic["column"]
    pool = documents(traffic, seed)
    parts = []
    for start in range(0, n, per):
        ids = daft_tpu.Series.from_numpy(np.arange(start, start + per, dtype=np.int64), "id")
        docs = daft_tpu.Series.from_pylist(pool[start:start + per], column)
        parts.append(MicroPartition.from_pydict({"id": ids, column: docs}))
    repeats = max(2, math.ceil(traffic["source_rows_per_s"] * seconds / n))
    return Traffic(DataFrame(LogicalPlanBuilder.in_memory(parts * repeats, parts[0].schema)), column, pool, n)
