"""The one general generator of image traffic: a pool of distinct images drawn
from the seed, served as a source long enough to outlast the run by repeating
the pool by reference (in memory) or by scanning its one file again (parquet).

Parameters (a traffic file's keys):

    column            the image column's name
    pool_rows         distinct images in the pool
    encoding          "raw": FixedShapeImage RGB uint8 at the model's input size
                      "jpeg": encoded bytes of varying size
    storage           "memory": one in-memory partition, repeated by reference
                      "parquet": one file (id, url, <column>), row groups of
                      ``row_group_rows``, scanned with read_parquet
    source_rows_per_s the source holds this many rows for every second of run
    jpeg              (encoding "jpeg") sizes [[width, height, share], ...] (the
                      stored sizes as a histogram: one entry where a recipe
                      fixes the size, many where it is read from a dataset's
                      width and height columns), quality, gray_share, lowpass
                      (content: noise drawn at 1/lowpass of the size and
                      enlarged, plus ``grain`` levels of pixel noise), sizes_seed

Every seed sees the same multiset of image sizes over the pool (drawn once from
``sizes_seed``), in another order and with other content, so that the seed does
not change the work.
"""

from __future__ import annotations

import io
import math
import os
from dataclasses import dataclass
from typing import List, Union

import numpy as np


@dataclass
class Traffic:
    df: object
    column: str
    pool: Union[np.ndarray, List[bytes]]
    pool_rows: int
    bytes_written: int = 0


def jpeg_sizes(p: dict, n: int) -> List[tuple]:
    """The pool's (width, height, gray) triples, the same for every seed."""
    rng = np.random.default_rng(p["sizes_seed"])
    shares = np.array([s[2] for s in p["sizes"]], float)
    which = rng.choice(len(shares), n, p=shares / shares.sum())
    gray = rng.random(n) < p["gray_share"]
    return [(int(p["sizes"][k][0]), int(p["sizes"][k][1]), bool(g)) for k, g in zip(which, gray)]


def _jpeg(seed: int, k: int, w: int, h: int, gray: bool, grain: np.ndarray, p: dict) -> bytes:
    """Image ``k`` of the pool: its content comes from (seed, k) alone, so the
    pool is the same however many threads encode it."""
    from PIL import Image

    rng = np.random.default_rng([seed, k])
    c = 1 if gray else 3
    f = p["lowpass"]
    small = rng.integers(0, 256, (h // f + 2, w // f + 2, c), dtype=np.uint8)
    img = Image.fromarray(small.squeeze(-1) if gray else small).resize((w, h), Image.BICUBIC)
    y, x = (int(v) for v in rng.integers(0, grain.shape[0] - max(w, h), 2))
    arr = np.minimum(np.asarray(img), 255 - 2 * p["grain"])  # room for the grain: uint8 wraps
    arr = arr + (grain[y:y + h, x:x + w, 0] if gray else grain[y:y + h, x:x + w])
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, "JPEG", quality=p["quality"])
    return buf.getvalue()


def jpeg_pool(p: dict, n: int, seed: int, threads: int = 4) -> List[bytes]:
    from concurrent.futures import ThreadPoolExecutor

    rng = np.random.default_rng(seed)
    sizes = jpeg_sizes(p, n)
    order = rng.permutation(n)
    # Pixel grain: one tile from the seed, cut at another offset for each image.
    side = max(max(s[:2]) for s in p["sizes"]) + 64
    grain = rng.integers(0, 2 * p["grain"] + 1, (side, side, 3), dtype=np.uint8)
    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(lambda k: _jpeg(seed, int(k), *sizes[order[k]], grain, p), range(n)))


def build(traffic: dict, config: dict, seed: int, workdir: str, seconds: float) -> Traffic:
    import daft_tpu
    from daft_tpu.dataframe.dataframe import DataFrame
    from daft_tpu.datatype import DataType
    from daft_tpu.logical.builder import LogicalPlanBuilder
    from daft_tpu.micropartition import MicroPartition

    n, column, size = traffic["pool_rows"], traffic["column"], config["image_size"]
    repeats = max(2, math.ceil(traffic["source_rows_per_s"] * seconds / n))
    rng = np.random.default_rng(seed)
    ids = np.arange(n, dtype=np.int64)
    if traffic["encoding"] == "raw":
        pool = np.frombuffer(rng.bytes(n * size * size * 3), np.uint8).reshape(n, -1)
    elif traffic["encoding"] == "jpeg":
        pool = jpeg_pool(traffic["jpeg"], n, seed)
    else:
        raise ValueError(f"unknown encoding {traffic['encoding']!r}")

    if traffic["storage"] == "memory":
        if traffic["encoding"] != "raw":
            raise ValueError("storage 'memory' holds raw images")
        img = daft_tpu.Series.from_numpy(pool, column, DataType.image("RGB", size, size))
        mp = MicroPartition.from_pydict({"id": daft_tpu.Series.from_numpy(ids, "id"), column: img})
        df = DataFrame(LogicalPlanBuilder.in_memory([mp] * repeats, mp.schema))
        return Traffic(df, column, pool, n)
    if traffic["storage"] == "parquet":
        import pyarrow as pa
        import pyarrow.parquet as pq

        os.makedirs(workdir, exist_ok=True)
        path = os.path.join(workdir, "pool.parquet")
        table = pa.table({"id": ids, "url": [f"https://example.org/img/{i:07d}.jpg" for i in ids],
                          column: pa.array(pool, pa.binary())})
        pq.write_table(table, path, row_group_size=traffic["row_group_rows"], compression="none")
        df = daft_tpu.read_parquet([path] * repeats)
        return Traffic(df, column, pool, n, os.path.getsize(path))
    raise ValueError(f"unknown storage {traffic['storage']!r}")
