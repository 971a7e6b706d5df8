"""The comparison ``embedding_gap``: unit-norm embeddings, matched by ``id`` to
the pool, against the configuration's plain reference.

Numbers compared (each printed beside its limit):

    embedding_gap        the widest L2 distance between a delivered embedding and
                         the reference's, over every occurrence in the window of
                         ``sample_rows`` pool rows drawn from the seed (the
                         largest encoded row among them). Limit: the
                         configuration's ``compare.embedding_gap_max``.
    rows_not_unit_norm   delivered rows of the window that are not finite or
                         whose norm is off 1 by more than 1e-3. Limit 0.
    ids_out_of_sequence  rows of the whole stream whose id is not its
                         predecessor's + 1 (mod the pool). Limit 0.

A comparison module gives the harness ``compare(cell, seed, pool, id_stream,
window_parts, control=False)`` -> ``{"numbers": {name: {"value", "limit"}},
"failed": rows the run counts as failed}`` and, with ``control``, ``"control"``:
the same numbers with the control (the reference one precision step down: both
operands of every matrix product in float8_e4m3) put in the program's place.
``lib.compare.verdict`` judges either set of numbers.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from lib.compare import out_of_sequence, sample_ids


def _numbers(rules: dict, emb: np.ndarray, hit: np.ndarray, want_rows: np.ndarray,
             sequence_faults: int) -> Dict[str, Dict[str, float]]:
    norms = np.linalg.norm(emb, axis=1)
    bad_norm = int(np.count_nonzero(~np.isfinite(norms) | (np.abs(norms - 1.0) > 1e-3)))
    gap = float(np.max(np.linalg.norm(emb[hit] - want_rows, axis=1)))
    return {
        "embedding_gap": {"value": gap, "limit": rules["embedding_gap_max"]},
        "rows_not_unit_norm": {"value": bad_norm, "limit": 0},
        "ids_out_of_sequence": {"value": sequence_faults, "limit": 0},
        "rows_compared": {"value": int(len(hit)), "limit": None},
    }


def compare(cell, seed: int, pool, id_stream: List[np.ndarray],
            window_parts: List[Tuple[np.ndarray, np.ndarray]], control: bool = False) -> dict:
    cfg, ref = cell.config, cell.reference
    rules = cfg["compare"]
    ids = np.concatenate([i for i, _ in window_parts])
    emb = np.concatenate([e for _, e in window_parts]).astype(np.float32)

    chosen = sample_ids(pool, rules["sample_rows"], seed)
    rows = pool[chosen] if isinstance(pool, np.ndarray) else [pool[i] for i in chosen]
    pixels = ref.preprocess(cfg, rows)
    want = ref.embed(cfg, seed, pixels)
    slot = {int(i): k for k, i in enumerate(chosen)}
    hit = np.flatnonzero(np.isin(ids, chosen))
    if len(hit) == 0:
        raise RuntimeError("none of the sampled pool rows was delivered in the window")
    slots = [slot[int(i)] for i in ids[hit]]
    sequence_faults = out_of_sequence(id_stream, len(pool))
    numbers = _numbers(rules, emb, hit, want[slots], sequence_faults)
    out = {"numbers": numbers, "failed": int(numbers["rows_not_unit_norm"]["value"])}
    if control:  # the reference in fp8 answers for the sampled rows in the program's place
        low = ref.embed(cfg, seed, pixels, precision="fp8")
        served = emb.copy()
        served[hit] = low[slots]
        out["control"] = _numbers(rules, served, hit, want[slots], sequence_faults)
    return out
