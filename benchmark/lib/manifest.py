"""From a cell's name in ``BENCHMARK.json`` to the files that make it up.

Nothing here names a configuration, a traffic mix, an entry, a reference or a
metric: each is found because a file with its name exists.

    workloads[].config   -> benchmark/configs/<config>.json
    workloads[].traffic  -> benchmark/traffic/<traffic>.json  ("generator": module beside it)
    config["entry"]      -> benchmark/entries/<entry>.py
    config["reference"]  -> benchmark/reference/<reference>.py
    config["comparison"] -> benchmark/comparisons/<comparison>.py  (``compare(...)``: what decides ``correct``)
    every metric's name  -> benchmark/metrics/<name>.py  (``read(run)``)
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str) -> ModuleType:
    """Import one file by path (metric files carry dots in their names)."""
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: ModuleType
    reference: ModuleType
    comparison: ModuleType
    generator: ModuleType
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, manifest_path: str = None, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``workload`` of the manifest, every file of it loaded. Each
    metric entry gains ``"read"``, the reader of ``metrics/<name>.py``."""
    manifest = load_json(manifest_path or os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; the manifest has {sorted(cells)}")
    w = cells[workload]
    config = dict(load_json(os.path.join(bench_dir, "configs", w["config"] + ".json")), name=w["config"])
    traffic = dict(load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")), name=w["traffic"])

    def module(kind: str, name: str) -> ModuleType:
        return load_module(os.path.join(bench_dir, kind, name + ".py"))

    def readers(entries: List[dict]) -> List[dict]:
        return [dict(m, read=module("metrics", m["name"]).read)
                for m in entries if _applies(m, workload)]

    return Cell(name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
                entry=module("entries", config["entry"]),
                reference=module("reference", config["reference"]),
                comparison=module("comparisons", config["comparison"]),
                generator=module("traffic", traffic["generator"]),
                end_to_end=readers(manifest["end_to_end"]),
                per_layer=readers(manifest["per_layer"]))
