"""Compile a configuration's forward for a described v5e chip, without the chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py <config> [--batch N]

Prints XLA's ``memory_analysis()`` and ``cost_analysis()`` for the program's
jitted forward at the configuration's batch, per row where that helps, beside
the benchmark's own count of operations from shapes. Nothing runs: a compile
that passes is not a chip run, and none of this is a measurement. Use it to
reckon whether a new cell fills enough of the chip before spending chip time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def analyse(config: dict, topology: str = "v5e:2x2", topo=None) -> dict:
    """``topo``: a topology already described (a test's fixture), else ``topology`` is described here."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from lib import manifest

    entry = manifest.load_module(os.path.join(BENCH_DIR, "entries", config["entry"] + ".py"))
    reference = manifest.load_module(os.path.join(BENCH_DIR, "reference", config["reference"] + ".py"))
    if topo is None:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, shapes = entry.lowerable(config)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem, cost = compiled.memory_analysis(), compiled.cost_analysis()
    rows = config["batch_size"]
    return {
        "config": config.get("name"), "batch_size": rows, "topology": topology,
        "argument_gb": mem.argument_size_in_bytes / 1e9,
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "output_gb": mem.output_size_in_bytes / 1e9,
        "xla_gflop_per_row": cost["flops"] / rows / 1e9,
        "xla_mb_accessed_per_row": cost["bytes accessed"] / rows / 1e6,
        "shape_count_gflop_per_row": reference.forward_flops_per_row(config) / 1e9,
    }


def main(argv=None) -> int:
    from lib import manifest

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", help="name of a file under benchmark/configs/, without .json")
    ap.add_argument("--batch", type=int, default=None, help="override the configuration's batch_size")
    args = ap.parse_args(argv)
    config = dict(manifest.load_json(os.path.join(BENCH_DIR, "configs", args.config + ".json")),
                  name=args.config)
    if args.batch:
        config["batch_size"] = args.batch
    print(json.dumps(analyse(config), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
