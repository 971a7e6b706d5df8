"""The device seam (daft_tpu/device.py) and chip_smoke.py, on the CPU: where
the compile cache goes, what device each out-of-process worker is told to
use, and that the smoke never passes without a chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_changes):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=600, cwd=ROOT, env=env)


_CACHE_PROBE = """
import json
import jax
from daft_tpu.device import setup_compile_cache
before = jax.config.jax_compilation_cache_dir
returned = setup_compile_cache()
import daft_tpu.ai.flax_provider, daft_tpu.ops.device_eval  # their callers
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized()
print(json.dumps([before, returned, jax.config.jax_compilation_cache_dir]))
"""


def test_compile_cache_env_var_wins(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and no code overwrites it."""
    want = str(tmp_path / "cc")
    proc = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=want,
                JAX_PLATFORMS=None)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout) == [want, want, want]


def test_compile_cache_defaults_into_the_checkout():
    """Unset: a fixed path inside the checkout, built from its location
    alone. A process held to the CPU gets no cache."""
    proc = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=None,
                JAX_PLATFORMS=None)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(ROOT, ".jax_cache")
    assert json.loads(proc.stdout) == [None, want, want]
    proc = _run(["-c", _CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=None,
                JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout) == [None, None, None]


def test_child_device_env_assigns_each_chip_once(monkeypatch):
    from daft_tpu import device

    monkeypatch.setattr(device, "local_chip_count", lambda: 2)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    chips = [device.child_device_env(i) for i in range(3)]
    assert [c.get("TPU_VISIBLE_CHIPS") for c in chips] == ["0", "1", None]
    assert "JAX_PLATFORMS" not in chips[0] and "JAX_PLATFORMS" not in chips[1]
    assert chips[2] == {"JAX_PLATFORMS": "cpu"}  # no chip left
    assert device.child_device_env(None) == {"JAX_PLATFORMS": "cpu"}
    # A driver held to the CPU holds every child there.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.child_device_env(0) == {"JAX_PLATFORMS": "cpu"}


def test_process_worker_child_gets_its_device_before_jax():
    """The decision reaches the child's environment (here: no chips, so CPU)
    and the driver starts it without initialising a backend itself."""
    code = """
import daft_tpu
from daft_tpu import col
from daft_tpu.runners.distributed import DistributedRunner
runner = DistributedRunner(num_workers=1, backend="process")
daft_tpu.get_context().set_runner(runner)
try:
    @daft_tpu.udf.func(return_dtype=daft_tpu.DataType.string())
    def child_env(x):
        import os
        return os.environ.get("JAX_PLATFORMS", "") + "|" + os.environ.get("TPU_VISIBLE_CHIPS", "")
    print(daft_tpu.from_pydict({"a": [1]}).select(child_env(col("a")).alias("e")).to_pydict()["e"])
finally:
    runner.manager.shutdown()
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized()
"""
    proc = _run(["-c", code], JAX_PLATFORMS=None)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "['cpu|']" in proc.stdout


def test_chip_smoke_fails_without_a_chip():
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "pass" not in proc.stdout and '"ok"' not in proc.stdout
    assert "a TPU is required" in proc.stderr


def test_chip_smoke_tiny_cpu_is_a_dry_run():
    proc = _run(["chip_smoke.py", "--tiny-cpu"], JAX_PLATFORMS="cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    # The record is the last line: a dry run prints no verdict after it.
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["chip_smoke"] == "dry" and '"ok"' not in proc.stdout
    assert rec["platform"] == "cpu"
    assert set(rec["phases"]) == {"A_embed_image", "A_jpeg_host_stage_ahead", "B_embed_text", "C_prompt",
                                  "C_prompt_hybrid", "C_prompt_longcat", "C_prompt_deepseek", "C_prompt_olmo", "D_device_chain",
                                  "E_pallas"}
    assert rec["phases"]["C_prompt_olmo"]["delta"] == {"serve.prefill": "chunked", "serve.decode_step": "recurrent"}
    selection = rec["phases"]["C_prompt_deepseek"]  # the tiny decoder's selection bites; its two kernels ran interpreted
    assert selection["paths"] == {"serve.prefill": ["masked", "expanded"], "serve.decode_step": ["masked", "absorbed"]}
    assert 0 < selection["selected_pair_share"] < 1 and 0 < selection["kernels"]["max_abs_diff_vs_masked_loop"] < 3e-2
    assert len(rec["phases"]["A_jpeg_host_stage_ahead"]["ready"]) == 2  # two morsels through the host stage
    kernel = rec["phases"]["C_prompt_longcat"]["mla_kernel"]  # interpreted here; the tiny model itself stays expanded
    assert kernel["shape"][2:] == [2, 517] and 0 < kernel["max_abs_diff_vs_expanded"] < 3e-2
    # The debug mode is never the default and never runs off the CPU.
    proc = _run(["chip_smoke.py", "--tiny-cpu"], JAX_PLATFORMS=None)
    assert proc.returncode != 0 and "dry" not in proc.stdout


def test_bench_fails_without_a_tpu():
    proc = _run(["bench.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "metric" not in proc.stdout
