"""Test configuration.

Mirrors the reference's env-switched runner parametrisation
(tests/conftest.py:34-41 in the reference): DAFT_RUNNER=native|distributed
runs the whole behavioral suite on either engine. Tests run on a virtual
8-device CPU mesh so multi-chip sharding logic is exercised without TPU
hardware (SURVEY.md §4 fake-device-mesh pattern). CPU is chosen through
JAX_PLATFORMS, set before jax is imported; worker processes inherit it.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (tests/test_faults.py); "
        "fast seeded specs run in tier-1 via `pytest -m chaos`",
    )
    config.addinivalue_line("markers", "slow: excluded from the tier-1 run")


def pytest_collection_modifyitems(config, items):
    # Enforce the `slow` marker's contract instead of trusting every
    # invocation to pass -m 'not slow': a bare `pytest tests/` skips slow
    # tests; any explicit -m expression (e.g. `-m slow`, `-m 'not chaos'`)
    # takes full control.
    if config.getoption("-m") or config.getoption("-k"):
        return
    # Explicit node-id selection is the most direct opt-in there is.
    explicit = [str(a) for a in config.invocation_params.args if "::" in str(a)]

    def selected_directly(item):
        return any(item.nodeid == a or
                   item.nodeid.endswith(a[a.index("::"):]) for a in explicit)

    skip_slow = pytest.mark.skip(reason="slow: select explicitly with -m slow")
    for item in items:
        if "slow" in item.keywords and not selected_directly(item):
            item.add_marker(skip_slow)


@pytest.fixture(scope="session")
def runner_name():
    return os.environ.get("DAFT_RUNNER", "native")


@pytest.fixture(autouse=True, scope="session")
def _configure_runner(runner_name):
    os.environ["DAFT_RUNNER"] = runner_name
    yield


@pytest.fixture
def make_df():
    """Build a DataFrame from a pydict (parametrisation point for future
    scan-based fixtures, reference tests/conftest.py:70-80)."""
    import daft_tpu

    def _make(data):
        return daft_tpu.from_pydict(data)

    return _make
