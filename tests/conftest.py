"""Test harness: run JAX on a virtual 8-device CPU mesh (the analogue of the
reference's Spark `local[N]` testing mode, SURVEY.md §4). Must run before any
jax import.

The suite FORCES CPU: a chip belongs to one process at a time, and many
tests spawn worker processes. Correctness is platform-independent (matmul
precision is pinned to 'highest' at package import); the chip is validated
by chip_smoke.py. Set BST_TEST_TPU=1 to opt in to the real chip.
"""

import os

# The package keeps every compile in a persistent cache (its __init__). A
# cold XLA:CPU cache re-exports each executable on write and stretched this
# suite from 554 s to 724 s, most of its 870 s limit; the tests that are
# about the cache (tests/test_chip_smoke.py) switch it back on.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

if not os.environ.get("BST_TEST_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import pytest  # noqa: E402


@pytest.fixture()
def synthetic_project(tmp_path):
    from bigstitcher_spark_tpu.utils.testdata import make_synthetic_project

    return make_synthetic_project(str(tmp_path / "proj"))
