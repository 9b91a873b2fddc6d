"""Test config: force CPU backend with 8 virtual devices so sharding /
multi-chip tests run hermetically (SURVEY §4: the fake-device strategy —
reference analog test/custom_runtime/test_custom_cpu_plugin.py:23)."""
import os

# set before jax is imported (jax reads it at import) and inherited by
# every process a test spawns: the suite never touches a chip
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow')")


@pytest.hookimpl(trylast=True)
def pytest_runtest_logreport(report):
    # CI wraps the suite in a hard timeout; with stdout block-buffered
    # (pipe/file), a killed run silently drops up to 8 KB of progress
    # output. Flush after every test so the log reflects actual progress.
    import sys

    sys.stdout.flush()
    sys.stderr.flush()


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(1234)
    np.random.seed(1234)
    yield
