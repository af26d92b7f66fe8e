"""Test configuration: force an 8-device virtual CPU mesh.

The program never chooses a platform in code (rlo_tpu/utils/device.py);
the test harness does, here and only here: this file holds the one
``jax_platforms`` update in the tree. JAX backends initialize lazily, so
setting it at conftest import — before the first jax.devices()/jit call —
is early enough. A subprocess a test starts gets the same two settings
through its environment (XLA_FLAGS is inherited from here; the test
passes JAX_PLATFORMS=cpu).
"""

import os

N_VIRTUAL_DEVICES = 8

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_VIRTUAL_DEVICES}"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running sweeps excluded from tier-1 "
        "(run explicitly with `pytest -m slow`)")


def pytest_collection_modifyitems(config, items):
    """Deselect `slow` tests unless a -m expression names them, so the
    tier-1 run (`pytest tests/`) never pays for the 500-run sweeps."""
    import pytest

    if "slow" in (config.getoption("-m") or ""):
        return
    skip = pytest.mark.skip(reason="slow sweep: opt in with -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
