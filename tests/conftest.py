"""Test config: an 8-device virtual CPU mesh, set before JAX starts.

The suite runs on the CPU: multi-chip sharding paths are validated on
the virtual host-platform mesh, and Pallas kernels run in interpret
mode. What only the chip can show is compiled for a described v5e in
tests/test_chip_compile.py and run on the chip by chip_smoke.py.
"""

import os

# forced, not setdefault: a shell that exports JAX_PLATFORMS=tpu would
# put the suite on one chip and break every 8-device mesh test
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import random

import numpy as np
import pytest


def pytest_configure(config):
    import jax

    # tests drive the jitted kernels directly with packed int64 ids
    jax.config.update("jax_enable_x64", True)


@pytest.fixture(autouse=True)
def _seed_rngs():
    random.seed(0)
    np.random.seed(0)
