"""Test harness: force an 8-device CPU "cluster".

The reference validated multi-node behavior on a real Slurm cluster; the
TPU-native analog is XLA's fake host devices
(``--xla_force_host_platform_device_count=8``).  Nothing imports JAX
before pytest loads this file, and XLA reads both variables when the
backend first starts, so setting them here — before the first
``import jax`` — is enough; child processes the tests spawn inherit them.
"""

import os
import sys

_N_DEVICES = "8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import scrubbed_env  # noqa: E402

os.environ.update(scrubbed_env(int(_N_DEVICES)))

import jax  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compile cache, should this suite ever run on an
# accelerator backend.  On the CPU harness this is a deliberate no-op:
# XLA:CPU executable serialization in the pinned jaxlib corrupts the
# heap (glibc "corrupted double-linked list" aborts mid-suite), so the
# helper only engages off-CPU unless JAX_COMPILATION_CACHE_DIR is set.
from skycomputing_tpu.utils import enable_persistent_compilation_cache  # noqa: E402

enable_persistent_compilation_cache()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == int(_N_DEVICES), (
        f"expected {_N_DEVICES} fake CPU devices, got {devs}"
    )
    return devs
