"""Test fixture: 8 virtual CPU devices.

The reference's only multi-node test story is a confidential, absent RTL
testbench simulating a 3-FPGA ring (readme.pdf §3.2, hw/README:1).  We make
multi-device testing first-class instead: every test runs on an 8-device
virtual CPU mesh so ring collectives, shardings and the full train step are
exercised without hardware.  The chip is reached only through the chip tool
(`python chip_smoke.py`), never from here.

jax reads both variables when it is first imported, which no plugin does
before this file loads: setting them here is enough.
"""

import os
import re
import sys

if "jax" in sys.modules:
    raise RuntimeError("jax was imported before tests/conftest.py could ask "
                       "for the 8-device CPU mesh")

# replace (not merely append) any inherited device-count flag: the suite is
# written against exactly 8 virtual devices
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    _flags.strip() + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
