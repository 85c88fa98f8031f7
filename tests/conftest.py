"""Test harness.

Reference test strategy parity (SURVEY §5): tests run on the CPU backend as
the de-facto reference implementation; distributed logic is exercised on a
virtual multi-device mesh (the analog of DL4J's Spark local[N] + Aeron
loopback tests). We force an 8-device CPU platform BEFORE jax import.
"""

import os

# Unit tests run on the CPU reference backend with Pallas kernels in
# interpret mode; the chip is exercised by chip_smoke.py, and what its
# compiler accepts by tests/test_tpu_compile.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tests dispatch from the checked-in tuning tables only: an inherited
# DL4J_TPU_TUNING_DIR would overlay a measured table and move thresholds.
os.environ.pop("DL4J_TPU_TUNING_DIR", None)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(12345)


@pytest.fixture
def jax_key():
    import jax

    return jax.random.key(0)


def assert_allclose(a, b, rtol=1e-5, atol=1e-6):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)
