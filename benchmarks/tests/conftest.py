"""Run by hand, on the CPU: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q -p no:cacheprovider``. Not part of tier-1."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def kernels_as_on_the_chip():
    """The program's Pallas kernels, interpreted, as the chip would take
    them: the flash kernel draws the attention masks that the reference
    follows (the generic path draws others)."""
    from deeplearning4j_tpu.environment import environment

    env = environment()
    was = env.helper_mode
    env.helper_mode = "pallas"
    yield
    env.helper_mode = was
