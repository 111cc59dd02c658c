"""CPU tests of the benchmark's own files: seconds, no chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from distributed_dot_product_tpu._compat import ensure_cpu_devices  # noqa: E402

ensure_cpu_devices(4)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'tiny')


@pytest.fixture
def tiny_root():
    return TINY
