"""Shared fixtures.  NOTE: no XLA_FLAGS device-count override here —
smoke tests and benches must see 1 CPU device; only the dry-run (and the
subprocess-based multi-device tests, which set the env var on their own
child processes) uses 512/8 placeholder devices."""

import os
import sys

import pytest

# the tests never write JAX's persistent compilation cache (the CLIs turn
# it on through repro.utils.device.enable_compile_cache); subprocesses
# inherit this
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, os.path.abspath(SRC))


@pytest.fixture(scope="session")
def small_space():
    from repro.core import GemmConfigSpace

    # 64^3 with d=(4,2,4): small enough to brute-force (size = C(9,3)*7*C(9,3))
    return GemmConfigSpace(64, 64, 64)


@pytest.fixture(scope="session")
def paper_space():
    from repro.core import GemmConfigSpace

    return GemmConfigSpace(1024, 1024, 1024)
