"""The benchmark's own tests run on the CPU, outside ``tests/`` (tier-1).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH_DIR), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def tiny():
    """(cell, configuration) of the CPU rehearsal."""
    import run as harness
    return harness.load_cell(rehearsal="tiny")
