"""The benchmark's CPU tests import its modules from benchmarks/chip and
their tiny cells from bench_tiny.py."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.join(HERE, "..", "..", "benchmarks", "chip")
sys.path.insert(0, os.path.abspath(CHIP))


@pytest.fixture
def cell_factory():
    from bench_tiny import tiny_cell
    return tiny_cell
