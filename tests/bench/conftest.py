"""Fixtures for the benchmark's tests: the cells' own files, cut to sizes
the CPU runs in seconds (Pallas kernels in interpret mode)."""
import sys

import pytest

import bench_helpers

if bench_helpers.ROOT not in sys.path:
    sys.path.insert(0, bench_helpers.ROOT)


@pytest.fixture
def tiny_serve():
    return bench_helpers.tiny_serve()


@pytest.fixture
def tiny_sweep():
    return bench_helpers.tiny_sweep()
