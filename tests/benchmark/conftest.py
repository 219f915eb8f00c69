"""Fixtures of the benchmark's own tests."""
import os

import pytest

from bench_testlib import make_root


@pytest.fixture
def bench_root(tmp_path):
    return make_root(str(tmp_path))


@pytest.fixture
def run_tiny(bench_root):
    """run_cell on the temporary root, on the CPU device the tests have
    (the look for a chip is the one thing skipped)."""
    import jax
    from benchmark import harness

    bench_dir = os.path.join(bench_root, "benchmark")

    def run(workload, seed=3, seconds=1.0, trace=False, control=None):
        return harness.run_cell(
            harness.load_cell(bench_root, bench_dir, workload), seed,
            seconds, trace, root=bench_root, bench_dir=bench_dir,
            devices=jax.devices()[:1], control=control)
    return run
