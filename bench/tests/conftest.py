"""CPU tests of the benchmark's own code (run: python -m pytest bench/tests)."""

import os
import sys
import time
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))


@pytest.fixture(scope="session")
def tree(tmp_path_factory):
    """A scratch benchmark tree with the tiny cells (see ``tiny.py``)."""
    import tiny

    return tiny.make_tree(tmp_path_factory.mktemp("bench_tree"))


@pytest.fixture
def bench_at(tree, monkeypatch):
    """The harness pointed at the scratch tree; runs cells on the CPU."""
    import harness

    monkeypatch.setattr(harness, "ROOT", tree)
    monkeypatch.setattr(harness, "BENCH", tree / "bench")

    def run(cell, seed=7, seconds=4.0, hooks=None):
        return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                                hooks=hooks, need_chip=False)

    return run
