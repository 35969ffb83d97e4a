import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture(scope="session")
def root() -> Path:
    return ROOT


@pytest.fixture(scope="session")
def bench(root):
    from chipbench import harness
    return harness.load_benchmark(root)


@pytest.fixture
def small_cell(root, bench):
    """A cell of BENCHMARK.json at a graph scale the CPU runs in seconds."""
    import dataclasses

    from chipbench import harness

    def make(name: str, scale: int = 8):
        cell = harness.load_cell(root, root / "chipbench", bench, name)
        return dataclasses.replace(cell, config={**cell.config,
                                                 "scale": scale})
    return make
