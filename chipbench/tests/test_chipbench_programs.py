"""Each algorithm file against `GREEngine.run` on the CPU, and the whole
run (the look for a chip skipped) coming out not correct when the
program's control takes the engine's place or the timed path is broken
underneath."""
import time

import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.faults import FAULTS

CELLS = ["rmat22.pagerank", "rmat22-sym.cc"]


def run_cell(cell, seed=2**31 + 21):
    return cell.runner.run(cell, seed, 0.0, False, time.perf_counter(),
                           {"hbm_bytes_per_s": 819e9}, lambda *a: None)


@pytest.mark.parametrize("name", CELLS)
def test_engine_agrees_with_the_reference(small_cell, name):
    cell = small_cell(name, scale=10)
    record = run_cell(cell)
    assert record.jobs == 1 and record.failed == 0
    for key, (value, limit) in record.compared.items():
        assert value <= limit, key


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limit(small_cell, monkeypatch, name):
    """The control's answer, put where the window's jobs hand back the
    engine's `vertex_data`, is judged by the runner and the result line."""
    import jax
    cell = small_cell(name, scale=10)
    jobs = cell.runner.jobs

    def control_jobs(prep, seconds):
        outs, ends = jobs(prep, seconds)
        answer = jnp.asarray(cell.program.control(prep.edges, cell.traffic),
                             jnp.float32)
        return [(answer, step) for _, step in outs], ends
    monkeypatch.setattr(cell.runner, "jobs", control_jobs)
    record = run_cell(cell)
    line = harness.result(record, {}, jax.devices())
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] == 1
    assert any(c["value"] > c["limit"] for c in line["compared"].values())


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_run_is_not_correct(small_cell, name, fault):
    """One chip has no exchange between chips, so that fault has no case."""
    import jax
    cell = small_cell(name, scale=8)
    with FAULTS[fault]():
        record = run_cell(cell)
    line = harness.result(record, {}, jax.devices())
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] == 1


def test_pagerank_superstep_bytes(small_cell):
    program = small_cell("rmat22.pagerank").program
    assert program.superstep_bytes(4, 10) == 4 * 10 + 20 * 4 + 4
    assert not hasattr(small_cell("rmat22-sym.cc").program,
                       "superstep_bytes")


def test_references_do_not_import_the_program(root):
    for name in ("pagerank", "cc"):
        text = (root / "chipbench" / "programs" / f"{name}.py").read_text()
        ref = text[text.index("def reference"):text.index("def control")]
        assert "repro" not in ref
