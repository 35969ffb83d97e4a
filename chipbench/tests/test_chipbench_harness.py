"""The benchmark's data: BENCHMARK.json's rules, discovery of its files by
name, the refusal of a machine without a TPU, and a new cell made of new
files alone."""
import copy
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
from types import SimpleNamespace

import pytest

from chipbench import harness


def test_benchmark_json_cells_and_metrics_load(root, bench):
    for w in bench["workloads"]:
        cell = harness.load_cell(root, root / "chipbench", bench, w["name"])
        assert cell.limits, w["name"]
        assert callable(cell.program.reference)
        assert callable(cell.program.control)
        assert callable(cell.runner.run)
    for m in bench["end_to_end"] + bench["per_layer"]:
        reader = harness.plugin(root / "chipbench", "metrics", m["name"])
        assert callable(reader.read), m["name"]


def test_per_layer_metrics_follow_their_cell_lists(bench):
    pagerank = {m["name"] for m in harness.metrics_for(
        bench, "rmat22.pagerank", trace=True)}
    cc = {m["name"] for m in harness.metrics_for(
        bench, "rmat22-sym.cc", trace=True)}
    assert "superstep_roofline" in pagerank - cc
    assert "supersteps" in cc - pagerank
    assert {"job_s", "peak_hbm_gb", "setup_s"} == {
        m["name"] for m in harness.metrics_for(bench, "rmat22-sym.cc",
                                               trace=False)}


@pytest.mark.parametrize("section,key,bad", [
    ("workloads", "name", "rmat22 pagerank"),
    ("workloads", "name", "-rmat22"),
    ("workloads", "name", "rmat22/pagerank"),
    ("workloads", "traffic", "page rank"),
    ("end_to_end", "name", "job" * 22),
    ("end_to_end", "unit", "jobs per second"),
    ("end_to_end", "unit", "µs"),
    ("end_to_end", "better", "faster"),
    ("end_to_end", "source", "program_counter"),
    ("per_layer", "unit", ""),
    ("per_layer", "moves", "not_a_metric"),
    ("per_layer", "layer", "two\nlines"),
])
def test_bad_names_and_units_are_refused(bench, section, key, bad):
    broken = copy.deepcopy(bench)
    broken[section][0][key] = bad
    with pytest.raises(harness.BenchmarkError):
        harness.validate(broken)


def test_unknown_keys_and_files_are_refused(root, bench):
    broken = copy.deepcopy(bench)
    broken["end_to_end"][0]["why"] = "a metric carries no why"
    with pytest.raises(harness.BenchmarkError):
        harness.validate(broken)
    with pytest.raises(harness.BenchmarkError):
        harness.plugin(root / "chipbench", "metrics", "no_such_metric")
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell(root, root / "chipbench", bench, "no.such.cell")


class FakeDevice(SimpleNamespace):
    pass


@pytest.mark.parametrize("devices,chips,ok", [
    ([FakeDevice(platform="cpu", device_kind="cpu")], 1, False),
    ([FakeDevice(platform="tpu", device_kind="TPU v9 imaginary")], 1, False),
    ([FakeDevice(platform="tpu", device_kind="TPU v5 lite")], 4, False),
    ([FakeDevice(platform="tpu", device_kind="TPU v5 lite")] * 4, 4, True),
])
def test_accelerator_needs_tpu_chips_and_a_peaks_row(root, monkeypatch,
                                                     devices, chips, ok):
    import jax
    peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
    monkeypatch.setattr(jax, "devices", lambda: devices)
    if ok:
        found, peak = harness.accelerator(chips, peaks)
        assert peak["hbm_bytes_per_s"] == 819e9 and len(found) == chips
    else:
        with pytest.raises(harness.NoAccelerator):
            harness.accelerator(chips, peaks)


def run_command(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "rmat22.pagerank",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu(root):
    proc = run_command(root)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(root, tmp_path):
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_command(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


NEW_PROGRAM = '''
"""In-degree by one superstep of sum-combine, a throwaway algorithm."""
import numpy as np


def build(traffic, edges):
    from repro.core.algorithms import degree_program
    return degree_program(), None


def reference(edges, traffic):
    return np.bincount(edges.dst, minlength=edges.num_vertices).astype(float)


def control(edges, traffic):
    return reference(edges, traffic) + 1.0


def compare(got, want):
    return {"degree_wrong": int(np.count_nonzero(got != want))}
'''

NEW_RUNNER = '''
"""A throwaway runner: the one-chip runner under another name."""
from pathlib import Path

from chipbench import harness

SINGLE = harness.plugin(Path(__file__).resolve().parents[1], "runners",
                        "single")


def run(*args, **kwargs):
    return SINGLE.run(*args, **kwargs)
'''

NEW_METRIC = '''
"""Edges per vertex of the cell's graph, a throwaway per-layer metric."""


def read(record, cell):
    return record.num_edges / record.num_vertices
'''


def test_a_new_cell_is_new_files_and_entries_only(root, bench, tmp_path):
    """A graph, a job, an algorithm, a runner and a per-layer metric added
    as files: the harness runs the new cell without an edit to any file
    that was there."""
    import jax
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(root / "chipbench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    config = json.loads((bench_dir / "configs" / "rmat22.json").read_text())
    config.update(scale=7, guarantees={"degree": {"degree_wrong": 0}})
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(config))
    (bench_dir / "traffic" / "indegree.json").write_text(json.dumps(
        {"algorithm": "degree", "runner": "single_again", "max_steps": 1}))
    (bench_dir / "programs" / "degree.py").write_text(
        textwrap.dedent(NEW_PROGRAM))
    (bench_dir / "runners" / "single_again.py").write_text(
        textwrap.dedent(NEW_RUNNER))
    (bench_dir / "metrics" / "edges_per_vertex.py").write_text(
        textwrap.dedent(NEW_METRIC))
    new = copy.deepcopy(bench)
    new["configs"].append({"name": "tiny", "source": "a test",
                           "file": "chipbench/configs/tiny.json",
                           "reduced": ["scale"], "why": "a test"})
    new["workloads"].append({"name": "tiny.indegree", "config": "tiny",
                             "traffic": "indegree", "chips": 1,
                             "why": "a test"})
    new["per_layer"].append({"name": "edges_per_vertex", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "host ingress", "moves": "setup_s",
                             "workloads": ["tiny.indegree"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    bench2 = harness.load_benchmark(tmp_path)
    cell = harness.load_cell(tmp_path, bench_dir, bench2, "tiny.indegree")
    record = cell.runner.run(cell, 11, 0.0, False, time.perf_counter(),
                             {"hbm_bytes_per_s": 819e9}, lambda *a: None)
    entries = harness.metrics_for(bench2, cell.name, trace=True)
    metrics = harness.read_metrics(bench_dir, entries, record, cell)
    line = harness.result(record, metrics, jax.devices())
    assert line["correct"] and line["attempted"] == 1
    assert metrics["edges_per_vertex"]["value"] == pytest.approx(
        record.num_edges / 128)
    assert list(line)[-1] == "compared"
    after = {p: p.read_bytes() for p in before}
    assert after == before
