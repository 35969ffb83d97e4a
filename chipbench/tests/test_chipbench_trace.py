"""The trace reduction on small traces recorded on a TPU v5e (one traced
window of each cell at scale 12, `chipbench/calibrate.py trace`)."""
import gzip
import json
import math
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
TRACES = ["rmat22.pagerank.scale12", "rmat22-sym.cc.scale12"]


@pytest.fixture(scope="module", params=TRACES)
def recorded(request, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "window.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{request.param}.xplane.pb.gz").read_bytes()))
    facts = json.loads((DATA / f"{request.param}.json").read_text())
    return path, facts


def test_window_and_busy_as_read_on_the_chip(recorded):
    path, facts = recorded
    summary = trace.reduce(path, [0])
    assert summary.window_s == pytest.approx(facts["window_s"], abs=1e-9)
    assert summary.busy_s == pytest.approx(facts["busy_s"], abs=1e-9)
    # the window span closes just after the host saw the last job end
    assert 0 <= summary.window_s - facts["host_ends"][-1] < 1e-3
    assert 0 < summary.busy_s < summary.window_s


def test_busy_union_matches_a_microsecond_timeline(recorded):
    path, _ = recorded
    spans, ops = trace.events(path)
    (lo, hi), = [(s, e) for name, s, e in spans if name == trace.WINDOW]
    covered = np.zeros(int(math.ceil((hi - lo) / 1e3)) + 1, bool)
    inside = 0
    for _, s, e in ops[0]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            covered[int((s - lo) // 1e3):int(math.ceil((e - lo) / 1e3))] = True
            inside += 1
    busy_s = trace.reduce(path, [0]).busy_s
    # each interval's two ends round outward by under a microsecond
    assert abs(covered.sum() * 1e-6 - busy_s) <= inside * 2e-6
    assert inside > 10


def test_ops_by_self_time_add_up_to_at_most_busy(recorded):
    path, _ = recorded
    summary = trace.reduce(path, [0])
    seconds = [sec for _, sec in summary.device_ops]
    assert 0 < len(seconds) <= trace.TOP
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) <= summary.busy_s * (1 + 1e-9)
    # a loop or a conditional holds the ops of its body: its self time
    # is small, so it does not lead the list
    assert summary.device_ops[0][0].split()[1] not in ("while",
                                                       "conditional")
    assert all(len(name) <= 120 for name, _ in summary.device_ops)


def test_idle_gaps_named_by_host_spans(recorded):
    path, _ = recorded
    summary = trace.reduce(path, [0])
    gaps = [sec for _, sec in summary.idle_gaps]
    assert 0 < len(gaps) <= trace.TOP
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= summary.window_s - summary.busy_s + 1e-9
    assert all(name.startswith(trace.SPAN_PREFIX)
               or name == "outside chipbench spans"
               for name, _ in summary.idle_gaps)


def test_superstep_busy_time(recorded, small_cell):
    """superstep_ms reads the traced busy time over the jobs' supersteps."""
    from chipbench import harness
    path, facts = recorded
    summary = trace.reduce(path, [0])
    record = harness.RunRecord(
        setup_s=1.0, ingress_s=1.0, compile_s=1.0,
        window_s=facts["host_ends"][-1], supersteps=facts["supersteps"],
        peak_bytes=None, num_vertices=4096, num_edges=10, compared={},
        failed=0, peak={"hbm_bytes_per_s": 819e9}, trace=summary)
    cell = small_cell("rmat22.pagerank")
    reader = harness.plugin(Path(trace.__file__).parent, "metrics",
                            "superstep_ms")
    assert reader.read(record, cell) == pytest.approx(
        summary.busy_s * 1e3 / sum(facts["supersteps"]))
    idle = harness.plugin(Path(trace.__file__).parent, "metrics",
                          "device_idle_share").read(record, cell)
    assert idle == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))


def test_reduce_refuses_a_chip_without_ops(recorded):
    path, _ = recorded
    with pytest.raises(ValueError, match="no device op"):
        trace.reduce(path, [7])


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(5, 7), (1, 3), (2, 4)], [[2, 4], [5, 7]]),
    ([(0, 20)], [[2, 10]]),
    ([(1, 3), (3, 6), (11, 15)], [[2, 6]]),
])
def test_union_merges_and_clips(intervals, merged):
    assert trace.union(intervals, 2, 10) == merged


def test_self_times_subtract_nested_ops():
    ops = [("%while.1 = () while()", 0, 100),
           ("%fusion.2 = f32[8]{0} fusion(), kind=kLoop", 10, 40),
           ("%fusion.3 = f32[8]{0} fusion(), kind=kCustom", 50, 90),
           ("%add.4 = f32[8]{0} add()", 60, 70)]
    assert trace.self_times(ops, 0, 100) == [
        ["while.1 while -> ()", 30], ["fusion.2 fusion kLoop -> f32[8]", 30],
        ["fusion.3 fusion kCustom -> f32[8]", 30], ["add.4 add -> f32[8]", 10]]
