"""The four-chip Agent-Graph cell on the CPU, and the readers of its
per-layer metrics on a trace recorded on four TPU v5e chips.

The cell's runner (`runners/agent.py`: set-up, window and check) runs end
to end on four forced host devices in a subprocess (the device count must
be set before JAX starts), at R-MAT scale 10, and is judged against the
program file's plain reference.  A flush that returns the identity drops
every contribution that crosses a chip, and the control's answer takes the
engine's place: both must come out not correct.

The trace (`chipbench/testdata/rmat22-hdrf4.pagerank.scale12-scoped.*`)
is one window at scale 12 (`python3 chipbench/runners/agent.py --scale 12
--seed 1`); its JSON holds what the run read and, as the other scoped
traces' do, the digest of the ops' `tf_op` strings as tensorflow's XSpace
reader read them.
"""
import gzip
import hashlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness, scopes, trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
RECORDED = "rmat22-hdrf4.pagerank.scale12-scoped"
CELL = "rmat22-hdrf4.pagerank"
EXCHANGE = ("gre.exchange.refresh", "gre.exchange.flush")

SCRIPT = r"""
import dataclasses, json, sys, time
from pathlib import Path
from unittest import mock

ROOT = Path(sys.argv[1])
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
import jax
import jax.numpy as jnp
import numpy as np
from chipbench import harness

bench = harness.load_benchmark(ROOT)
cell = harness.load_cell(ROOT, ROOT / "chipbench", bench, "__CELL__")
cell = dataclasses.replace(cell, config={**cell.config, "scale": 10})
seed = 2**31 + 21


def run():
    record = cell.runner.run(cell, seed, 0.0, False, time.perf_counter(),
                             {}, lambda *a: None)
    line = harness.result(record, {}, jax.devices()[:4])
    entries = harness.metrics_for(bench, cell.name, trace=True)
    metrics = harness.read_metrics(ROOT / "chipbench", entries, record, cell)
    return {"line": line, "metrics": metrics, "spans": record.spans,
            "counters": record.counters, "supersteps": record.supersteps}


def identity_flush(topo, combined, axes, monoid, send_slot=None,
                   recv_master=None, num_segments=None):
    return jnp.full(((num_segments or topo.part.num_slots),)
                    + combined.shape[1:], monoid.identity, combined.dtype)


jobs = cell.runner.jobs


def control_jobs(prep, seconds):
    outs, ends = jobs(prep, seconds)
    ag = prep.graph
    answer = np.zeros(ag.k * ag.cap, np.float32)
    answer[ag.old2new] = cell.program.control(prep.edges, cell.traffic)
    stacked = jnp.asarray(answer.reshape(ag.k, ag.cap))
    return [(stacked, step) for _, step in outs], ends


out = {"devices": len(jax.devices()), "run": run()}
with mock.patch("repro.core.exchange.flush_combiners", identity_flush):
    out["dropped_flush"] = run()
with mock.patch.object(cell.runner, "jobs", control_jobs):
    out["control"] = run()
print("AGENT_RUNS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(root, tmp_path_factory):
    script = tmp_path_factory.mktemp("agent") / "agent_runs.py"
    script.write_text(SCRIPT.replace("__CELL__", CELL))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, str(script), str(root)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("AGENT_RUNS "))
    return json.loads(line[len("AGENT_RUNS "):])


def test_four_shards_agree_with_the_reference(runs):
    assert runs["devices"] == 4
    line = runs["run"]["line"]
    assert line["correct"] is True
    assert line["attempted"] == 1 and line["failed"] == 0
    assert runs["run"]["supersteps"] == [30]
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("case", ["dropped_flush", "control"])
def test_dropped_flush_and_control_are_not_correct(runs, case):
    line = runs[case]["line"]
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] == 1
    assert line["compared"]["max_rel_err"]["value"] > \
        line["compared"]["max_rel_err"]["limit"]


def test_setup_spans_and_counters_ride_on_the_record(runs):
    got = runs["run"]
    assert set(got["spans"]) == {"gre.ingress.hdrf", "gre.ingress.agent_graph",
                                 "gre.ingress.topology"}
    counters = got["counters"]
    assert counters["masters"] == 1 << 10
    assert counters["replication_factor"] == pytest.approx(
        (counters["masters"] + counters["scatter_agents"]
         + counters["combiner_agents"]) / counters["masters"])
    metrics = got["metrics"]
    assert metrics["partition_s"]["value"] == pytest.approx(
        got["spans"]["gre.ingress.hdrf"]
        + got["spans"]["gre.ingress.agent_graph"])
    assert metrics["replication_factor"]["value"] == pytest.approx(
        counters["replication_factor"])
    # no trace on the CPU: the exchange scopes have nothing to read
    assert "refresh_ms" not in metrics and "flush_ms" not in metrics


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("agent-trace") / "window.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{RECORDED}.xplane.pb.gz").read_bytes()))
    return path, json.loads((DATA / f"{RECORDED}.json").read_text())


def record_of(facts, summary=None, scope_s=None):
    runner = harness.plugin(DATA.parent, "runners", "agent")
    return runner.AgentRunRecord(
        setup_s=0.0, ingress_s=0.0, compile_s=0.0, window_s=0.0,
        supersteps=facts["supersteps"], peak_bytes=None, num_vertices=0,
        num_edges=0, compared={}, failed=0, peak={}, trace=summary,
        spans=facts["spans"], counters=facts["counters"],
        scopes=scope_s or {})


def metric(name):
    return harness.plugin(DATA.parent, "metrics", name)


def test_decoder_reads_each_chips_tf_op(recorded):
    path, facts = recorded
    names = scopes.op_names(path)
    assert sorted(names) == [0, 1, 2, 3]
    assert [len(names[c]) for c in range(4)] == facts["xla_ops"]
    joined = "\n".join(t or "" for c in range(4) for t in names[c])
    assert hashlib.sha256(joined.encode()).hexdigest() == \
        facts["tf_op_sha256"]


def test_exchange_scopes_read_from_the_recorded_trace(recorded):
    """The runner's reduction of the recorded window gives the scopes it
    recorded, refresh_ms and flush_ms read them, and the scopes' self
    times, `unscoped` included, add up to `superstep_ms`."""
    path, facts = recorded
    runner = harness.plugin(DATA.parent, "runners", "agent")
    summary, scope_s = runner.trace_readings(path, [0, 1, 2, 3])
    assert summary.busy_s == pytest.approx(facts["busy_s"], rel=1e-9)
    assert scope_s == pytest.approx(facts["scopes"], rel=1e-9)
    assert set(scope_s) == set(scopes.SCOPES) | set(EXCHANGE) | {
        scopes.UNSCOPED}
    record = record_of(facts, summary, scope_s)
    steps = sum(facts["supersteps"])
    refresh = metric("refresh_ms").read(record, None)
    flush = metric("flush_ms").read(record, None)
    assert refresh == pytest.approx(
        scope_s["gre.exchange.refresh"] * 1e3 / steps)
    assert flush == pytest.approx(scope_s["gre.exchange.flush"] * 1e3 / steps)
    assert refresh > 0 and flush > 0
    assert sum(scope_s.values()) * 1e3 / steps == pytest.approx(
        metric("superstep_ms").read(record, None), rel=1e-6)


# unscoped inside the run: the loop's control (the mesh-global keep-going
# predicate and its pmax, the while and cond shells), the shard's entry and
# exit, and what the compiler made (no name stack, or the shard_map's own
# or an operand's name), as `tests/test_tpu_compile.py` requires
SHARD_CONTROL = re.compile(
    r"jit\(run_shard\)/shard_map((/(while|body|cond|branch_\d+_fun))*"
    r"/(and|lt|gt|reduce_or|convert_element_type|pmax|cond|while)"
    r"|/squeeze|/broadcast_in_dim|/broadcast\.\d+)")


def run_intervals(path, chip):
    """(start, end) ns of each execution of the shard program on `chip`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(Path(path).read_bytes())
    plane = next(p for p in data.planes if p.name == f"/device:TPU:{chip}")
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    return [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events
            if e.name.startswith("jit_run_shard(")]


def test_unscoped_ops_of_the_run_are_loop_control(recorded):
    path, _ = recorded
    ops = scopes.scoped_ops(path)
    for chip in range(4):
        runs = run_intervals(path, chip)
        assert runs
        for tf_op, start, end in ops[chip]:
            if not any(lo <= start and end <= hi for lo, hi in runs):
                continue
            if scopes.scope_of(tf_op) != scopes.UNSCOPED or not tf_op:
                continue
            name = tf_op.rstrip(":")
            assert (not name.startswith("jit(run_shard)/shard_map/")
                    or SHARD_CONTROL.fullmatch(name)), name


def test_all_to_alls_run_under_the_exchange_scopes(recorded):
    path, _ = recorded
    _, ops = trace.events(path)
    names = scopes.op_names(path)
    for chip in range(4):
        collectives = [tf_op for (hlo, _, _), tf_op in zip(ops[chip],
                                                           names[chip])
                       if "all-to-all" in hlo]
        assert collectives
        assert {scopes.scope_of(t) for t in collectives} <= set(EXCHANGE)


def test_partition_and_replication_read_from_the_recording(recorded):
    _, facts = recorded
    record = record_of(facts)
    assert metric("partition_s").read(record, None) == pytest.approx(
        facts["spans"]["gre.ingress.hdrf"]
        + facts["spans"]["gre.ingress.agent_graph"])
    assert metric("replication_factor").read(record, None) == \
        facts["counters"]["replication_factor"]


@pytest.mark.parametrize("name", ["refresh_ms", "flush_ms", "partition_s",
                                  "replication_factor"])
def test_readers_return_none_without_their_readings(name):
    """A program that has no exchange scopes, ingress spans or counters
    (the one-chip runner's record, or an older program under this
    runner) leaves the metric out."""
    plain = harness.RunRecord(
        setup_s=1.0, ingress_s=1.0, compile_s=1.0, window_s=1.0,
        supersteps=[30], peak_bytes=None, num_vertices=4, num_edges=4,
        compared={}, failed=0, peak={})
    assert metric(name).read(plain, None) is None
    empty = record_of({"supersteps": [30], "spans": {}, "counters": {}})
    assert metric(name).read(empty, None) is None
