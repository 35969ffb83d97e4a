"""The XSpace decoder and the per-scope reduction, on traces recorded on a
TPU v5e with the engine's named scopes in place (one traced window of each
cell at scale 12, `chipbench/calibrate.py trace`).

Each trace's JSON holds, beside what the recording read, the digest of
its ops' `tf_op` strings as tensorflow's own XSpace reader
(`tsl/profiler/protobuf/xplane_pb2`) read them when the trace was saved:
the decoder here must read the same strings, op by op.
"""
import gzip
import hashlib
import json
from pathlib import Path

import pytest

from chipbench import scopes, trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
SCOPED = ["rmat22.pagerank.scale12-scoped", "rmat22-sym.cc.scale12-scoped"]
UNSCOPED = ["rmat22.pagerank.scale12", "rmat22-sym.cc.scale12"]


def unpack(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "window.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{name}.xplane.pb.gz").read_bytes()))
    return path


@pytest.fixture(scope="module", params=SCOPED)
def scoped(request, tmp_path_factory):
    facts = json.loads((DATA / f"{request.param}.json").read_text())
    return unpack(tmp_path_factory, request.param), facts


def digest(tf_ops) -> str:
    return hashlib.sha256("\n".join(t or "" for t in tf_ops).encode()
                          ).hexdigest()


def test_decoder_reads_each_ops_tf_op(scoped):
    path, facts = scoped
    names = scopes.op_names(path)
    assert sorted(names) == [0]
    assert len(names[0]) == facts["xla_ops"]
    assert digest(names[0]) == facts["tf_op_sha256"]
    run = [t for t in names[0] if t and t.startswith("jit(run)/")]
    assert {scopes.scope_of(t) for t in run} >= set(scopes.SCOPES)


def test_ops_pair_with_their_metadata_in_line_order(scoped):
    path, _ = scoped
    _, ops = trace.events(path)
    paired = scopes.scoped_ops(path)
    assert [(s, e) for _, s, e in paired[0]] == [(s, e) for _, s, e in ops[0]]
    # an op's HLO opcode and the last primitive of its name stack agree
    # where both name a gather or a scatter of the engine
    for (hlo, _, _), (tf_op, _, _) in zip(ops[0], paired[0]):
        if tf_op and tf_op.startswith("jit(run)/") and " scatter(" in hlo:
            assert scopes.scope_of(tf_op) == "gre.combine", (hlo, tf_op)


def run_intervals(path):
    """(start, end) ns of each execution of the `jit(run)` program."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(Path(path).read_bytes())
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    return [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events
            if e.name.startswith("jit_run(")]


def test_scopes_add_up_to_the_runs_device_time(scoped):
    """Within each execution of `GREEngine.run`, the three scopes hold all
    but the loop's control and the ops the compiler made: at least 99% of
    the run's device self time."""
    path, _ = scoped
    runs = run_intervals(path)
    assert runs
    total, by_scope = 0.0, {}
    for lo, hi in runs:
        times = scopes.scope_times(path, [0], lo, hi)
        total += sum(times.values())
        for scope, sec in times.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + sec
    assert set(by_scope) <= set(scopes.SCOPES) | {scopes.UNSCOPED}
    scoped_s = sum(by_scope.get(s, 0.0) for s in scopes.SCOPES)
    assert scoped_s >= 0.99 * total
    assert all(by_scope.get(s, 0.0) > 0 for s in scopes.SCOPES)


def test_window_scope_times_add_up_to_busy(scoped):
    path, _ = scoped
    summary = trace.reduce(path, [0])
    spans, _ = trace.events(path)
    (lo, hi), = [(s, e) for name, s, e in spans if name == trace.WINDOW]
    times = scopes.scope_times(path, [0], lo, hi)
    assert sum(times.values()) <= summary.busy_s * (1 + 1e-9)
    assert sum(times.values()) == pytest.approx(
        sum(sec for _, sec in trace.self_times(
            [(n, s, e) for n, s, e in trace.events(path)[1][0]], lo, hi)
            ) * 1e-9, rel=1e-9)


@pytest.mark.parametrize("name", UNSCOPED)
def test_traces_without_scopes_read_as_unscoped(tmp_path_factory, name):
    path = unpack(tmp_path_factory, name)
    names = scopes.op_names(path)
    assert len(names[0]) == len(trace.events(path)[1][0])
    assert any(t and t.startswith("jit(run)/") for t in names[0])
    assert set(scopes.scope_times(path, [0])) == {scopes.UNSCOPED}


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(run)/while/body/cond/branch_1_fun/gre.scatter/jit(_take)/gather",
     "gre.scatter"),
    ("jit(run)/gre.scatter/cond/branch_0_fun/gre.combine/scatter-add",
     "gre.combine"),
    ("jit(run)/while/body/gre.apply/select_n", "gre.apply"),
    ("jit(run)/while/body/reduce_or", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
])
def test_scope_is_the_innermost_gre_component(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_wire_format_fields():
    # field 1 varint 150, field 2 "hi", field 3 fixed64 1, field 4 fixed32 2
    buf = (b"\x08\x96\x01" + b"\x12\x02hi" + b"\x19" + (1).to_bytes(8, "little")
           + b"\x25" + (2).to_bytes(4, "little"))
    got = [(n, bytes(v) if isinstance(v, memoryview) else v)
           for n, v in scopes.fields(buf)]
    assert got == [(1, 150), (2, b"hi"), (3, 1), (4, 2)]
    with pytest.raises(ValueError, match="wire type"):
        list(scopes.fields(b"\x0b"))


def test_cli_prints_scope_times(scoped, capsys):
    path, _ = scoped
    assert scopes.main([str(path.parent)]) == 0
    printed = json.loads(capsys.readouterr().out)
    assert set(scopes.SCOPES) <= set(printed)
