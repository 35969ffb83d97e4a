"""One state lifecycle for both engines: `DistGREEngine` seeds, admits and
re-keys its tuned plan through the single-shard engine's pieces, so on a
1-device mesh its states are `GREEngine`'s, relabelled by `old2new`."""
import json

import jax
import numpy as np
import pytest

from repro.core import algorithms
from repro.core.agent_graph import apply_edge_delta, build_agent_graph
from repro.core.dist_engine import DistGREEngine
from repro.core.engine import DevicePartition, GREEngine
from repro.core.partition import greedy_partition
from repro.core.plan import KernelPlan, SuperstepPlan
from repro.graph.generators import circulant_graph, rmat_edges
from repro.graph.structures import EdgeDelta
from repro.serving import GraphQueryBatcher
from repro.tuning import PlanCache, plan_cache_key

D = 4
# name -> (program, runs on the symmetrized graph)
PROGRAMS = {
    "bfs": (lambda: algorithms.bfs_program(D), False),
    "sssp": (algorithms.sssp_program, False),
    "cc": (algorithms.cc_program, True),
    "pagerank": (algorithms.pagerank_program, False),
    "ppr": (lambda: algorithms.ppr_push_program(D), False),
}
# name -> (source, lane_tracking); the lane cases need D lanes
SOURCES = {
    "none": (None, False),
    "one": (3, False),
    "lane-masked": ([5, None, 9, 2], False),
    "lane-tracked": ([5, None, None, 9], True),
}
INIT_CASES = [(p, s) for p in PROGRAMS for s in SOURCES
              if p in ("bfs", "ppr") or SOURCES[s][0] is None
              or np.ndim(SOURCES[s][0]) == 0]


@pytest.fixture(scope="module")
def graphs():
    g = rmat_edges(scale=7, edge_factor=6, seed=3, weights=True).dedup()
    out = {}
    for sym in (False, True):
        gg = g.as_undirected().dedup() if sym else g
        ag = build_agent_graph(gg, greedy_partition(gg, 1, batch_size=64), 1)
        out[sym] = (DevicePartition.from_graph(gg), ag)
    return out


def _engines(name, **kw):
    program = PROGRAMS[name][0]()
    mesh = jax.make_mesh((1,), ("graph",))
    return (GREEngine(program, **kw),
            DistGREEngine(program, mesh, ("graph",), **kw))


def _assert_same(program, ag, single, dist):
    """`dist` (stacked `[1, ...]`) is `single` with master v at row
    `old2new[v]`; the slots past the masters (agents, the sink) hold the
    monoid identity, and no slot but a master's is active."""
    single, dist = jax.device_get((single, dist))
    n, order = single.vertex_data.shape[0], ag.old2new
    np.testing.assert_array_equal(dist.vertex_data[0][order],
                                  single.vertex_data)
    np.testing.assert_array_equal(dist.scatter_data[0][order],
                                  single.scatter_data[:n])
    np.testing.assert_array_equal(dist.active_scatter[0][order],
                                  single.active_scatter[:n])
    for rest in (single.scatter_data[n:], dist.scatter_data[0][ag.cap:]):
        np.testing.assert_array_equal(
            rest, np.full_like(rest, program.monoid.identity))
    assert not single.active_scatter[n:].any()
    assert dist.active_scatter[0].sum() == single.active_scatter.sum()
    assert int(dist.step[0]) == int(single.step)
    if single.lane_active is None:
        assert dist.lane_active is None
    else:
        np.testing.assert_array_equal(dist.lane_active[0],
                                      single.lane_active)


def _to_mesh(ag, engine, single):
    """A single-shard state laid out as a 1-device mesh state."""
    base = jax.device_get(engine.init_state(
        ag, source=[None] * D, lane_tracking=True))
    vd, sd = np.array(base.vertex_data), np.array(base.scatter_data)
    act = np.zeros_like(base.active_scatter)
    n = single.vertex_data.shape[0]
    vd[0][ag.old2new] = single.vertex_data
    sd[0][ag.old2new] = np.asarray(single.scatter_data)[:n]
    act[0][ag.old2new] = np.asarray(single.active_scatter)[:n]
    return type(single)(vd, sd, act, np.asarray(single.step)[None],
                        np.asarray(single.lane_active)[None])


@pytest.mark.parametrize("name,case", INIT_CASES)
def test_init_state_is_one_lifecycle(graphs, name, case):
    """`init_state` on a 1-device mesh is the single-shard engine's state,
    relabelled: no source, one source, lane-masked sources and lane
    tracking, for every program."""
    program, sym = PROGRAMS[name]
    part, ag = graphs[sym]
    source, tracked = SOURCES[case]
    single, dist = _engines(name)
    _assert_same(program(), ag,
                 single.init_state(part, source=source,
                                   lane_tracking=tracked),
                 dist.init_state(ag, source=source, lane_tracking=tracked))


@pytest.mark.parametrize("engine", ["single", "mesh"])
def test_lane_tracking_follows_one_rule(graphs, engine):
    """Both engines take lane tracking on the same terms: a program with
    `lane_activates` and no source (every lane free) or D sources; a
    scalar source, a source per fewer lanes, or a program without a
    per-lane halt rule is refused."""
    part, ag = graphs[False]
    target = part if engine == "single" else ag
    bfs = _engines("bfs")[engine == "mesh"]
    state = bfs.init_state(target, lane_tracking=True)
    assert not np.asarray(state.lane_active).any()
    for source in (3, [5, 9]):
        with pytest.raises(ValueError, match="lane_tracking"):
            bfs.init_state(target, source=source, lane_tracking=True)
    with pytest.raises(ValueError, match="lane_tracking"):
        _engines("sssp")[engine == "mesh"].init_state(
            target, source=None, lane_tracking=True)


@pytest.mark.parametrize("name", ["bfs", "ppr"])
def test_admit_step_is_one_lifecycle(graphs, name):
    """The serving admit step — reset a lane, evict one, seed at a vertex
    the last superstep left active (its row is kept) and at one it left
    inactive (its stale row is normalized) — gives the same state from
    both engines, from the same state after one superstep."""
    program = PROGRAMS[name][0]()
    part, ag = graphs[False]
    single, dist = _engines(name, frontier="dense")
    state = single.init_state(part, source=[5, None, None, 9],
                              lane_tracking=True)
    state = single.make_superstep(part)(part, state)
    active = np.flatnonzero(np.asarray(state.active_scatter))
    assert active.size and not bool(state.active_scatter[5])
    sources = np.array([active[0], 5, -1, -1])
    lanes = np.array([1, 2, 3, D], np.int32)
    got = dist.make_admit(ag)(_to_mesh(ag, dist, state), sources, lanes)
    want = single.make_admit(part)(state, sources, lanes)
    _assert_same(program, ag, want, got)
    assert np.asarray(want.lane_active)[1:].tolist() == [True, True, False]


def _rekey_scenario(tmp_path):
    """A BFS agent graph whose tuned plan is in the cache, a delta that
    halves its edges (a fingerprint bin moves) and a second plan stored
    under the mutated graph's key."""
    g = circulant_graph(1 << 9, degree=8)
    program = algorithms.bfs_program(D)
    ag = build_agent_graph(g, greedy_partition(g, 1), 1)
    rng = np.random.default_rng(2)
    pick = rng.choice(g.num_edges, size=g.num_edges // 2, replace=False)
    delta = EdgeDelta(rem_src=np.asarray(g.src)[pick],
                      rem_dst=np.asarray(g.dst)[pick])
    key = lambda a: plan_cache_key(agent_graph=a, program=program,
                                   mesh_size=1)
    new_key = key(apply_edge_delta(ag, delta)[0])
    assert new_key != key(ag)
    path = tmp_path / "plans.json"
    PlanCache(path).store(key(ag), SuperstepPlan(strategy="flat",
                                                 frontier_cap=32))
    PlanCache(path).store(new_key, SuperstepPlan(strategy="compact",
                                                 frontier_cap=16))
    mesh = jax.make_mesh((1,), ("graph",))
    dist = DistGREEngine(program, mesh, ("graph",), plan="auto-tuned",
                         plan_cache=path)
    return g, ag, delta, dist, new_key


@pytest.mark.parametrize("via", ["rerun_incremental", "serving"])
def test_mesh_rekeys_tuned_plan_after_a_delta(tmp_path, via):
    """A `plan="auto-tuned"` `DistGREEngine` re-keys its plan when a delta
    moves a fingerprint bin — through `rerun_incremental` and through the
    batcher's swap — as `GREEngine.refresh_plan` does on one shard; the
    re-keyed plan never changes the answer."""
    g, ag, delta, dist, new_key = _rekey_scenario(tmp_path)
    source = [0, 7, None, None]
    if via == "serving":
        batcher = GraphQueryBatcher(dist, ag)
        assert dist.shard_engine.frontier == "flat"
        batcher.apply_delta(delta)
        q = batcher.submit(7)
        batcher.run()
        result = q.result
    else:
        _, prev = dist.run(ag, source=source, max_steps=300)
        assert dist.shard_engine.frontier == "flat"
        _, out, _, _ = dist.rerun_incremental(ag, prev, delta, source=source,
                                              max_steps=300)
        result = out[:, 1]
    assert dist._plan_key == new_key
    assert (dist.shard_engine.frontier,
            dist.shard_engine.frontier_cap) == ("compact", 16)
    cold = GREEngine(algorithms.bfs_program(D))
    part = DevicePartition.from_graph(g.apply_edge_delta(delta))
    want = cold.run(part, cold.init_state(part, source=[None, 7, None, None]),
                    300)
    np.testing.assert_array_equal(
        np.nan_to_num(result, posinf=-1.0),
        np.nan_to_num(np.asarray(want.vertex_data)[:, 1], posinf=-1.0))


@pytest.mark.parametrize("use_pallas", [None, True, False])
@pytest.mark.parametrize("name", ["pagerank", "cc", "bfs"])
def test_plan_carries_the_engine_kernel_route(graphs, name, use_pallas):
    """`make_plan` records the engine's own route, an unset one included,
    the plan survives a JSON round trip, and an engine that adopts it
    resolves the same combine route as the engine it came from."""
    program, sym = PROGRAMS[name]
    part, _ = graphs[sym]
    eng = GREEngine(program(), use_pallas=use_pallas)
    plan = eng.make_plan()
    assert plan.kernel.use_pallas is use_pallas
    wire = json.loads(json.dumps(plan.to_json()))
    assert SuperstepPlan.from_json(wire) == plan
    adopted = GREEngine(program(), plan=SuperstepPlan.from_json(wire))
    assert adopted.use_pallas is use_pallas
    assert adopted.combine_route(part) == eng.combine_route(part)


def test_stored_boolean_routes_load_as_before():
    """Plans stored before the route could be unset still load: a boolean
    stays that boolean, and a kernel stage without one forces XLA."""
    good = SuperstepPlan().to_json()
    for stored, want in ((True, True), (False, False), (None, None)):
        plan = SuperstepPlan.from_json(
            {**good, "kernel": {"use_pallas": stored}})
        assert plan.kernel.use_pallas is want
    assert SuperstepPlan.from_json({**good, "kernel": {}}).kernel == \
        KernelPlan(use_pallas=False)
