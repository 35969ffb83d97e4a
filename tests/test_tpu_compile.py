"""Compile the main path for a described TPU v5e (no chip needed).

The TPU compiler is installed alongside jax, so the Pallas combine, the
single-chip superstep loop and the 4-chip distributed run are lowered and
compiled here for a `v5e:2x2` topology: what Mosaic or XLA would refuse on
the chip (block layouts, VMEM/SMEM budgets, partitioning) fails here.
Nothing runs.  The topology is described inside a fixture, never at import
time (only one process at a time may load the TPU library).
"""
import dataclasses
import difflib
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import algorithms
from repro.core.agent_graph import build_agent_graph
from repro.core.dist_engine import DistGREEngine
from repro.core.engine import DevicePartition, GREEngine
from repro.core.partition import hash_partition
from repro.core.plan import KernelPlan
from repro.graph.generators import rmat_edges
from repro.kernels import segment_combine as sc

# E of the benchmark's cells (chipbench/configs): R-MAT scale 22 for
# PageRank, its symmetrized copy for CC, both over 2**22 vertices
CELL_EDGES = {"pagerank": 65_242_601, "cc": 128_303_672}
CELL_VERTICES = 1 << 22


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("d", [1, 64])
@pytest.mark.parametrize("op", ["sum", "min"])
@pytest.mark.parametrize("table", ["static", "dynamic"])
def test_pallas_combine_compiles(one_chip, table, op, d):
    e, v = 8192, 2048
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    msgs, dst = s((e, d), jnp.float32), s((e,), jnp.int32)
    if table == "static":
        fn = lambda m, i, t: sc.segment_combine_pallas(m, i, t, v, op)
        args = (msgs, dst, s((2, sc.table_length(e, v)), jnp.int32))
    else:
        fn = lambda m, i: sc.tile_segment_combine_pallas(m, i, v, op)
        args = (msgs, dst)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name,source", [("pagerank", None), ("sssp", 0)])
def test_engine_run_with_pallas_compiles(one_chip, name, source):
    """PageRank takes the dense scan (ingress-time block table); SSSP the
    bucketed frontier tiles (per-superstep table) and the dense fallback."""
    g = rmat_edges(scale=9, edge_factor=8, seed=1, weights=True).dedup()
    part = DevicePartition.from_graph(g)
    eng = GREEngine(getattr(algorithms, f"{name}_program")(),
                    use_pallas=True)
    state = eng.init_state(part, source=source)
    hlo = GREEngine.run.lower(eng, _abstract(part, one_chip),
                              _abstract(state, one_chip),
                              16).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_dist_pipelined_run_compiles_for_four_chips(topo):
    """`DistGREEngine.make_run` (pipelined exchange, SSSP) on a 4-device
    mesh of the described chips: the flush collectives partition."""
    g = rmat_edges(scale=9, edge_factor=8, seed=2, weights=True).dedup()
    ag = build_agent_graph(g, hash_partition(g, 4), 4)
    prog = algorithms.sssp_program()
    host = DistGREEngine(prog, jax.make_mesh((1,), ("graph",)),
                         exchange="pipelined")
    topo_arrays = host.device_topology(ag)
    state = host.init_state(ag, source=0)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("graph",))
    rows = NamedSharding(mesh, P("graph"))
    eng = DistGREEngine(prog, mesh, ("graph",), exchange="pipelined")
    compiled = eng.make_run(ag, max_steps=16).lower(
        _abstract(topo_arrays, rows), _abstract(state, rows)).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo or "all-reduce" in hlo


def test_refresh_exchange_compiles_without_sort(topo):
    """The Agent-Graph refresh at a shard's real exchange width (32K agent
    slots per shard) compiles with no sort: the TPU compiler lowers a
    sub-32-bit scatter (the activity flags) through a sort, which costs
    seconds of compile per scatter at this width."""
    from types import SimpleNamespace

    from repro.core.exchange import refresh_scatter_agents
    from repro.dist.sharding import shard_map
    k, x, slots = 4, 8192, 65536
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("graph",))
    rows = NamedSharding(mesh, P("graph"))

    def shard(send, recv, sd, act):
        t = SimpleNamespace(scat_send_master=send[0], scat_recv_slot=recv[0])
        sd, act = refresh_scatter_agents(t, sd[0], act[0], "graph")
        return sd[None], act[None]

    fn = jax.jit(shard_map(shard, mesh=mesh, in_specs=(P("graph"),) * 4,
                           out_specs=P("graph")))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rows)
    hlo = fn.lower(s((k, k, x), jnp.int32), s((k, k, x), jnp.int32),
                   s((k, slots), jnp.float32),
                   s((k, slots), jnp.bool_)).compile().as_text()
    assert "all-to-all" in hlo
    assert " sort(" not in hlo


# what the loop itself runs outside the engine's scopes: the keep-going
# predicate (step < max_steps, any active), the while and cond shells and
# the conversion of a cond's predicate to its branch index
LOOP_CONTROL = re.compile(
    r"jit\(run\)(/(while|body|cond|branch_\d+_fun))*"
    r"/(and|lt|reduce_or|convert_element_type|cond|while)")


@pytest.mark.parametrize("name,frontier,counters", [
    ("pagerank", "auto", 0), ("cc", "dense", 0), ("cc", "auto", 0),
    ("cc", "auto", 64)])
def test_every_engine_op_falls_under_a_scope(one_chip, name, frontier,
                                             counters):
    """Each op of the compiled `GREEngine.run` carries `gre.scatter`,
    `gre.combine` or `gre.apply` in its name stack (the metadata a profile
    reads), except the loop's own control.  Ops the compiler makes with no
    `jit(run)` name stack (loop-carry copies, reducer regions, the gather's
    index clamp) are left out."""
    g = rmat_edges(scale=9, edge_factor=8, seed=1).dedup()
    part = DevicePartition.from_graph(g)
    eng = GREEngine(getattr(algorithms, f"{name}_program")(),
                    frontier=frontier)
    state = eng.init_state(part, counters=counters)
    hlo = GREEngine.run.lower(eng, _abstract(part, one_chip),
                              _abstract(state, one_chip),
                              64).compile().as_text()
    scopes, outside = set(), set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        if not op_name.startswith("jit(run)/"):
            continue
        scope = re.findall(r"/(gre\.\w+)", op_name)
        if scope:
            scopes.add(scope[-1])
        elif not LOOP_CONTROL.fullmatch(op_name):
            outside.add(op_name)
    assert not outside
    assert scopes == {"gre.scatter", "gre.combine", "gre.apply"}


# the same for the shard function of `DistGREEngine.make_run`, where the
# keep-going predicate is made mesh-global by a pmax (its compare and
# conversions included), plus the shard's entry and exit (the stacked
# operands' leading axis dropped and restored) and the broadcasts the
# partitioner makes of constants, named by their HLO instruction
SHARD_CONTROL = re.compile(
    r"jit\(run_shard\)/shard_map((/(while|body|cond|branch_\d+_fun))*"
    r"/(and|lt|gt|reduce_or|convert_element_type|pmax|cond|while)"
    r"|/squeeze|/broadcast_in_dim|/broadcast\.\d+)")


def test_every_agent_op_falls_under_a_scope(topo):
    """The 4-device agent PageRank run, compiled for a `v5e:2x2`: every op
    of the shard function carries an engine scope or one of the exchange's
    two (`gre.exchange.refresh`, `gre.exchange.flush`), except the loop's
    control, and every `all-to-all` is under an exchange scope."""
    from repro.core.partition_stream import hdrf_partition
    g = rmat_edges(scale=9, edge_factor=8, seed=2).dedup()
    ag = build_agent_graph(g, hdrf_partition(g, 4), 4)
    prog = algorithms.pagerank_program()
    host = DistGREEngine(prog, jax.make_mesh((1,), ("graph",)))
    topo_arrays = host.device_topology(ag)
    state = host.init_state(ag)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("graph",))
    rows = NamedSharding(mesh, P("graph"))
    eng = DistGREEngine(prog, mesh, ("graph",), exchange="agent")
    hlo = eng.make_run(ag, max_steps=30).lower(
        _abstract(topo_arrays, rows), _abstract(state, rows)
    ).compile().as_text()
    scopes, outside = set(), set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        if not op_name.startswith("jit(run_shard)/shard_map/"):
            continue
        scope = re.findall(r"/(gre\.[\w.]+)", op_name)
        if scope:
            scopes.add(scope[-1])
        elif not SHARD_CONTROL.fullmatch(op_name):
            outside.add(op_name)
    assert not outside
    assert scopes == {"gre.scatter", "gre.combine", "gre.apply",
                      "gre.exchange.refresh", "gre.exchange.flush"}
    collectives = [line for line in hlo.splitlines()
                   if re.search(r"= \S+ all-to-all\(", line)]
    assert len(collectives) >= 2
    for line in collectives:
        assert re.search(r'op_name="[^"]*/gre\.exchange\.(refresh|flush)/',
                         line), line[:200]


def _cell_shapes(name, sharding):
    """`(program, part, state)` with the shapes of the benchmark cell that
    runs `name` (abstract: nothing is allocated).  The statics come from a
    small graph's partition; the dense plan reads none of them."""
    e, v = CELL_EDGES[name], CELL_VERTICES
    g = rmat_edges(scale=9, edge_factor=8, seed=1,
                   weights=name == "pagerank").dedup()
    small = DevicePartition.from_graph(g)
    col = lambda n, dt: jax.ShapeDtypeStruct((n,), dt, sharding=sharding)
    part = dataclasses.replace(
        small, src=col(e, jnp.int32), dst=col(e, jnp.int32),
        edge_mask=col(e, jnp.bool_), num_masters=v, num_slots=v + 1,
        edge_props={k: col(e, a.dtype) for k, a in small.edge_props.items()},
        aux={k: col(v, a.dtype) for k, a in small.aux.items()},
        csr_indptr=col(v + 2, jnp.int32), csr_eidx=col(e, jnp.int32),
        bucket_id=col(v + 1, jnp.int32),
        combine_table=jax.ShapeDtypeStruct(
            (2, sc.table_length(e, v + 1)), jnp.int32, sharding=sharding))
    program = getattr(algorithms, f"{name}_program")()
    state = GREEngine(program).init_state(small)
    slots = {small.num_masters: v, small.num_slots: v + 1}
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (slots.get(a.shape[0], a.shape[0]),) + a.shape[1:] if a.ndim
        else (), a.dtype, sharding=sharding), state)
    return program, part, state


def edge_sized_copies(hlo: str, num_edges: int) -> list:
    """Instructions of a compiled module that copy an edge column on the
    combine's operand path: a pad, copy, transpose or fusion under
    `gre.combine` whose result spans the edges (E up to the next edge
    block), and any array of rank 2 or more that does (every edge column
    is 1-D, so such an array is a relayout)."""
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if not m:
            continue
        dims = [tuple(map(int, d.split(",")))
                for d in re.findall(r"\[([\d,]+)\]", m.group(1))]
        spans = [d for d in dims
                 if any(num_edges <= n < num_edges + sc.BLOCK_E for n in d)]
        copy = (m.group(2) in ("pad", "copy", "copy-start", "transpose",
                               "fusion") and "gre.combine" in line)
        if spans and (copy or any(len(d) > 1 for d in spans)):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("name", ["pagerank", "cc"])
def test_default_dense_combine_is_the_kernel_without_edge_copies(one_chip,
                                                                 name):
    """At the cells' sizes, the default engine's dense combine (PageRank,
    and CC's masked scan) compiles for the chip to the Pallas kernel under
    `gre.combine`, with no edge-sized pad, copy or transpose in the loop or
    hoisted out of it, and with temporaries within 1% of the XLA route's.
    `use_pallas=False` still forces XLA."""
    program, part, state = _cell_shapes(name, one_chip)
    compiled = {}
    for use_pallas in (None, False):
        eng = GREEngine(program, frontier="dense", use_pallas=use_pallas)
        compiled[use_pallas] = GREEngine.run.lower(eng, part, state,
                                                   64).compile()
    hlo = compiled[None].as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("gre.combine" in line for line in kernels)
    assert edge_sized_copies(hlo, CELL_EDGES[name]) == []
    assert "tpu_custom_call" not in compiled[False].as_text()
    temp = {k: c.memory_analysis().temp_size_in_bytes
            for k, c in compiled.items()}
    assert temp[None] <= 1.01 * temp[False]


# one shard of the four-chip cell `rmat22-hdrf4.pagerank` as a run lays it
# out: edge slots, masters (2**22 vertices over four chips), the slot space
# and the exchange slots per peer
AGENT_SHARD = {"edges": 16_311_768, "masters": 1 << 20, "slots": 1_745_849,
               "exchange": 120_176}


def _agent_cell_shapes(exchange, rows):
    """`(program, agent graph, topology, state)` of the 4-device agent
    PageRank with the shapes of `AGENT_SHARD` on each shard (abstract:
    nothing is allocated).  The statics come from a small graph's agent
    graph; the dense plan reads none of them.  Under the pipelined exchange
    half the edges and half the agent slots are taken to be remote."""
    from repro.core.partition_stream import hdrf_partition
    k = 4
    g = rmat_edges(scale=9, edge_factor=8, seed=2).dedup()
    ag = build_agent_graph(g, hdrf_partition(g, k), k)
    program = algorithms.pagerank_program()
    host = DistGREEngine(program, jax.make_mesh((1,), ("graph",)),
                         exchange=exchange)
    small, state = host.device_topology(ag), host.init_state(ag)
    e, cap, slots, x = AGENT_SHARD.values()
    combiners = (slots - 1 - cap) // 2

    def blocks(n, dtype, *rest):   # k shards' [n, *rest], shard after shard
        return jax.ShapeDtypeStruct((k * n,) + rest, dtype, sharding=rows)

    def with_edges(p, n, num_segments):
        return dataclasses.replace(
            p, src=blocks(n, jnp.int32), dst=blocks(n, jnp.int32),
            edge_mask=blocks(n, jnp.bool_), num_masters=cap,
            num_slots=slots,
            edge_props={name: blocks(n, a.dtype)
                        for name, a in p.edge_props.items()},
            aux={name: blocks(cap, a.dtype) for name, a in p.aux.items()},
            csr_indptr=blocks(slots + 1, jnp.int32),
            csr_eidx=blocks(n, jnp.int32),
            bucket_id=blocks(slots, jnp.int32),
            combine_table=blocks(2, jnp.int32,
                                 sc.table_length(n, num_segments)))

    peers = lambda: blocks(k, jnp.int32, x)
    if small.tiles is None:
        part, tiles = with_edges(small.part, e, slots), None
    else:
        part = dataclasses.replace(
            small.part, num_masters=cap, num_slots=slots,
            aux={name: blocks(cap, a.dtype)
                 for name, a in small.part.aux.items()})
        tiles = dataclasses.replace(
            small.tiles,
            part_remote=with_edges(small.tiles.part_remote, e // 2,
                                   combiners + 1),
            part_local=with_edges(small.tiles.part_local, e - e // 2,
                                  cap + 1),
            comb_send_compact=peers(), comb_recv_master=peers(),
            num_combiners=combiners)
    topo_abs = dataclasses.replace(
        small, part=part, tiles=tiles, comb_send_slot=peers(),
        comb_recv_master=peers(), scat_send_master=peers(),
        scat_recv_slot=peers())
    stacked = lambda n, dtype: jax.ShapeDtypeStruct((k, n), dtype,
                                                    sharding=rows)
    state = dataclasses.replace(
        state, vertex_data=stacked(cap, jnp.float32),
        scatter_data=stacked(slots, jnp.float32),
        active_scatter=stacked(slots, jnp.bool_),
        step=jax.ShapeDtypeStruct((k,), jnp.int32, sharding=rows))
    # each combine's (edges, segment space)
    combines = ([(e, slots)] if tiles is None else
                [(e // 2, combiners + 1), (e - e // 2, cap + 1)])
    return program, ag, topo_abs, state, combines


@pytest.mark.parametrize("exchange", ["agent", "pipelined"])
def test_agent_shards_combine_on_the_kernel_without_edge_copies(topo,
                                                                exchange):
    """The 4-device agent PageRank run with the four-chip cell's shard
    shapes, compiled for a `v5e:2x2`: by default each shard's dense combine
    is the Pallas kernel under `gre.combine`, over the shard's slot space
    (agent exchange) or over each split tile's own space (pipelined), with
    no edge-sized pad, copy, transpose or relayout in the loop or hoisted
    out of it.  `use_pallas=False`, and a tuned plan whose `KernelPlan`
    holds False, compile no kernel."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("graph",))
    rows = NamedSharding(mesh, P("graph"))
    program, ag, topo_abs, state, combines = _agent_cell_shapes(exchange,
                                                                rows)
    default = DistGREEngine(program, mesh, ("graph",), exchange=exchange)
    tuned = dataclasses.replace(default.plan,
                                kernel=KernelPlan(use_pallas=False))
    engines = {
        "default": default,
        "xla": DistGREEngine(program, mesh, ("graph",), exchange=exchange,
                             use_pallas=False),
        "tuned-xla": DistGREEngine(program, mesh, ("graph",),
                                   exchange=exchange, plan=tuned)}
    compiled = {name: eng.make_run(ag, max_steps=30).lower(
        topo_abs, state).compile() for name, eng in engines.items()}
    hlo = compiled["default"].as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("gre.combine" in line for line in kernels)
    # each kernel reads the block schedule of its own combine
    schedules = {int(n) for line in kernels
                 for n in re.findall(r"s32\[3,(\d+)\]", line)}
    assert schedules == {sc.table_length(e, nseg) for e, nseg in combines}
    for e, _ in combines:
        assert edge_sized_copies(hlo, e) == []
    for name in ("xla", "tuned-xla"):
        assert "tpu_custom_call" not in compiled[name].as_text()
    temp = {name: c.memory_analysis().temp_size_in_bytes
            for name, c in compiled.items()}
    if exchange == "agent":
        assert temp["default"] <= 1.01 * temp["xla"]
    else:
        # the kernel's outputs are padded to whole dst blocks over two
        # small segment spaces (+0.7 MB on 11 MB of temporaries); a copy
        # of an edge column would add a whole tile's column
        assert temp["default"] - temp["xla"] < 4 * min(e for e, _ in
                                                      combines)


# ---------------------------------------------- the compiled runs, as text
LOWERED = Path(__file__).resolve().parent / "data" / "lowered"


def lowered_runs(topo) -> dict:
    """name -> StableHLO text of the engines' compiled runs, lowered for the
    described chip with no source locations: `GREEngine.run` for PageRank
    and CC on a scale-8 R-MAT partition, and `DistGREEngine.make_run`
    (agent exchange, PageRank) on a 1-device mesh.  Only public calls, so
    the same function lowers any tree of the engine."""
    one = SingleDeviceSharding(topo.devices[0])
    g = rmat_edges(scale=8, edge_factor=8, seed=1, weights=True).dedup()
    # no traceback frames in locations, so the Pallas kernel's serialized
    # body carries no source paths or line numbers either
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        texts = {}
        for name, graph in (("pagerank", g),
                            ("cc", g.as_undirected().dedup())):
            part = DevicePartition.from_graph(graph)
            eng = GREEngine(getattr(algorithms, f"{name}_program")())
            state = eng.init_state(part)
            texts[f"run-{name}"] = GREEngine.run.lower(
                eng, _abstract(part, one), _abstract(state, one),
                30).as_text()
        prog = algorithms.pagerank_program()
        ag = build_agent_graph(g, hash_partition(g, 1), 1)
        host = DistGREEngine(prog, jax.make_mesh((1,), ("graph",)))
        mesh = jax.sharding.Mesh(np.asarray(topo.devices[:1]), ("graph",))
        rows = NamedSharding(mesh, P("graph"))
        eng = DistGREEngine(prog, mesh, ("graph",), exchange="agent")
        texts["make_run-agent"] = eng.make_run(ag, max_steps=30).lower(
            _abstract(host.device_topology(ag), rows),
            _abstract(host.init_state(ag), rows)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    return {name: "\n".join(line for line in text.splitlines()
                            if not line.startswith("#loc")) + "\n"
            for name, text in texts.items()}


@pytest.mark.parametrize("name", ["run-pagerank", "run-cc", "make_run-agent"])
def test_compiled_runs_match_reference_text(topo, name):
    """The engines' compiled runs lower to the StableHLO kept in
    `tests/data/lowered/` (made by `python tests/test_tpu_compile.py`):
    a change to the state lifecycle around the run leaves the program that
    runs on the chip as it was."""
    got = lowered_runs(topo)[name]
    want = (LOWERED / f"{name}.mlir").read_text()
    if got != want:
        diff = difflib.unified_diff(want.splitlines(), got.splitlines(),
                                    "reference", "lowered", lineterm="")
        pytest.fail("\n".join(line[:200] for line in list(diff)[:40]))


if __name__ == "__main__":
    # (re)write the reference text with the `repro` on PYTHONPATH
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    LOWERED.mkdir(parents=True, exist_ok=True)
    for name, text in lowered_runs(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")).items():
        (LOWERED / f"{name}.mlir").write_text(text)
        print(name, len(text))
