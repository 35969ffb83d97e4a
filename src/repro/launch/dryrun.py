import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell for the production meshes and record memory/cost/roofline artifacts.

  single-pod: (16, 16)    = ("data", "model")          — 256 chips
  multi-pod:  (2, 16, 16) = ("pod", "data", "model")   — 512 chips

Usage:
  python -m repro.launch.dryrun                      # all 40 cells, both meshes
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --mesh single        # single-pod only
  python -m repro.launch.dryrun --graph              # GRE graph-engine dryrun
  python -m repro.launch.dryrun --out results/dryrun # JSON records per cell
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import all_cells
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as rl


def run_cell(arch: str, shape: str, mesh, save_hlo: str = "") -> dict:
    from repro.launch.cells import build_cell
    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    jitted = jax.jit(cell.step_fn, out_shardings=cell.out_shardings,
                     donate_argnums=cell.donate_argnums)
    lowered = jitted.lower(*cell.abstract_args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = {"arch": arch, "shape": shape, "kind": cell.kind,
           "mesh": dict(mesh.shape), "n_devices": mesh.size,
           "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
           "meta": cell.meta, "ok": True}
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device_gib": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        rec["xla_cost"] = {"flops": float(ca.get("flops", -1)),
                           "bytes_accessed": float(ca.get("bytes accessed", -1))}
    except Exception as e:  # pragma: no cover
        rec["xla_cost"] = {"error": str(e)}
    text = compiled.as_text()
    rec["roofline"] = rl.analyze(text)
    if save_hlo:
        Path(save_hlo).write_text(text)
        rec["hlo_path"] = save_hlo
    return rec


def run_graph_engine_dryrun(mesh) -> dict:
    """The paper's own workload on the production mesh: one PageRank
    superstep program over an (estimated-shape) Agent-Graph partition."""
    import jax.numpy as jnp
    from repro.core import algorithms
    from repro.core.dist_engine import DistGREEngine
    from repro.core.engine import EngineState
    from repro.launch.cells import _abstract_topo, _agent_shape_estimates, _sds
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    K = mesh.size
    V, E = 1 << 26, (1 << 26) * 16          # paper's weak-scaling family
    est = _agent_shape_estimates(V, E, K)
    slots = est["cap"] + est["s_pad"] + est["c_pad"] + 1
    spec = P(axes)
    topo_abs = _abstract_topo(est, K, mesh, spec)
    state_abs = EngineState(
        vertex_data=_sds((K, est["cap"]), jnp.float32, mesh, spec),
        scatter_data=_sds((K, slots), jnp.float32, mesh, spec),
        active_scatter=_sds((K, slots), jnp.bool_, mesh, spec),
        step=_sds((K,), jnp.int32, mesh, spec),
    )
    eng = DistGREEngine(algorithms.pagerank_program(), mesh, axes,
                        exchange="agent")

    class _FakeAG:  # make_run only reads shapes via device_topology/state
        pass

    def run30(topo, state):
        # inline the shard body: 30 canonical supersteps with AgentExchange
        import jax as _jax
        from repro.dist.sharding import shard_map as _shard_map

        def shard(topo_s, state_s):
            sq = lambda t: _jax.tree.map(lambda a: a[0], t)
            topo_l, st = sq(topo_s), sq(state_s)
            backend = eng.make_exchange(topo_l)

            def body(i, s):
                return eng.shard_engine.superstep(topo_l.part, s, backend)

            out = _jax.lax.fori_loop(0, 30, body, st)
            return _jax.tree.map(lambda a: a[None], out)

        return _shard_map(
            shard, mesh=mesh,
            in_specs=(_jax.tree.map(lambda _: spec, topo,
                                    is_leaf=lambda x: hasattr(x, "ndim")),
                      _jax.tree.map(lambda _: spec, state,
                                    is_leaf=lambda x: hasattr(x, "ndim"))),
            out_specs=_jax.tree.map(lambda _: spec, state,
                                    is_leaf=lambda x: hasattr(x, "ndim")))(
            topo, state)

    t0 = time.time()
    lowered = jax.jit(run30).lower(topo_abs, state_abs)
    compiled = lowered.compile()
    rec = {"arch": "gre-pagerank", "shape": f"rmat26x16_k{K}",
           "kind": "graph-superstep", "mesh": dict(mesh.shape),
           "compile_s": round(time.time() - t0, 2),
           "meta": {"V": V, "E": E, "supersteps": 30, "agent_est": est},
           "roofline": rl.analyze(compiled.as_text()), "ok": True}
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {"peak_per_device_gib": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3)}
    except Exception as e:
        rec["memory"] = {"error": str(e)}
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--graph", action="store_true",
                    help="also dry-run the GRE graph engine itself")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    cells = list(all_cells())
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]

    n_fail = 0
    for mesh_name, mesh in meshes:
        if args.graph:
            rec = run_graph_engine_dryrun(mesh)
            print(f"[{mesh_name}] gre-pagerank superstep: "
                  f"compile {rec['compile_s']}s "
                  f"dominant={rec['roofline']['dominant']}")
            (outdir / f"graph_{mesh_name}.json").write_text(
                json.dumps(rec, indent=1))
        for arch, shape in cells:
            tag = f"{arch}__{shape}__{mesh_name}"
            hlo = str(outdir / f"{tag}.hlo") if args.save_hlo else ""
            try:
                rec = run_cell(arch, shape, mesh, save_hlo=hlo)
                r = rec["roofline"]
                mem = rec["memory"].get("peak_per_device_gib", "?")
                print(f"[{mesh_name}] {arch:22s} {shape:14s} "
                      f"compile={rec['compile_s']:7.1f}s "
                      f"mem/dev={mem}GiB "
                      f"compute={r['compute_time_s']:.3e}s "
                      f"memory={r['memory_time_s']:.3e}s "
                      f"coll={r['collective_time_s']:.3e}s "
                      f"dominant={r['dominant']}", flush=True)
            except Exception as e:
                n_fail += 1
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"[{mesh_name}] {arch:22s} {shape:14s} FAILED: "
                      f"{type(e).__name__}: {str(e)[:160]}", flush=True)
            (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    print(f"\ndry-run complete; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
