"""Warm-start invalidation for incremental re-convergence (docs/incremental.md).

After a batch of edge mutations, `rerun_incremental` re-converges from the
previous fixed point instead of from scratch.  Added edges are easy: under a
min monoid, re-delivering a fixed-point value is idempotent, so activating
the add endpoints and letting the normal frontier machinery run is both safe
and exact.  Removals are the hard half — a min-monoid fixed point can hold
values that were only attainable THROUGH a removed edge, and min cannot
retract — so this module computes the set of (vertex, lane) entries whose
values are no longer certified by the surviving graph and resets them to the
program's initial values before re-seeding.

Two invalidation policies (`VertexProgram.invalidation`):

* ``"path"`` (BFS/SSSP) — support-based worklist invalidation in the
  Ramalingam–Reps tradition: entry ``(x, d)`` keeps its value iff some live
  in-edge ``(w, x)`` from an untainted ``w`` reproduces it BITWISE
  (``scatter_msg(val[w], prop) == val[x]``), or ``x`` is lane ``d``'s
  source.  Uncertified entries taint, and entries they were supporting are
  re-examined, wave by wave — work proportional to the affected region, not
  the graph.  Sound because these programs' messages are strictly
  increasing (``+1`` / positive weights), so stale values cannot support
  each other around a cycle.

* ``"component"`` (CC) — label propagation has CYCLIC support (two stale
  labels in a split-off component certify each other), so the worklist
  under-taints.  Instead, taint everything forward-reachable from the
  removed edges' destinations over the PRE-delta edge set — the region
  whose in-reachable set (and hence min label) the removal could have
  changed.

All passes run host-side in numpy on the master-vertex id space; the message
check goes through the program's own ``scatter_msg`` on f32 inputs, so the
certificate is bitwise-identical to what the device superstep would deliver.
"""
from __future__ import annotations

import numpy as np


def check_supported(program, report) -> None:
    """Raise unless `program` can warm-start over this delta.

    Iterative programs (halts=False, e.g. PageRank) always can — they
    recompute from whatever state they hold.  Halting traversals need the
    min monoid (idempotent re-delivery), and removals additionally need an
    invalidation policy.
    """
    if not program.halts:
        return
    if program.monoid.name != "min":
        raise ValueError(
            f"{program.name}: incremental warm start needs an idempotent "
            f"(min) monoid or an iterative program; a halting "
            f"{program.monoid.name}-monoid traversal cannot reuse a prior "
            "fixed point (already-delivered mass does not re-deliver)")
    if report.num_removed and program.invalidation is None:
        raise ValueError(
            f"{program.name}: edge removals need an invalidation policy "
            "(VertexProgram.invalidation = 'path' or 'component')")


def source_mask(shape, source) -> np.ndarray:
    """Protected entries the invalidation pass must never taint: lane d's
    source vertex holds the seeded 0.0 by definition, not by edge support.
    `source` follows `init_state` conventions (scalar, or a per-lane
    sequence with None/negative = unseeded)."""
    out = np.zeros(shape, dtype=bool)
    if source is None:
        return out
    if np.ndim(source) == 0:
        out[int(source)] = True
        return out
    for d, sv in enumerate(source):
        if sv is not None and int(sv) >= 0:
            out[int(sv), d] = True
    return out


def support_taint(program, num_vertices, src, dst, eprop, values,
                  suspect, protected) -> np.ndarray:
    """The "path" policy: worklist certification over the NEW live edges.

    `values` is the previous fixed point (`[V]` or `[V, D]` f32, original
    vertex order); `suspect` seeds the worklist (destinations of removed
    edges); `protected` entries (sources) never taint.  Returns the tainted
    mask, same shape as `values`.
    """
    import jax.numpy as jnp
    finite = np.isfinite(values)
    eligible = finite & ~protected
    if src.shape[0] == 0:
        return suspect & eligible
    msgs = np.asarray(program.scatter_msg(
        jnp.asarray(values[src]),
        None if eprop is None else jnp.asarray(eprop)))
    # bitwise certificate: edge (w, x) supports val[x] iff re-scattering
    # w's value reproduces it exactly (same f32 ops as the device path)
    support_edge = msgs == values[dst]
    tainted = np.zeros_like(suspect)
    pending = suspect & eligible
    while True:
        supported = np.zeros_like(tainted)
        np.logical_or.at(supported, dst, support_edge & ~tainted[src])
        newly = pending & ~supported & ~tainted
        if not newly.any():
            return tainted
        tainted |= newly
        # entries whose certificate ran through a newly tainted supporter
        # must be re-examined against the shrunken untainted set
        child = np.zeros_like(tainted)
        np.logical_or.at(child, dst, support_edge & newly[src])
        pending |= child & eligible


def reach_taint(num_vertices, src, dst, seeds) -> np.ndarray:
    """The "component" policy: forward reachability from `seeds` over the
    given edge set (pre-delta: survivors + removed).  Returns `[V]` bool."""
    tainted = np.zeros(num_vertices, dtype=bool)
    if seeds.shape[0] == 0:
        return tainted
    tainted[seeds] = True
    if src.shape[0] == 0:
        return tainted
    while True:
        reach = np.zeros(num_vertices, dtype=bool)
        np.logical_or.at(reach, dst, tainted[src])
        new = reach & ~tainted
        if not new.any():
            return tainted
        tainted |= new


def compute_taint(program, num_vertices, live_src, live_dst, live_prop,
                  values, report, protected) -> np.ndarray:
    """Dispatch on `program.invalidation`; returns a mask shaped like
    `values` (all-False when the delta removed nothing)."""
    if report.num_removed == 0:
        return np.zeros(values.shape, dtype=bool)
    if program.invalidation == "component":
        old_src = np.concatenate([live_src, report.removed_src])
        old_dst = np.concatenate([live_dst, report.removed_dst])
        t = reach_taint(num_vertices, old_src, old_dst, report.removed_dst)
        t = np.broadcast_to(
            t.reshape((num_vertices,) + (1,) * (values.ndim - 1)),
            values.shape).copy()
        return t & np.isfinite(values) & ~protected
    suspect = np.zeros(values.shape, dtype=bool)
    suspect[report.removed_dst] = True
    return support_taint(program, num_vertices, live_src, live_dst,
                         live_prop, values, suspect, protected)


def warm_seed_active(num_vertices, live_src, live_dst, tainted_any,
                     added_src, init_active) -> np.ndarray:
    """The warm-start activity seeds (`[V]` bool, master space):

    * sources of ADDED edges — their (possibly finite) values must travel
      the new edges;
    * in-neighbors of tainted vertices — they re-deliver the surviving
      certified values into the reset region (min idempotence makes the
      re-delivery a no-op everywhere it is not needed);
    * tainted vertices the program itself seeds active (`init_active`,
      e.g. CC re-scatters its reset self-labels).

    An empty delta yields an empty seed set: the warm run terminates at
    superstep 0 with the previous fixed point intact.
    """
    act = np.zeros(num_vertices, dtype=bool)
    if added_src.shape[0]:
        act[added_src] = True
    if tainted_any.any():
        if live_src.shape[0]:
            into_taint = tainted_any[live_dst]
            act[live_src[into_taint]] = True
        act |= tainted_any & init_active
    return act


def warm_start_columns(program, report, source, edges, aux, prev, init):
    """The warm start's policy on ORIGINAL-order master columns, for both
    engines (`EngineLifecycle.warm_start_state` maps each layout's master
    rows out and back): reset the entries the delta no longer certifies to
    their fresh values, and pick the vertices that start active.

    `edges` is the live `(src, dst, prop)` in original ids, `aux` the
    program's init aux in original order, `prev` and `init` the
    `(vertex_data, scatter_data)` columns of the previous fixed point and
    of the freshly seeded state.  Returns the `(vertex_data, scatter_data,
    active)` columns.
    """
    live_src, live_dst, live_prop = edges
    vd_prev, sd_prev = prev
    n = vd_prev.shape[0]
    tainted = compute_taint(program, n, live_src, live_dst, live_prop,
                            vd_prev, report,
                            source_mask(vd_prev.shape, source))
    tainted_any = tainted if tainted.ndim == 1 else tainted.any(axis=-1)
    active = warm_seed_active(n, live_src, live_dst, tainted_any,
                              report.added_src,
                              np.asarray(program.init_active(n, aux)))
    return (np.where(tainted, init[0], vd_prev),
            np.where(tainted, init[1], sd_prev), active)
