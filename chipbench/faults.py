"""Faults planted under the timed path, to see a broken run judged not
correct: by the CPU tests at a small scale, and by `calibrate.py faults`
at a cell's own size on the chip.

Each is a context manager that patches `GREEngine` while it is open; an
engine compiled inside it runs the broken superstep.  One chip has no
exchange between chips, so that fault has no entry here.
"""
from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock


@contextlib.contextmanager
def unchanged_step():
    """Each superstep returns the vertex state it was given."""
    from repro.core.engine import GREEngine

    def apply(self, part, state, combined):
        import jax.numpy as jnp
        return dataclasses.replace(
            state, step=state.step + 1,
            active_scatter=jnp.zeros_like(state.active_scatter))
    with mock.patch.object(GREEngine, "apply", apply):
        yield


@contextlib.contextmanager
def half_the_edges():
    """Odd edge positions are sent to the sink; a sum is doubled, as a mean
    over the rest would be."""
    from repro.core.engine import GREEngine
    scatter = GREEngine.scatter_combine

    def half(self, part, state, num_segments=None):
        import jax.numpy as jnp
        odd = jnp.arange(part.dst.shape[0]) % 2 == 1
        part = dataclasses.replace(
            part, dst=jnp.where(odd, part.num_masters, part.dst))
        out = scatter(self, part, state, num_segments)
        return out * 2 if self.program.monoid.name == "sum" else out
    with mock.patch.object(GREEngine, "scatter_combine", half):
        yield


@contextlib.contextmanager
def altered_answer():
    """Vertex 0's value is changed where apply produces it."""
    from repro.core.engine import GREEngine
    apply = GREEngine.apply

    def altered(self, part, state, combined):
        new = apply(self, part, state, combined)
        return dataclasses.replace(new,
                                   vertex_data=new.vertex_data.at[0].add(1.0))
    with mock.patch.object(GREEngine, "apply", altered):
        yield


FAULTS = {f.__name__: f for f in (unchanged_step, half_the_edges,
                                  altered_answer)}
