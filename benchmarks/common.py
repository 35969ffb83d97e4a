"""Shared benchmark utilities.

Every `emit` also records a machine-readable result into `RESULTS`
(`benchmarks/run.py --json` dumps them as the CI perf artifact); passing
`edges=` adds the cross-benchmark comparable ns/edge number.

Timing is the tuner's probe harness (`repro.tuning.evaluator.measure`) —
one clock discipline for autotuner probes and bench-gate numbers — and
`time_fn` results carry the run's max/median dispersion as `.noise`, so
the artifact records how repeatable each entry was ON THE MACHINE THAT
PRODUCED IT.  `compare.py` turns the baseline's recorded dispersion into
a per-entry regression margin instead of one hand-picked headroom.
"""
from __future__ import annotations

from typing import Callable, Optional

from repro.tuning.evaluator import measure

# Machine-readable results accumulated across one benchmark run
# (list of dicts: name, us_per_call, optional ns_per_edge/noise, derived).
RESULTS: list = []


class TimedUs(float):
    """A microseconds median that remembers its dispersion.  Behaves as a
    plain float everywhere (ratios, formatting, min/max) so benchmark
    arithmetic is unchanged; `emit` reads `.noise` off it to record the
    per-entry repeatability without every call site threading a second
    value."""

    noise: float

    def __new__(cls, us: float, noise: float = 1.0):
        obj = super().__new__(cls, us)
        obj.noise = noise
        return obj


def refuse_cpu_children_on_tpu(bench: str) -> None:
    """Subprocess benches time CPU-simulated meshes in child processes.  On
    a TPU host the parent (which has already touched JAX) holds the chip,
    and the children would quietly time the CPU instead — refuse."""
    import jax
    if jax.devices()[0].platform == "tpu":
        raise RuntimeError(
            f"{bench} times CPU-simulated multi-device child processes, but "
            f"this process sees a TPU ({jax.devices()[0].device_kind}) and "
            f"holds it; run this bench with JAX_PLATFORMS=cpu, which makes "
            f"it a CPU smoke check, not a chip measurement")


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> TimedUs:
    """Median wall time per call in microseconds (blocking on outputs),
    with the max/median dispersion across the timed iterations attached
    as `.noise`."""
    m = measure(fn, *args, warmup=warmup, iters=iters)
    return TimedUs(m.us, m.noise)


def emit(name: str, us: float, derived: str = "",
         edges: Optional[int] = None, gate: bool = True,
         noise: Optional[float] = None):
    """`gate=False` marks entries whose ABSOLUTE time is scheduler-dominated
    (e.g. multi-device runs on oversubscribed CI hosts): they stay in the
    artifact for trend reading and still fail `compare.py` when missing,
    but are exempt from the regression ratio gate.

    `noise` (defaulting to the `.noise` a `time_fn` result carries)
    records the entry's repeated-run dispersion; committed into
    BENCH_baseline.json it becomes that entry's regression margin."""
    rec = {"name": name, "us_per_call": round(us, 3)}
    if edges:
        rec["ns_per_edge"] = round(us * 1e3 / edges, 6)
    if derived:
        rec["derived"] = derived
    if not gate:
        rec["gate"] = False
    if noise is None:
        noise = getattr(us, "noise", None)
    if noise is not None:
        rec["noise"] = round(float(noise), 3)
    RESULTS.append(rec)
    print(f"{name},{us:.1f},{derived}", flush=True)
