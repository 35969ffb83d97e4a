"""The benchmark's data and the files it is made of, found by name.

`BENCHMARK.json` (at the checkout's root) lists the cells: each names a
configuration and a traffic mix.  Everything else is a file of its own
under the benchmark's directory:

  configs/<config>.json     the graph deployment: its generator, sizes,
                            guarantees (the limits of the comparison)
  traffic/<traffic>.json    the job: its algorithm, runner and parameters
  generators/<kind>.py      `generate(config, seed) -> EdgeList`
  programs/<algorithm>.py   the engine program (`build`), its plain
                            reference, `compare`, the lower-precision
                            `control`, and optionally its work function
  runners/<runner>.py       `run(...) -> RunRecord`: set-up, the measured
                            window, the check
  metrics/<metric>.py       `read(record, cell) -> float | None`

A new graph, job, algorithm, runner or metric is a new file plus its
entries in BENCHMARK.json; no file that exists has to change.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Optional

import numpy as np

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


class BenchmarkError(ValueError):
    """The benchmark's data break its own rules."""


class NoAccelerator(RuntimeError):
    """JAX found no TPU, too few chips, or a chip without a peaks row."""


@dataclasses.dataclass
class EdgeList:
    """A generated graph on the host: directed edges, int32 vertex ids."""

    num_vertices: int
    src: np.ndarray
    dst: np.ndarray
    weight: Optional[np.ndarray] = None   # float32 per edge, or None

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


@dataclasses.dataclass
class TraceSummary:
    """What `chipbench.trace.reduce` reads from one traced window."""

    busy_s: float          # union of device-op intervals, mean over chips
    window_s: float        # length of the traced window
    device_ops: list       # [[op name, seconds]], most time first, <= 10
    idle_gaps: list        # [[host span, seconds]], longest first, <= 10


@dataclasses.dataclass
class RunRecord:
    """Everything one run measured; the metric readers take from it."""

    setup_s: float
    ingress_s: float
    compile_s: float
    window_s: float                # window start to the end of the last job
    supersteps: list               # per job in the window
    peak_bytes: Optional[int]      # peak_bytes_in_use after the window
    num_vertices: int
    num_edges: int
    compared: dict                 # name -> (worst value over jobs, limit)
    failed: int                    # jobs over any limit
    peak: dict                     # the chip's row of peaks.json
    trace: Optional[TraceSummary] = None

    @property
    def jobs(self) -> int:
        return len(self.supersteps)


@dataclasses.dataclass
class Cell:
    """One `workloads` entry with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    generator: ModuleType
    program: ModuleType
    runner: ModuleType

    @property
    def limits(self) -> dict:
        return self.config["guarantees"][self.traffic["algorithm"]]


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise BenchmarkError(f"{what} {name!r}: a name is 1-64 of "
                             f"A-Z a-z 0-9 _ . - and starts with no . or -")
    return name


def check_line(text, what: str) -> str:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        raise BenchmarkError(f"{what}: 1-200 characters on one line, no tab")
    return text


def validate(bench: dict) -> dict:
    """Refuse a BENCHMARK.json whose names, units or keys break the rules
    the harness relies on; returns it unchanged."""
    if set(bench) != TOP_KEYS:
        raise BenchmarkError(f"BENCHMARK.json keys {sorted(bench)}; "
                             f"want {sorted(TOP_KEYS)}")
    for section, (required, optional) in ENTRY_KEYS.items():
        seen = set()
        for entry in bench[section]:
            keys = set(entry)
            if not required <= keys <= required | optional:
                raise BenchmarkError(f"{section} entry {entry.get('name')!r}"
                                     f" has keys {sorted(keys)}")
            name = check_name(entry["name"], f"{section} name")
            if name in seen:
                raise BenchmarkError(f"{section}: {name!r} twice")
            seen.add(name)
            if "unit" in entry and not UNIT.fullmatch(str(entry["unit"])):
                raise BenchmarkError(f"unit {entry['unit']!r} of {name}: 1-16"
                                     f" of A-Z a-z 0-9 _ / % . -, no space")
            if "better" in entry and entry["better"] not in ("lower",
                                                             "higher"):
                raise BenchmarkError(f"{name}: better is lower or higher")
            for key in ("why", "layer", "source"):
                if key in entry:
                    check_line(entry[key], f"{name} {key}")
    metric_names = {m["name"] for m in bench["end_to_end"]}
    if metric_names & {m["name"] for m in bench["per_layer"]}:
        raise BenchmarkError("a metric name is both end-to-end and per-layer")
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        for key in c["reduced"]:
            check_name(key, f"config {c['name']} reduced key")
    for w in bench["workloads"]:
        if w["config"] not in configs:
            raise BenchmarkError(f"cell {w['name']}: no config "
                                 f"{w['config']!r}")
        check_name(w["traffic"], f"cell {w['name']} traffic")
        if w["chips"] not in (1, 4):
            raise BenchmarkError(f"cell {w['name']}: chips is 1 or 4")
    for m in bench["end_to_end"]:
        if m["source"] not in E2E_SOURCES:
            raise BenchmarkError(f"{m['name']}: end-to-end source is "
                                 f"host_clock or device_trace")
    for m in bench["per_layer"]:
        if m["source"] not in SOURCES:
            raise BenchmarkError(f"{m['name']}: source {m['source']!r}")
        if m["moves"] not in metric_names:
            raise BenchmarkError(f"{m['name']} moves {m['moves']!r}, which "
                                 f"is no end-to-end metric")
    for m in bench["end_to_end"] + bench["per_layer"]:
        unknown = set(m.get("workloads", ())) - cells
        if unknown:
            raise BenchmarkError(f"{m['name']} lists unknown cells "
                                 f"{sorted(unknown)}")
    return bench


def load_benchmark(root: Path) -> dict:
    return validate(json.loads((Path(root) / "BENCHMARK.json").read_text()))


def plugin(bench_dir: Path, kind: str, name: str) -> ModuleType:
    """Import `<bench_dir>/<kind>/<name>.py` once per process (a name may
    hold `.` and `-`, so the file is loaded from its path, under a module
    name made from that path)."""
    path = Path(bench_dir) / kind / f"{check_name(name, kind)}.py"
    if not path.is_file():
        raise BenchmarkError(f"no {kind} file {path}")
    key = "chipbench_plugin_" + re.sub(r"[^A-Za-z0-9_]", "_",
                                       str(path.resolve()))
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def data_file(bench_dir: Path, kind: str, name: str) -> dict:
    path = Path(bench_dir) / kind / f"{check_name(name, kind)}.json"
    if not path.is_file():
        raise BenchmarkError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_cell(root: Path, bench_dir: Path, bench: dict, name: str) -> Cell:
    """The cell `name` of `bench`, with its configuration, traffic and
    code files from `bench_dir`."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(f"no cell {name!r} in BENCHMARK.json")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = json.loads((Path(root) / config_entry["file"]).read_text())
    traffic = data_file(bench_dir, "traffic", entry["traffic"])
    if traffic["algorithm"] not in config.get("guarantees", {}):
        raise BenchmarkError(f"config {entry['config']} states no guarantee"
                             f" for {traffic['algorithm']!r}")
    return Cell(name=name, chips=entry["chips"], config=config,
                traffic=traffic,
                generator=plugin(bench_dir, "generators",
                                 config["generator"]),
                program=plugin(bench_dir, "programs", traffic["algorithm"]),
                runner=plugin(bench_dir, "runners", traffic["runner"]))


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of `cell` reports: end-to-end without the
    trace, per-layer with it, each where its `workloads` list allows."""
    section = bench["per_layer" if trace else "end_to_end"]
    return [m for m in section if cell in m.get("workloads", (cell,))]


def accelerator(chips: int, peaks: dict):
    """The first `chips` TPUs and their peaks row; NoAccelerator else."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"the benchmark needs a TPU; JAX found "
                            f"{devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found "
                            f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoAccelerator(f"no row for device kind {kind!r} in peaks.json")
    return devices, peaks[kind]


def read_metrics(bench_dir: Path, entries: list, record: RunRecord,
                 cell: Cell) -> dict:
    """Each entry's reader applied to the record; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for entry in entries:
        value = plugin(bench_dir, "metrics", entry["name"]).read(record, cell)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def result(record: RunRecord, metrics: dict, devices) -> dict:
    """The run's result line, with the compared numbers last."""
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": record.peak_bytes}
    line = {"correct": record.jobs > 0 and record.failed == 0,
            "attempted": record.jobs, "failed": record.failed,
            "metrics": metrics, "device": device}
    if record.trace is not None:
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_s
        line["breakdown"] = {"device_ops": record.trace.device_ops,
                             "idle_gaps": record.trace.idle_gaps}
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in record.compared.items()}
    return line
