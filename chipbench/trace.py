"""Device busy time, idle share and where the time went, from one traced
window.

The runner records the window under `jax.profiler` and marks it with the
host span `chipbench.window` (a `jax.profiler.TraceAnnotation`); the jobs
inside carry spans named `chipbench.<phase>`.  The profiler writes an
`.xplane.pb`; in it each chip is a plane `/device:TPU:<id>` whose line
"XLA Ops" holds one event per operation that ran, on the same clock as
the host spans.

  busy_s      the union of the op intervals inside the window, mean over
              the chips traced
  window_s    the length of the `chipbench.window` span
  device_ops  the 10 ops with the most self time inside the window (an
              op's time less the ops nested in it: a while loop or a
              conditional spans the ops of its body on the same line)
  idle_gaps   the 10 longest stretches inside the window with no op on
              the first chip, each named by the innermost `chipbench.*`
              host span at its start
"""
from __future__ import annotations

import re
from pathlib import Path

from chipbench.harness import TraceSummary

WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
TOP = 10


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off: the host spans that
    the reduction reads are TraceMe annotations, and tracing every Python
    call would slow the host inside the window."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


def events(path: Path):
    """(host spans, ops by chip id) of an `.xplane.pb`: host spans are
    (name, start_ns, end_ns) of every `chipbench.*` event; ops are
    (name, start_ns, end_ns) of each device plane's "XLA Ops" line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(Path(path).read_bytes())
    spans, ops = [], {}
    for plane in data.planes:
        device = DEVICE_PLANE.fullmatch(plane.name)
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ops.setdefault(int(device.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not device:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return spans, ops


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    merged = []
    for start, end in sorted((max(s, lo), min(e, hi))
                             for s, e in intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def short_name(hlo: str) -> str:
    """`%fusion.3 = f32[4097]{0:T(1024)} fusion(...), kind=kCustom, ...`
    as `fusion.3 fusion kCustom -> f32[4097]`: the op, its opcode and
    fusion kind, and its result without layouts, at most 120 letters."""
    m = re.match(r"%?(\S+) = (.*?) ([\w-]+)\(", hlo)
    if not m:
        return hlo[:120]
    name, shape, opcode = m.groups()
    kind = re.search(r"kind=(\w+)", hlo)
    shape = re.sub(r"\{[^{}]*\}", "", shape).replace("/*index=5*/", "")
    text = f"{name} {opcode}{' ' + kind.group(1) if kind else ''} -> {shape}"
    return text[:120]


def self_times(ops, lo: float, hi: float) -> list:
    """(short name, self ns) of each op inside [lo, hi): its clipped time
    less that of the ops nested directly inside it."""
    out, stack = [], []        # stack: indices into out, with their ends
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= e - s
        out.append([short_name(name), e - s])
        stack.append((len(out) - 1, e))
    return out


def reduce(path: Path, device_ids) -> TraceSummary:
    """The window's summary; raises when the trace holds no window or no
    device op inside it (a reduction that reads nothing is a fault)."""
    spans, ops = events(path)
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW} spans, want 1")
    lo, hi = windows[0]
    busy = {}
    for dev in device_ids:
        busy[dev] = union([(s, e) for _, s, e in ops.get(dev, ())], lo, hi)
    busy_ns = [sum(e - s for s, e in iv) for iv in busy.values()]
    if not busy_ns or min(busy_ns) <= 0:
        raise ValueError(f"{path}: no device op inside the window on chips "
                         f"{list(device_ids)} (planes with ops: {sorted(ops)})")
    by_op = {}
    for dev in device_ids:
        for name, ns in self_times(ops[dev], lo, hi):
            by_op[name] = by_op.get(name, 0.0) + ns * 1e-9
    first = busy[device_ids[0]]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inner = [(name, s, e) for name, s, e in spans if name != WINDOW]

    def host_span(t):
        around = [(s, name) for name, s, e in inner if s <= t < e]
        return max(around)[1] if around else "outside chipbench spans"

    gaps.sort(key=lambda g: g[0] - g[1])
    return TraceSummary(
        busy_s=sum(busy_ns) / len(busy_ns) * 1e-9,
        window_s=(hi - lo) * 1e-9,
        device_ops=[[name, sec] for name, sec in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[host_span(s), (e - s) * 1e-9] for s, e in gaps[:TOP]])
