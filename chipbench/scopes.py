#!/usr/bin/env python3
"""Device self time per engine scope, read from a profiler trace.

The engine names its device work with three `jax.named_scope`s:
`gre.scatter`, `gre.combine` and `gre.apply` (`src/repro/core/engine.py`,
`frontier.py`).  XLA keeps each op's JAX name stack in the op's metadata,
and the profiler writes it into the `.xplane.pb` as the `tf_op` stat of
the op's event metadata, for example
`jit(run)/while/body/cond/branch_1_fun/gre.scatter/jit(_take)/gather`.
`jax.profiler.ProfileData` returns only per-event stats, so this module
decodes the few XSpace fields it needs from the protobuf wire format
itself (tensorflow is not a dependency): plane names, lines, each event's
`metadata_id`, each event metadata's stats, and the stat metadata names.

An op's scope is the innermost `gre.*` component of its `tf_op`; a fusion
carries the metadata of its root op, so it counts under that op's scope.
Times come from `chipbench.trace.events` and self times from
`chipbench.trace.self_times` (an op's time less the ops nested in it), so
they are computed as the benchmark's other trace readings are.

    python3 chipbench/scopes.py <.xplane.pb or a directory holding one>

prints the self seconds per scope, summed over the TPU chips traced,
inside the `chipbench.window` span where the trace has one and over the
whole trace otherwise.  Ops under no `gre.*` scope count as "unscoped".
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

SCOPES = ("gre.scatter", "gre.combine", "gre.apply")
UNSCOPED = "unscoped"


def fields(buf: bytes):
    """(field number, value) of each field of one protobuf message: an
    int for varint and fixed-width fields, a memoryview for
    length-delimited ones."""
    buf, i, n = memoryview(buf), 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            if byte < 0x80:
                return value
            shift += 7

    while i < n:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            yield number, varint()
        elif wire == 2:
            size = varint()
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield number, int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _string(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map(entries) -> dict:
    """A protobuf map<int64, message> from its repeated entries."""
    out = {}
    for entry in entries:
        got = dict(fields(entry))
        out[got.get(1, 0)] = got.get(2, b"")
    return out


def _plane(buf) -> tuple:
    """(name, lines, event metadata, stat metadata) of one XPlane."""
    name, lines, events, stats = "", [], [], []
    for number, value in fields(buf):
        if number == 2:
            name = _string(value)
        elif number == 3:
            lines.append(value)
        elif number == 4:
            events.append(value)
        elif number == 5:
            stats.append(value)
    return name, lines, events, stats


def _stat_names(entries) -> dict:
    return {key: _string(dict(fields(value)).get(2, b""))
            for key, value in _map(entries).items()}


def _str_stat(stats, names: dict, want: str):
    """The string value of stat `want` in an XStat list: a `str_value`
    (field 5) or a `ref_value` (field 7) naming a stat metadata entry."""
    for stat in stats:
        got = dict(fields(stat))
        if names.get(got.get(1)) != want:
            continue
        if 5 in got:
            return _string(got[5])
        if 7 in got:
            return names.get(got[7])
    return None


def op_names(path: Path) -> dict:
    """`{chip id: [tf_op or None, ...]}`: the `tf_op` of each event of each
    device plane's "XLA Ops" line, in the line's order (the order of
    `chipbench.trace.events`' ops of that chip)."""
    space = Path(path).read_bytes()
    out = {}
    for number, value in fields(space):
        if number != 1:
            continue
        name, lines, events, stats = _plane(value)
        device = trace.DEVICE_PLANE.fullmatch(name)
        if not device:
            continue
        names = _stat_names(stats)
        tf_op = {key: _str_stat([v for n, v in fields(meta) if n == 5],
                                names, "tf_op")
                 for key, meta in _map(events).items()}
        for line in lines:
            got = list(fields(line))
            if _string(next((v for n, v in got if n == 2), b"")) != \
                    trace.OPS_LINE:
                continue
            ops = out.setdefault(int(device.group(1)), [])
            for n, event in got:
                if n == 4:
                    ops.append(tf_op.get(dict(fields(event)).get(1, 0)))
    return out


def scope_of(tf_op) -> str:
    """The innermost `gre.*` component of an op's name stack."""
    inner = [part for part in (tf_op or "").split("/")
             if part.startswith("gre.")]
    return inner[-1] if inner else UNSCOPED


def scoped_ops(path: Path) -> dict:
    """`{chip id: [(tf_op, start_ns, end_ns), ...]}` of the XLA ops."""
    _, ops = trace.events(path)
    names = op_names(path)
    out = {}
    for chip, events in ops.items():
        tf_ops = names.get(chip, [])
        if len(tf_ops) != len(events):
            raise ValueError(f"{path}: chip {chip} has {len(events)} ops but"
                             f" {len(tf_ops)} op metadata entries")
        out[chip] = [(tf_op, s, e) for tf_op, (_, s, e)
                     in zip(tf_ops, events)]
    return out


def scope_times(path: Path, device_ids, lo=None, hi=None) -> dict:
    """`{scope: self seconds}` of the ops of `device_ids` inside
    [lo, hi) ns (the whole trace by default), summed over the chips."""
    ops = scoped_ops(path)
    lo = float("-inf") if lo is None else lo
    hi = float("inf") if hi is None else hi
    out = {}
    for chip in device_ids:
        named = [(scope_of(tf_op), s, e) for tf_op, s, e in ops.get(chip, ())]
        for scope, ns in trace.self_times(named, lo, hi):
            out[scope] = out.get(scope, 0.0) + ns * 1e-9
    return out


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = Path(args[0])
    if path.is_dir():
        path = next(path.rglob("*.xplane.pb"))
    spans, ops = trace.events(path)
    window = [(s, e) for name, s, e in spans if name == trace.WINDOW]
    lo, hi = window[0] if len(window) == 1 else (None, None)
    print(json.dumps(scope_times(path, sorted(ops), lo, hi), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
