"""superstep_ms: device busy milliseconds per superstep, from the trace:
busy time inside the traced window over the supersteps of its jobs."""


def read(record, cell):
    steps = sum(record.supersteps)
    if record.trace is None or steps == 0:
        return None
    return record.trace.busy_s * 1e3 / steps
