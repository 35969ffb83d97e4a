"""superstep_roofline: percent of the HBM roofline a superstep reaches.

The least time a superstep could take is the bytes it needs over the
chip's peak HBM bandwidth (peaks.json); the share is that over the
measured device busy time per superstep.  The bytes come from the program
file's work function `superstep_bytes(V, E)`, which counts what the
algorithm must read and write whatever route runs it.  BENCHMARK.json
lists the cells whose program has one (frontier-driven work has no fixed
count).
"""


def read(record, cell):
    steps = sum(record.supersteps)
    if record.trace is None or steps == 0:
        return None
    least_s = (cell.program.superstep_bytes(record.num_vertices,
                                            record.num_edges)
               / record.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (record.trace.busy_s / steps)
