"""peak_hbm_gb: the fullest chip's `peak_bytes_in_use` after the window,
in 1e9 bytes.  It guards the largest graph one chip can hold.

The value is the device allocator's counter, which the benchmark's
process reads on the host (`Device.memory_stats()`) once the window has
closed: no clock and no trace.  BENCHMARK.json labels it `host_clock`,
the one end-to-end source that is read on the host and not from the
profiler's trace.
"""


def read(record, cell):
    return None if record.peak_bytes is None else record.peak_bytes / 1e9
