"""device_idle_share: percent of the traced window in which no operation
ran on the device: 100 * (1 - busy / window)."""


def read(record, cell):
    if record.trace is None:
        return None
    return 100.0 * (1.0 - record.trace.busy_s / record.trace.window_s)
