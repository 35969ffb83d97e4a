"""setup_s: seconds from process start to the window's start, on the host
clock: JAX start-up, graph generation, ingress, compile or cache load."""


def read(record, cell):
    return record.setup_s
