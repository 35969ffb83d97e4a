"""ingress_s: seconds of `DevicePartition.from_graph` on the host clock,
ending in `block_until_ready` (host sorts, CSR layout, degree buckets,
block table, copies to the device)."""


def read(record, cell):
    return record.ingress_s
