"""compile_s: seconds to lower and compile `GREEngine.run` ahead of the
window, on the host clock (a load from the persistent cache after the
first run in a checkout)."""


def read(record, cell):
    return record.compile_s
