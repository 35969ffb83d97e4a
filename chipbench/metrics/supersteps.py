"""supersteps: `EngineState.step` of the window's last job, the plan
executor's superstep count (program counter)."""


def read(record, cell):
    return record.supersteps[-1] if record.supersteps else None
