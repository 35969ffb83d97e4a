"""replication_factor: copies of a vertex over the Agent-Graph's
partitions, (masters + scatter agents + combiner agents) / masters, the
program's counter (`AgentGraph.counters`).  None where the run recorded
no such counter."""


def read(record, cell):
    counters = getattr(record, "counters", None) or {}
    return counters.get("replication_factor")
