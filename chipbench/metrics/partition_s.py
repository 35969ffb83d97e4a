"""partition_s: seconds of host partitioning in set-up, from the program's
host spans: `gre.ingress.hdrf` (the streaming edge placement) plus
`gre.ingress.agent_graph` (`build_agent_graph`).  None where the run
recorded either span not at all."""

SPANS = ("gre.ingress.hdrf", "gre.ingress.agent_graph")


def read(record, cell):
    spans = getattr(record, "spans", None) or {}
    if not all(name in spans for name in SPANS):
        return None
    return sum(spans[name] for name in SPANS)
