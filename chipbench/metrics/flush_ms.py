"""flush_ms: device self milliseconds per superstep under the scope
`gre.exchange.flush` (the combiner flush: the gather of combiner partials,
the `all_to_all` and the segment fold into master slots), from the trace:
the scope's self time inside the window, mean over the chips, over the
supersteps of the window's jobs.  None where the run recorded no such
scope."""


def read(record, cell):
    scopes = getattr(record, "scopes", None) or {}
    steps = sum(record.supersteps)
    if "gre.exchange.flush" not in scopes or steps == 0:
        return None
    return scopes["gre.exchange.flush"] * 1e3 / steps
