"""Routing (``core/identifier.py``, ``core/inter_node.py``): per slot,
the program's ``identify`` plus ``route`` spans, mean over slots."""
from perfbench.metrics import _spans


def read(run):
    ident = _spans.intervals(run, "identify")
    route = _spans.intervals(run, "route")
    if not ident:
        return None
    return 1e3 * (sum(ident) + sum(route)) / len(ident)
