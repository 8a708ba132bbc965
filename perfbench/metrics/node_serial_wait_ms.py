"""Dispatch (``core/coordinator.py::_dispatch``): mean wait from the
start of the ``run_slot`` call to the start of the request's own node's
``process_slot`` -- the time spent behind the nodes served before it.
Source: the harness's clock around ``process_slot``."""


def read(run):
    recs = [r for r in run["recs"] if r.node_start >= 0]
    if not recs:
        return None
    return 1e3 * sum(r.node_start - r.dispatch for r in recs) / len(recs)
