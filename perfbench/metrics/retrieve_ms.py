"""Retrieval (``cluster/node.py::_retrieve``, ``retrieval/index.py``):
the program's ``retrieve`` span, one per node-slot, mean."""
from perfbench.metrics import _spans


def read(run):
    v = _spans.intervals(run, "retrieve")
    return 1e3 * sum(v) / len(v) if v else None
