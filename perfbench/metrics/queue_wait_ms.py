"""Node queue (``serving/scheduler.py``): the program's ``queue_wait``
span per request (submit to admission), mean."""
from perfbench.metrics import _spans


def read(run):
    v = _spans.per_request(run, "queue_wait")
    return 1e3 * sum(v) / len(v) if v else None
