"""Engine prefill (``serving/engine.py`` refill and prefix prefill): the
program's ``prefill`` span per admitted request, mean."""
from perfbench.metrics import _spans


def read(run):
    v = _spans.per_request(run, "prefill")
    return 1e3 * sum(v) / len(v) if v else None
