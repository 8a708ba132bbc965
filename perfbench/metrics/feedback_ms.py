"""Feedback (``core/coordinator.py::_feedback``, with the PPO update of
``core/identifier.py::maybe_update`` when one runs): the program's
``feedback`` span, one per slot, mean.  Also logs the ``ppo_update``
spans' share of the feedback time."""
from perfbench import progtrace
from perfbench.metrics import _spans


def read(run):
    v = _spans.intervals(run, "feedback")
    if not v:
        return None
    upd = _spans.intervals(run, "ppo_update")
    progtrace.log(f"trace: feedback {1e3 * sum(v):.1f} ms over {len(v)} "
                  f"slots; ppo_update {1e3 * sum(upd):.1f} ms in "
                  f"{len(upd)} updates")
    return 1e3 * sum(v) / len(v)
