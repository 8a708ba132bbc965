"""Engine prefill on the device: the ``XLA Modules`` time of the
programs the program's table (``serving/engine.py::PROGRAM_LAYERS``)
puts in ``prefill``, over the per-request ``prefill`` spans that start
in the device window on the shared clock, in ms a request."""
from perfbench import progtrace
from perfbench.metrics import _spans


def read(run):
    win = run.get("device_window")
    pre = _spans.spans_in_window(run, "prefill")
    if win is None or pre is None:
        return None
    n = sum(1 for e in pre if e.get("trace", "-") != "-")
    t = progtrace.device_seconds(progtrace.events(run), "prefill", *win)
    return 1e3 * t / n if t and n else None
