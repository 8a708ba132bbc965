"""Engine decode on the device: the ``XLA Modules`` time of the programs
the program's table (``serving/engine.py::PROGRAM_LAYERS``) puts in
``decode``, over the decode-loop steps of the ``decode_segment`` spans
(attribute ``steps``) that start in the device window on the shared
clock, in ms a step."""
from perfbench import progtrace
from perfbench.metrics import _spans


def read(run):
    win = run.get("device_window")
    segs = _spans.spans_in_window(run, "decode_segment")
    if win is None or not segs:
        return None
    steps = sum((e.get("attrs") or {}).get("steps", 0)
                for e in _spans.once(segs))
    t = progtrace.device_seconds(progtrace.events(run), "decode", *win)
    return 1e3 * t / steps if t and steps else None
