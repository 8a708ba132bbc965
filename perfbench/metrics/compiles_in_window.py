"""Compiles (the program's ``compile`` spans, one per backend compile,
from ``obs.trace.watch_compiles``): how many overlap the measured
window -- a shape that set-up did not warm.  None for a program without
the listener."""


def read(run):
    from repro.obs import trace
    if not hasattr(trace, "watch_compiles"):
        return None
    w0 = run["probe"].t0
    w1 = w0 + run["win"]["span_s"]
    return float(sum(1 for e in run.get("spans", [])
                     if e.get("name") == "compile" and e.get("t1") is not None
                     and e["t1"] > w0 and e["t0"] < w1))
