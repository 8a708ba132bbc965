"""Engine decode on the host (``ContinuousSession.run_segment``): the
program's ``decode_segment`` spans, each batched interval once, over the
decode-loop steps they carry (attribute ``steps``), in ms a step (the
twin of ``decode_step_ms``)."""
from perfbench.metrics import _spans


def read(run):
    segs = _spans.once(e for e in run.get("spans", [])
                       if e.get("name") == "decode_segment"
                       and "steps" in (e.get("attrs") or {}))
    steps = sum(e["attrs"]["steps"] for e in segs)
    return 1e3 * sum(e["t1"] - e["t0"] for e in segs) / steps \
        if steps else None
