"""Device, whole step: model FLOPs of the tokens actually prefilled
(forked prefixes not again) and generated, per node from the
configuration's shapes (``perfbench/peaks.py``), over the traced window
times the chip's peak, in %."""
from perfbench import peaks


def read(run):
    win = run.get("trace_window")
    if win is None:
        return None
    span = (win[1] - win[0]) / 1e9
    models = run["model_dicts"]
    flops = 0.0
    for r in run["recs"]:
        if r.node < 0 or not r.tokens:
            continue
        m = models[r.node]
        p = min(r.prefix_len, len(r.prompt) - 1) if r.prefix_len else 0
        flops += peaks.request_flops(m, len(r.prompt) - p, len(r.prompt),
                                     len(r.tokens))
    for n, plen in run["probe"].prefix_prefills:
        flops += peaks.prefix_flops(models[n], plen)
    peak, _ = peaks.peaks(run["device"]["kind"])
    return 100.0 * flops / (span * peak) if flops and span > 0 else None
