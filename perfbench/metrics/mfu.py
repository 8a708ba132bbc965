"""Device, whole step: model FLOPs of the tokens actually prefilled
(forked prefixes not again) and generated, per node from the
configuration's shapes (``perfbench/peaks.py``), over the measured
window times the chip's peak, in %.  Every request of the window counts,
over the window's span on the harness's clock (the traced run's
profiler covers only the end of the window); a run whose trace could
not be read reads as nothing, as the other device numbers do."""
from perfbench import peaks


def read(run):
    if run.get("device_window") is None:
        return None
    span = run["win"]["span_s"]
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
