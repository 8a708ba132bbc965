"""Kernel (``kernels/paged_attention.py``): the least time the chip
needs for the paged decode reads -- the live tokens' K and V of every
full-attention layer at the model's dtype (bf16), at peak HBM bandwidth
-- over the kernel's device time, in %.  Both sides cover the part of
the window that the device trace recorded: the kernel's time in it, and
the reads of the requests answered before it ends."""
from perfbench import devtrace, peaks

KERNEL = "paged_decode_attention"


def read(run):
    win = run.get("device_window")
    full = run.get("trace_window")
    events = run.get("events") or []
    if win is None or full is None:
        return None
    t_kernel = devtrace.kernel_seconds(events, KERNEL, *win)
    if t_kernel <= 0:
        return None
    until = (win[1] - full[0]) / 1e9        # seconds after the window opened
    models = run["model_dicts"]
    nbytes = 0.0
    for r in run["recs"]:
        if r.node < 0 or not r.tokens or r.done > until:
            continue
        nbytes += peaks.paged_kv_bytes(models[r.node], len(r.prompt),
                                       len(r.tokens))
    _, bw = peaks.peaks(run["device"]["kind"])
    return 100.0 * (nbytes / bw) / t_kernel if nbytes else None
