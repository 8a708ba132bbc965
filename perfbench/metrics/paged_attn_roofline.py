"""Kernel (``kernels/paged_attention.py``): the least time the chip
needs for the paged decode reads -- the live tokens' K and V of every
full-attention layer at the model's dtype (bf16), at peak HBM bandwidth
-- over the kernel's device time, in %.  Both sides cover the device
window, the part of the window that the trace covers and the reduction
read: the kernel's time in it, and the reads of the requests answered
between the profiler's start and the device window's end (it starts
between two slots, with no request in flight, so each of them was
served whole inside).  The harness's
clock is put on the trace's by ``bench.traced``, which it entered at the
harness time ``traced_at_s``."""
from perfbench import devtrace, peaks

KERNEL = "paged_decode_attention"


def read(run):
    win = run.get("device_window")
    covered = run.get("trace_window")
    events = run.get("events") or []
    if win is None or covered is None:
        return None
    t_kernel = devtrace.kernel_seconds(events, KERNEL, *win)
    if t_kernel <= 0:
        return None
    since = run["traced_at_s"]              # harness seconds, trace covered[0]
    until = since + (win[1] - covered[0]) / 1e9
    models = run["model_dicts"]
    nbytes = 0.0
    for r in run["recs"]:
        if r.node < 0 or not r.tokens or not since <= r.done <= until:
            continue
        nbytes += peaks.paged_kv_bytes(models[r.node], len(r.prompt),
                                       len(r.tokens))
    _, bw = peaks.peaks(run["device"]["kind"])
    return 100.0 * (nbytes / bw) / t_kernel if nbytes else None
