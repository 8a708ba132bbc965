"""Device, as the program's host work leaves it: share of the device
window in which no program ran on the chip while an ``obs.request``
annotation was open (a slot in flight: idle the host's own work caused,
as against idle for want of requests), in %.  Also logs all idle of the
window, that inside ``obs.request`` split by the innermost ``obs.*``
annotation open over it (top 5), and that outside.  Source: the
program's span annotations and ``XLA Modules`` events in the trace."""
from perfbench import progtrace


def read(run):
    win = run.get("device_window")
    got = progtrace.idle_under(run, "request")
    if win is None or got is None:
        return None
    total = sum(idle for _, _, idle in progtrace.idle_pieces(run))
    split = sorted(progtrace.idle_by_innermost(run, "request").items(),
                   key=lambda kv: -kv[1])
    progtrace.log(
        f"trace: no program on the chip {total / 1e9:.4f} s of the "
        f"{(win[1] - win[0]) / 1e9:.3f} s covered window; inside "
        f"obs.request {got[0] / 1e9:.4f} s, by innermost obs.* annotation "
        f"{[[k, round(v / 1e9, 4)] for k, v in split[:5]]}; outside "
        f"{(total - got[0]) / 1e9:.4f} s")
    return 100.0 * got[0] / (win[1] - win[0])
