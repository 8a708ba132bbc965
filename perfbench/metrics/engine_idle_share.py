"""Node queue (``ContinuousQueue.run`` under ``LiveEdgeNode``'s
``generate`` span): share of the time an ``obs.generate`` annotation is
open in the device window in which no program ran on the chip -- the
host's round trips between the device programs of one engine run, in
%.  Source: the program's span annotations and ``XLA Modules`` events
in the trace."""
from perfbench import progtrace


def read(run):
    got = progtrace.idle_under(run, "generate")
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
