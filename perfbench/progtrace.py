"""The program's timeline in the profiler trace: the ``obs.<span>``
annotations its spans enter while tracing is on and the device programs
it runs (one ``XLA Modules`` event per execution), both among the
events ``devtrace.read`` keeps.  A program without the annotations (or
without the program table) reads as nothing: every reader then returns
None.  Busy, here, is the union of the device's program executions (its
``XLA Modules`` intervals), not of its ops: idle is time in which no
program ran on the chip.  The clock that puts the program's span times
on the trace's is ``perfbench/metrics/_spans.py``'s.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import devtrace
from perfbench.devtrace import Event

MODULES = devtrace.MODULES
PREFIX = "obs."


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ events


def events(run) -> List[Event]:
    """The run's program events, ``XLA Modules`` executions and ``obs.*``
    annotations, from the events its loader kept (once a run)."""
    if "_progtrace_events" not in run:
        run["_progtrace_events"] = [
            e for e in run.get("events") or []
            if e.line == MODULES or e.name.startswith(PREFIX)]
    return run["_progtrace_events"]


# ---------------------------------------------------------------- programs


def program_name(module: str) -> str:
    """``jit__decode_cont_impl(123)`` -> ``_decode_cont_impl``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def program_layers() -> Optional[Dict[str, str]]:
    """The program's table of device programs by layer, if it has one."""
    try:
        from repro.serving.engine import PROGRAM_LAYERS
    except ImportError:
        return None
    return dict(PROGRAM_LAYERS)


def device_seconds(evs: Sequence[Event], layer: str, t0_ns: float,
                   t1_ns: float) -> Optional[float]:
    """Summed device time of the executions, starting in [t0, t1], of
    the programs the program's table puts in ``layer``."""
    table = program_layers()
    if table is None:
        return None
    return sum(e.dur_ns for e in evs if e.line == MODULES
               and t0_ns <= e.start_ns < t1_ns
               and table.get(program_name(e.name)) == layer) / 1e9


def busy(evs: Sequence[Event], t0_ns: float, t1_ns: float
         ) -> List[Tuple[float, float]]:
    """Union of the first TPU's program executions inside [t0, t1]."""
    planes = sorted({e.plane for e in evs if e.line == MODULES})
    if not planes:
        return []
    return devtrace.union(
        (max(e.start_ns, t0_ns), min(e.start_ns + e.dur_ns, t1_ns))
        for e in evs if e.plane == planes[0] and e.line == MODULES
        and e.start_ns < t1_ns and e.start_ns + e.dur_ns > t0_ns)


# ------------------------------------------------------------------- idle


def pieces(anns: Sequence[Event], busy_iv: Sequence[Tuple[float, float]],
           t0_ns: float, t1_ns: float) -> List[Tuple[float, Tuple[str, ...],
                                                     float]]:
    """[t0, t1] cut at every annotation boundary: per piece, its length,
    the names of the annotations open over it (outermost first: one
    thread's annotations nest) and the device's idle time in it."""
    live = [i for i, e in enumerate(anns)
            if e.start_ns < t1_ns and e.start_ns + e.dur_ns > t0_ns]
    # at one instant: ends before starts, outer starts before inner ones,
    # inner ends before outer ones
    marks = sorted([(max(anns[i].start_ns, t0_ns), 1, -anns[i].dur_ns, i)
                    for i in live] +
                   [(min(anns[i].start_ns + anns[i].dur_ns, t1_ns), 0,
                     anns[i].dur_ns, i) for i in live])
    iv = np.asarray(busy_iv, np.float64).reshape(-1, 2)
    bs, be = iv[:, 0], iv[:, 1]
    cum = np.concatenate([[0.0], np.cumsum(be - bs)])

    def busy_until(t: np.ndarray) -> np.ndarray:
        if not len(bs):
            return np.zeros_like(t)
        i = np.searchsorted(bs, t, side="right") - 1
        k = np.maximum(i, 0)
        part = np.clip(t - bs[k], 0.0, be[k] - bs[k])
        return np.where(i >= 0, cum[k] + part, 0.0)

    out_a, out_b, stacks = [], [], []
    open_: List[int] = []
    prev = t0_ns
    for t, kind, _, i in marks + [(t1_ns, 2, 0.0, -1)]:
        if t > prev:
            out_a.append(prev)
            out_b.append(t)
            stacks.append(tuple(anns[j].name for j in open_))
            prev = t
        if kind == 1:
            open_.append(i)
        elif kind == 0 and i in open_:
            open_.remove(i)
    if not out_a:
        return []
    a = np.asarray(out_a)
    b = np.asarray(out_b)
    idle = (b - a) - (busy_until(b) - busy_until(a))
    return list(zip((b - a).tolist(), stacks, idle.tolist()))


def idle_pieces(run):
    """``pieces`` over the device window, for the run's ``obs.*``
    annotations and program executions (once a run); None without the
    window or the annotations."""
    if "_progtrace_pieces" in run:
        return run["_progtrace_pieces"]
    win = run.get("device_window")
    out = None
    if win is not None:
        evs = events(run)
        anns = [e for e in evs if e.name.startswith(PREFIX)
                and not e.plane.startswith("/device:")]
        if anns:
            out = pieces(anns, busy(evs, *win), *win)
    run["_progtrace_pieces"] = out
    return out


def idle_under(run, name: str) -> Optional[Tuple[float, float]]:
    """(idle ns, length ns) of the device window's time in which the
    annotation ``obs.<name>`` is open."""
    ps = idle_pieces(run)
    if ps is None:
        return None
    ann = PREFIX + name
    got = [(n, idle) for n, stack, idle in ps if ann in stack]
    if not got:
        return None
    return sum(i for _, i in got), sum(n for n, _ in got)


def idle_by_innermost(run, name: str) -> Dict[str, float]:
    """Idle ns under ``obs.<name>``, split by the innermost open
    ``obs.*`` annotation."""
    out: Dict[str, float] = {}
    ann = PREFIX + name
    for _, stack, idle in idle_pieces(run) or []:
        if ann in stack and idle > 0:
            out[stack[-1]] = out.get(stack[-1], 0.0) + idle
    return out
