"""The program's own timeline in the profiler trace: the ``obs.<span>``
annotations its spans enter while tracing is on, the device programs it
runs (one ``XLA Modules`` event per execution), and the one offset that
puts ``obs`` span times (``perf_counter``) on the trace's clock.

``devtrace.load`` keeps device ops and ``bench.*`` annotations only, so
the readers here read the same ``.xplane.pb`` once more for these two
kinds of events, or take them from the run's events where a loader
already kept them.  A program without the annotations (or without the
program table) reads as nothing: every reader then returns None.

* busy, here, is the union of the device's program executions (its
  ``XLA Modules`` intervals), not of its ops: idle is time in which no
  program ran on the chip;
* the shared clock: ``offset_ns`` is the median, over every request
  span matched to its ``obs.request`` annotation, of annotation start -
  span ``t0``.  The two are read back to back in ``obs.trace``, so the
  residuals are microseconds; a run whose residuals spread wider (5th
  to 95th percentile) than ``MAX_SPREAD_NS``, or with fewer than
  ``MIN_PAIRS`` pairs, has no clock.
"""
from __future__ import annotations

import glob
import itertools
import os
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import devtrace
from perfbench.devtrace import Event

MODULES = "XLA Modules"
PREFIX = "obs."
MIN_PAIRS = 20
MAX_SPREAD_NS = 100e3
MATCH_TOL_NS = 1e6


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ loading


def select(planes, host_lines: Optional[set] = None,
           max_per_line: int = devtrace.MAX_DEVICE_OPS) -> List[Event]:
    """The ``XLA Modules`` events of the TPU planes (the first
    ``max_per_line`` of each line) and the host events named ``obs.*``
    (on the ``(plane, line)`` pairs of ``host_lines`` where given)."""
    out = []
    for plane in planes:
        pn = plane.name
        device = pn.startswith("/device:")
        for line in plane.lines:
            ln = line.name
            if device:
                if pn.startswith("/device:TPU:") and ln == MODULES:
                    out.extend(Event(pn, ln, e.name, float(e.start_ns),
                                     float(e.duration_ns))
                               for e in itertools.islice(line.events,
                                                         max_per_line))
                continue
            if host_lines is not None and (pn, ln) not in host_lines:
                continue
            for e in line.events:
                nm = e.name
                if nm.startswith(PREFIX):
                    out.append(Event(pn, ln, nm, float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def load(trace_dir: str, host_lines: Optional[set] = None) -> List[Event]:
    """``select`` over the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    return select(jax.profiler.ProfileData.from_file(files[-1]).planes,
                  host_lines)


def _kept(e: Event) -> bool:
    return e.line == MODULES or e.name.startswith(PREFIX)


def events(run) -> List[Event]:
    """The run's program events: from ``run["events"]`` where its loader
    kept them, else read from the run's trace directory (once a run)."""
    if "_progtrace_events" in run:
        return run["_progtrace_events"]
    have = run.get("events") or []
    out = [e for e in have if _kept(e)]
    if not out and run.get("trace_window") is not None:
        cell = run["cell"]
        where = Path(cell.root) / "perfbench" / ".out" / f"trace-{cell.name}"
        # obs annotations are entered on the thread that enters bench.*
        host = {(e.plane, e.line) for e in have
                if not e.plane.startswith("/device:")} or None
        t = time.perf_counter()
        out = load(str(where), host)
        log(f"trace: read again for {sum(e.line == MODULES for e in out)} "
            f"program executions and "
            f"{sum(e.name.startswith(PREFIX) for e in out)} obs.* "
            f"annotations in {time.perf_counter() - t:.1f} s")
    run["_progtrace_events"] = out
    return out


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


# ------------------------------------------------------------- the clock


def offset_from(spans: Iterable[dict], anns: Sequence[Event]
                ) -> Optional[dict]:
    """Match each ``request`` span (one per trace of a batched context)
    to the ``obs.request`` annotation its context entered: -> {offset_ns,
    pairs, spread_ns (5th to 95th percentile of the residuals),
    range_ns}, or None when nothing matches."""
    ctx: Dict[Tuple[float, float], int] = {}
    for e in spans:
        if e.get("name") == "request" and e.get("t1") is not None \
                and e.get("trace", "-") != "-":
            k = (e["t0"], e["t1"])
            ctx[k] = ctx.get(k, 0) + 1
    req = sorted((e.start_ns, e.dur_ns) for e in anns
                 if e.name == PREFIX + "request")
    if not ctx or not req:
        return None
    keys = sorted(ctx)
    t0 = np.asarray([k[0] for k in keys]) * 1e9
    dur = np.asarray([k[1] - k[0] for k in keys]) * 1e9
    n = np.asarray([ctx[k] for k in keys])
    a0 = np.asarray([r[0] for r in req])
    ad = np.asarray([r[1] for r in req])

    def match(off: float) -> np.ndarray:
        """Per context, the index of its annotation under ``off``, or -1."""
        want = t0 + off
        j = np.clip(np.searchsorted(a0, want), 0, len(a0) - 1)
        jl = np.maximum(j - 1, 0)
        j = np.where(np.abs(a0[jl] - want) < np.abs(a0[j] - want), jl, j)
        ok = (np.abs(a0[j] - want) < MATCH_TOL_NS) & \
            (np.abs(ad[j] - dur) < MATCH_TOL_NS)
        return np.where(ok, j, -1)

    # candidate offsets: a few contexts against every annotation
    sample = np.unique(np.linspace(0, len(keys) - 1, min(5, len(keys)))
                       .astype(int))
    cands = (a0[None, :] - t0[sample, None]).ravel()
    best = max(cands, key=lambda c: int(n[match(c) >= 0].sum()))
    j = match(best)
    ok = j >= 0
    if not ok.any():
        return None
    resid = np.repeat(a0[j[ok]] - t0[ok], n[ok])
    lo, hi = np.percentile(resid, [5, 95])
    return {"offset_ns": float(np.median(resid)), "pairs": int(len(resid)),
            "spread_ns": float(hi - lo),
            "range_ns": float(resid.max() - resid.min())}


def clock(run) -> Optional[float]:
    """The run's span -> trace offset in ns, or None with fewer than
    ``MIN_PAIRS`` pairs or residuals spread over ``MAX_SPREAD_NS`` (once a
    run, logged)."""
    if "_progtrace_clock" in run:
        return run["_progtrace_clock"]
    anns = [e for e in events(run) if e.name == PREFIX + "request"]
    got = offset_from(run.get("spans", []), anns) if anns else None
    off = None
    if got is not None and got["pairs"] >= MIN_PAIRS \
            and got["spread_ns"] <= MAX_SPREAD_NS:
        off = got["offset_ns"]
    if anns:
        log(f"trace: shared clock {'kept' if off is not None else 'refused'}"
            f": {got} over {len(anns)} obs.request annotations (needs "
            f">= {MIN_PAIRS} pairs, spread <= {MAX_SPREAD_NS / 1e3:.0f} us)")
    run["_progtrace_clock"] = off
    return off


def spans_in_window(run, name: str) -> Optional[List[dict]]:
    """The ``name`` spans whose start, on the trace's clock, lies in the
    device window; None without a window or a clock."""
    win = run.get("device_window")
    if win is None:
        return None
    off = clock(run)
    if off is None:
        return None
    return [e for e in run.get("spans", []) if e.get("name") == name
            and e.get("t1") is not None
            and win[0] <= e["t0"] * 1e9 + off < win[1]]


def once(spans: Iterable[dict]) -> List[dict]:
    """One event per interval: a batched span records one per trace."""
    seen = set()
    out = []
    for e in spans:
        if e.get("t1") is not None and (e["t0"], e["t1"]) not in seen:
            seen.add((e["t0"], e["t1"]))
            out.append(e)
    return out
