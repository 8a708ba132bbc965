"""Shared reading of the program's ``obs`` spans (one event per trace
of a batched span: an interval is counted once), and the clock that
puts span times on the profiler trace's.

The shared clock: ``offset_ns`` is the median, over every request span
matched to its ``obs.request`` annotation, of annotation start - span
``t0``.  The two are read back to back in ``obs.trace``, so the
residuals are microseconds; a run whose residuals spread wider (5th to
95th percentile) than ``MAX_SPREAD_NS``, or with fewer than
``MIN_PAIRS`` pairs, has no clock."""
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from perfbench import progtrace
from perfbench.devtrace import Event

REQUEST = progtrace.PREFIX + "request"

MIN_PAIRS = 20
MAX_SPREAD_NS = 100e3
MATCH_TOL_NS = 1e6


def intervals(run, name):
    seen = set()
    out = []
    for e in run.get("spans", []):
        if e.get("name") != name or e.get("t1") is None:
            continue
        key = (e["t0"], e["t1"])
        if key not in seen:
            seen.add(key)
            out.append(e["t1"] - e["t0"])
    return out


def per_request(run, name):
    return [e["t1"] - e["t0"] for e in run.get("spans", [])
            if e.get("name") == name and e.get("t1") is not None
            and e.get("trace", "-") != "-"]


def once(spans: Iterable[dict]) -> List[dict]:
    """One event per interval: a batched span records one per trace."""
    seen = set()
    out = []
    for e in spans:
        if e.get("t1") is not None and (e["t0"], e["t1"]) not in seen:
            seen.add((e["t0"], e["t1"]))
            out.append(e)
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
                 if e.name == REQUEST)
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
    anns = [e for e in progtrace.events(run) if e.name == REQUEST]
    got = offset_from(run.get("spans", []), anns) if anns else None
    off = None
    if got is not None and got["pairs"] >= MIN_PAIRS \
            and got["spread_ns"] <= MAX_SPREAD_NS:
        off = got["offset_ns"]
    if anns:
        progtrace.log(
            f"trace: shared clock {'kept' if off is not None else 'refused'}"
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
