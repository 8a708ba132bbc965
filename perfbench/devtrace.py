"""Reduction of a JAX profiler trace to device numbers.

The trace is read once into plain events ``(plane, line, name, start_ns,
dur_ns)``; everything else, here and in ``progtrace``, works on those,
so the arithmetic is checked on a small recorded event list.

* busy: the union of the intervals in which an operation ran on a
  device (lines named ``XLA Ops`` of ``/device:TPU:<n>`` planes),
  averaged over the devices used;
* idle share: 1 - busy / window;
* kernel time: the summed device durations of the events whose name
  contains the kernel's name;
* idle gaps: the longest gaps between busy intervals, each named by the
  innermost host annotation (``jax.profiler.TraceAnnotation``) that
  covers the gap's midpoint;
* a cut device trace: the profiler keeps a bounded number of device
  events (about 6.2 million on a v5e, some 20 s of a busy window), and
  ``read`` takes fewer still, so the device's events can stop while the
  host still drives the engines.  ``device_cut`` finds that point;
  device numbers are then read over the part of the window the events
  cover, never over a tail that was not read.

The host and device planes of one trace are not on exactly one clock: on
the v5e a device op appears about 1 ms before the host annotation that
launched it.  Against a window of tens of seconds that is noise; it does
blur the naming of gaps shorter than a few milliseconds.
"""
from __future__ import annotations

import functools
import itertools
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


@dataclass(slots=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


# Device events read per line of a device: about ten seconds of the ops
# of a busy v5e, and about half of what its profiler keeps before it
# stops recording.
MAX_DEVICE_OPS = 3_000_000
OPS = "XLA Ops"
MODULES = "XLA Modules"
# host annotations: the harness's own, and those the program's live
# ``obs`` spans enter while tracing is on
HOST_PREFIXES = ("bench.", "obs.")


def read(planes, max_device_ops: int = MAX_DEVICE_OPS) -> List[Event]:
    """The events of a profile that the reduction reads: of each TPU
    plane, the first ``max_device_ops`` events of its ``XLA Ops`` line
    (one per operation) and of its ``XLA Modules`` line (one per program
    execution); of the host planes, the annotations named ``bench.*``
    or ``obs.*``.  A busy window holds millions of device ops, so
    nothing else is turned into Python objects; past the limit,
    ``device_cut`` finds the end of what was read as it finds the end of
    a cut trace."""
    out = []
    for plane in planes:
        pn = plane.name
        if pn.startswith("/device:"):
            if not pn.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                ln = line.name
                if ln == OPS or ln == MODULES:
                    out.extend([Event(pn, ln, e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                for e in itertools.islice(line.events,
                                                          max_device_ops)])
            continue
        for line in plane.lines:
            ln = line.name
            for e in line.events:
                nm = e.name
                if nm.startswith(HOST_PREFIXES):
                    out.append(Event(pn, ln, nm, float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def is_device_op(e: Event) -> bool:
    return e.plane.startswith("/device:TPU:") and e.line == OPS


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    iv = np.asarray(list(intervals), np.float64).reshape(-1, 2)
    if not len(iv):
        return []
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    # a new merged interval starts where a start lies past every end so far
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    ends = reach[np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])]
    return list(zip(starts.tolist(), ends.tolist()))


def device_busy(events: Sequence[Event], t0_ns: float, t1_ns: float
                ) -> Tuple[float, Dict[str, List[Tuple[float, float]]]]:
    """(busy seconds averaged over devices, per-device busy intervals)
    inside [t0, t1]."""
    per_dev: Dict[str, List[Tuple[float, float]]] = {}
    for e in events:
        if not is_device_op(e):
            continue
        a = max(e.start_ns, t0_ns)
        b = min(e.start_ns + e.dur_ns, t1_ns)
        if b > a:
            per_dev.setdefault(e.plane, []).append((a, b))
    merged = {d: union(iv) for d, iv in per_dev.items()}
    if not merged:
        return 0.0, {}
    busy = sum(sum(b - a for a, b in iv) for iv in merged.values()) \
        / len(merged) / 1e9
    return busy, merged


@functools.lru_cache(maxsize=None)
def short_name(name: str) -> str:
    """``%fusion.3 = bf16[..] fusion(...), ...`` -> ``fusion.3 fusion``:
    the op's name and kind, without its operands."""
    if " = " not in name:
        return name[:120]
    lhs, rhs = name.split(" = ", 1)
    m = _KIND.search(rhs)
    return f"{lhs.lstrip('%')} {m.group(1)}" if m else lhs.lstrip("%")


_KIND = re.compile(r"[\]\)}]\s([a-z][a-z0-9-]*)\(")


def _op_time(events: Sequence[Event], t0_ns: float, t1_ns: float
             ) -> Dict[str, float]:
    """Summed device time per short op name of the ops starting in
    [t0, t1] (summed per full name first: names repeat by the million)."""
    raw: Dict[str, float] = {}
    for e in events:
        if is_device_op(e) and t0_ns <= e.start_ns < t1_ns:
            raw[e.name] = raw.get(e.name, 0.0) + e.dur_ns
    acc: Dict[str, float] = {}
    for name, t in raw.items():
        k = short_name(name)
        acc[k] = acc.get(k, 0.0) + t
    return acc


def kernel_seconds(events: Sequence[Event], needle: str, t0_ns: float,
                   t1_ns: float) -> float:
    """Summed device time of custom calls (kernels) whose op name
    contains ``needle``."""
    return sum(t for nm, t in _op_time(events, t0_ns, t1_ns).items()
               if needle in nm and nm.endswith("custom-call")) / 1e9


def top_ops(events: Sequence[Event], t0_ns: float, t1_ns: float,
            n: int = 10) -> List[List]:
    top = sorted(_op_time(events, t0_ns, t1_ns).items(),
                 key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(events: Sequence[Event], busy: Sequence[Tuple[float, float]],
              t0_ns: float, t1_ns: float, n: int = 10,
              where: bool = False) -> List[List]:
    """The ``n`` longest idle gaps of one device, each named by the
    innermost host annotation that covers its midpoint (``where`` adds
    the gap's start, in seconds after t0)."""
    gaps = []
    prev = t0_ns
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1_ns > prev:
        gaps.append((prev, t1_ns))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [e for e in events if not e.plane.startswith("/device:")
            and e.name.startswith("bench.")]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [e for e in host if e.start_ns <= mid <= e.start_ns
                 + e.dur_ns]
        name = min(cover, key=lambda e: e.dur_ns).name if cover \
            else "unannotated"
        out.append([name, (b - a) / 1e9] + ([(a - t0_ns) / 1e9]
                                            if where else []))
    return out


def device_cut(events: Sequence[Event], t0_ns: float, t1_ns: float,
               launch_prefix: str = "bench.node", slack_s: float = 1.0
               ) -> Optional[float]:
    """Where the device trace stops inside [t0, t1], if it was cut: the
    end of the last device op, when it lies more than ``slack_s`` before
    t1 and a host annotation that launches device work (its name starts
    with ``launch_prefix``) starts after it.  None when the device's
    events cover the window."""
    ends = [e.start_ns + e.dur_ns for e in events
            if is_device_op(e) and e.start_ns < t1_ns
            and e.start_ns + e.dur_ns > t0_ns]
    if not ends:
        return None
    last = min(max(ends), t1_ns)
    if t1_ns - last <= slack_s * 1e9:
        return None
    later = any(not e.plane.startswith("/device:")
                and e.name.startswith(launch_prefix)
                and last < e.start_ns < t1_ns for e in events)
    return last if later else None


def annotation_window(events: Sequence[Event], name: str
                      ) -> Optional[Tuple[float, float]]:
    """[start, end] in trace nanoseconds of the host annotation ``name``
    (the measured window is annotated as one span)."""
    for e in events:
        if e.name == name and not e.plane.startswith("/device:"):
            return e.start_ns, e.start_ns + e.dur_ns
    return None
