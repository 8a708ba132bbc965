"""Nestable request spans with per-request trace ids.

A *span* is a named interval (`t0`..`t1` on the `perf_counter` clock)
tied to one trace id; spans opened while another span of the same
trace is open become its children, so a JSONL dump reconstructs the
full causal tree of a request: identify -> route -> retrieve/federate
-> queue_wait -> prefill -> decode_segment* -> decode -> detokenize.

Three shapes cover every call site in the serving hierarchy:

* ``span(name, trace=...)`` — ordinary per-request context manager.
* ``span(name, traces=[...])`` — one *batched* stage (identify, route,
  a decode segment) that covers many requests at once: one wall-clock
  interval, one event emitted per participating trace.
* ``emit(name, trace, t0, t1)`` — retroactive span for intervals whose
  endpoints were observed without a context manager (queue wait,
  admission-to-completion decode latency).

While tracing is on, every live span context also enters one
``jax.profiler.TraceAnnotation`` named ``obs.<span name>`` (a batched
span enters one for all its traces), so a profiler trace names each
host interval, and each device idle gap under it, by the program's own
span.  Retroactive ``emit`` spans have no annotation.  ``watch_compiles``
adds a retroactive ``compile`` span for each backend compile.

Disabled mode is the default and is *free*: ``span()`` returns a
shared null context manager without reading the clock or making an
annotation (see the no-op test in tests/test_obs.py, which
monkeypatches this module's ``perf_counter``), and ``emit``/``event``
return immediately.  Instrumentation must never enter jitted code —
spans time host-side orchestration only (docs/ARCHITECTURE.md,
invariants).
"""
from __future__ import annotations

import itertools
from time import perf_counter
from typing import Dict, List, Optional, Sequence


class _NullSpan:
    """Shared disabled-mode span: no clock reads, no allocation."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("trace", "sid", "parent", "name", "t0", "t1", "attrs")

    def __init__(self, trace, sid, parent, name, t0, attrs):
        self.trace = trace
        self.sid = sid
        self.parent = parent
        self.name = name
        self.t0 = t0
        self.t1 = None
        self.attrs = attrs

    def to_event(self):
        ev = {"kind": "span", "trace": self.trace, "id": self.sid,
              "parent": self.parent, "name": self.name,
              "t0": self.t0, "t1": self.t1}
        if self.attrs:
            ev["attrs"] = self.attrs
        return ev


class _SpanCtx:
    """Live context manager over one or more per-trace spans, and the
    one profiler annotation that mirrors them (already entered)."""
    __slots__ = ("_tracer", "_spans", "_ann")

    def __init__(self, tracer, spans, ann):
        self._tracer = tracer
        self._spans = spans
        self._ann = ann

    def __enter__(self):
        return self

    def set(self, **attrs):
        for s in self._spans:
            s.attrs = dict(s.attrs or {}, **attrs)
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        self._ann.__exit__(None, None, None)
        for s in self._spans:
            s.t1 = t1
            self._tracer._close(s)
        return False


def _annotation(name: str):
    """The profiler annotation mirroring a live span, entered.  Its start
    and the span's ``t0`` are read back to back, which is what lets a
    reader put span times on the profiler's clock."""
    from jax.profiler import TraceAnnotation
    ann = TraceAnnotation("obs." + name)
    ann.__enter__()
    return ann


class Tracer:
    """Global span emitter; one open-span stack per trace id."""

    def __init__(self):
        self.enabled = False
        self.recorder = None
        self._stacks: Dict[str, List[int]] = {}
        self._ids = itertools.count(1)
        self._n_traces = itertools.count(1)

    # ------------------------------------------------------------- api
    def span(self, name: str, trace: Optional[str] = None,
             traces: Optional[Sequence[Optional[str]]] = None, **attrs):
        """Open a span (context manager). ``traces`` makes it batched:
        one interval, one event per trace id."""
        if not self.enabled:
            return NULL_SPAN
        ann = _annotation(name)
        t0 = perf_counter()
        tids = list(traces) if traces is not None else [trace]
        if not tids:
            tids = [None]
        spans = []
        for tid in tids:
            tid = str(tid) if tid is not None else "-"
            stack = self._stacks.setdefault(tid, [])
            parent = stack[-1] if stack else None
            s = _Span(tid, next(self._ids), parent, name, t0,
                      dict(attrs) if attrs else None)
            stack.append(s.sid)
            spans.append(s)
        return _SpanCtx(self, spans, ann)

    def emit(self, name: str, trace: Optional[str], t0: float, t1: float,
             **attrs):
        """Record an already-finished interval as a child of whatever
        span is currently open for ``trace``."""
        if not self.enabled:
            return
        tid = str(trace) if trace is not None else "-"
        stack = self._stacks.get(tid)
        parent = stack[-1] if stack else None
        s = _Span(tid, next(self._ids), parent, name, t0,
                  dict(attrs) if attrs else None)
        s.t1 = t1
        self.recorder.record(s.to_event())

    def event(self, name: str, trace: Optional[str] = None, **attrs):
        """Point-in-time marker (e.g. a cache hit/miss)."""
        if not self.enabled:
            return
        t = perf_counter()
        tid = str(trace) if trace is not None else "-"
        stack = self._stacks.get(tid)
        ev = {"kind": "event", "trace": tid, "id": next(self._ids),
              "parent": stack[-1] if stack else None, "name": name, "t": t}
        if attrs:
            ev["attrs"] = attrs
        self.recorder.record(ev)

    def now(self) -> float:
        """Clock read for retroactive spans; 0.0 while disabled so
        callers can stamp unconditionally without paying for the read."""
        return perf_counter() if self.enabled else 0.0

    def new_trace(self, prefix: str = "r") -> str:
        return f"{prefix}{next(self._n_traces)}"

    def reset(self):
        self._stacks.clear()

    # -------------------------------------------------------- internal
    def _close(self, span: _Span):
        stack = self._stacks.get(span.trace)
        if stack and span.sid in stack:
            # tolerate out-of-order exits from interleaved batched spans
            stack.remove(span.sid)
        if self.recorder is not None:
            self.recorder.record(span.to_event())


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def query_trace(qid) -> str:
    """Canonical trace id for a cluster Query: ``q<qid>``."""
    return f"q{qid}"


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_WATCHING = False


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == _COMPILE_EVENT and _TRACER.enabled:
        t1 = perf_counter()
        attrs = {"program": kw["fun_name"]} if "fun_name" in kw else {}
        _TRACER.emit("compile", None, t1 - secs, t1, **attrs)


def watch_compiles() -> None:
    """Register, once per process, the ``jax.monitoring`` listener that
    records a retroactive ``compile`` span (attribute ``program``) for
    each backend compile made while tracing is on."""
    global _WATCHING
    if _WATCHING:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _WATCHING = True
