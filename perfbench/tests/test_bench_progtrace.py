"""The program's timeline in the trace (``perfbench/progtrace.py``) and
the readers built on it: what the loader keeps, that the harness's own
reductions read the same with those events beside theirs, the shared
clock, and each reader on a small synthetic run with a known offset."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import devtrace, progtrace, spec
from perfbench.devtrace import Event
from perfbench.metrics import _spans

DATA = Path(__file__).resolve().parent / "data"
NEW = ("host_bound_idle_share", "engine_idle_share", "decode_device_step_ms",
       "prefill_device_ms", "feedback_ms", "node_wait_span_ms",
       "decode_segment_step_ms", "compiles_in_window")
OFF = 5e9                         # trace ns = span s * 1e9 + OFF
SLOTS = 25


def _ns(*events):
    return [SimpleNamespace(name=n, start_ns=a, duration_ns=d)
            for n, a, d in events]


def _line(name, *events):
    return SimpleNamespace(name=name, events=_ns(*events))


def test_select_keeps_modules_and_obs_annotations():
    """``devtrace.read`` keeps, besides the ops and ``bench.*``, the
    ``XLA Modules`` events of the TPU planes and the host ``obs.*``
    annotations of every host line; the cap applies per device line."""
    planes = [
        SimpleNamespace(name="/device:TPU:0", lines=[
            _line("XLA Ops", ("%fusion.1 = f32[4]{0} fusion()", 0, 5)),
            _line("XLA Modules", ("jit__decode_cont_impl(1)", 0, 10),
                  ("jit__paged_refill_impl(2)", 20, 5),
                  ("jit__decode_cont_impl(1)", 30, 10)),
            _line("Steps", ("1", 0, 40))]),
        SimpleNamespace(name="/device:CPU:0", lines=[
            _line("XLA Modules", ("jit_f(3)", 0, 1))]),
        SimpleNamespace(name="/host:CPU", lines=[
            _line("python", ("obs.request", 0, 50), ("bench.window", 0, 99),
                  ("PjitFunction(f)", 1, 1), ("obs.generate", 5, 20)),
            _line("other", ("obs.request", 3, 4))]),
    ]
    ev = devtrace.read(planes)
    assert [(e.line, e.name) for e in progtrace.events({"events": ev})] == [
        ("XLA Modules", "jit__decode_cont_impl(1)"),
        ("XLA Modules", "jit__paged_refill_impl(2)"),
        ("XLA Modules", "jit__decode_cont_impl(1)"),
        ("python", "obs.request"), ("python", "obs.generate"),
        ("other", "obs.request")]
    assert [e.name for e in ev if devtrace.is_device_op(e)] == [
        "%fusion.1 = f32[4]{0} fusion()"]
    assert [e.name for e in ev if e.name.startswith("bench.")] == [
        "bench.window"]
    ev = devtrace.read(planes, max_device_ops=2)
    assert sum(e.line == "XLA Modules" for e in ev) == 2


def test_harness_reductions_unchanged_beside_program_events():
    """The recorded v5e probe holds six ``XLA Modules`` events; with those
    and ``obs.*`` annotations beside the device ops and ``bench.*``
    annotations, every reduction of ``devtrace`` reads the same numbers
    as on those alone."""
    rows = json.loads((DATA / "v5e_probe_events.json").read_text())
    every = [Event(*r) for r in rows]
    kept = [e for e in every if e.line != "XLA Modules"]
    assert len(kept) == len(every) - 6
    win = devtrace.annotation_window(kept, "bench.window")
    extra = [Event("/host:CPU", "python", "obs.request", win[0] + 1e6,
                   5e6), Event("/host:CPU", "python", "obs.generate",
                               win[0] + 2e6, 3e6)]
    for ev in (every, every + extra):
        assert devtrace.annotation_window(ev, "bench.window") == win
        a0, a1 = win[0] - 2e6, win[1]
        assert devtrace.device_busy(ev, a0, a1) == \
            devtrace.device_busy(kept, a0, a1)
        assert devtrace.kernel_seconds(ev, "paged_decode_attention", a0,
                                       a1) == devtrace.kernel_seconds(
            kept, "paged_decode_attention", a0, a1)
        assert devtrace.top_ops(ev, a0, a1) == devtrace.top_ops(kept, a0,
                                                                 a1)
        _, merged = devtrace.device_busy(kept, a0, a1)
        busy = merged["/device:TPU:0"]
        assert devtrace.idle_gaps(ev, busy, a0, a1, where=True) == \
            devtrace.idle_gaps(kept, busy, a0, a1, where=True)
        assert devtrace.device_cut(ev, a0, a1) == \
            devtrace.device_cut(kept, a0, a1)


def test_program_name():
    assert progtrace.program_name("jit__decode_cont_impl(123)") == \
        "_decode_cont_impl"
    assert progtrace.program_name("jit_decode_step(9)") == "decode_step"


def _run(slots=SLOTS, jitter_ns=1e3, seed=0):
    """A synthetic traced run.  Slot k opens at 1 + k/10 s for 50 ms with
    two requests; ``obs.generate`` is open 10-40 ms in; the device runs a
    prefill program 10-12 ms and decode programs 12-20 and 22-38 ms in.
    So per slot: 24 ms idle under ``obs.request`` (4 of them under
    ``obs.generate``), 8 decode steps in 24 ms of decode device time,
    2 ms of prefill for 2 requests."""
    rng = np.random.default_rng(seed)
    ev, spans = [], []
    ms = 1e6

    def host(name, a, d):
        ev.append(Event("/host:CPU", "python", name, a, d))

    def dev(name, a, d):
        ev.append(Event("/device:TPU:0", "XLA Modules", name, a, d))

    def span(name, trace, t0, t1, **attrs):
        e = {"kind": "span", "trace": trace, "name": name, "t0": t0,
             "t1": t1}
        if attrs:
            e["attrs"] = attrs
        spans.append(e)

    for k in range(slots):
        t0 = 1.0 + k / 10
        a = t0 * 1e9 + OFF + rng.uniform(-jitter_ns, jitter_ns)
        host("obs.request", a, 50 * ms)
        host("obs.generate", a + 10 * ms, 30 * ms)
        dev("jit__paged_refill_impl(7)", a + 10 * ms, 2 * ms)
        dev("jit__decode_cont_impl(8)", a + 12 * ms, 8 * ms)
        dev("jit__decode_cont_impl(8)", a + 22 * ms, 16 * ms)
        for i, tid in enumerate((f"q{2 * k}", f"q{2 * k + 1}")):
            span("request", tid, t0, t0 + 0.05)
            span("node_slot", tid, t0 + 0.002 * (i + 1), t0 + 0.045)
            span("prefill", tid, t0 + 0.0105, t0 + 0.011)
            span("decode_segment", tid, t0 + 0.012, t0 + 0.021, steps=8)
            span("feedback", tid, t0 + 0.041, t0 + 0.049)
    span("compile", "-", 0.2, 0.3, program="jit(f)")    # before the window
    span("compile", "-", 1.5, 1.6, program="jit(g)")
    span("compile", "-", 2.0, 2.2, program="jit(h)")
    win = (1.0e9 + OFF, 1.0e9 + OFF + slots * 1e8)
    return {"events": ev, "spans": spans, "device_window": win,
            "trace_window": win, "probe": SimpleNamespace(t0=1.0),
            "win": {"span_s": slots / 10}}


def _readers():
    return {n: spec.load_reader(n) for n in NEW}


def test_shared_clock_recovers_the_offset():
    run = _run()
    got = _spans.offset_from(run["spans"], [
        e for e in run["events"] if e.name == "obs.request"])
    assert got["pairs"] == 2 * SLOTS
    assert got["offset_ns"] == pytest.approx(OFF, abs=1e3)
    assert got["spread_ns"] <= 2e3
    assert _spans.clock(run) == pytest.approx(OFF, abs=1e3)


def test_clock_refused_when_residuals_spread_or_pairs_are_few():
    assert _spans.clock(_run(jitter_ns=200e3)) is None
    assert _spans.clock(_run(slots=9)) is None       # 18 pairs
    assert _spans.clock(_run(slots=10)) is not None  # 20 pairs


def test_idle_split_by_innermost_annotation():
    run = _run()
    idle, length = progtrace.idle_under(run, "request")
    assert idle == pytest.approx(SLOTS * 24e6)
    assert length == pytest.approx(SLOTS * 50e6)
    split = progtrace.idle_by_innermost(run, "request")
    assert split["obs.generate"] == pytest.approx(SLOTS * 4e6)
    assert split["obs.request"] == pytest.approx(SLOTS * 20e6)


def test_each_reader_on_a_synthetic_run():
    got = {n: r(_run()) for n, r in _readers().items()}
    assert got == {
        "host_bound_idle_share": pytest.approx(24.0),
        "engine_idle_share": pytest.approx(100 * 4 / 30),
        "decode_device_step_ms": pytest.approx(3.0),
        "prefill_device_ms": pytest.approx(1.0),
        "feedback_ms": pytest.approx(8.0),
        "node_wait_span_ms": pytest.approx(3.0),
        "decode_segment_step_ms": pytest.approx(9.0 / 8),
        "compiles_in_window": 2.0}


def test_device_readers_need_the_clock():
    run = _run(jitter_ns=200e3)
    r = _readers()
    assert r["decode_device_step_ms"](run) is None
    assert r["prefill_device_ms"](run) is None
    # the idle shares read the trace's own clock only
    assert r["host_bound_idle_share"](run) == pytest.approx(24.0, rel=1e-3)


def test_readers_read_nothing_from_a_program_without_them(monkeypatch):
    """A program without the annotations, the ``steps`` attribute, the
    node and feedback spans, the program table or the compile listener
    (the parent of this change) reads as None, without raising."""
    from repro.obs import trace
    from repro.serving import engine
    run = _run()
    run["events"] = [e for e in run["events"]
                     if not e.name.startswith("obs.")]
    run["spans"] = [dict(e, attrs={}) if e["name"] == "decode_segment"
                    else e for e in run["spans"]
                    if e["name"] not in ("node_slot", "feedback",
                                         "compile")]
    monkeypatch.delattr(trace, "watch_compiles")
    monkeypatch.delattr(engine, "PROGRAM_LAYERS")
    for name, read in _readers().items():
        assert read(run) is None, name
