"""The traced run profiles the end of its window: the covered part is
found from ``bench.traced`` and cut where the device events stop, the
readers apportion the window's work to it, the trace is read once, and
an untraced run never touches the profiler."""
import re
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import devtrace, harness, progtrace
from perfbench.devtrace import Event
from perfbench.metrics import mfu, paged_attn_roofline
from perfbench.tests import tiny
from perfbench.timeline import Record

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ("%paged_decode_attention.1 = bf16[4,16,128]{2,1,0} custom-call("
          "s32[4,72]{1,0} %block_tables.1)")


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[
            SimpleNamespace(name=n, start_ns=a, duration_ns=d)
            for n, a, d in evs]) for ln, evs in lines.items()])


def test_progtrace_reads_the_events_load_kept():
    """On a stored tiny trace, ``progtrace.events`` takes from what
    ``devtrace.read`` kept the very ``XLA Modules`` executions and
    ``obs.*`` annotations that the second read of the trace returned
    before ``devtrace`` kept them (listed here as it returned them), and
    nothing else."""
    from jax._src.lib import _profile_data
    xs = _profile_data.ProfileData.text_proto_to_serialized_xspace(
        (DATA / "tiny_trace.pbtxt").read_text())
    ev = devtrace.read(
        _profile_data.ProfileData.from_serialized_xspace(xs).planes)
    second_read = [
        ("/device:TPU:0", "XLA Modules", "jit__paged_refill_impl(7)",
         2000.0, 10000.0),
        ("/device:TPU:0", "XLA Modules", "jit__decode_cont_impl(8)",
         12500.0, 9000.0),
        ("/device:TPU:0", "XLA Modules", "jit__paged_refill_impl(7)",
         23000.0, 1000.0),
        ("/host:CPU", "python3", "obs.request", 1600.0, 23000.0),
        ("/host:CPU", "python3", "obs.generate", 2000.0, 20000.0)]
    got = progtrace.events({"events": ev})
    assert [(e.plane, e.line, e.name, e.start_ns, e.dur_ns)
            for e in got] == second_read
    assert sum(devtrace.is_device_op(e) for e in ev) == 4
    assert [e.name for e in ev if e.name.startswith("bench.")] == [
        "bench.traced", "bench.run_slot", "bench.node0.engine",
        "bench.front_wait"]
    assert devtrace.annotation_window(ev, "bench.traced") == (1000.0,
                                                              31000.0)


def _info(start_s=0.001):
    return {"cell": SimpleNamespace(readers={}),
            "probe": SimpleNamespace(start_s=start_s), "traced_at_s": 39.0,
            "recs": [], "model_dicts": [{}]}


def test_covered_part_is_bench_traced_cut_where_device_events_stop():
    """The covered part is the ``bench.traced`` annotation, not the
    window's (which opens before the profiler and so is not in the
    trace); device numbers cover it up to the point where the device's
    events stop while the host still launches engine work."""
    s = 1e9
    profile = SimpleNamespace(planes=[
        _plane("/device:TPU:0", **{
            "XLA Ops": [("%fusion.1 = f32[4]{0} fusion()", 40 * s, 1 * s),
                        (KERNEL, 41.5 * s, 0.5 * s)],
            "XLA Modules": [("jit__decode_cont_impl(8)", 40 * s, 2 * s)]}),
        _plane("/host:CPU", python3=[
            ("bench.window", 0, 51 * s),          # not recorded in a run
            ("bench.traced", 39 * s, 12.5 * s),
            ("bench.node0.engine", 40 * s, 2 * s),
            ("bench.front_wait", 42 * s, 2 * s),
            ("bench.node0.engine", 45 * s, 5 * s)])])
    info = _info()
    _, dev, breakdown = harness._read_trace(info, profile)
    assert info["trace_window"] == (39 * s, 51.5 * s)
    assert info["device_window"] == (39 * s, 42 * s)
    assert dev["window_s"] == pytest.approx(3.0)
    assert dev["busy_s"] == pytest.approx(1.5)
    assert breakdown["idle_gaps"][0] == ["bench.traced", pytest.approx(1.0)]
    assert "start_trace" not in [g[0] for g in breakdown["idle_gaps"]]
    # a start of the profiler that held the front loop is named too
    info = _info(start_s=0.25)
    _, _, breakdown = harness._read_trace(info, profile)
    assert ["start_trace", 0.25] in breakdown["idle_gaps"]
    # no profile, or one without the covered part: nothing is read
    for p in (None, SimpleNamespace(planes=profile.planes[:1])):
        info = _info()
        assert harness._read_trace(info, p) == ({}, {}, None)
        assert info["trace_window"] is None


def _run_for_roofline():
    """The covered part opens 40 s into the window (trace ns 1,000 s) and
    its device events end 8 s later.  Each request: a 10-token prompt and
    5 answer tokens on the tiny ``attn`` model, 2 layers of 2 KV heads of
    16 at bf16, so 2 x 2 x 2 x 16 x 2 = 256 bytes a context token over 4
    decode steps of contexts 11..14: 256 x 50 = 12,800 bytes."""
    s = 1e9
    rec = {"node": 0, "prompt": [5] * 10, "tokens": [6] * 5}
    recs = [Record(0, 30.0, dispatch=30.0, done=31.0, **rec),   # before
            Record(1, 39.8, dispatch=39.9, done=40.5, **rec),   # its slot
            Record(2, 42.0, dispatch=42.0, done=43.0, **rec),   # inside
            Record(3, 47.5, dispatch=47.6, done=48.3, **rec),   # after cut
            Record(4, 44.0, dispatch=44.0, done=-1.0, node=0,   # unanswered
                   prompt=[5] * 10)]
    events = [Event("/device:TPU:0", "XLA Ops", KERNEL, 1000.5 * s,
                    1e-8 * s),
              Event("/device:TPU:0", "XLA Ops", KERNEL, 1003 * s, 1e-8 * s),
              Event("/device:TPU:0", "XLA Ops", KERNEL, 1010 * s, 5e-8 * s)]
    return {"device_window": (1000 * s, 1008 * s),
            "trace_window": (1000 * s, 1012 * s), "traced_at_s": 40.0,
            "events": events, "recs": recs,
            "model_dicts": [tiny.TINY_MODELS["attn"]],
            "device": {"kind": "TPU v5 lite"}}


def test_paged_roofline_counts_the_reads_of_the_covered_part_only():
    """Two requests answered between the profiler's start and the end of
    the device window (one of them carried by the slot whose start began
    the covered part): 25,600 bytes at 819 GB/s, over the 20 ns of the
    kernel's two events inside the device window."""
    got = paged_attn_roofline.read(_run_for_roofline())
    assert got == pytest.approx(100 * (25600 / 819e9) / 20e-9)


def test_mfu_reads_the_window_from_the_harness_clock():
    """Every request of the window over the window's span: the harness's
    clock of it reads what the span of ``bench.window`` read, when the
    profiler still covered the whole window."""
    from perfbench import peaks
    m = tiny.TINY_MODELS["attn"]
    recs = [Record(i, 0.0, node=0, prompt=[5] * 20, tokens=[6] * 4,
                   prefix_len=8) for i in range(3)]
    window = [Event("/host:CPU", "python3", "bench.window", 7e9, 51.25e9)]
    span = devtrace.annotation_window(window, "bench.window")
    flops = 3 * peaks.request_flops(m, 20 - 8, 20, 4) + \
        peaks.prefix_flops(m, 8)
    as_annotated = 100 * flops / ((span[1] - span[0]) / 1e9 * 197e12)
    run = {"device_window": (40e9, 48e9), "win": {"span_s": 51.25},
           "model_dicts": [m], "recs": recs,
           "probe": SimpleNamespace(prefix_prefills=[(0, 8)]),
           "device": {"kind": "TPU v5 lite"}}
    assert mfu.read(run) == pytest.approx(as_annotated, rel=1e-12)
    assert mfu.read(dict(run, device_window=None)) is None


def test_untraced_run_makes_no_profiler_call(tmp_path, monkeypatch):
    import jax
    from jax._src.lib import _profiler

    def refuse(*a, **k):
        raise AssertionError("the profiler was called in an untraced run")

    for name in ("start_trace", "stop_trace", "TraceAnnotation",
                 "ProfileOptions", "trace"):
        monkeypatch.setattr(jax.profiler, name, refuse)
    monkeypatch.setattr(_profiler, "ProfilerSession", refuse)
    root = tiny.make_copy(tmp_path)
    res = harness.run(root, "tiny.rag", 2**31 + 5, 2.0, False,
                      time.perf_counter(), require_tpu=False)
    assert res["correct"] is True and res["attempted"] >= 1
    assert "breakdown" not in res


def test_traced_run_profiles_the_end_of_its_window(tmp_path, monkeypatch,
                                                   capsys):
    """With one traced second at the end of a 3 s window, the profiler
    starts between two slots, two or more seconds in; the covered part
    runs from there to the window's close; every phase is timed on one
    line."""
    monkeypatch.setattr(harness, "TRACED_S", 1.0)
    root = tiny.make_copy(tmp_path)
    res = harness.run(root, "tiny.rag", 2**31 + 21, 3.0, True,
                      time.perf_counter(), require_tpu=False)
    err = capsys.readouterr().err
    assert res["correct"] is True
    at = float(re.search(r"profiler started ([\d.]+) s into", err).group(1))
    assert at >= 2.0
    covered = float(re.search(r"in the ([\d.]+) s covered part", err)
                    .group(1))
    span = float(re.search(r"slots over ([\d.]+) s;", err).group(1))
    assert 0 < covered <= span - at + 0.05
    assert re.search(r"trace: phases \(s\): set-up [\d.]+, window [\d.]+, "
                     r"start stall [\d.]+, stop [\d.]+, read [\d.]+, "
                     r"reduce [\d.]+, check [\d.]+; whole run [\d.]+", err)
    # span readers still read the whole window
    assert res["metrics"]["prefill_ms"]["value"] > 0
