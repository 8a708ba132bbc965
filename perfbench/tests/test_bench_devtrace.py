"""Trace reduction: busy union, idle share, kernel time and idle gaps,
on a small synthetic trace and on a small trace recorded on the chip."""
import json
from pathlib import Path

import pytest

from perfbench import devtrace
from perfbench.devtrace import Event

DATA = Path(__file__).resolve().parent / "data"


def _dev(name, a, b, dev=0):
    return Event(f"/device:TPU:{dev}", "XLA Ops", name, a, b - a)


def _host(name, a, b):
    return Event("/host:CPU", "python", name, a, b - a)


KERNEL = ("%paged_decode_attention.1 = bf16[4,16,128]{2,1,0} custom-call("
          "s32[4,72]{1,0} %block_tables.1)")
SYNTH = [
    _host("bench.window", 0, 1000),
    _host("bench.run_slot", 50, 700),
    _host("bench.node0.engine", 100, 400),
    _host("bench.front_wait", 700, 1000),
    _dev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 100, 200),
    _dev(KERNEL, 150, 250),                     # overlaps fusion.1
    _dev("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %b)", 300, 400),
    _dev(KERNEL, 390, 420),
    _dev("%outside = f32[4]{0} fusion(f32[4]{0} %c)", 1200, 1300),
]


def test_busy_is_the_union_of_device_intervals():
    busy, merged = devtrace.device_busy(SYNTH, 0, 1000)
    assert merged["/device:TPU:0"] == [(100, 250), (300, 420)]
    assert busy == pytest.approx(270e-9)


def test_busy_averages_over_devices():
    ev = SYNTH + [_dev("x", 0, 1000, dev=1)]
    busy, _ = devtrace.device_busy(ev, 0, 1000)
    assert busy == pytest.approx((270e-9 + 1000e-9) / 2)


def test_kernel_time_sums_its_events_in_the_window():
    assert devtrace.kernel_seconds(SYNTH, "paged_decode_attention", 0,
                                   1000) == pytest.approx(130e-9)
    assert devtrace.kernel_seconds(SYNTH, "fusion", 0, 1000) == 0.0


def test_idle_gaps_named_by_innermost_annotation():
    _, merged = devtrace.device_busy(SYNTH, 0, 1000)
    gaps = devtrace.idle_gaps(SYNTH, merged["/device:TPU:0"], 0, 1000)
    assert gaps[0] == ["bench.front_wait", pytest.approx(580e-9)]
    names = dict((g[0], g[1]) for g in gaps)
    assert names["bench.node0.engine"] == pytest.approx(50e-9)
    assert devtrace.annotation_window(SYNTH, "bench.window") == (0, 1000)


def test_top_ops():
    top = devtrace.top_ops(SYNTH, 0, 1000)
    assert top[0] == ["paged_decode_attention.1 custom-call",
                      pytest.approx(130e-9)]
    assert all(not name.startswith("outside") for name, _ in top)


def test_recorded_v5e_trace():
    """Three paged-kernel calls and three bf16 matmuls, each under its own
    host annotation, recorded on one TPU v5 lite."""
    ev = [Event(*row) for row in
          json.loads((DATA / "v5e_probe_events.json").read_text())]
    win = devtrace.annotation_window(ev, "bench.window")
    assert win is not None
    # the device plane's clock runs about 1 ms behind the host's in this
    # trace (a kernel appears to start before the annotation that launched
    # it): widen this 70 ms window by 2 ms so its first kernel falls in
    win = (win[0] - 2e6, win[1])
    k = devtrace.kernel_seconds(ev, "paged_decode_attention", *win)
    assert k == pytest.approx((428808 + 429198 + 428802) * 1e-9)
    busy, merged = devtrace.device_busy(ev, *win)
    assert list(merged) == ["/device:TPU:0"]
    assert 1.5e-3 < busy < 2.0e-3
    idle = 1 - busy / ((win[1] - win[0]) / 1e9)
    assert 0.9 < idle < 1.0
    gaps = devtrace.idle_gaps(ev, merged["/device:TPU:0"], *win)
    # the 10 ms sleeps between calls are the longest gaps, inside the
    # window's annotation; the widened edge is outside every annotation
    assert {g[0] for g in gaps} <= {"bench.window", "bench.kernel",
                                    "bench.mm", "unannotated"}
    assert max(g[1] for g in gaps) > 9e-3


def test_cut_device_trace_is_found_and_left_out():
    """Device events stop while the host still launches engine work: the
    trace was cut there, and busy time is read up to that point only."""
    ev = [_host("bench.window", 0, 10e9),
          _host("bench.node0.engine", 1e9, 3e9),
          _dev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 1e9, 2e9),
          _host("bench.node0.engine", 5e9, 9e9)]      # nothing recorded
    cut = devtrace.device_cut(ev, 0, 10e9)
    assert cut == pytest.approx(2e9)
    busy, _ = devtrace.device_busy(ev, 0, cut)
    assert busy == pytest.approx(1.0)


def test_idle_end_of_window_is_not_a_cut():
    """A device idle at the end while the host only waits for arrivals
    (no engine launch after the last op) is idle time, not a cut."""
    assert devtrace.device_cut(SYNTH, 0, 1000) is None
    ev = [_host("bench.window", 0, 10e9),
          _dev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 1e9, 2e9),
          _host("bench.front_wait", 2e9, 10e9)]
    assert devtrace.device_cut(ev, 0, 10e9) is None


def test_load_reads_at_most_the_limit_of_device_ops():
    """A profile with more device ops than the limit: the first ones
    are read, host annotations all, and the end of what was read is
    found as a cut."""
    from jax._src.lib import _profile_data
    ops = "".join(f"events {{ metadata_id: 1 offset_ps: {i * 10**12} "
                  f"duration_ps: {5 * 10**11} }} " for i in range(50))
    text = (
        'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: '
        f'"XLA Ops" timestamp_ns: 0 {ops}}} event_metadata {{ key: 1 '
        'value { id: 1 name: "%fusion.1 = f32[4]{0} fusion()" } } } '
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" '
        'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 60000000000000 } events { metadata_id: 2 offset_ps: '
        '40000000000000 duration_ps: 1000000000000 } events { metadata_id: 3 '
        'offset_ps: 1000 duration_ps: 10 } } event_metadata { key: 1 '
        'value { id: 1 name: "bench.window" } } event_metadata { key: 2 '
        'value { id: 2 name: "bench.node0.engine" } } event_metadata { '
        'key: 3 value { id: 3 name: "runtime internals" } } }')
    xs = _profile_data.ProfileData.text_proto_to_serialized_xspace(text)
    profile = _profile_data.ProfileData.from_serialized_xspace(xs)
    ev = devtrace.read(profile.planes, max_device_ops=20)
    assert sum(devtrace.is_device_op(e) for e in ev) == 20
    assert {e.name for e in ev if e.plane == "/host:CPU"} == {
        "bench.window", "bench.node0.engine"}
    win = devtrace.annotation_window(ev, "bench.window")
    assert win == (0, 60e9)
    assert devtrace.device_cut(ev, *win) == pytest.approx(19.5e9)
