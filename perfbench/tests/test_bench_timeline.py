"""Metric arithmetic on synthetic timelines."""
import numpy as np
import pytest

from perfbench import timeline
from perfbench.timeline import Record


def _steady(n=200, gap=0.1, service=0.05, stall_at=None, stall=0.0):
    """A one-server open loop: request i is due at i*gap and served in
    ``service`` s after the server frees up; a stall holds the server."""
    recs, free = [], 0.0
    for i in range(n):
        due = i * gap
        start = max(due, free)
        if stall_at is not None and due >= stall_at and free <= stall_at:
            start = max(start, stall_at + stall)
        r = Record(i, due, dispatch=start, node=0, node_start=start,
                   first=start + 0.02, done=start + service,
                   ret=start + service, tokens=list(range(11)), answers=1)
        free = r.ret
        recs.append(r)
    return recs


def test_tails_without_queueing():
    t = timeline.tails(_steady())
    assert t["latency"] == pytest.approx(50.0)
    assert t["ttft"] == pytest.approx(20.0)
    assert t["tpot"] == pytest.approx(3.0)       # 30 ms over 10 gaps


def test_stall_moves_tails_and_rate():
    # a stall late in the window: requests due in it wait, and the last
    # answers come later, so the same work takes a longer span
    calm = _steady()
    stalled = _steady(stall_at=18.0, stall=3.0)
    a, b = timeline.tails(calm), timeline.tails(stalled)
    # requests due during the stall wait for it: the tail sees the wait
    assert b["latency"] > a["latency"] + 1000.0
    assert b["ttft"] > a["ttft"] + 1000.0
    span_a = max(r.ret for r in calm)
    span_b = max(r.ret for r in stalled)
    assert span_b > span_a
    assert timeline.output_tok_s(stalled, span_b) \
        < timeline.output_tok_s(calm, span_a)


def test_failed_requests_miss_limits_and_leave_tails():
    recs = _steady(n=20)
    recs[3].tokens = []
    recs[4].answers = 2
    assert not recs[3].ok and not recs[4].ok
    assert timeline.attainment(recs, 1.0, 1.0) == pytest.approx(18 / 20)
    assert timeline.output_tok_s(recs, 2.0) == pytest.approx(18 * 11 / 2.0)


def test_percentile_is_over_all_requests():
    recs = _steady(n=100)
    for r in recs[:10]:
        r.ret += 1.0                    # the slowest tenth
    lat = timeline.tails(recs)["latency"]
    assert lat == pytest.approx(1e3 * np.percentile(
        [r.ret - r.due for r in recs], 95))
    assert lat > 1000.0


def test_mean_latency_is_over_every_completed_request():
    recs = _steady(n=100)
    assert timeline.latency_mean_ms(recs) == pytest.approx(50.0)
    recs[7].tokens = []                   # failed: left out of the mean
    recs[8].ret += 1.0
    assert timeline.latency_mean_ms(recs) == pytest.approx(
        50.0 + 1000.0 / 99)
    assert timeline.latency_mean_ms([]) is None


def test_stall_moves_mean_latency():
    calm = timeline.latency_mean_ms(_steady())
    stalled = timeline.latency_mean_ms(_steady(stall_at=10.0, stall=2.0))
    assert stalled > calm + 100.0
