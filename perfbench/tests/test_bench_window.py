"""The open-loop front loop on a fake cluster: every due request is
dispatched once, in calls of at most the front door's ``max_slot``."""
import contextlib
from types import SimpleNamespace

from perfbench import harness, timeline
from perfbench.traffic import Request


class _Runtime:
    def __init__(self):
        self.calls = []

    def run_slot(self, batch, slo):
        self.calls.append([q.qid for q in batch])


class _Probe:
    def __init__(self):
        self.t = 0.0
        self.recs = {}

    def now(self):
        self.t += 0.01
        return self.t

    def _ann(self, name):
        return contextlib.nullcontext()


def _world(n_due_at_once, max_slot):
    sched = [Request(i, 0.0, 0, i, f"q{i}", "a") for i in
             range(n_due_at_once)]
    traffic = {"limits": {"latency_s": 3.0}, "warmup": {"max_slot":
                                                         max_slot}}
    return SimpleNamespace(
        schedule=sched, queries=[SimpleNamespace(qid=i) for i in
                                 range(n_due_at_once)],
        runtime=_Runtime(), cell=SimpleNamespace(traffic=traffic))


def test_a_burst_is_split_at_max_slot():
    w = _world(37, 16)
    probe = _Probe()
    win = harness.run_window(w, probe, 1.0)
    assert [len(c) for c in w.runtime.calls] == [16, 16, 5]
    assert win["largest_slot"] == 16 and win["dispatched"] == 37
    assert sorted(q for c in w.runtime.calls for q in c) == list(range(37))
    assert all(isinstance(r, timeline.Record) and r.answers == 1
               for r in probe.recs.values())
