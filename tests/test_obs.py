"""Observability layer: span nesting + causal order across a full
paged+federated request, the profiler annotation each live span enters,
compile spans, metrics snapshot/delta, flight-recorder ring wraparound,
and the disabled-mode no-op guarantee (zero events, zero clock reads,
zero annotations on the decode segment path)."""
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.cluster import Query
from repro.obs.metrics import MetricsRegistry, percentile
from repro.obs.recorder import FlightRecorder
from repro.serving.sampling import GenerationParams
from repro.serving.scheduler import ContinuousStats, QueueStats

# tools/ lives at the repo root (not on the src/ path)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from tools import trace_report  # noqa: E402

SLO = 120.0


# --------------------------------------------------------------- unit layer


def test_percentile_empty_is_zero():
    assert percentile([], 99) == 0.0
    xs = [0.1, 0.2, 0.3, 0.4]
    assert percentile(xs, 50) == pytest.approx(np.percentile(xs, 50))


def test_stats_percentile_helpers_empty_safe():
    q = QueueStats()
    assert q.latency_p99 == 0.0 and q.latency_mean == 0.0
    c = ContinuousStats()
    assert c.ttft_p99 == 0.0 and c.ttft_mean == 0.0
    assert c.latency_p99 == 0.0 and c.latency_mean == 0.0
    c.ttft_s.extend([0.1, 0.2, 0.3])
    assert c.ttft_p99 == pytest.approx(np.percentile(c.ttft_s, 99))
    assert c.ttft_mean == pytest.approx(0.2)


def test_metrics_snapshot_and_delta():
    reg = MetricsRegistry()
    reg.counter("reqs", node=0).inc(3)
    reg.gauge("util").set(0.5)
    reg.histogram("lat").observe(1.0)
    snap = reg.snapshot()
    assert snap["reqs{node=0}"] == 3
    assert snap["util"] == 0.5
    assert snap["lat"]["count"] == 1 and snap["lat"]["sum"] == 1.0
    reg.counter("reqs", node=0).inc(2)
    reg.gauge("util").set(0.75)
    reg.histogram("lat").observe(3.0)
    d = reg.delta(snap)
    assert d["reqs{node=0}"] == 2            # counters diff
    assert d["util"] == 0.75                 # gauges last-write-wins
    assert d["lat"]["count"] == 1 and d["lat"]["sum"] == 3.0
    assert d["lat"]["p50"] == pytest.approx(2.0)   # percentiles current
    # unchanged entries drop out of the delta
    reg.counter("idle").inc(0)
    assert "idle" not in reg.delta(reg.snapshot())


def test_metrics_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_recorder_ring_wraparound(tmp_path):
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.record({"kind": "event", "trace": "t", "id": i, "parent": None,
                    "name": f"e{i}", "t": float(i)})
    assert len(rec) == 8
    assert rec.total == 20
    assert rec.dropped == 12
    assert [e["id"] for e in rec.events()] == list(range(12, 20))
    path = rec.export_jsonl(str(tmp_path / "ring.jsonl"))
    meta, events, errors = trace_report.load(path)
    assert not errors
    assert meta["dropped"] == 12 and meta["events"] == 8
    assert len(events) == 8 and events[0]["id"] == 12


def test_span_nesting_and_retroactive_emit(tmp_path):
    rec = obs.enable(capacity=64)
    try:
        tr = obs.get_tracer()
        with tr.span("request", trace="r1"):
            with tr.span("retrieve", trace="r1", k=2):
                tr.event("semantic_cache", "r1", hit=False)
            tr.emit("queue_wait", "r1", 1.0, 2.0, slot=0)
            # batched span: one interval, one record per trace, each
            # nesting under its own trace's open stack
            with tr.span("decode_segment", traces=["r1", "r2"], rows=2):
                pass
    finally:
        obs.disable()
    path = rec.export_jsonl(str(tmp_path / "nest.jsonl"))
    meta, events, errors = trace_report.load(path)
    assert not trace_report.check(meta, events, errors, min_complete=0.0)
    spans = {(e["trace"], e["name"]): e for e in events
             if e["kind"] == "span"}
    root = spans[("r1", "request")]
    assert root["parent"] is None
    assert spans[("r1", "retrieve")]["parent"] == root["id"]
    assert spans[("r1", "retrieve")]["attrs"] == {"k": 2}
    assert spans[("r1", "queue_wait")]["parent"] == root["id"]
    assert spans[("r1", "queue_wait")]["t0"] == 1.0
    ev = next(e for e in events if e["kind"] == "event")
    assert ev["parent"] == spans[("r1", "retrieve")]["id"]
    # the batched segment emitted once per trace over the same interval
    seg1, seg2 = spans[("r1", "decode_segment")], \
        spans[("r2", "decode_segment")]
    assert seg1["t0"] == seg2["t0"] and seg1["t1"] == seg2["t1"]
    assert seg1["parent"] == root["id"] and seg2["parent"] is None


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    annotation made, entered and exited."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kw):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append(("enter", name))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False

        log.append(("make", name))
        return Ann()


def test_enabled_span_enters_one_annotation(monkeypatch):
    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    obs.enable(capacity=64)
    try:
        tr = obs.get_tracer()
        with tr.span("request", trace="r1"):
            # batched: one annotation for all three traces
            with tr.span("decode_segment", traces=["r1", "r2", "r3"],
                         rows=3):
                pass
            tr.emit("queue_wait", "r1", 1.0, 2.0)     # retroactive: none
    finally:
        obs.disable()
    assert anns.log == [
        ("make", "obs.request"), ("enter", "obs.request"),
        ("make", "obs.decode_segment"), ("enter", "obs.decode_segment"),
        ("exit", "obs.decode_segment"), ("exit", "obs.request")]


def test_backend_compile_becomes_a_compile_span():
    rec = obs.enable(capacity=64)
    try:
        def tripled_plus_seven(x):
            return 3 * x + 7

        jax.jit(tripled_plus_seven)(np.arange(5.0)).block_until_ready()
    finally:
        obs.disable()
    comp = [e for e in rec.events() if e["name"] == "compile"]
    assert any(e["attrs"]["program"] == "jit(tripled_plus_seven)"
               and e["t1"] >= e["t0"] for e in comp), comp
    # off again: a compile records nothing
    n = len(rec.events())
    jax.jit(lambda x: x - 11)(np.arange(3.0)).block_until_ready()
    assert len(rec.events()) == n


# ------------------------------------------------------- live integration


@pytest.fixture(scope="module")
def obs_cluster():
    """Two tiny paged+federated live nodes plus a runtime, with one
    traced slot already replayed into a recorder."""
    from repro.cluster.runtime import ClusterRuntime
    from repro.launch.cluster_serve import build_cluster
    nodes, qas, tok, encoder, ident, _ = build_cluster(
        2, smoke=True, entities=3, batch=2, max_len=192, new_tokens=4,
        top_k=2, seed=0, federated=True, fanout=2, cache=True, paged=True)
    runtime = ClusterRuntime(nodes, ident, seed=0)
    obs.registry().reset()
    rec = obs.enable()
    try:
        queries = []
        for qid, qa in enumerate(qas[:4]):
            emb = encoder.encode([qa.question])[0]
            queries.append(Query(qa.domain, emb, qid=qid,
                                 question=qa.question,
                                 reference=qa.answer))
        runtime.run_slot(queries, SLO)
    finally:
        obs.disable()
    return nodes, rec, [f"q{i}" for i in range(4)]


def test_traced_slot_causal_span_order(obs_cluster, tmp_path):
    nodes, rec, tids = obs_cluster
    path = rec.export_jsonl(str(tmp_path / "slot.jsonl"))
    meta, events, errors = trace_report.load(path)
    # the CI gate passes on a real paged+federated dump: schema valid,
    # all spans closed, parents resolve, >=95% complete request trees
    assert not trace_report.check(meta, events, errors, min_complete=0.95)
    comp, rooted, frac = trace_report.completeness(events)
    assert rooted == len(tids) and frac == 1.0
    by_trace = trace_report.spans_by_trace(events)
    for tid in tids:
        spans = [e for e in by_trace[tid] if e["kind"] == "span"]
        t0 = {}
        for e in spans:
            t0.setdefault(e["name"], e["t0"])
            t0[e["name"]] = min(t0[e["name"]], e["t0"])
        root = next(e for e in spans if e["name"] == "request")
        assert root["parent"] is None
        # every stage nests (transitively) under the request root
        ids = {e["id"]: e for e in spans}
        for e in spans:
            top = e
            while top["parent"] is not None:
                top = ids[top["parent"]]
            assert top is root
        # causal stage order within the trace
        assert t0["identify"] <= t0["route"] <= t0["retrieve"] \
            <= t0["prefill"] <= t0["decode"] <= t0["detokenize"]
        assert t0["queue_wait"] <= t0["prefill"]
        # federated retrieval nests under the retrieve span
        fed = next(e for e in spans if e["name"] == "federate")
        ret = next(e for e in spans if e["name"] == "retrieve")
        assert fed["parent"] == ret["id"]
    # paged sessions with a shared retrieved-context prefix surface
    # prefix-cache lookups as point events on some refilled trace
    assert any(e["kind"] == "event" and e["name"] == "prefix_cache"
               for e in events)
    assert any(e["kind"] == "event" and e["name"] == "semantic_cache"
               for e in events)


def test_traced_slot_metrics_rollup(obs_cluster):
    nodes, rec, tids = obs_cluster
    snap = obs.registry().snapshot()
    admitted = sum(v for k, v in snap.items()
                   if k.startswith("queue_requests_admitted"))
    assert admitted >= len(tids)
    assert sum(v for k, v in snap.items()
               if k.startswith("node_queries")) == len(tids)
    assert snap["ppo_reward"]["count"] == len(tids)
    assert "kv_pool_utilization" in snap
    assert 0.0 <= snap["kv_pool_utilization"] <= 1.0
    assert snap["kv_pool_high_watermark"] >= 1
    assert any(k.startswith("node_assigned_share") for k in snap)


def test_disabled_mode_never_reads_clock(obs_cluster, monkeypatch):
    """With tracing off, the serving path must not touch the tracer's
    clock, make a profiler annotation or allocate span state — the
    instrument is free when unused."""
    import repro.obs.trace as trace_mod
    nodes, _, _ = obs_cluster
    assert not obs.enabled()

    def boom():
        raise AssertionError("perf_counter read on the disabled path")

    def no_annotation(name, **kw):
        raise AssertionError(f"annotation {name} made on the disabled path")

    monkeypatch.setattr(trace_mod, "perf_counter", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    tr = obs.get_tracer()
    assert tr.span("decode_segment", traces=["a", "b"]) is obs.NULL_SPAN
    assert tr.now() == 0.0
    tr.event("prefix_cache", "a", hit=True)       # returns, no record
    tr.emit("decode", "a", 0.0, 1.0)
    # a real decode segment: begin_frame + run_segment + release on the
    # fixture's paged engine, with the tracer clock booby-trapped
    eng = nodes[0].engine
    sess = eng.continuous_session(GenerationParams(max_new_tokens=2),
                                  prefix_cache=2)
    sess.begin_frame([[5, 6, 7], [8, 9]], [2, 2])
    done = 0
    while sess.active():
        done += len(sess.run_segment(drain=True))
    sess.release()
    assert done == 2
    assert tr.recorder is None


def test_traced_slot_names_each_node_stage(obs_cluster, tmp_path):
    """Dispatch, tokenisation, the engine run, scoring and feedback each
    have a span of their own, under the right parent, and every decode
    segment carries the decode-loop steps it ran."""
    nodes, rec, tids = obs_cluster
    events = rec.events()
    by_id = {e["id"]: e for e in events if e["kind"] == "span"}
    for tid in tids:
        spans = {e["name"]: e for e in events
                 if e["kind"] == "span" and e["trace"] == tid}
        root = spans["request"]
        slot = spans["node_slot"]
        assert slot["parent"] == root["id"]
        assert slot["attrs"]["node"] in (0, 1)
        for name in ("retrieve", "tokenize", "generate", "score"):
            assert spans[name]["parent"] == slot["id"], name
        assert spans["tokenize"]["t1"] <= spans["generate"]["t0"] \
            <= spans["generate"]["t1"] <= spans["score"]["t0"]
        assert by_id[spans["detokenize"]["parent"]]["name"] == "score"
        assert by_id[spans["prefill"]["parent"]]["name"] == "generate"
        assert spans["feedback"]["parent"] == root["id"]
        assert spans["feedback"]["t0"] >= slot["t1"]
    segs = [e for e in events if e["kind"] == "span"
            and e["name"] == "decode_segment"]
    assert segs and all(e["attrs"]["steps"] >= 1 for e in segs)
    # the dump with the new spans still passes the CI gate
    path = rec.export_jsonl(str(tmp_path / "stages.jsonl"))
    assert trace_report.main([path, "--check"]) == 0


def test_paged_decode_segment_counts_kv_blocks(obs_cluster):
    """A paged decode segment records, at its entry, the block-table
    columns the paged kernel reads (each live row's first // bs to
    length // bs) and the B x nb_cap columns of the whole table."""
    nodes, _, _ = obs_cluster
    eng = nodes[0].engine
    prompts, budgets = [[5, 6, 7], [8, 9]], [3, 2]
    rec = obs.enable()
    try:
        sess = eng.continuous_session(
            GenerationParams(max_new_tokens=3), prefix_cache=2)
        sess.begin_frame(prompts, budgets)
        L, bs = sess.length, eng.block_size
        sess.run_segment(drain=True)
        sess.release()
    finally:
        obs.disable()
    segs = [e for e in rec.events() if e["kind"] == "span"
            and e["name"] == "decode_segment"]
    live = sum(L // bs - (L - len(p)) // bs + 1 for p in prompts)
    grid = eng.batch_size * eng._cont_nb_cap(L + max(budgets) + 2)
    assert segs and all(e["attrs"]["kv_blocks_live"] == live
                        and e["attrs"]["kv_blocks_grid"] == grid
                        for e in segs)
