"""Standing engine: one long-lived ContinuousQueue session across
``run()`` calls.

Covers the promises docs/ARCHITECTURE.md makes for standing mode:
token-exact parity with per-run scheduling for every cache kind (paged
and non-paged) including requests that straddle a slot boundary
mid-decode, frame counts flat in the number of slots on a steady
stream, mid-frame SLO shed (hints act at the next run without draining
the live frame), arrival-anchored TTFT/latency, monotone-counter
snapshot/delta accounting, and randomized submit/run/spike/drain
interleavings that must never deadlock, lose a request id, overrun a
budget, or leak a KV block.
"""
import time

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving import ContinuousQueue, GenerationParams, ServeEngine


def make_engine(arch, key, *, paged, batch_size=2, max_len=96,
                prefill_chunk=8, block_size=16):
    cfg = get_smoke_config(arch)
    cf = float(cfg.moe.num_experts) if cfg.moe else None
    params = Model(cfg).init_params(key, max_seq=max_len)
    return ServeEngine(cfg, params, max_len=max_len, batch_size=batch_size,
                       moe_capacity_factor=cf, prefill_chunk=prefill_chunk,
                       paged=paged, block_size=block_size)


def reference_solo(eng, prompt, budget):
    gp = GenerationParams(max_new_tokens=budget)
    return eng.generate_reference([prompt], gen=gp)[0][:budget]


# whisper decodes with learned absolute positions: parity with the
# solo reference needs power-of-two prompt lengths (same caveat as
# test_continuous_batching.test_midstream_refill_parity).  Prompts 2/3
# also stay no longer than the slot-1 frame's live position: a
# non-paged refill only fits a prompt *below* the shared position, and
# the straddle assertion needs r2 and r3 admitted in the same refill.
ARCH_PROMPTS = {
    "llama3-8b": [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15],
                  [3, 1, 4, 1], [9, 2, 6]],
    "gemma2-9b": [[1, 2, 3, 4, 5, 6], [7, 8, 9], [11, 12, 13, 14],
                  [3, 1, 4, 1, 5], [9, 2, 6]],
    "xlstm-350m": [[1, 2, 3, 4, 5, 6], [7, 8, 9], [11, 12, 13, 14],
                   [3, 1, 4, 1, 5], [9, 2, 6]],
    "hymba-1.5b": [[1, 2, 3, 4, 5, 6], [7, 8, 9], [11, 12, 13, 14],
                   [3, 1, 4, 1, 5], [9, 2, 6]],
    "whisper-base": [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12],
                     [5] * 8, [7] * 8, [3] * 8],
}
BUDGETS = [6, 2, 8, 4, 5]


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("paged", [False, True],
                         ids=["nonpaged", "paged"])
@pytest.mark.parametrize("arch", list(ARCH_PROMPTS))
def test_standing_stream_parity(arch, paged, key):
    """A standing queue fed slot-by-slot — including a request left
    straddling a slot boundary mid-decode — must emit the exact greedy
    tokens of a solo reference run, for every cache kind."""
    eng = make_engine(arch, key, paged=paged)
    prompts, budgets = ARCH_PROMPTS[arch], BUDGETS
    refs = [reference_solo(eng, p, b) for p, b in zip(prompts, budgets)]
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=8),
                        standing=True)
    # slot 1: two requests, wait for both
    r0 = q.submit(prompts[0], budgets[0])
    r1 = q.submit(prompts[1], budgets[1])
    q.run(wait_for=[r0, r1])
    # slot 2: wait only for the short request; the long one (budget 8)
    # keeps its row and straddles into the next slot mid-decode
    r2 = q.submit(prompts[2], budgets[2])
    r3 = q.submit(prompts[3], budgets[3])
    q.run(wait_for=[r3])
    assert r2 in q.unfinished()
    # slot 3: the straggler finishes alongside a new arrival
    r4 = q.submit(prompts[4], budgets[4])
    q.run(wait_for=[r2, r4])
    assert q.unfinished() == []
    for rid, ref in zip([r0, r1, r2, r3, r4], refs):
        assert q.result(rid).tokens == ref, (arch, paged, rid)
    # a paged standing session admits through refill into its one frame
    if paged:
        assert q.stats.frames == 1
    q.close()
    assert q._session is None


# ------------------------------------------------------------ frame counts


def test_frames_flat_on_steady_stream(key):
    """Frame count must not scale with the slot count: a steady stream
    through a paged standing queue stays in ONE warm frame, admitting
    every post-frame request via refill (a per-slot queue would open a
    frame per slot)."""
    eng = make_engine("llama3-8b", key, paged=True)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=4),
                        standing=True)
    n_slots = 6
    for s in range(n_slots):
        rids = [q.submit([s + 1, j + 2, 5], 3) for j in range(2)]
        q.run(wait_for=rids)
    assert q.stats.frames == 1
    assert q.stats.refills >= 2 * n_slots - eng.batch_size
    q.close()


def test_nonpaged_standing_restarts_only_when_frame_is_full(key):
    """A non-paged standing frame's shared position only grows; once
    admission no longer fits (position + budget > max_len) the frame
    restarts — frames stay far below slot count but need not be 1."""
    eng = make_engine("llama3-8b", key, paged=False, max_len=96)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=4),
                        standing=True)
    n_slots = 8
    for s in range(n_slots):
        rids = [q.submit([s + 1, j + 2, 5], 3) for j in range(2)]
        q.run(wait_for=rids)
    assert q.stats.frames < n_slots
    assert q.unfinished() == []
    q.close()


# ------------------------------------------------------------ mid-frame shed


def test_midframe_shed_and_recovery(key):
    """A shed hint set while the frame is live drops the pending tail
    at the next run() — without draining the frame: the straddling row
    keeps decoding.  Clearing the hint restores normal admission and
    the straggler still finishes with exact tokens."""
    eng = make_engine("llama3-8b", key, paged=True)
    ref_long = reference_solo(eng, [1, 2, 3], 8)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=8),
                        standing=True)
    r_short = q.submit([4, 5, 6], 2)
    r_long = q.submit([1, 2, 3], 8)
    q.run(wait_for=[r_short])
    assert r_long in q.unfinished()          # frame is live mid-decode
    frames_before = q.stats.frames

    # synthetic FIRING: shed everything pending at the next run
    q.set_shed(1.0)
    shed_rids = [q.submit([7, 8], 4), q.submit([9, 1], 4)]
    q.run(wait_for=shed_rids)
    for rid in shed_rids:
        c = q.result(rid)
        assert c.shed and c.tokens == []
    assert q.stats.shed_hint_drops == 2
    assert r_long in q.unfinished()          # shed did not drain the frame
    assert q.stats.frames == frames_before

    # recovery: clearing the hint must not cost a frame restart either
    q.set_shed(0.0)
    r_new = q.submit([2, 4, 6], 3)
    q.run(wait_for=[r_long, r_new])
    assert q.result(r_long).tokens == ref_long
    assert len(q.result(r_new).tokens) == 3
    assert not q.result(r_new).shed
    assert q.stats.frames == frames_before
    q.close()


def test_shed_trace_is_terminal_and_complete(key, tmp_path):
    """A request dropped by a shed hint emits a terminal ``shed`` span,
    and trace_report counts its causal tree as complete — the CI
    saturation smoke replays spike traffic where shedding is routine,
    so shed trees must not read as instrumentation gaps."""
    import os
    import sys

    from repro import obs
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools import trace_report

    eng = make_engine("llama3-8b", key, paged=False)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=3),
                        standing=True)
    rec = obs.enable(capacity=256)
    try:
        tr = obs.get_tracer()
        with tr.span("request", trace="shed-1"):
            rid = q.submit([1, 2, 3], 2, trace="shed-1")
            q.set_shed(1.0)
            q.run(wait_for=[rid])
    finally:
        obs.disable()
        q.set_shed(0.0)
        q.close()
    assert q.result(rid).shed
    path = rec.export_jsonl(str(tmp_path / "shed.jsonl"))
    meta, events, errors = trace_report.load(path)
    assert not errors
    names = {e["name"] for e in events if e["trace"] == "shed-1"}
    assert "shed" in names and "decode" not in names
    comp, rooted, frac = trace_report.completeness(events)
    assert (comp, rooted, frac) == (1, 1, 1.0)


# --------------------------------------------------- arrival-anchored timing


def test_ttft_and_latency_are_arrival_anchored(key):
    """TTFT and latency must be measured from submit(), not from the
    start of run(): a request that sat in the queue before the engine
    was pumped carries its queue wait (regression: they used to be
    run()-relative, hiding cross-slot waits entirely)."""
    eng = make_engine("llama3-8b", key, paged=False)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=3),
                        standing=True)
    rid = q.submit([1, 2, 3], 3)
    wait = 0.05
    time.sleep(wait)
    q.run(wait_for=[rid])
    c = q.result(rid)
    assert c.ttft_s >= wait
    assert c.done_s >= c.ttft_s
    assert q.stats.ttft_s[-1] == c.ttft_s
    q.close()


def test_wait_for_requires_standing(key):
    eng = make_engine("llama3-8b", key, paged=False)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=3))
    rid = q.submit([1, 2, 3], 2)
    with pytest.raises(ValueError, match="standing"):
        q.run(wait_for=[rid])


# ------------------------------------------------------------ snapshot/delta


def test_stats_snapshot_delta():
    """Per-slot stats are deltas of monotone counters: delta() must
    cover exactly the interval since the snapshot, including the
    per-request ttft/latency sample lists."""
    from repro.serving import ContinuousStats
    st_ = ContinuousStats()
    st_.requests, st_.tokens_out, st_.frames = 3, 12, 1
    st_.ttft_s, st_.latency_s = [0.1, 0.2], [0.3, 0.4]
    base = st_.snapshot()
    st_.requests += 2
    st_.tokens_out += 7
    st_.refills += 4
    st_.ttft_s += [0.5]
    st_.latency_s += [0.6, 0.7]
    d = st_.delta(base)
    assert (d.requests, d.tokens_out, d.frames, d.refills) == (2, 7, 0, 4)
    assert d.ttft_s == [0.5] and d.latency_s == [0.6, 0.7]
    # a fresh queue's delta against the zero snapshot is its totals
    zero = ContinuousStats().snapshot()
    full = st_.delta(zero)
    assert full.requests == st_.requests
    assert full.ttft_s == st_.ttft_s


def test_depth_and_oldest_wait(key):
    eng = make_engine("llama3-8b", key, paged=False)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=2),
                        standing=True)
    assert q.depth() == 0 and q.oldest_wait_s() == 0.0
    r0 = q.submit([1, 2], 2)
    q.submit([3, 4], 2)
    assert q.depth() == 2
    assert q.oldest_wait_s() > 0.0
    q.run(wait_for=[r0])
    assert q.depth() == q.pending() + len(q._owner)
    q.run()
    assert q.depth() == 0 and q.oldest_wait_s() == 0.0
    q.close()


# ------------------------------------------------------- stress (hypothesis)


def _run_interleaving(eng, ops, *, max_budget=3):
    """Drive one randomized submit/run/spike/shed/drain interleaving;
    returns (queue, {rid: budget})."""
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=max_budget),
                        standing=True)
    budgets = {}
    nxt = [1]

    def submit(n):
        for _ in range(n):
            b = 1 + (nxt[0] % max_budget)
            prompt = [(nxt[0] + j) % 31 + 1 for j in range(2 + nxt[0] % 4)]
            budgets[q.submit(prompt, b)] = b
            nxt[0] += 1

    for op in ops:
        if op == 0:
            submit(1)
        elif op == 1:                          # spike burst
            submit(4)
        elif op == 2:                          # wait for half the backlog
            rids = q.unfinished()
            if rids:
                q.run(wait_for=rids[:max(1, len(rids) // 2)])
        elif op == 3:                          # full drain
            q.run()
        elif op == 4:                          # empty-slot run
            q.run(wait_for=[])
        elif op == 5:                          # shed pulse
            q.set_shed(0.5)
            q.run(wait_for=q.unfinished())
            q.set_shed(0.0)
    q.run()                                    # final drain
    return q, budgets


def _check_interleaving(q, budgets):
    assert q.unfinished() == []                # nothing lost or stuck
    shed = 0
    for rid, b in budgets.items():
        c = q.result(rid)
        if c.shed:
            shed += 1
            assert c.tokens == []
        else:
            assert len(c.tokens) == b          # budgets honored exactly
            assert c.done_s >= c.ttft_s >= 0.0
    assert shed == q.stats.shed_hint_drops
    assert len(budgets) == q.stats.requests


@pytest.fixture(scope="module")
def stress_engine():
    return make_engine("llama3-8b", jax.random.PRNGKey(7), paged=False)


@pytest.fixture(scope="module")
def stress_engine_paged():
    return make_engine("llama3-8b", jax.random.PRNGKey(11), paged=True)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=2, max_size=5))
def test_streamed_admission_stress(stress_engine, ops):
    """No interleaving of submit/run/spike/empty-run/shed/drain may
    deadlock, lose a rid, or violate a per-request budget."""
    q, budgets = _run_interleaving(stress_engine, ops)
    _check_interleaving(q, budgets)
    q.close()


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(0, 5), min_size=6, max_size=12))
def test_streamed_admission_stress_paged_heavy(stress_engine_paged, ops):
    """Heavy paged interleavings: on top of the stream invariants,
    close() must return every KV block to the pool with all refcounts
    at zero."""
    eng = stress_engine_paged
    q, budgets = _run_interleaving(eng, ops)
    _check_interleaving(q, budgets)
    sess = q._session
    q.close()
    assert sess is not None
    assert sess.allocator.available == eng.num_blocks   # no leaked blocks
    assert (sess.allocator.refcount == 0).all()
