"""Paper-core invariants: Algorithm 1, intra-node OCO solver, pool
manager ULD/LD/RLD semantics, PPO identifier."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.edge_pool import MODEL_SPECS, pool_for_family
from repro.core.inter_node import inter_node_schedule
from repro.core.intra_node import (_project_capped_simplex, _project_R,
                                   IntraNodeScheduler)
from repro.core.latency_model import LatencyOracle, fit_latency_models
from repro.serving.pool import ModelPoolManager


# ----------------------------------------------------------- projections


@given(st.lists(st.floats(-5, 5), min_size=1, max_size=12),
       st.floats(0.1, 3.0))
@settings(max_examples=60, deadline=None)
def test_capped_simplex_projection(v, cap):
    x = _project_capped_simplex(np.asarray(v), cap)
    assert (x >= -1e-12).all()
    assert x.sum() <= cap + 1e-9
    # fixed point: projecting a feasible point returns it
    y = _project_capped_simplex(x, cap)
    assert np.allclose(x, y, atol=1e-9)


@given(st.integers(1, 6), st.integers(0, 10 ** 6))
@settings(max_examples=40, deadline=None)
def test_R_projection(n, seed):
    rng = np.random.default_rng(seed)
    rmin = rng.uniform(0.02, 0.9 / n, n)
    R = rng.uniform(-1, 2, n)
    out = _project_R(R, rmin, 1.0)
    assert (out >= rmin - 1e-9).all()
    assert out.sum() <= 1.0 + 1e-9


# ----------------------------------------------------------- Algorithm 1


@given(st.integers(1, 300), st.integers(2, 6), st.integers(0, 10 ** 6))
@settings(max_examples=40, deadline=None)
def test_inter_node_invariants(B, N, seed):
    rng = np.random.default_rng(seed)
    probs = rng.dirichlet(np.ones(N), size=B)
    caps = rng.uniform(1, B, N)
    a, p = inter_node_schedule(probs, caps, rng)
    assert a.shape == (B,) and ((a >= 0) & (a < N)).all()   # all assigned
    assert abs(p.sum() - 1.0) < 1e-9                        # proportions
    counts = np.bincount(a, minlength=N)
    if B <= caps.sum():
        # no node exceeds its (un-inflated) capacity by more than 1
        assert (counts <= np.ceil(caps) + 1).all()
    else:
        # inflation keeps everything assigned proportionally
        infl = caps + caps / caps.sum() * (B - caps.sum())
        assert (counts <= np.ceil(infl) + 1).all()


# ----------------------------------------------------------- pool manager


def test_pool_manager_lifecycle():
    pool = pool_for_family("llama")
    mgr = ModelPoolManager(pool, num_gpus=1)
    small, mid = pool[0].name, pool[1].name
    # fresh load of two models
    rep = mgr.apply({(small, 0): 0.3, (mid, 0): 0.6})
    assert {m for m, _ in rep.loads} == {small, mid}
    assert rep.max_tl == pytest.approx(
        MODEL_SPECS[small].load_time_s + MODEL_SPECS[mid].load_time_s)
    # unchanged allocation -> free
    rep = mgr.apply({(small, 0): 0.3, (mid, 0): 0.6})
    assert rep.max_tl == 0.0 and not rep.loads and not rep.reloads
    # resource change -> reload; unload -> free
    rep = mgr.apply({(small, 0): 0.5})
    assert (small, 0) in rep.reloads
    assert (mid, 0) in rep.unloads
    assert rep.max_tl == pytest.approx(MODEL_SPECS[small].load_time_s)


def test_pool_manager_epsilon_snap_no_reload():
    """An R change within epsilon_1 is not a significant change: the
    model keeps serving (no RLD, no load time) but the tracked R still
    moves to the new value."""
    pool = pool_for_family("llama")
    mgr = ModelPoolManager(pool, num_gpus=1, eps=0.05)
    m = pool[0].name
    mgr.apply({(m, 0): 0.30})
    rep = mgr.apply({(m, 0): 0.33})              # |dR| = 0.03 <= eps
    assert rep.max_tl == 0.0 and not rep.reloads and not rep.loads
    assert mgr.R[0][m] == pytest.approx(0.33)
    rep = mgr.apply({(m, 0): 0.40})              # |dR| = 0.07 > eps -> RLD
    assert (m, 0) in rep.reloads
    assert rep.max_tl == pytest.approx(MODEL_SPECS[m].load_time_s)


def test_pool_manager_unload_then_reload_consecutive_slots():
    """Unloading is free, but bringing the model back next slot is a
    fresh LD that pays l_m again (no warm-cache shortcut)."""
    pool = pool_for_family("llama")
    mgr = ModelPoolManager(pool, num_gpus=1)
    m = pool[0].name
    rep = mgr.apply({(m, 0): 0.3})
    assert (m, 0) in rep.loads
    rep = mgr.apply({})                          # ULD: ~free
    assert (m, 0) in rep.unloads and rep.max_tl == 0.0
    assert mgr.deployed(0) == {}
    rep = mgr.apply({(m, 0): 0.3})               # back -> full LD cost
    assert (m, 0) in rep.loads and not rep.reloads
    assert rep.max_tl == pytest.approx(MODEL_SPECS[m].load_time_s)


def test_pool_manager_over_memory_boundaries():
    """Exactly-full GPUs pass; anything past gpu_mem (or below the
    model's startup minimum) is rejected before mutating state."""
    pool = pool_for_family("llama")
    mgr = ModelPoolManager(pool, num_gpus=2)
    a, b = pool[0].name, pool[1].name
    mgr.apply({(a, 0): 0.5, (b, 0): 0.5})        # sum == gpu_mem: fine
    with pytest.raises(AssertionError):
        mgr.apply({(a, 0): 0.5, (b, 0): 0.52})
    # failed validation must not have clobbered the deployment state
    assert mgr.deployed(0) == {a: 0.5, b: 0.5}
    # per-GPU accounting: same total split across GPUs is fine
    rep = mgr.apply({(a, 0): 0.5, (b, 1): 0.52})
    assert (b, 1) in rep.reloads or (b, 1) in rep.loads


def test_pool_manager_memory_validation():
    pool = pool_for_family("llama")
    mgr = ModelPoolManager(pool, num_gpus=1)
    with pytest.raises(AssertionError):
        mgr.apply({(pool[0].name, 0): 0.7, (pool[1].name, 0): 0.7})
    with pytest.raises(AssertionError):   # below min startup memory
        mgr.apply({(pool[2].name, 0): 0.05})


# ----------------------------------------------------------- intra-node


def _make_sched(num_gpus=1, seed=0):
    pool = pool_for_family("llama")
    oracle = LatencyOracle(seed=seed)
    fits = {s.name: fit_latency_models(oracle, s, seed=seed)[0]["quadratic"]
            for s in pool}
    Q = {s.name: s.base_quality for s in pool}
    mgr = ModelPoolManager(pool, num_gpus)
    return IntraNodeScheduler(0, pool, num_gpus, fits, Q, mgr), oracle, pool


def test_intra_node_respects_memory_and_budget():
    sched, oracle, pool = _make_sched()
    alloc = sched.schedule(n_queries=200, budget_s=15.0)
    assert alloc.p, "no allocation found"
    per_gpu = {}
    for (m, k), r in alloc.R.items():
        per_gpu.setdefault(k, 0.0)
        per_gpu[k] += r
        assert r >= sched.mgr.specs[m].min_mem_frac - 1e-6
    assert all(v <= 1.0 + 1e-6 for v in per_gpu.values())
    assert sum(alloc.p.values()) <= 1.0 + 1e-6


def test_intra_node_adapts_to_budget():
    """Strict budget -> small models dominate; loose -> larger models."""
    sched, _, pool = _make_sched()
    tight = sched.schedule(500, budget_s=5.0)
    sched2, _, _ = _make_sched()
    loose = sched2.schedule(500, budget_s=60.0)

    def big_share(alloc):
        tot = sum(alloc.p.values()) or 1
        return sum(v for (m, k), v in alloc.p.items()
                   if "8b" in m or "3b" in m) / tot

    assert big_share(loose) > big_share(tight)


def test_intra_node_quality_beats_fixed_small():
    """The OCO allocation should match or beat small-only under a loose
    budget (it can use larger models)."""
    sched, _, pool = _make_sched()
    alloc = sched.schedule(300, budget_s=40.0)
    q_small = pool[0].base_quality
    assert alloc.objective >= q_small * sum(alloc.p.values()) - 1e-6
