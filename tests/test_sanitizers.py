"""Runtime sanitizers (tools/sanitize.py): the recompile guard, the
donation poisoner (TPU-faithful donation semantics on CPU), the
ENGINE_DONATIONS table's cross-check against the IL002 static extractor,
and the Pallas interpret-mode parity harness."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from _sanitizers import (
    ENGINE_DONATIONS,
    RecompileError,
    RecompileGuard,
    jitted_functions,
    pallas_parity_report,
    poison_donated,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- recompile guard


def test_recompile_guard_passes_on_stable_shapes():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((4,)))  # warm
    with RecompileGuard({"f": f}):
        for _ in range(3):
            f(jnp.ones((4,)))


def test_recompile_guard_fires_on_shape_change():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((4,)))
    with pytest.raises(RecompileError, match="1 jit cache miss"):
        with RecompileGuard({"f": f}):
            f(jnp.ones((5,)))  # new shape -> retrace


def test_recompile_guard_budget_and_non_jitted_skipped():
    f = jax.jit(lambda x: x + 1)
    with RecompileGuard({"f": f, "not_jitted": len}, budget=1):
        f(jnp.ones((2,)))  # one allowed miss


def test_recompile_guard_does_not_mask_inner_errors():
    f = jax.jit(lambda x: x)
    with pytest.raises(ValueError, match="inner"):
        with RecompileGuard({"f": f}):
            raise ValueError("inner")


def test_jitted_functions_finds_engine_wrappers(small_engine):
    found = jitted_functions(small_engine)
    for name in ENGINE_DONATIONS:
        if hasattr(small_engine, name):
            assert name in found, name


# ------------------------------------------------------ donation poisoner


def test_poison_donated_raises_on_use_after_donate():
    step = jax.jit(lambda p, buf: buf + p, donate_argnums=(1,))
    step = poison_donated(step, (1,))
    buf = jnp.ones((8,))
    out = step(2.0, buf)
    assert out is not None
    with pytest.raises(RuntimeError, match="deleted"):
        buf.sum()  # use-after-donate: poisoned buffer is dead


def test_poison_donated_rebinding_idiom_passes():
    step = jax.jit(lambda p, buf: buf + p, donate_argnums=(1,))
    step = poison_donated(step, (1,))
    buf = jnp.zeros((8,))
    for _ in range(4):
        buf = step(1.0, buf)  # correct: rebind from the results
    assert float(buf[0]) == 4.0


def test_poison_donated_handles_pytree_args():
    step = jax.jit(lambda p, tree: jax.tree.map(lambda a: a + p, tree),
                   donate_argnums=(1,))
    step = poison_donated(step, (1,))
    tree = {"a": jnp.ones((2,)), "b": jnp.zeros((3,))}
    out = step(1.0, tree)
    assert set(out) == {"a", "b"}
    with pytest.raises(RuntimeError, match="deleted"):
        tree["a"].sum()


# --------------------------------------------------- poisoned engine e2e


@pytest.fixture
def small_engine(key):
    from repro.configs import get_smoke_config
    from repro.models import Model
    from repro.serving import ServeEngine
    cfg = get_smoke_config("llama3-8b")
    m = Model(cfg)
    params = m.init_params(key, max_seq=64)
    return ServeEngine(cfg, params, max_len=64, batch_size=2)


def test_poisoned_engine_generates(small_engine, poisoned):
    """The engine's own dispatch paths must survive TPU-faithful
    donation semantics: every donated buffer is rebound, never reused."""
    eng = poisoned(small_engine)
    for name, pos in ENGINE_DONATIONS.items():
        fn = getattr(eng, name, None)
        if fn is not None:
            assert getattr(fn, "__wrapped_donations__", None) == pos
    outs = eng.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=4)
    assert len(outs) == 2 and all(len(o) == 4 for o in outs)


@pytest.mark.parametrize("archs", [("olmo-1b", "xlstm-350m"),
                                   ("hymba-1.5b", "qwen2-moe-a2.7b")])
def test_poisoned_standing_cluster_serves(archs, poisoned):
    """The standing paged node path (frame, refill, prefix fork, COW
    copy, decode segments straddling slots) under TPU-faithful donation:
    every routed request is answered and none is left behind."""
    from repro.cluster import ClusterRuntime, LiveWorkload, replay_trace
    from repro.launch.cluster_serve import build_cluster
    nodes, qas, _, enc, ident, _ = build_cluster(
        2, smoke=True, archs=archs, queue="standing", paged=True,
        admission="sjf")
    for node in nodes:
        poisoned(node.engine)
    rt = ClusterRuntime(nodes, ident)
    rt.initialize()
    rep = replay_trace(rt, LiveWorkload(qas, enc, seed=2), n_slots=3,
                       slo_s=30.0, base_volume=8, trace="spike")
    lost = sum(node.unfinished() for node in nodes)
    rt.close()
    assert lost == 0
    assert sum(n.stats.queries for n in nodes) \
        == sum(m.n_queries for m in rep.slots)
    assert all(n.stats.refills and n.stats.prefix_hits for n in nodes)


def test_engine_decode_has_no_recompiles(small_engine, recompile_guard):
    eng = small_engine
    eng.generate([[1, 2, 3]], max_new_tokens=3)  # warm every shape
    with recompile_guard(eng):
        eng.generate([[9, 8, 7]], max_new_tokens=3)


# ----------------------------------------- donation table cross-check


def test_engine_donations_matches_static_extractor():
    """ENGINE_DONATIONS is a hand-written mirror of engine.py's jit
    wrappers; the IL002 extractor reads the actual source, so this pins
    the poisoner to the code and fails if either drifts."""
    tools = os.path.join(_REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from invariant_lint.core import Source
    from invariant_lint.rules.il002_donation import _collect_donated

    src = Source.parse(os.path.join(
        _REPO, "src", "repro", "serving", "engine.py"))
    static = _collect_donated([src])
    engine_static = {k: v for k, v in static.items()
                     if k in ENGINE_DONATIONS or k.startswith("_")}
    assert engine_static == ENGINE_DONATIONS


# --------------------------------------------------------- Pallas parity


@pytest.mark.slow
def test_pallas_parity_all_kernels():
    report = pallas_parity_report(seed=0)
    assert {r["kernel"] for r in report} == {
        "flash_attention", "paged_attention", "topk_scores",
        "topk_indices", "ivf_topk_scores", "ivf_topk_indices"}
    bad = [r for r in report if not r["ok"]]
    assert not bad, bad
