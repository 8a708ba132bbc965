"""Staging prefills (refill, prefix prefill) in passes of several chunks
(``ServeEngine.staging_passes``) against the one-chunk-a-pass scan they
replace, on the same inputs: the first token's logits, the pool's K/V at
the row's blocks and the row's non-pooled state agree to a float32
tolerance — for a plain refill, a prefix prefill and the fork of its
question suffix, with chunk counts that give k < G, k = G and a scan of
full passes plus a remainder pass (the 9-chunk prefix also ends mid
block).  Also the pass layout rules (window cap, MoE capacity) and the
pass counters and span attributes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving import ContinuousQueue, GenerationParams, ServeEngine
from repro.serving import engine as engine_mod

C, BS, MAX_LEN = 8, 16, 160
# float32 activations of O(1): ~1e3 ulp, room for sums regrouped by pass
# (mLSTM chunks, attention blocks) — a wrong pass is off by O(1)
TOL = 1e-4


def _engine(cfg, params, pass_max, monkeypatch, cf=None):
    monkeypatch.setattr(engine_mod, "PREFILL_PASS_MAX", pass_max)
    return ServeEngine(cfg, params, max_len=MAX_LEN, batch_size=1,
                       moe_capacity_factor=cf, prefill_chunk=C, paged=True,
                       block_size=BS)


def _stage(eng, cache, toks, table, row_state, length0, l_end, first0):
    """One staging prefill of ``toks`` through a row of ``cache``."""
    def f(params, toks, cache, table, row_state):
        stg = eng._paged_row_staging(cache, row_state, table, length0,
                                     first0)
        return eng._staging_prefill(params, toks, stg, l_end)
    return jax.jit(f)(eng.params, jnp.asarray(toks), cache,
                      jnp.asarray(table), row_state)


def _row_state(eng, stg):
    return {"slots": {n: stg["slots"][n] for n in eng._nonpooled}}


def _admissions(eng, k):
    """Logits, pool blocks and row state after a plain refill of a
    ``k``-chunk prompt, a ``k``-chunk prefix prefill, and the fork of a
    38-token (5-chunk) question suffix onto that prefix."""
    nb = eng.nb_total
    table = np.arange(nb, dtype=np.int32)
    cache = eng._paged_fresh_cache(jnp.zeros(1, jnp.int32),
                                   jnp.zeros(1, jnp.int32),
                                   jnp.full((1, nb), -1, jnp.int32))
    zero = eng._paged_zero_row_state()
    L0 = k * C
    p = L0 - 3                                   # left pad of 3
    prompt = [1 + (7 * i + i // 5) % 13 for i in range(p)]
    toks = np.zeros((1, L0), np.int32)
    toks[0, 3:] = prompt
    out = {}
    logits, stg = _stage(eng, cache, toks, table, zero, 0, L0, 3)
    out["plain"] = (logits, stg)
    # the prefix prefill is the same staging pass over the same tokens;
    # its fork continues in the prefix's blocks from position L0
    pool = dict(cache, slots={n: stg["slots"][n] if n in eng._pooled_set
                              else cache["slots"][n]
                              for n in cache["slots"]})
    q = 38
    suffix = np.zeros((1, 5 * C), np.int32)
    suffix[0, :q] = [2 + (3 * i) % 11 for i in range(q)]
    out["fork"] = _stage(eng, pool, suffix, table, _row_state(eng, stg),
                         L0, L0 + q, 3)
    return out


@pytest.mark.parametrize("k", [2, 4, 9])
@pytest.mark.parametrize("arch", [
    "llama3-8b",                 # pooled attention
    "xlstm-350m",                # mLSTM + sLSTM state
    "hymba-1.5b",                # 16-token window + mamba: G = 2
    "qwen2-moe-a2.7b",           # dropless MoE: passes not pinned
])
def test_staging_passes_match_chunk_scan(arch, k, key, monkeypatch):
    cfg = get_smoke_config(arch)
    params = Model(cfg).init_params(key, max_seq=MAX_LEN)
    cf = float(cfg.moe.num_experts) if cfg.moe else None
    ref = _engine(cfg, params, C, monkeypatch, cf)          # C-token passes
    new = _engine(cfg, params, 4 * C, monkeypatch, cf)
    assert ref.staging_passes(k) == (k, 1, 0)
    n, g, r = new.staging_passes(k)
    assert g > 1 and n * g + r == k
    if cfg.sliding_window:
        assert g * C <= cfg.sliding_window
    got, want = _admissions(new, k), _admissions(ref, k)
    nblk = -(-(k * C + 38) // BS)
    for path in ("plain", "fork"):
        (lg, sg), (lw, sw) = got[path], want[path]
        np.testing.assert_allclose(lg, lw, rtol=TOL, atol=TOL,
                                   err_msg=path)
        for name in sg["slots"]:
            pooled = name in new._pooled_set
            for leaf_g, leaf_w in zip(jax.tree.leaves(sg["slots"][name]),
                                      jax.tree.leaves(sw["slots"][name])):
                if pooled:                # the row's blocks of the pool
                    leaf_g, leaf_w = leaf_g[:, :nblk], leaf_w[:, :nblk]
                np.testing.assert_allclose(
                    leaf_g, leaf_w, rtol=TOL, atol=TOL,
                    err_msg=f"{path} {name}")


def test_pass_layout_caps_window_and_pins_dropping_moe(key, monkeypatch):
    """A windowed config never gets a pass longer than its window; a MoE
    config whose expert capacity can drop tokens keeps one chunk a pass
    (its drops would depend on the pass), a dropless one does not."""
    monkeypatch.setattr(engine_mod, "PREFILL_PASS_MAX", 1024)
    for arch in ("gemma2-9b", "hymba-1.5b"):
        cfg = get_smoke_config(arch)
        eng = ServeEngine(cfg, Model(cfg).init_params(key, max_seq=64),
                          max_len=64, batch_size=1, prefill_chunk=C,
                          paged=True)
        assert eng.staging_passes(7) == (3, cfg.sliding_window // C, 1)
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    params = Model(cfg).init_params(key, max_seq=64)
    for cf, layout in ((1.25, (9, 1, 0)),
                       (float(cfg.moe.num_experts), (1, 9, 0))):
        eng = ServeEngine(cfg, params, max_len=64, batch_size=1,
                          moe_capacity_factor=cf, prefill_chunk=C)
        assert eng.staging_passes(9) == layout


def test_prefill_pass_counters_and_span_attrs(key, monkeypatch):
    """``prefill_passes``/``prefill_tokens`` count every staging pass and
    staged token (pads included), monotone across runs and exact in
    ``snapshot()``/``delta()``; the refill's ``prefill`` span and the
    ``prefix_prefill`` span carry ``passes``."""
    cfg = get_smoke_config("llama3-8b")
    params = Model(cfg).init_params(key, max_seq=MAX_LEN)
    monkeypatch.setattr(engine_mod, "PREFILL_PASS_MAX", 4 * C)
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, batch_size=2,
                      prefill_chunk=C, paged=True, block_size=BS)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=3),
                        key=jax.random.PRNGKey(1), standing=True)
    ctx = [1 + i % 9 for i in range(67)]          # 9 chunks: 4 + 4 + 1
    rec = obs.enable(capacity=256)
    try:
        # the first request opens the frame (no staging prefill); the
        # second refills: a 9-chunk prefix prefill (3 passes) and its
        # 1-chunk question suffix (1 pass)
        q.submit([5, 6, 7], prefix_len=0, trace="a")
        rid = q.submit(ctx + [4, 4, 1], prefix_len=len(ctx), trace="b")
        q.run(wait_for=[rid])
        base = q.stats.snapshot()
        assert (base["prefill_passes"], base["prefill_tokens"]) == (4, 80)
        # a prefix hit stages only the suffix
        rid = q.submit(ctx + [7, 8], prefix_len=len(ctx), trace="c")
        q.run(wait_for=[rid])
    finally:
        obs.disable()
    d = q.stats.delta(base)
    assert (d.prefill_passes, d.prefill_tokens) == (1, 8)
    assert (q.stats.prefill_passes, q.stats.prefill_tokens) == (5, 88)
    spans = {(e["trace"], e["name"]): e.get("attrs", {})
             for e in rec.events() if e["kind"] == "span"}
    assert spans[("-", "prefix_prefill")]["passes"] == 3
    assert spans[("b", "prefill")]["passes"] == 4
    assert spans[("b", "prefill")]["staged_tokens"] == 80
    assert spans[("c", "prefill")]["passes"] == 1
    assert "passes" not in spans[("a", "prefill")]   # the frame's
    q.close()
