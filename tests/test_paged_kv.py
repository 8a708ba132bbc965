"""Paged KV cache: block allocator, paged continuous parity for every
cache kind, shared-prefix forking, admission policy and truncation.

The parity bar is the same as test_continuous_batching: token-exact
agreement with a *solo* ``generate_reference`` run per prompt (batched
references left-pad recurrent rows differently).  The paged path must
additionally leave the block pool leak-free after ``release()``.
"""
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops, ref
from repro.models import Model
from repro.models import cache as cache_lib
from repro.serving import ContinuousQueue, GenerationParams, ServeEngine
from repro.serving import engine as engine_mod


def make_paged_engine(arch, key, batch_size=2, max_len=96, prefill_chunk=8,
                      block_size=16, num_blocks=None):
    cfg = get_smoke_config(arch)
    cf = float(cfg.moe.num_experts) if cfg.moe else None
    params = Model(cfg).init_params(key, max_seq=max_len)
    return ServeEngine(cfg, params, max_len=max_len, batch_size=batch_size,
                       moe_capacity_factor=cf, prefill_chunk=prefill_chunk,
                       paged=True, block_size=block_size,
                       num_blocks=num_blocks)


def solo_refs(eng, prompts, budget):
    gp = GenerationParams(max_new_tokens=budget)
    return [eng.generate_reference([p], gen=gp)[0][:budget] for p in prompts]


def drain(sess, outs, n, budget):
    while len(outs) < n:
        for slot, toks in sess.run_segment(drain=True):
            outs[slot] = toks[:budget]
    return outs


# ---------------------------------------------------------------- allocator


def test_block_allocator_alloc_free_refcount():
    a = cache_lib.BlockAllocator(4)
    ids = a.alloc(3)
    assert sorted(ids) == [0, 1, 2] and a.available == 1
    shared = a.fork(ids[:2])
    assert shared == ids[:2]
    a.free(ids)                       # drops one owner; ids[:2] survive
    assert a.available == 2
    a.free(shared)
    assert a.available == 4
    assert (a.refcount == 0).all()


def test_block_allocator_errors_and_backpressure():
    a = cache_lib.BlockAllocator(2)
    ids = a.alloc(2)
    assert not a.can_alloc(1)
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(ids)
    with pytest.raises(ValueError):
        a.free([ids[0]])              # double free
    with pytest.raises(ValueError):
        a.fork([ids[0]])              # fork of a free block
    assert a.can_alloc(2)
    with pytest.raises(ValueError):
        cache_lib.BlockAllocator(0)


def test_block_allocator_utilization_and_watermark():
    a = cache_lib.BlockAllocator(8)
    assert a.utilization() == 0.0 and a.high_watermark == 0
    ids = a.alloc(5)
    assert a.in_use == 5
    assert a.utilization() == pytest.approx(5 / 8)
    assert a.high_watermark == 5
    a.free(ids[:3])
    assert a.utilization() == pytest.approx(2 / 8)
    assert a.high_watermark == 5              # watermark never recedes
    more = a.alloc(4)
    assert a.high_watermark == 6
    shared = a.fork(more[:2])
    assert a.forks == 2                       # COW shares counted
    assert a.in_use == 6                      # forks add owners, not blocks
    assert a.can_alloc(2) and a.exhaustions == 0
    assert not a.can_alloc(5)
    assert a.exhaustions == 1                 # failed probes counted
    a.free(more)
    a.free(shared)
    a.free(ids[3:])
    assert a.utilization() == 0.0 and a.available == 8


def test_block_allocator_recycle_no_leak():
    a = cache_lib.BlockAllocator(3)
    for _ in range(5):
        ids = a.alloc(2)
        more = a.fork(ids)
        a.free(ids)
        a.free(more)
    assert a.available == 3 and (a.refcount == 0).all()


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("arch,long_ctx", [
    pytest.param("llama3-8b", False, id="llama3-8b"),   # pooled attention
    pytest.param("gemma2-9b", False, id="gemma2-9b"),   # local + pooled
    pytest.param("xlstm-350m", False, id="xlstm-350m"),  # recurrent only
    pytest.param("hymba-1.5b", False, id="hymba-1.5b"),  # window + mamba
    pytest.param("whisper-base", False, id="whisper-base"),  # enc-dec
    pytest.param("llama3-8b", True, id="llama3-8b-long"),
    pytest.param("gemma2-9b", True, id="gemma2-9b-long"),
    pytest.param("xlstm-350m", True, id="xlstm-350m-long"),
    pytest.param("hymba-1.5b", True, id="hymba-1.5b-long"),
    pytest.param("qwen2-moe-a2.7b", True, id="qwen2-moe-a2.7b-long"),
])
def test_paged_parity_frame_refill_fork(arch, long_ctx, key, monkeypatch):
    """One frame, a plain paged refill, and a prefix-cache fork must all
    be token-exact against solo references — for every cache kind.

    ``long_ctx``: a 67-token context under 32-token staging passes (4
    chunks of 8; 2 under the 16-token window of gemma2/hymba), so the
    plain refill and the prefix prefill (9 chunks) each run a scan of
    full passes and a remainder pass, the prefix ends mid-block, and the
    windowed configs cross their window."""
    if long_ctx:
        monkeypatch.setattr(engine_mod, "PREFILL_PASS_MAX", 32)
    eng = make_paged_engine(arch, key)
    if arch == "whisper-base":        # learned positions: pow-2 prompts
        ctx = [5, 6, 7, 2, 3, 4, 1, 2]
        q1, q2 = [4, 4, 1, 3, 2, 6, 7, 5], [9, 3, 1, 5, 2, 6, 7, 4]
    else:
        ctx = [5, 6, 7, 2, 3, 4, 1, 2, 9, 9, 3]
        q1, q2 = [4, 4, 1], [7, 8, 2]
    if long_ctx:
        ctx = [1 + (5 * i + i // 7) % 11 for i in range(67)]
        assert eng.staging_passes(9)[2] > 0
    budget = 5
    refs = solo_refs(eng, [ctx + q1, ctx + q2], budget)
    sess = eng.continuous_session(GenerationParams(max_new_tokens=budget),
                                  key=jax.random.PRNGKey(7), prefix_cache=4)
    sess.begin_frame([ctx + q1, ctx + q2], [budget, budget])
    outs = drain(sess, {}, 2, budget)
    assert [outs[s] for s in sorted(outs)] == refs

    # plain refill (no prefix): exact and block-accounted
    sess.refill(0, ctx + q1, budget)
    outs = drain(sess, {}, 1, budget)
    assert outs[0] == refs[0]

    # prefix fork: first admission prefills the prefix (miss), the
    # second forks its blocks (hit) — both token-exact
    for slot, q in zip(range(2), (q1, q2)):
        assert sess.can_refill(len(ctx + q), budget,
                               prefix_len=len(ctx), prompt=ctx + q)
        sess.refill(slot, ctx + q, budget, prefix_len=len(ctx))
    outs = drain(sess, {}, 2, budget)
    assert [outs[s] for s in sorted(outs)] == refs
    assert sess.prefix_cache.hits == 1 and sess.prefix_cache.misses == 1

    sess.release()                    # leak check: every block returned
    assert sess.allocator.available == eng.num_blocks
    assert (sess.allocator.refcount == 0).all()


def test_paged_long_running_no_drain(key):
    """A paged session admits indefinitely through one frame: total
    served tokens exceed what any single static frame could hold, with
    no drain-and-restart (frames == 1)."""
    eng = make_paged_engine("llama3-8b", key, max_len=64, prefill_chunk=8)
    budget = 6
    prompts = [[1 + (7 * i + j) % 9 for j in range(5 + i % 7)]
               for i in range(12)]
    refs = solo_refs(eng, prompts, budget)
    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=budget),
                        key=jax.random.PRNGKey(3))
    rids = [q.submit(p) for p in prompts]
    outs = q.run()
    assert [outs[r] for r in rids] == refs
    assert q.stats.frames == 1        # never drained and restarted
    served = sum(len(p) for p in prompts) + sum(len(outs[r]) for r in rids)
    assert served > eng.max_len * eng.batch_size


def test_prefix_fork_cow_midblock_tail(key):
    """A prefix whose padded length is not a block multiple forks its
    full blocks and copies the tail block (COW): the cached entry keeps
    its own tail, so a second fork still hits and stays exact."""
    eng = make_paged_engine("llama3-8b", key, prefill_chunk=8,
                            block_size=16)
    ctx = [5, 6, 7, 2, 3, 4, 1, 2]    # L0 = 8 -> mid-block tail (8 % 16)
    qs = [[4, 4, 1], [7, 8, 2], [9, 1, 5]]
    budget = 4
    refs = solo_refs(eng, [ctx + q for q in qs], budget)
    sess = eng.continuous_session(GenerationParams(max_new_tokens=budget),
                                  key=jax.random.PRNGKey(5), prefix_cache=4)
    sess.begin_frame([[1, 2, 3]], [1])
    drain(sess, {}, 1, 1)
    for i, q in enumerate(qs):
        sess.refill(0, ctx + q, budget, prefix_len=len(ctx))
        outs = drain(sess, {}, 1, budget)
        assert outs[0] == refs[i]
    pc = sess.prefix_cache
    assert pc.misses == 1 and pc.hits == 2
    sess.release()
    assert sess.allocator.available == eng.num_blocks


def test_paged_pool_exhaustion_backpressure(key):
    """can_refill reports backpressure while the pool is full and
    recovers once a row finishes and returns its blocks; the scheduler
    path still completes every request."""
    eng = make_paged_engine("llama3-8b", key, batch_size=2, max_len=96,
                            prefill_chunk=8, block_size=16, num_blocks=2)
    budget = 4
    sess = eng.continuous_session(GenerationParams(max_new_tokens=budget),
                                  key=jax.random.PRNGKey(1))
    long_p = list(range(1, 20))       # ceil((24 + 4) / 16) = 2 blocks
    sess.begin_frame([long_p], [budget])
    assert not sess.can_refill(len(long_p), budget)   # pool is full
    drain(sess, {}, 1, budget)                        # row done -> freed
    assert sess.can_refill(len(long_p), budget)
    sess.release()

    q = ContinuousQueue(eng, GenerationParams(max_new_tokens=budget))
    with pytest.raises(ValueError):                   # can never fit
        q.submit(list(range(1, 40)), max_new_tokens=budget)
    rids = [q.submit(long_p) for _ in range(3)]       # fit one at a time
    outs = q.run()
    assert all(len(outs[r]) == budget for r in rids)


# ------------------------------------------------------- admission policy


def test_sjf_admits_shortest_prefill_first(key):
    """With both candidates admissible, SJF refills the cheap prefill
    first (better mean TTFT); FIFO keeps submission order."""
    long_p = [1 + i % 9 for i in range(32)]           # 4 chunks
    short_p = [2, 7, 1, 8, 2, 8, 1, 8]                # 1 chunk
    frame_p = [3, 1, 4, 1, 5]
    ttft = {}
    for policy in ("fifo", "sjf"):
        eng = make_paged_engine("llama3-8b", key, batch_size=1)
        q = ContinuousQueue(eng, GenerationParams(max_new_tokens=4),
                            key=jax.random.PRNGKey(2), policy=policy)
        q.submit(frame_p)                             # occupies the frame
        rid_long = q.submit(long_p)
        rid_short = q.submit(short_p)
        q.run()
        ttft[policy] = (q.result(rid_long).ttft_s,
                        q.result(rid_short).ttft_s)
    assert ttft["fifo"][0] < ttft["fifo"][1]          # FIFO: long first
    assert ttft["sjf"][1] < ttft["sjf"][0]            # SJF: short first


def test_sjf_rejects_unknown_policy(key):
    eng = make_paged_engine("llama3-8b", key)
    with pytest.raises(ValueError):
        ContinuousQueue(eng, GenerationParams(max_new_tokens=4),
                        policy="lifo")


# ------------------------------------------------------------- truncation


def test_truncation_keeps_prefix_hash_stable(key):
    """Over-long prompts truncate the retrieved-context prefix at a
    chunk boundary, so every question against the same context (within
    a chunk class) still maps to one cache entry — and never splits the
    kept prefix mid-chunk."""
    eng = make_paged_engine("llama3-8b", key, batch_size=1, max_len=96,
                            prefill_chunk=8)
    gen = GenerationParams(max_new_tokens=16)
    q = ContinuousQueue(eng, gen, key=jax.random.PRNGKey(4))
    cap = eng.cont_max_prompt_len(gen.max_new_tokens)
    ctx = [1 + i % 9 for i in range(90)]              # over-long prefix
    qs = [[4] * 10, [7] * 14, [2] * 12]               # one chunk class
    rids = []
    for suffix in qs:
        with pytest.warns(UserWarning, match="truncated-left"):
            rids.append(q.submit(ctx + suffix, prefix_len=len(ctx)))
    reqs = list(q._pending)
    assert all(len(r.prompt) <= cap for r in reqs)
    # identical kept prefix across question lengths -> one cache key
    p0 = reqs[0].prefix_len
    assert p0 % eng.prefill_chunk == 0 and p0 >= 1
    assert all(r.prefix_len == p0 for r in reqs)
    assert all(r.prompt[:p0] == reqs[0].prompt[:p0] for r in reqs)
    outs = q.run()
    assert all(len(outs[r]) == gen.max_new_tokens for r in rids)
    assert q.stats.prefix_misses == 1 and q.stats.prefix_hits == 1
    # without a prefix the old plain truncate-left still applies
    with pytest.warns(UserWarning, match="truncated-left"):
        rid = q.submit(list(range(1, 120)))
    assert q._pending[-1].prefix_len == 0
    assert len(q._pending[-1].prompt) == cap


# ----------------------------------------------------------------- kernel


def _paged_case(B, H, KV, hd, bs, nb, alloc, first, last, seed=0):
    """Random q and pool, and per-row tables holding ``alloc[b]``
    distinct pool blocks (the rest -1)."""
    rng = np.random.default_rng(seed)
    P = sum(alloc) + 3
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((P, bs, KV, hd)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((P, bs, KV, hd)), jnp.float32)
    ids = iter(rng.permutation(P).tolist())
    tables = np.full((B, nb), -1, np.int32)
    for b, n in enumerate(alloc):
        tables[b, :n] = [next(ids) for _ in range(n)]
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(first, jnp.int32), jnp.asarray(last, jnp.int32))


# (B, H, KV, hd, bs, nb), blocks allocated per row, first, last, softcap,
# blocks per chunk (None: the kernel's own).  hd 128 takes the path that
# copies blocks itself, narrower heads the grid's pipeline.
PAGED_CASES = {
    # GQA broadcast, -1 (unallocated) table entries, per-row windows
    "gqa": ((3, 4, 2, 16, 8, 4), [3, 2, 4], [2, 0, 5], [20, 9, 30],
            None, None),
    "gqa-softcap": ((3, 4, 2, 16, 8, 4), [3, 2, 4], [2, 0, 5],
                    [20, 9, 30], 30.0, None),
    # olmo-1b's heads and block: 8 blocks a chunk; row 0 spans three
    # chunks and ends mid-chunk, nb = 20 is not a multiple of 8
    "mha-16x128": ((2, 16, 16, 128, 16, 20), [20, 9], [0, 3], [300, 130],
                   None, None),
    "gqa4-hd64": ((2, 8, 2, 64, 16, 6), [6, 4], [0, 7], [90, 60],
                  None, 2),
    "gqa4-hd128-softcap": ((2, 8, 2, 128, 8, 7), [7, 5], [3, 0],
                           [50, 33], 30.0, 3),
    "mha-softcap": ((2, 2, 2, 128, 8, 5), [5, 3], [1, 0], [38, 20],
                    30.0, None),
    # spans of 1..4 chunks of three blocks, the last cut mid-chunk
    "multi-chunk": ((3, 2, 2, 128, 8, 11), [11, 7, 4], [0, 9, 2],
                    [87, 54, 30], None, 3),
    "nb-not-multiple": ((2, 2, 2, 128, 8, 7), [7, 7], [0, 0], [55, 17],
                        None, 2),
    "first-past-blocks": ((2, 2, 2, 128, 8, 9), [9, 9], [33, 20],
                          [71, 25], None, 2),
    # row 1 finished (last = -1): zeros, and the other rows as if it were
    # live
    "inactive-row": ((3, 2, 2, 128, 8, 6), [6, 5, 6], [0, 2, 5],
                     [47, -1, 40], None, 2),
    "inactive-row-narrow": ((3, 4, 4, 32, 8, 6), [6, 5, 6], [0, 2, 5],
                            [47, -1, 40], None, 2),
}


@pytest.mark.parametrize("case", list(PAGED_CASES), ids=list(PAGED_CASES))
def test_paged_attention_kernel_matches_ref(case, monkeypatch):
    """Pallas paged decode kernel vs the jnp oracle, in the TPU
    interpreter (memory it never wrote reads NaN, a copy lands at its
    wait); a row with nothing to attend reads zeros in both, and leaves
    the other rows as they are."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import paged_attention as pa
    shape, alloc, first, last, softcap, chunk = PAGED_CASES[case]
    if chunk is not None:
        _, _, KV, hd, bs, _ = shape
        monkeypatch.setattr(pa, "CHUNK_BYTES", chunk * pa._block_vmem_bytes(
            bs, KV, hd, jnp.float32))
    args = _paged_case(*shape, alloc, first, last)
    want = ref.paged_attention_ref(*args, softcap=softcap)
    interpret = pltpu.InterpretParams(uninitialized_memory="nan")
    got = pa.paged_decode_attention_pallas(*args, softcap=softcap,
                                           interpret=interpret)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    dead = np.asarray(last) < np.asarray(first)
    assert (np.asarray(got)[dead] == 0).all()
    if dead.any():
        live_last = np.where(dead, 40, last)
        args2 = args[:5] + (jnp.asarray(live_last, jnp.int32),)
        other = pa.paged_decode_attention_pallas(*args2, softcap=softcap,
                                                 interpret=interpret)
        np.testing.assert_array_equal(np.asarray(other)[~dead],
                                      np.asarray(got)[~dead])


def test_paged_attention_chunk_follows_block_bytes():
    """A chunk holds about CHUNK_BYTES of K as laid out in VMEM, never
    more columns than the table has."""
    from repro.kernels.paged_attention import blocks_per_chunk
    assert blocks_per_chunk(16, 16, 128, jnp.float32, 72) == 8   # olmo
    assert blocks_per_chunk(16, 16, 128, jnp.bfloat16, 72) == 16
    assert blocks_per_chunk(16, 5, 64, jnp.float32, 72) == 16    # padded
    assert blocks_per_chunk(16, 16, 128, jnp.float32, 3) == 3


def test_paged_attention_all_blocks_unallocated_row():
    """A row whose table is all -1 (freshly admitted, nothing written)
    must not NaN: the online softmax self-corrects to zeros."""
    B, H, KV, hd, bs, nb, P = 2, 2, 1, 8, 4, 2, 4
    q = jnp.ones((B, H, hd), jnp.float32)
    k_pool = jnp.ones((P, bs, KV, hd), jnp.float32)
    v_pool = jnp.ones((P, bs, KV, hd), jnp.float32)
    tables = jnp.asarray([[0, 1], [-1, -1]], jnp.int32)
    first = jnp.asarray([0, 0], jnp.int32)
    last = jnp.asarray([5, 0], jnp.int32)
    out = ops.paged_decode_attention(q, k_pool, v_pool, tables, first, last,
                                     use_pallas=False)
    assert np.isfinite(np.asarray(out)).all()
    from repro.kernels.paged_attention import paged_decode_attention_pallas
    out_k = paged_decode_attention_pallas(q, k_pool, v_pool, tables, first,
                                          last, interpret=True)
    assert np.isfinite(np.asarray(out_k)).all()
    np.testing.assert_allclose(np.asarray(out_k[0]), np.asarray(out[0]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch,heads", [
    pytest.param("olmo-1b", dict(head_dim=128), id="mha-lane-dense"),
    pytest.param("llama3-8b", dict(num_kv_heads=2), id="gqa-narrow"),
])
def test_paged_segment_kernel_matches_oracle(arch, heads, key, monkeypatch):
    """One drain segment in which a row finishes mid-segment and then
    reads nothing: with the Pallas kernel forced on (interpret mode)
    every row's tokens equal the jnp oracle path's, for heads the kernel
    copies itself (hd 128) and heads the grid's pipeline copies."""
    import dataclasses
    prompts = [[5, 6, 7, 2, 3, 4, 1], [9, 3, 1, 5, 2, 6, 7, 4, 2, 8, 1],
               [4, 4, 1, 3]]
    budgets = [2, 7, 5]
    cfg = dataclasses.replace(get_smoke_config(arch), **heads)
    params = Model(cfg).init_params(key, max_seq=96)

    def segment():
        eng = ServeEngine(cfg, params, max_len=96, batch_size=4,
                          prefill_chunk=8, paged=True, block_size=16)
        sess = eng.continuous_session(GenerationParams(max_new_tokens=7),
                                      key=jax.random.PRNGKey(7))
        sess.begin_frame(prompts, budgets)
        outs = dict(sess.run_segment(drain=True))
        assert sorted(outs) == [0, 1, 2] and sess.segments == 1
        return outs

    want = segment()
    assert [len(want[i]) for i in range(3)] == budgets
    monkeypatch.setattr(ops, "paged_decode_attention", functools.partial(
        ops.paged_decode_attention, use_pallas=True))
    assert segment() == want


def test_program_layer_table_names_every_jitted_program(key):
    """``PROGRAM_LAYERS`` reads device time by layer from the programs'
    names: every program a paged engine jits is in it, and every name in
    it is a jitted program's, so a rename fails here, not in a reader."""
    from repro.serving.engine import PROGRAM_LAYERS
    eng = make_paged_engine("llama3-8b", key)
    jitted = {v.__name__ for v in vars(eng).values()
              if type(v).__name__ == "PjitFunction"}
    assert jitted == set(PROGRAM_LAYERS)
    assert set(PROGRAM_LAYERS.values()) <= {"decode", "prefill", "other"}
    assert PROGRAM_LAYERS["_decode_cont_impl"] == "decode"
    for name in ("_paged_refill_impl", "_paged_prefix_prefill_impl",
                 "_paged_prefill_chunk_impl"):
        assert PROGRAM_LAYERS[name] == "prefill"
