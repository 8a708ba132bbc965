"""Pallas TPU kernel: paged decode attention through a block table.

The decode-side read of the paged KV cache (ServeEngine(paged=True)):
each batch row's context lives in fixed-size blocks of a shared pool
[P, bs, KV, hd], addressed by a per-row block table [B, nb].  The
table, ``first`` and ``last`` ride in scalar prefetch.  Row b reads
table columns ``first[b] // bs`` to ``last[b] // bs`` and no others, a
chunk of blocks at a time (about ``CHUNK_BYTES`` of K: 8 of olmo-1b's
f32 blocks), while the next chunk's copies are in flight.  A row whose
``last`` is below its ``first`` (``last = -1`` marks a finished row)
copies nothing and reads zeros.

How a chunk reaches VMEM depends on the head width.  With lane-dense
heads (hd a multiple of 128) the pool stays in HBM and the kernel
copies each live block itself, one DMA per block into one of two chunk
buffers, walking only the live chunks of the live rows: the work of a
call is O(live blocks).  Narrower heads cannot be sliced out of an HBM
ref by the TPU compiler, so there the grid walks (row, chunk) and
Pallas's pipeline copies the chunk's blocks; a step past a row's live
span repeats the last live step's blocks, which the pipeline does not
copy again, and scores nothing (a finished row's steps copy one chunk).

Validity is positional: pool block ``table[b, j]`` covers absolute
positions [j*bs, (j+1)*bs); a slot is attended iff
``first[b] <= pos <= last[b]`` and the block is allocated
(``table[b, j] >= 0``).  An unallocated block in the live span is not
copied and is masked.  Online-softmax state (m, l, acc) merges the
chunks as the flash kernel merges blocks; softcap (gemma2) supported,
sliding windows are not (rolling slots stay per-row and never page).

The contraction is chosen by shape.  With one query head per KV head
(G = H / KV = 1) the scores are a broadcast multiply of q with each
[bs, KV, hd] block reduced over hd, and p.v one reduced over the
block's slots: no MXU pass and no relayout.  With G > 1 each KV head's
[G, hd] queries meet its [bs, hd] keys on the MXU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK_BYTES = 1 << 20          # K bytes a chunk aims at, as laid out in VMEM
LANES = 128


def _block_vmem_bytes(bs: int, kv: int, hd: int, dtype) -> int:
    """One pool block's bytes in VMEM: the last two dims (KV, hd) are
    tiled in (8 * packing, 128) tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    return bs * (-(-kv // sub) * sub) * (-(-hd // LANES) * LANES) * itemsize


def blocks_per_chunk(bs: int, kv: int, hd: int, dtype, nb: int) -> int:
    """Pool blocks the kernel copies and scores per chunk."""
    per = CHUNK_BYTES // _block_vmem_bytes(bs, kv, hd, dtype)
    return max(1, min(nb, per))


def _span(first_ref, last_ref, b, *, nb: int, bs: int, C: int):
    """Row b's first and last live table column and its live chunks."""
    lo = jax.lax.div(jnp.maximum(first_ref[b], 0), bs)
    last = last_ref[b]
    hi = jnp.where(last >= 0,
                   jnp.minimum(jax.lax.div(jnp.maximum(last, 0), bs),
                               nb - 1), -1)
    n = jnp.maximum(hi - lo + 1, 0)
    return lo, hi, jax.lax.div(n + C - 1, C)


def _attend(q, blocks, first, last, m_s, l_s, acc, *,
            softcap: Optional[float]):
    """Merge one chunk into a row's online softmax (m_s, l_s, acc).
    ``q`` [H, hd] f32, already scaled; ``blocks`` one (k_ref, v_ref,
    pos0, live) per block, refs [bs, KV, hd] and ``pos0`` the block's
    first absolute position.  A block that is not live was not copied:
    its weights are exactly 0, and its V (zeroed) stays out of acc."""
    H, hd = q.shape
    bs, KV = blocks[0][0].shape[:2]
    G = H // KV
    ss, oks = [], []
    for k_ref, _, pos0, live in blocks:
        k = k_ref[...].astype(jnp.float32)                  # [bs, KV, hd]
        if G == 1:
            # [bs, KV, hd] * [KV, hd] summed over hd -> [bs, KV, 1]
            s = jnp.sum(k * q[None], axis=2, keepdims=True)
            t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        else:
            # [KV,G,hd] x [bs,KV,hd] -> [KV,G,bs] (batch KV, contract hd)
            s = jax.lax.dot_general(q.reshape(KV, G, hd), k,
                                    (((2,), (2,)), ((0,), (1,))),
                                    preferred_element_type=jnp.float32)
            t = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = pos0 + t
        ok = (pos >= first) & (pos <= last) & live
        ss.append(jnp.where(ok, s, NEG_INF))
        oks.append(ok)
    ax = 0 if G == 1 else 2
    m_prev = m_s[...]
    m_new = functools.reduce(jnp.maximum, [jnp.max(s, axis=ax) for s in ss])
    m_new = jnp.maximum(m_prev, m_new.reshape(H, 1))
    m_b = m_new.reshape((1, KV, 1) if G == 1 else (KV, G, 1))
    l_new, pv = 0.0, 0.0
    for (_, v_ref, _, _), s, ok in zip(blocks, ss, oks):
        p = jnp.where(ok, jnp.exp(s - m_b), 0.0)
        v = v_ref[...].astype(jnp.float32)
        if G == 1:
            l_new = l_new + jnp.sum(p, axis=0)
            pv = pv + jnp.sum(p * v, axis=0)                # [KV, hd]
        else:
            l_new = l_new + jnp.sum(p, axis=2).reshape(H, 1)
            # [KV,G,bs] x [bs,KV,hd] -> [KV,G,hd]
            pv = pv + jax.lax.dot_general(
                p, v, (((2,), (0,)), ((0,), (1,))),
                preferred_element_type=jnp.float32).reshape(H, hd)
    alpha = jnp.exp(m_prev - m_new)
    acc[...] = acc[...] * alpha + pv
    l_s[...] = l_s[...] * alpha + l_new
    m_s[...] = m_new


def _reset(m_s, l_s, acc):
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc[...] = jnp.zeros_like(acc)


def _result(acc, l_s, dtype):
    return (acc[...] / jnp.maximum(l_s[...], 1e-30)).astype(dtype)


def _paged_attn_kernel(tbl_ref, first_ref, last_ref, q_ref, k_hbm, v_hbm,
                       o_ref, kbuf, vbuf, sems, m_s, l_s, acc, *,
                       nb: int, softcap: Optional[float]):
    """Lane-dense heads: one grid step walks every live row's live
    chunks, copying each chunk's blocks out of the HBM pool itself."""
    B, H, hd = q_ref.shape
    _, C, bs, _, _ = kbuf.shape
    span = functools.partial(_span, first_ref, last_ref, nb=nb, bs=bs, C=C)

    def blocks(b, c):
        """(live, pool id) of each block of row b's chunk c."""
        lo, hi, _ = span(b)
        out = []
        for i in range(C):
            j = lo + c * C + i
            page = tbl_ref[b * nb + jnp.minimum(j, nb - 1)]
            out.append(((j <= hi) & (page >= 0), jnp.maximum(page, 0)))
        return out

    def dma(page, slot, i):
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, i],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, i],
                                      sems.at[1, slot]))

    def start(b, c, slot):
        for i, (live, page) in enumerate(blocks(b, c)):
            @pl.when(live)
            def _copy():
                for cp in dma(page, slot, i):
                    cp.start()

            # an uncopied block keeps whatever the buffer held; its
            # weights are 0, and V must be finite for 0 * V to stay 0
            @pl.when(~live)
            def _clear():
                vbuf[slot, i] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(b, c, slot):
        for i, (live, page) in enumerate(blocks(b, c)):
            @pl.when(live)
            def _wait():
                for cp in dma(page, slot, i):
                    cp.wait()

    def next_live(b):
        """The first row after b with a chunk to score; B if none."""
        def body(r, found):
            return jnp.where((found == B) & (span(r)[2] > 0), r, found)
        return jax.lax.fori_loop(b + 1, B, body, jnp.int32(B))

    first_row = next_live(jnp.int32(-1))

    @pl.when(first_row < B)
    def _prime():
        start(first_row, 0, 0)

    def row(b, g):
        lo, _, n = span(b)
        after = next_live(b)
        q = q_ref[b].astype(jnp.float32) * (1.0 / math.sqrt(hd))
        _reset(m_s, l_s, acc)

        def chunk(c, g):
            slot = g % 2

            @pl.when(c + 1 < n)
            def _same_row():
                start(b, c + 1, 1 - slot)

            @pl.when((c + 1 == n) & (after < B))
            def _next_row():
                start(after, 0, 1 - slot)

            wait(b, c, slot)
            _attend(q, [(kbuf.at[slot, i], vbuf.at[slot, i],
                         (lo + c * C + i) * bs, live)
                        for i, (live, _) in enumerate(blocks(b, c))],
                    first_ref[b], last_ref[b], m_s, l_s, acc,
                    softcap=softcap)
            return g + 1

        g = jax.lax.fori_loop(0, n, chunk, g)
        o_ref[b] = _result(acc, l_s, o_ref.dtype)
        return g

    jax.lax.fori_loop(0, B, row, jnp.int32(0))


def _chunk_column(tbl, first, last, b, c, i, *, nb: int, bs: int, C: int):
    """Table column block i of grid step (b, c) reads: past the row's
    live span it stays where the last live step left it."""
    lo, hi, _ = _span(first, last, b, nb=nb, bs=bs, C=C)
    c_live = jax.lax.div(jnp.maximum(hi - lo - i, 0), C)
    return jnp.clip(lo + jnp.minimum(c, c_live) * C + i, 0, nb - 1)


def _paged_attn_grid_kernel(tbl_ref, first_ref, last_ref, q_ref, *refs,
                            nb: int, C: int, softcap: Optional[float]):
    """Narrow heads: the grid walks (row, chunk); the pipeline copies the
    C blocks of a step (C pool operands each), a step past the live span
    scores nothing."""
    k_refs, v_refs = refs[:C], refs[C:2 * C]
    o_ref, m_s, l_s, acc = refs[2 * C:]
    b, c = pl.program_id(0), pl.program_id(1)
    _, H, hd = q_ref.shape
    bs = k_refs[0].shape[1]
    lo, hi, n = _span(first_ref, last_ref, b, nb=nb, bs=bs, C=C)

    @pl.when(c == 0)
    def _init():
        _reset(m_s, l_s, acc)

    @pl.when(c < n)
    def _score():
        q = q_ref[0].astype(jnp.float32) * (1.0 / math.sqrt(hd))
        blocks = []
        for i in range(C):
            j = lo + c * C + i
            page = tbl_ref[b * nb + jnp.minimum(j, nb - 1)]
            blocks.append((k_refs[i].at[0], v_refs[i].at[0], j * bs,
                           (j <= hi) & (page >= 0)))
        _attend(q, blocks, first_ref[b], last_ref[b], m_s, l_s, acc,
                softcap=softcap)

    @pl.when(c == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = _result(acc, l_s, o_ref.dtype)


def paged_decode_attention_pallas(
        q: jax.Array,                 # [B, H, hd] one query per row
        k_pool: jax.Array,            # [P, bs, KV, hd] block pool
        v_pool: jax.Array,            # [P, bs, KV, hd]
        block_tables: jax.Array,      # [B, nb] pool ids; -1 unallocated
        first: jax.Array,             # [B] first valid abs position
        last: jax.Array,              # [B] last valid abs position
        *, softcap: Optional[float] = None,
        interpret=False) -> jax.Array:
    """Block-table-gathered decode attention -> [B, H, hd]; a row with
    nothing to attend (``last < first``) reads zeros.  ``interpret``:
    False, True or a ``pltpu.InterpretParams``."""
    B, H, hd = q.shape
    P, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    C = blocks_per_chunk(bs, KV, hd, k_pool.dtype, nb)
    state = [pltpu.VMEM((H, 1), jnp.float32),
             pltpu.VMEM((H, 1), jnp.float32),
             pltpu.VMEM((H, hd), jnp.float32)]
    if hd % LANES == 0:
        kernel = functools.partial(_paged_attn_kernel, nb=nb,
                                   softcap=softcap)
        whole = pl.BlockSpec((B, H, hd), lambda i, t, f, l: (0, 0, 0))
        hbm = pl.BlockSpec(memory_space=pltpu.HBM)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[whole, hbm, hbm], out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, C, bs, KV, hd), k_pool.dtype),
                            pltpu.VMEM((2, C, bs, KV, hd), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))] + state)
        pools = (k_pool, v_pool)
    else:
        kernel = functools.partial(_paged_attn_grid_kernel, nb=nb, C=C,
                                   softcap=softcap)
        col = functools.partial(_chunk_column, nb=nb, bs=bs, C=C)
        blocks = [pl.BlockSpec(
            (1, bs, KV, hd),
            lambda b, c, t, f, l, i=i: (
                jnp.maximum(t[b * nb + col(t, f, l, b, c, i)], 0), 0, 0, 0))
            for i in range(C)]
        row = pl.BlockSpec((1, H, hd), lambda b, c, t, f, l: (b, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, -(-nb // C)),
            in_specs=[row] + blocks + blocks, out_specs=row,
            scratch_shapes=state)
        pools = (k_pool,) * C + (v_pool,) * C
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid_spec.grid)),
    )(block_tables.astype(jnp.int32).reshape(-1), first.astype(jnp.int32),
      last.astype(jnp.int32), q, *pools)
