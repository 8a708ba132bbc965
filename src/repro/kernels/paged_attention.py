"""Pallas TPU kernel: paged decode attention through a block table.

The decode-side read of the paged KV cache (ServeEngine(paged=True)):
each batch row's context lives in fixed-size blocks of a shared pool
[P, bs, KV, hd], addressed by a per-row block table [B, nb].  The grid
walks (row, block); the table rides in scalar prefetch
(``PrefetchScalarGridSpec``) so the index map DMAs exactly the row's
j-th live block into VMEM — the per-step read cost is O(live blocks),
not O(max_len), which is the whole point of replacing the static
``kv_cap`` crop.

Validity is positional: pool block ``table[b, j]`` covers absolute
positions [j*bs, (j+1)*bs); a slot is attended iff
``first[b] <= pos <= last[b]`` and the block is allocated
(``table[b, j] >= 0``).  Unallocated entries clamp to block 0 in the
index map and are masked in-kernel.  Online-softmax scratch (m, l, acc)
merges blocks exactly like the flash kernel; softcap (gemma2) supported,
sliding windows are not (rolling slots stay per-row and never page).
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


def _paged_attn_kernel(tbl_ref, first_ref, last_ref, q_ref, k_ref, v_ref,
                       o_ref, acc, m_s, l_s, *, block_size: int,
                       softcap: Optional[float]):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32)                   # [H, hd]
    k = k_ref[0].astype(jnp.float32)                   # [bs, KV, hd]
    v = v_ref[0].astype(jnp.float32)
    H, hd = q.shape
    bs, KV = k.shape[0], k.shape[1]
    G = H // KV

    qg = q.reshape(KV, G, hd)
    # [KV,G,hd] x [bs,KV,hd] -> [KV,G,bs]  (batch KV, contract hd)
    s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    valid = (pos >= first_ref[b]) & (pos <= last_ref[b]) \
        & (tbl_ref[b, j] >= 0)
    s = jnp.where(valid, s, NEG_INF).reshape(H, bs)

    # online softmax merge (an all-masked block leaves m at NEG_INF and
    # contributes weight-1 garbage, but the first valid block's
    # alpha = exp(NEG_INF - m_valid) = 0 rescales it away exactly)
    m_new = jnp.maximum(m_s[...], jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_s[...] - m_new)
    p = jnp.exp(s - m_new)
    pg = p.reshape(KV, G, bs)
    # [KV,G,bs] x [bs,KV,hd] -> [KV,G,hd]  (batch KV, contract bs)
    pv = jax.lax.dot_general(pg, v, (((2,), (0,)), ((0,), (1,))),
                             preferred_element_type=jnp.float32)
    acc[...] = acc[...] * alpha + pv.reshape(H, hd)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_s[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention_pallas(
        q: jax.Array,                 # [B, H, hd] one query per row
        k_pool: jax.Array,            # [P, bs, KV, hd] block pool
        v_pool: jax.Array,            # [P, bs, KV, hd]
        block_tables: jax.Array,      # [B, nb] pool ids; -1 unallocated
        first: jax.Array,             # [B] first valid abs position
        last: jax.Array,              # [B] last valid abs position
        *, softcap: Optional[float] = None,
        interpret: bool = False) -> jax.Array:
    """Block-table-gathered decode attention -> [B, H, hd]."""
    B, H, hd = q.shape
    P, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    kernel = functools.partial(_paged_attn_kernel, block_size=bs,
                               softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, t, f, l: (b, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, j, t, f, l: (jnp.maximum(t[b, j], 0),
                                                0, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd),
                         lambda b, j, t, f, l: (jnp.maximum(t[b, j], 0),
                                                0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, t, f, l: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(block_tables.astype(jnp.int32), first.astype(jnp.int32),
      last.astype(jnp.int32), q, k_pool, v_pool)
