"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def attention_ref(
    q: jax.Array,                 # [B, H, Sq, hd]
    k: jax.Array,                 # [B, KV, Sk, hd]
    v: jax.Array,                 # [B, KV, Sk, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Naive O(S^2) attention with GQA broadcast; f32 softmax."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Sq, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qf, kf) / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)      # aligned to the right
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksh->bkgqh", p, vf)
    return o.reshape(B, H, Sq, hd).astype(q.dtype)


def paged_attention_ref(
    q: jax.Array,                 # [B, H, hd] one query per row
    k_pool: jax.Array,            # [P, bs, KV, hd] block pool
    v_pool: jax.Array,            # [P, bs, KV, hd]
    block_tables: jax.Array,      # [B, nb] pool ids; -1 unallocated
    first: jax.Array,             # [B] first valid abs position
    last: jax.Array,              # [B] last valid abs position
    *,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Oracle for the paged decode kernel: gather each row's blocks out
    of the pool, mask by position validity ``first <= pos <= last`` (and
    block allocation), f32 softmax; GQA broadcast.  A row with no valid
    slot (``last = -1`` marks a finished row) reads zeros.
    -> [B, H, hd]."""
    B, H, hd = q.shape
    P, bs, KV, _ = k_pool.shape
    nb = block_tables.shape[1]
    G = H // KV
    tbl = jnp.clip(block_tables, 0, P - 1)
    k = k_pool[tbl].reshape(B, nb * bs, KV, hd).astype(jnp.float32)
    v = v_pool[tbl].reshape(B, nb * bs, KV, hd).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k) / math.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(nb * bs, dtype=jnp.int32)[None]
    ok = jnp.repeat(block_tables >= 0, bs, axis=1)
    mask = (pos >= first[:, None]) & (pos <= last[:, None]) & ok
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v)
    o = jnp.where(mask.any(axis=1)[:, None, None, None], o, 0.0)
    return o.reshape(B, H, hd).astype(q.dtype)


def topk_ref(queries: jax.Array, docs: jax.Array, k: int
             ) -> Tuple[jax.Array, jax.Array]:
    """queries [Nq, D], docs [Nd, D] -> (scores [Nq,k], idx [Nq,k]);
    exact inner-product search."""
    scores = queries.astype(jnp.float32) @ docs.astype(jnp.float32).T
    return jax.lax.top_k(scores, k)


def ivf_topk_ref(queries: jax.Array, list_emb: jax.Array,
                 list_ids: jax.Array, probe_ids: jax.Array, k: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Oracle for the IVF probe kernel: gather each query's ``nprobe``
    inverted lists, score the union, top-k.  Padding (id -1) scores
    -1e30; the stable tie-break matches the kernel's carried-first
    merge order, so indices agree exactly."""
    q = queries.astype(jnp.float32)
    cand_emb = list_emb[probe_ids].astype(jnp.float32)   # [Nq, P, L, D]
    cand_ids = list_ids[probe_ids]                       # [Nq, P, L]
    s = jnp.einsum("qd,qpld->qpl", q, cand_emb)
    s = jnp.where(cand_ids >= 0, s, -1e30)
    nq = q.shape[0]
    s, ids = s.reshape(nq, -1), cand_ids.reshape(nq, -1)
    top_s, pos = jax.lax.top_k(s, k)
    top_i = jnp.take_along_axis(ids, pos, axis=1)
    return top_s, jnp.where(top_s <= -1e30, -1, top_i)
