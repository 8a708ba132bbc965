"""shard_map collective patterns.

Two TPU-native analogues of CoEdge-RAG's cross-node operations:

1. ``distributed_topk`` — the paper's per-node Faiss search + coordinator
   merge, as corpus-sharded local top-k + all_gather + global re-top-k.
   Each `data`-axis group holds one corpus shard ("edge node"); queries
   are replicated; the merge is exact (top-k of a union is the top-k of
   the per-shard top-ks).

2. ``flash_decode_seq_sharded`` — single-token attention over a KV cache
   whose *sequence* dim is sharded over `data` (the long_500k layout):
   each device attends to its local KV span and the partial (numerator,
   logsumexp) pairs merge with a psum — the distributed flash-decoding
   trick, giving exact softmax without gathering the 500k-token cache.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def distributed_topk(queries: jax.Array, corpus: jax.Array, k: int,
                     mesh: Mesh, axis: str = "data",
                     use_pallas: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """queries [Nq,D] (replicated), corpus [Nd,D] (sharded on `axis`).
    Returns global (scores [Nq,k], indices [Nq,k]) into the full corpus."""
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    shard_len = corpus.shape[0] // n_shards

    def local(q, c):
        if use_pallas:
            from repro.kernels.ops import retrieval_topk
            s, i = retrieval_topk(q, c, k)
        else:
            s = q.astype(jnp.float32) @ c.astype(jnp.float32).T
            s, i = jax.lax.top_k(s, k)
        # globalize indices
        shard_id = jax.lax.axis_index(axis)
        i = i + shard_id * shard_len
        # gather all shards' candidates and re-select
        s_all = jax.lax.all_gather(s, axis, axis=1, tiled=True)  # [Nq, P*k]
        i_all = jax.lax.all_gather(i, axis, axis=1, tiled=True)
        sg, pos = jax.lax.top_k(s_all, k)
        ig = jnp.take_along_axis(i_all, pos, axis=1)
        return sg, ig

    other = tuple(a for a in mesh.axis_names if a != axis)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), P(axis, None)),
                       out_specs=(P(), P()),
                       check_vma=False)
    return fn(queries, corpus)


def flash_decode_seq_sharded(
    q: jax.Array,              # [B, 1, H, hd] (replicated over data)
    k_cache: jax.Array,        # [B, S, KV, hd], S sharded over `axis`
    v_cache: jax.Array,        # [B, S, KV, hd]
    q_position: jax.Array,     # [B]
    mesh: Mesh, axis: str = "data",
    softcap: Optional[float] = None,
) -> jax.Array:
    """Exact one-token attention over a sequence-sharded cache."""
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    S = k_cache.shape[1]
    shard_len = S // n_shards

    def local(q, kc, vc, qp):
        B, _, H, hd = q.shape
        KV = kc.shape[2]
        G = H // KV
        scale = 1.0 / math.sqrt(hd)
        shard_id = jax.lax.axis_index(axis)
        kpos = shard_id * shard_len + jnp.arange(shard_len)
        qh = q[:, 0].reshape(B, KV, G, hd).astype(jnp.float32)
        s = jnp.einsum("bkgh,bskh->bkgs", qh, kc.astype(jnp.float32)) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos[None, :] <= qp[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        m = s.max(-1)                                     # local max
        p = jnp.exp(s - m[..., None])
        l = p.sum(-1)
        o = jnp.einsum("bkgs,bskh->bkgh", p, vc.astype(jnp.float32))
        # merge partials: rescale by global max, psum numerators
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        o = jax.lax.psum(o * corr[..., None], axis)
        l = jax.lax.psum(l * corr, axis)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, 1, H, hd).astype(q.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), P(None, axis, None, None),
                                 P(None, axis, None, None), P()),
                       out_specs=P(),
                       check_vma=False)
    return fn(q, k_cache, v_cache, q_position)
