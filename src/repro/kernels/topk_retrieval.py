"""Pallas TPU kernel: blocked exact inner-product top-k retrieval.

The RAG vector-search hot loop: queries [Nq, D] against a document-
embedding matrix [Nd, D], returning the top-k scores and indices per
query.  This is the TPU-native analogue of the paper's per-node Faiss
flat index — a streaming matmul over VMEM-resident document tiles with a
running top-k merge, instead of a CPU SIMD scan.

Grid: (num_q_blocks, num_doc_blocks), doc-block axis innermost; scratch
keeps the running [q_block, k] best scores/indices across doc tiles.
The merge concatenates the carried top-k with the new tile's scores and
re-selects top-k via jax.lax.top_k (lowered to a bitonic sort on TPU —
fine for k <= 32).

``ivf_topk_pallas`` is the IVF probe variant: instead of streaming over
every document tile, the doc axis walks only the query's ``nprobe``
inverted lists, whose block offsets come from a scalar-prefetched
``probe_ids`` table (``PrefetchScalarGridSpec`` — the index map reads
the routing decision before the kernel body runs, so each grid step
DMAs exactly one probed list into VMEM).  The running-merge scratch
logic is shared with the exact kernel.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _topk_kernel(q_ref, d_ref, score_ref, idx_ref, best_s, best_i, *,
                 k: int, d_block: int, n_docs: int):
    j = pl.program_id(1)
    nd = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, NEG_INF)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...].astype(jnp.float32)                 # [bq, D]
    d = d_ref[...].astype(jnp.float32)                 # [bd, D]
    s = jax.lax.dot_general(q, d, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bd]
    doc_ids = j * d_block + jax.lax.broadcasted_iota(
        jnp.int32, (1, d_block), 1)                    # [1, bd]
    valid = doc_ids < n_docs
    s = jnp.where(valid, s, NEG_INF)
    doc_ids = jnp.broadcast_to(doc_ids, s.shape)
    # merge with running best
    cat_s = jnp.concatenate([best_s[...], s], axis=1)  # [bq, k+bd]
    cat_i = jnp.concatenate([best_i[...], doc_ids], axis=1)
    top_s, pos = jax.lax.top_k(cat_s, k)
    best_s[...] = top_s
    best_i[...] = jnp.take_along_axis(cat_i, pos, axis=1)

    @pl.when(j == nd - 1)
    def _finalize():
        score_ref[...] = best_s[...]
        idx_ref[...] = best_i[...]


def topk_pallas(queries: jax.Array, docs: jax.Array, k: int, *,
                q_block: int = 128, d_block: int = 512,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """queries [Nq, D], docs [Nd, D] -> (scores [Nq, k], idx [Nq, k])."""
    Nq, D = queries.shape
    Nd = docs.shape[0]
    q_block = min(q_block, max(Nq, 8))
    d_block = min(d_block, max(Nd, max(k, 8)))
    pq, pd = (-Nq) % q_block, (-Nd) % d_block
    if pq:
        queries = jnp.pad(queries, ((0, pq), (0, 0)))
    if pd:
        docs = jnp.pad(docs, ((0, pd), (0, 0)))
    nq, nd = queries.shape[0] // q_block, docs.shape[0] // d_block

    kernel = functools.partial(_topk_kernel, k=k, d_block=d_block, n_docs=Nd)
    scores, idx = pl.pallas_call(
        kernel,
        grid=(nq, nd),
        in_specs=[
            pl.BlockSpec((q_block, D), lambda i, j: (i, 0)),
            pl.BlockSpec((d_block, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((q_block, k), lambda i, j: (i, 0)),
            pl.BlockSpec((q_block, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((queries.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((queries.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, k), jnp.float32),
            pltpu.VMEM((q_block, k), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(queries, docs)
    return scores[:Nq], idx[:Nq]


def _ivf_topk_kernel(probe_ref, q_ref, emb_ref, ids_ref, score_ref,
                     idx_ref, best_s, best_i, *, k: int):
    del probe_ref                     # consumed by the index maps only
    j = pl.program_id(1)
    nprobe = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, NEG_INF)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...].astype(jnp.float32)                 # [1, D]
    d = emb_ref[0].astype(jnp.float32)                 # [L, D]
    ids = ids_ref[...]                                 # [1, L], -1 = pad
    s = jax.lax.dot_general(q, d, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [1, L]
    s = jnp.where(ids >= 0, s, NEG_INF)
    cat_s = jnp.concatenate([best_s[...], s], axis=1)  # [1, k+L]
    cat_i = jnp.concatenate([best_i[...], ids], axis=1)
    top_s, pos = jax.lax.top_k(cat_s, k)
    best_s[...] = top_s
    best_i[...] = jnp.take_along_axis(cat_i, pos, axis=1)

    @pl.when(j == nprobe - 1)
    def _finalize():
        score_ref[...] = best_s[...]
        idx_ref[...] = best_i[...]


def ivf_topk_pallas(queries: jax.Array, list_emb: jax.Array,
                    list_ids: jax.Array, probe_ids: jax.Array, k: int, *,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """IVF probe: score each query only against its ``nprobe`` routed
    inverted lists, merging partial top-k across lists in VMEM scratch.

    queries   [Nq, D]            query embeddings
    list_emb  [n_lists, L, D]    lists padded to a uniform length L
    list_ids  [n_lists, L]       global doc ids, -1 on padding
    probe_ids [Nq, nprobe] int32 routed list per (query, probe) step
    -> (scores [Nq, k] f32, global ids [Nq, k] i32; (NEG_INF, -1) fill
    when a query's probed lists hold fewer than k documents).
    """
    Nq, D = queries.shape
    _, L, _ = list_emb.shape
    nprobe = probe_ids.shape[1]
    kernel = functools.partial(_ivf_topk_kernel, k=k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Nq, nprobe),
        in_specs=[
            pl.BlockSpec((1, D), lambda i, j, p: (i, 0)),
            pl.BlockSpec((1, L, D), lambda i, j, p: (p[i, j], 0, 0)),
            pl.BlockSpec((1, L), lambda i, j, p: (p[i, j], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i, j, p: (i, 0)),
            pl.BlockSpec((1, k), lambda i, j, p: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.int32),
        ],
    )
    scores, idx = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Nq, k), jnp.float32),
            jax.ShapeDtypeStruct((Nq, k), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(probe_ids.astype(jnp.int32), queries, list_emb, list_ids)
    return scores, idx
