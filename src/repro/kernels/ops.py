"""jit'd public wrappers around the Pallas kernels.

The backend decides how a kernel runs.  On the TPU it compiles for the
chip, and the paged decode read always takes the kernel.  On any other
backend (the CPU tests) kernels run with ``interpret=True`` — the
Pallas interpreter executes the kernel body op by op, validating the
exact TPU program — and the paged decode read takes its jnp oracle.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.topk_retrieval import ivf_topk_pallas, topk_pallas


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "q_block", "kv_block", "use_pallas"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    q_block: int = 128, kv_block: int = 128,
                    use_pallas: bool = True) -> jax.Array:
    """[B,H,Sq,hd] x [B,KV,Sk,hd]^2 -> [B,H,Sq,hd]."""
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_block=q_block, kv_block=kv_block, interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("softcap", "use_pallas"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, first, last, *,
                           softcap: Optional[float] = None,
                           use_pallas: Optional[bool] = None) -> jax.Array:
    """Paged decode read: one query per row gathered through the block
    table.  [B,H,hd] x pool [P,bs,KV,hd]^2 x tables [B,nb] -> [B,H,hd].

    ``use_pallas=None`` resolves by backend: the TPU path runs the
    Pallas kernel, which reads only each row's live blocks; elsewhere
    the jnp oracle serves (the interpreter would replay the kernel's
    copies op by op every decode step).  A row with ``last`` below its
    ``first`` reads zeros on both paths."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return ref.paged_attention_ref(q, k_pool, v_pool, block_tables,
                                       first, last, softcap=softcap)
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, block_tables, first, last, softcap=softcap,
        interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("k", "q_block", "d_block",
                                             "use_pallas"))
def retrieval_topk(queries, docs, k: int, *, q_block: int = 128,
                   d_block: int = 512, use_pallas: bool = True
                   ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k inner-product search. [Nq,D] x [Nd,D] -> ([Nq,k],[Nq,k])."""
    if not use_pallas:
        return ref.topk_ref(queries, docs, k)
    return topk_pallas(queries, docs, k, q_block=q_block, d_block=d_block,
                       interpret=_default_interpret())


@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def ivf_retrieval_topk(queries, list_emb, list_ids, probe_ids, k: int, *,
                       use_pallas: bool = True
                       ) -> Tuple[jax.Array, jax.Array]:
    """IVF probe top-k: score queries [Nq,D] only against their routed
    inverted lists (list_emb [n_lists,L,D], ids [n_lists,L] with -1
    padding, probe_ids [Nq,nprobe]) -> ([Nq,k], [Nq,k])."""
    if not use_pallas:
        return ref.ivf_topk_ref(queries, list_emb, list_ids, probe_ids, k)
    return ivf_topk_pallas(queries, list_emb, list_ids, probe_ids, k,
                           interpret=_default_interpret())
