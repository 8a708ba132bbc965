"""Pallas TPU flash-attention kernel (forward).

TPU adaptation of FlashAttention: online-softmax over KV tiles streamed
HBM->VMEM, with MXU-aligned tiles (q/kv block sizes multiples of 128 and
head_dim padded to 128).  GQA is handled in the BlockSpec index maps (the
KV block for query head h is h // group_size), causal + sliding-window
masking is position-based, and Gemma-style logit softcapping is fused.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the KV-block axis is
innermost ("arbitrary" semantics) so the f32 accumulator/running-max/
running-sum scratch persists across KV steps of one Q tile — the classic
flash recurrence.  Fully-masked KV tiles (strictly-future under causal,
or strictly-outside a sliding window) are skipped with pl.when.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], q_block: int, kv_block: int,
                  seq_k: int, seq_q: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions (queries right-aligned to the KV sequence)
    q_pos = i * q_block + jax.lax.broadcasted_iota(jnp.int32, (q_block, 1), 0) \
        + (seq_k - seq_q)
    k_pos = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)

    # tile-level skip: strictly-future tiles (causal) / expired tiles (window)
    first_q = i * q_block + (seq_k - seq_q)
    last_q = first_q + q_block - 1
    first_k = j * kv_block
    live = True
    if causal:
        live = jnp.logical_and(live, first_k <= last_q)
    if window is not None:
        last_k = first_k + kv_block - 1
        live = jnp.logical_and(live, last_k > first_q - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < seq_k                             # guards padding
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,                 # [B, H, Sq, hd]
    k: jax.Array,                 # [B, KV, Sk, hd]
    v: jax.Array,                 # [B, KV, Sk, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    q_block = min(q_block, max(Sq, 8))
    kv_block = min(kv_block, max(Sk, 8))
    pq, pk = (-Sq) % q_block, (-Sk) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sq_p, Sk_p = Sq + pq, Sk + pk
    nq, nk = Sq_p // q_block, Sk_p // kv_block

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, q_block=q_block, kv_block=kv_block,
        seq_k=Sk, seq_q=Sq)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_block, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kv_block, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, hd), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel", "arbitrary")),
    )(q, k, v)
    return out[:, :, :Sq]
