"""OLMo-style decoder layers (OLMo-1B, arXiv:2402.00838): ``attn``, causal
softmax attention over the whole context, and ``local``, the same within
``sliding_window``; each followed by the feed-forward block.

Equations: the block's norm (non-parametric LayerNorm for OLMo), RoPE
(rotate-half) where ``pos_embedding`` is ``rope``, grouped-query heads
repeated to the query heads, the feed-forward block's norm and a SwiGLU
MLP (the layout also admits ``gelu_glu``; the reference computes SiLU
for both), tied embeddings.  The attention and feed-forward pieces are
also what other families' layers reuse.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from perfbench import reference as R
from perfbench import weights as W
from perfbench.arch import Kind, part
from perfbench.peaks import head_dim
from perfbench.reference import HI, mm

# ------------------------------------------------------------------ layout


def attention_block(cfg) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {"wq": W.dense(d, cfg.num_heads * hd),
         "wk": W.dense(d, cfg.num_kv_heads * hd),
         "wv": W.dense(d, cfg.num_kv_heads * hd),
         "wo": W.dense(cfg.num_heads * hd, d)}
    if cfg.qk_norm:
        p["q_norm"] = ((hd,), "ones")
        p["k_norm"] = ((hd,), "ones")
    return p


def ffn_block(cfg) -> dict:
    """The feed-forward block's leaves (none where ``mlp_type`` is
    ``none``): its norm and a gated MLP, or the part ``moe`` where the
    configuration states a ``moe`` group."""
    if cfg.mlp_type == "none":
        return {}
    if cfg.moe is not None:
        return {"ln2": W.norm(cfg), "moe": part("moe").block(cfg)}
    if cfg.mlp_type not in ("swiglu", "gelu_glu"):
        raise ValueError(f"mlp {cfg.mlp_type!r} has no layout here")
    d = cfg.d_model
    return {"ln2": W.norm(cfg),
            "mlp": {"wi": W.dense(d, cfg.d_ff), "wg": W.dense(d, cfg.d_ff),
                    "wo": W.dense(cfg.d_ff, d)}}


def block(cfg) -> dict:
    return {"ln1": W.norm(cfg), "attn": attention_block(cfg),
            **ffn_block(cfg)}

# --------------------------------------------------------------- reference


def attention(p, h, c, quant, window: Optional[int]):
    T = h.shape[0]
    H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    q = mm(h, p["wq"], quant).reshape(T, H, hd)
    k = mm(h, p["wk"], quant).reshape(T, KV, hd)
    v = mm(h, p["wv"], quant).reshape(T, KV, hd)
    if c.get("pos_embedding", "rope") == "rope":
        q, k = R.rope(q, c["rope_theta"]), R.rope(k, c["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) / math.sqrt(hd)
    t = jnp.arange(T)
    mask = t[None, :] <= t[:, None]
    if window is not None:
        mask = mask & (t[:, None] - t[None, :] < window)
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI)
    return mm(a.reshape(T, H * hd), p["wo"], quant)


def swiglu(p, h, quant):
    return mm(jax.nn.silu(mm(h, p["wg"], quant)) * mm(h, p["wi"], quant),
              p["wo"], quant)


def ffn(p, x, c, quant):
    """The residual stream after the feed-forward block, where the layer
    has one."""
    if "mlp" not in p and "moe" not in p:
        return x
    h = R.norm(p.get("ln2", {}), x, c["norm_type"])
    out = part("moe").layer(p["moe"], h, c, quant) if "moe" in p \
        else swiglu(p["mlp"], h, quant)
    return R.residual(x, out, c)


def _layer(window_of):
    def layer(p, x, c, quant):
        h = R.norm(p.get("ln1", {}), x, c["norm_type"])
        x = R.residual(x, attention(p["attn"], h, c, quant, window_of(c)), c)
        return ffn(p, x, c, quant)
    return layer

# ------------------------------------------------------------------ counts


def attention_params(m: dict) -> int:
    d, hd = m["d_model"], head_dim(m)
    return d * m["num_heads"] * hd * 2 + 2 * d * m["num_kv_heads"] * hd


def ffn_params(m: dict) -> int:
    if m.get("mlp_type", "swiglu") == "none":
        return 0
    if m.get("moe") is not None:
        return part("moe").params(m)
    return 3 * m["d_model"] * m["d_ff"]


def attention_flops(m: dict, ctx: int, window: Optional[int]) -> float:
    """Scores and values of one query over its live context."""
    return 4.0 * min(ctx, window or ctx) * m["num_heads"] * head_dim(m)


def ffn_flops(m: dict, ctx: int) -> float:
    """What the ``moe`` part counts beyond two FLOPs a weight."""
    return part("moe").flops(m, ctx) if m.get("moe") is not None else 0.0


def kv_bytes(m: dict, itemsize: int) -> int:
    """K and V of one context token, read through the paged pool."""
    return 2 * m["num_kv_heads"] * head_dim(m) * itemsize


def _params(m: dict) -> int:
    return attention_params(m) + ffn_params(m)


def _flops(window_of):
    def flops(m: dict, ctx: int) -> float:
        return attention_flops(m, ctx, window_of(m)) + ffn_flops(m, ctx)
    return flops


def _full(m: dict) -> None:
    return None


def _window(m: dict) -> Optional[int]:
    return m.get("sliding_window")


KINDS = {
    "attn": Kind(block=block, layer=_layer(_full), params=_params,
                 flops=_flops(_full), kv_bytes=kv_bytes),
    # a rolling window per row, not the paged pool: nothing read there
    "local": Kind(block=block, layer=_layer(_window), params=_params,
                  flops=_flops(_window)),
}
