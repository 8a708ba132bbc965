"""Hymba layers (arXiv:2411.13676): ``hymba``, attention heads and Mamba
(Mamba-1) heads in parallel on the same normed input, fused as the mean
of the two RMSNorm'd branch outputs, then the feed-forward block.

The attention is grouped-query within ``sliding_window`` on every layer;
the paper keeps 3 global layers and meta tokens, which the serving model
does not.  The Mamba branch: in-projection to x and z, a causal depthwise
convolution, SiLU, data-dependent dt, B and C, the selective scan with
A = -exp(A_log), the skip D, gated by SiLU(z), out-projection.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import reference as R
from perfbench import weights as W
from perfbench.arch import Kind, olmo
from perfbench.reference import F32, mm


def _dt_rank(d: int) -> int:
    return max(1, math.ceil(d / 16))

# ------------------------------------------------------------------ layout


def mamba_block(cfg) -> dict:
    d = cfg.d_model
    inner = cfg.ssm.expand * d
    st, w = cfg.ssm.state_size, cfg.ssm.conv_width
    r = _dt_rank(d)
    return {
        "in_proj": W.dense(d, 2 * inner),
        "conv_w": ((w, inner), ("normal", 1.0 / math.sqrt(w))),
        "conv_b": ((inner,), "zeros"),
        "x_proj": W.dense(inner, r + 2 * st),
        "dt_proj": W.dense(r, inner),
        "dt_bias": ((inner,), ("const", -4.6)),
        "A_log": ((inner, st), "a_log", "float32"),
        "D": ((inner,), "ones", "float32"),
        "out_proj": W.dense(inner, d),
    }


def block(cfg) -> dict:
    return {"ln1": W.norm(cfg), "attn": olmo.attention_block(cfg),
            "mamba": mamba_block(cfg), "bn_a": W.norm(cfg),
            "bn_m": W.norm(cfg), **olmo.ffn_block(cfg)}

# --------------------------------------------------------------- reference


def mamba(p, h, c, quant):
    T = h.shape[0]
    st = c["ssm"]["state_size"]
    xz = mm(h, p["in_proj"], quant)
    xi, z = jnp.split(xz, 2, axis=-1)
    w = p["conv_w"].astype(F32)                                 # [W, I]
    W_ = w.shape[0]
    xpad = jnp.pad(xi, ((W_ - 1, 0), (0, 0)))
    conv = sum(xpad[i:i + T] * w[i][None] for i in range(W_))
    xc = jax.nn.silu(conv + p["conv_b"].astype(F32)[None])
    proj = mm(xc, p["x_proj"], quant)
    r = proj.shape[-1] - 2 * st
    dt_r, Bm, Cm = proj[:, :r], proj[:, r:r + st], proj[:, r + st:]
    dt = jax.nn.softplus(mm(dt_r, p["dt_proj"], quant)
                         + p["dt_bias"].astype(F32)[None])
    A = -jnp.exp(p["A_log"].astype(F32))                        # [I, S]

    def step(hs, xs):
        dt_t, b_t, c_t, x_t = xs
        hs = hs * jnp.exp(dt_t[:, None] * A) + dt_t[:, None] * b_t[None] \
            * x_t[:, None]
        return hs, hs @ c_t + p["D"].astype(F32) * x_t

    h0 = jnp.zeros(A.shape, F32)
    _, y = jax.lax.scan(step, h0, (dt, Bm, Cm, xc))
    return mm(y * jax.nn.silu(z), p["out_proj"], quant)


def layer(p, x, c, quant):
    nk = c["norm_type"]
    h = R.norm(p.get("ln1", {}), x, nk)
    ao = olmo.attention(p["attn"], h, c, quant, c.get("sliding_window"))
    mo = mamba(p["mamba"], h, c, quant)
    x = R.residual(x, 0.5 * (R.norm(p["bn_a"], ao, nk)
                             + R.norm(p["bn_m"], mo, nk)), c)
    return olmo.ffn(p, x, c, quant)

# ------------------------------------------------------------------ counts


def _params(m: dict) -> int:
    d = m["d_model"]
    inner = m["ssm"]["expand"] * d
    st = m["ssm"]["state_size"]
    r = _dt_rank(d)
    return olmo.attention_params(m) + olmo.ffn_params(m) + d * 2 * inner \
        + inner * (r + 2 * st) + r * inner + inner * d


def _flops(m: dict, ctx: int) -> float:
    inner = m["ssm"]["expand"] * m["d_model"]
    return olmo.attention_flops(m, ctx, m.get("sliding_window")) \
        + 6.0 * inner * m["ssm"]["state_size"] + olmo.ffn_flops(m, ctx)


# the windowed K/V is a rolling buffer per row, not the paged pool
KINDS = {"hymba": Kind(block=block, layer=layer, params=_params,
                       flops=_flops)}
