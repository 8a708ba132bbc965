"""xLSTM layers (arXiv:2405.04517): ``mlstm`` and ``slstm``, each the
block's norm (LayerNorm for xLSTM) then the cell, with no separate MLP.

mLSTM is written in its parallel (quadratic) form with the stabiliser
started at m_0 = 0; sLSTM is the stabilised exponential-gated recurrence
with a *diagonal* recurrent matrix (the paper's is block-diagonal) and a
1e-6 floor on the normaliser.  The departures are the serving model's,
which the check holds it to.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import reference as R
from perfbench import weights as W
from perfbench.arch import Kind
from perfbench.peaks import head_dim
from perfbench.reference import F32, HI, mm

# ------------------------------------------------------------------ layout


def mlstm_block(cfg) -> dict:
    d, H, hd = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
    return {"ln1": W.norm(cfg),
            "cell": {"wq": W.dense(d, H * hd), "wk": W.dense(d, H * hd),
                     "wv": W.dense(d, H * hd), "wi": W.dense(d, H),
                     "wf": W.dense(d, H), "wog": W.dense(d, H * hd),
                     "out": W.dense(H * hd, d)}}


def slstm_block(cfg) -> dict:
    d = cfg.d_model
    return {"ln1": W.norm(cfg),
            "cell": {"w": W.dense(d, 4 * d),
                     "r": ((4 * d,), ("normal", 0.1)),
                     "out": W.dense(d, d)}}

# --------------------------------------------------------------- reference


def mlstm(p, h, c, quant):
    T = h.shape[0]
    H, hd = c["num_heads"], c["head_dim"]
    q = mm(h, p["wq"], quant).reshape(T, H, hd) / math.sqrt(hd)
    k = mm(h, p["wk"], quant).reshape(T, H, hd) / math.sqrt(hd)
    v = mm(h, p["wv"], quant).reshape(T, H, hd)
    li = mm(h, p["wi"], quant)                                  # [T, H]
    lf = jax.nn.log_sigmoid(mm(h, p["wf"], quant))
    F = jnp.cumsum(lf, axis=0)
    logd = F[:, None, :] - F[None, :, :] + li[None, :, :]       # [t, s, H]
    t = jnp.arange(T)
    causal = (t[None, :] <= t[:, None])[..., None]
    logd = jnp.where(causal, logd, -jnp.inf)
    m = jnp.maximum(F, jnp.max(logd, axis=1))                   # [T, H]
    d = jnp.exp(logd - m[:, None, :])
    sc = jnp.einsum("thd,shd->tsh", q, k, precision=HI) * d
    num = jnp.einsum("tsh,shd->thd", sc, v, precision=HI)
    den = jnp.maximum(jnp.abs(jnp.sum(sc, axis=1)), jnp.exp(-m))
    hid = (num / den[..., None]).reshape(T, H * hd)
    y = hid * jax.nn.sigmoid(mm(h, p["wog"], quant))
    return mm(y, p["out"], quant)


def slstm(p, h, c, quant):
    d = h.shape[-1]
    pre = mm(h, p["w"], quant)                                  # [T, 4d]
    r = p["r"].astype(F32)

    def step(st, pre_t):
        cc, n, hh, m = st
        z_in = pre_t + jnp.concatenate([hh] * 4) * r
        li = z_in[:d]
        lf = jax.nn.log_sigmoid(z_in[d:2 * d])
        z = jnp.tanh(z_in[2 * d:3 * d])
        o = jax.nn.sigmoid(z_in[3 * d:])
        m_new = jnp.maximum(lf + m, li)
        i_p = jnp.exp(li - m_new)
        f_p = jnp.exp(lf + m - m_new)
        cc = f_p * cc + i_p * z
        n = f_p * n + i_p
        hh = o * cc / jnp.maximum(n, 1e-6)
        return (cc, n, hh, m_new), hh

    zero = jnp.zeros((d,), F32)
    _, hs = jax.lax.scan(step, (zero, zero, zero, zero), pre)
    return mm(hs, p["out"], quant)


def _layer(cell):
    def layer(p, x, c, quant):
        h = R.norm(p.get("ln1", {}), x, c["norm_type"])
        return R.residual(x, cell(p["cell"], h, c, quant), c)
    return layer

# ------------------------------------------------------------------ counts


def _mlstm_params(m: dict) -> int:
    d, H, hd = m["d_model"], m["num_heads"], head_dim(m)
    return d * H * hd * 4 + 2 * d * H + H * hd * d


def _slstm_params(m: dict) -> int:
    d = m["d_model"]
    return d * 4 * d + d * d


KINDS = {
    "mlstm": Kind(block=mlstm_block, layer=_layer(mlstm),
                  params=_mlstm_params,
                  flops=lambda m, ctx: 6.0 * m["num_heads"] * head_dim(m)
                  * head_dim(m)),
    "slstm": Kind(block=slstm_block, layer=_layer(slstm),
                  params=_slstm_params,
                  flops=lambda m, ctx: 12.0 * m["d_model"]),
}
