"""Plain float32 references of the node architectures, for the check.

Straightforward ``jax.numpy`` over one sequence: no cache, no batching,
no chunking, no kernels, every matmul at ``precision=HIGHEST``.  It
imports nothing of the program; it reads the weights that
``perfbench/weights.py`` makes from the seed, by the same names.

Layer equations, and where they depart from the published models (the
departures are the serving model's, which the check holds it to):

* ``attn`` (OLMo-1B, arXiv:2402.00838): non-parametric LayerNorm, RoPE
  (rotate-half), causal softmax attention, SwiGLU MLP, tied embeddings.
* ``mlstm`` / ``slstm`` (xLSTM, arXiv:2405.04517): LayerNorm then the
  cell, no separate MLP.  mLSTM is written in its parallel (quadratic)
  form with the stabiliser started at m_0 = 0; sLSTM is the stabilised
  exponential-gated recurrence with a *diagonal* recurrent matrix (the
  paper's is block-diagonal) and 1e-6 floor on the normaliser.
* ``hymba`` (Hymba, arXiv:2411.13676): attention heads (GQA, sliding
  window on every layer; the paper keeps 3 global layers and meta
  tokens, which this model does not) and Mamba heads in parallel on the
  same RMSNorm'd input, fused as the mean of the two RMSNorm'd branch
  outputs; then a SwiGLU MLP.

``quant="fp8"`` is the control: every matmul input (weights per tensor,
activations per row) is rounded to float8 e4m3 with an amax scale.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _q8(x, axis):
    """Round to float8 e4m3 with an amax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x, w, quant: Optional[str]):
    x = x.astype(F32)
    w = w.astype(F32)
    if quant == "fp8":
        x = _q8(x, -1)
        w = _q8(w, None)
    return jnp.matmul(x, w, precision=HI)


def norm(p, x, kind: str, eps: float = 1e-6):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["scale"].astype(F32)[None]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"].astype(F32)[None] + p["bias"].astype(F32)[None]
    return y


def rope(x, theta: float):
    """x [T, H, hd] at positions 0..T-1, rotate-half pairing."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]          # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, h, c, quant, window: Optional[int]):
    T = h.shape[0]
    H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    q = mm(h, p["wq"], quant).reshape(T, H, hd)
    k = mm(h, p["wk"], quant).reshape(T, KV, hd)
    v = mm(h, p["wv"], quant).reshape(T, KV, hd)
    if c.get("pos_embedding", "rope") == "rope":
        q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
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


def mamba(p, h, c, quant):
    T = h.shape[0]
    st = c["ssm"]["state_size"]
    xz = mm(h, p["in_proj"], quant)
    xi, z = jnp.split(xz, 2, axis=-1)
    w = p["conv_w"].astype(F32)                                 # [W, I]
    W = w.shape[0]
    xpad = jnp.pad(xi, ((W - 1, 0), (0, 0)))
    conv = sum(xpad[i:i + T] * w[i][None] for i in range(W))
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


def layer(p, x, c, kind: str, quant: Optional[str]):
    nk = c["norm_type"]
    h = norm(p.get("ln1", {}), x, nk)
    if kind in ("attn", "local"):
        win = c.get("sliding_window") if kind == "local" else None
        x = x + attention(p["attn"], h, c, quant, win)
    elif kind == "hymba":
        ao = attention(p["attn"], h, c, quant, c.get("sliding_window"))
        mo = mamba(p["mamba"], h, c, quant)
        x = x + 0.5 * (norm(p["bn_a"], ao, nk) + norm(p["bn_m"], mo, nk))
    elif kind == "mlstm":
        return x + mlstm(p["cell"], h, c, quant)
    elif kind == "slstm":
        return x + slstm(p["cell"], h, c, quant)
    else:
        raise ValueError(kind)
    if "mlp" in p:
        x = x + swiglu(p["mlp"], norm(p.get("ln2", {}), x, nk), quant)
    return x


def _slice_cycle(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


@partial(jax.jit, static_argnames=("kind", "cj", "quant"))
def _layer_jit(p, x, kind, cj, quant):
    import json
    return layer(p, x, json.loads(cj), kind, quant)


@partial(jax.jit, static_argnames=("cj", "quant"))
def _head_jit(params, x, cj, quant):
    import json
    c = json.loads(cj)
    x = norm(params["final_norm"], x, c["norm_type"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    return mm(x, head, quant)


def logits(params, tokens, c: dict, quant: Optional[str] = None):
    """Logits [T, V] (float32) at every position of one token sequence.
    Runs layer by layer (one compiled program per layer kind), so the
    reference needs one layer's float32 weights at a time."""
    import json
    c = dict(c)
    if not c.get("head_dim"):
        c["head_dim"] = c["d_model"] // c["num_heads"]
    cj = json.dumps(c, sort_keys=True)
    x = params["embed"][tokens].astype(F32)
    pattern = c["layer_pattern"]
    n_cyc = c["num_layers"] // len(pattern)
    for cyc in range(n_cyc):
        for i, kind in enumerate(pattern):
            p = _slice_cycle(params["blocks"][f"s{i}_{kind}"], cyc)
            x = _layer_jit(p, x, kind, cj, quant)
    return _head_jit(params, x, cj, quant)
