"""Plain float32 references of the node architectures, for the check.

Straightforward ``jax.numpy`` over one sequence: no cache, no batching,
no chunking, no kernels, every matmul at ``precision=HIGHEST``.  It
imports nothing of the program; it reads the weights that
``perfbench/weights.py`` makes from the seed, by the same names.

Here are the walk over the layers, the embedding and the head, and the
arithmetic every layer shares; each layer's equations, and where they
depart from the published model, are in its kind's module in
``perfbench/arch/``.

``quant="fp8"`` is the control: every matmul input (weights per tensor,
activations per row) is rounded to float8 e4m3 with an amax scale.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from perfbench import arch

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


def residual(x, branch, c: dict):
    """The residual stream after adding one branch's output, scaled by
    the model's ``residual_scale`` where a family gives one."""
    scale = arch.model_part(c["layer_pattern"], "residual_scale")
    return x + branch if scale is None else x + scale(c) * branch


def _slice_cycle(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


@partial(jax.jit, static_argnames=("kind", "cj", "quant"))
def _layer_jit(p, x, kind, cj, quant):
    import json
    return arch.kind(kind).layer(p, x, json.loads(cj), quant)


@partial(jax.jit, static_argnames=("cj", "quant"))
def _head_jit(params, x, cj, quant):
    import json
    c = json.loads(cj)
    x = norm(params["final_norm"], x, c["norm_type"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    out = mm(x, head, quant)
    scale = arch.model_part(c["layer_pattern"], "logit_scale")
    return out if scale is None else out * scale(c)


def logits(params, tokens, c: dict, quant: Optional[str] = None):
    """Logits [T, V] (float32) at every position of one token sequence.
    Runs layer by layer (one compiled program per layer kind), so the
    reference needs one layer's float32 weights at a time; each layer is
    its kind's module's in ``perfbench/arch/``."""
    import json
    c = dict(c)
    if not c.get("head_dim"):
        c["head_dim"] = c["d_model"] // c["num_heads"]
    cj = json.dumps(c, sort_keys=True)
    pattern = c["layer_pattern"]
    scale = arch.model_part(pattern, "embed_scale")     # each kind known
    x = params["embed"][tokens].astype(F32)
    if scale is not None:
        x = x * scale(c)
    n_cyc = c["num_layers"] // len(pattern)
    for cyc in range(n_cyc):
        for i, kind in enumerate(pattern):
            p = _slice_cycle(params["blocks"][f"s{i}_{kind}"], cyc)
            x = _layer_jit(p, x, kind, cj, quant)
    return _head_jit(params, x, cj, quant)
