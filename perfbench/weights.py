"""Seeded weights, made on the device in one jitted call per node.

The layout is written out here from the configuration (the names and
shapes the serving model reads), and every leaf is drawn from the seed
by a rule of the benchmark's own: dense matrices N(0, 1/d_in), token
embeddings N(0, 0.02^2), norm scales 1, biases 0, and Mamba's fixed
initial values (dt bias -4.6, A_log = log(1..state), D = 1).  The
program is handed these arrays; the plain reference makes the same ones
again from the seed, so it takes nothing that the program made.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Shape = Tuple[int, ...]


def _norm(cfg) -> Dict[str, tuple]:
    d = cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": ((d,), "ones")}
    if cfg.norm_type == "layernorm":
        return {"scale": ((d,), "ones"), "bias": ((d,), "zeros")}
    return {}


def _dense(d_in: int, d_out: int) -> tuple:
    return ((d_in, d_out), ("normal", 1.0 / math.sqrt(d_in)))


def _attention(cfg) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {"wq": _dense(d, cfg.num_heads * hd),
         "wk": _dense(d, cfg.num_kv_heads * hd),
         "wv": _dense(d, cfg.num_kv_heads * hd),
         "wo": _dense(cfg.num_heads * hd, d)}
    if cfg.qk_norm:
        p["q_norm"] = ((hd,), "ones")
        p["k_norm"] = ((hd,), "ones")
    return p


def _mamba(cfg) -> dict:
    d = cfg.d_model
    inner = cfg.ssm.expand * d
    st, w = cfg.ssm.state_size, cfg.ssm.conv_width
    r = max(1, math.ceil(d / 16))
    return {
        "in_proj": _dense(d, 2 * inner),
        "conv_w": ((w, inner), ("normal", 1.0 / math.sqrt(w))),
        "conv_b": ((inner,), "zeros"),
        "x_proj": _dense(inner, r + 2 * st),
        "dt_proj": _dense(r, inner),
        "dt_bias": ((inner,), ("const", -4.6)),
        "A_log": ((inner, st), "a_log", "float32"),
        "D": ((inner,), "ones", "float32"),
        "out_proj": _dense(inner, d),
    }


def _block(cfg, kind: str) -> dict:
    d = cfg.d_model
    p: dict = {"ln1": _norm(cfg)}
    if kind in ("attn", "local"):
        p["attn"] = _attention(cfg)
    elif kind == "hymba":
        p["attn"] = _attention(cfg)
        p["mamba"] = _mamba(cfg)
        p["bn_a"] = _norm(cfg)
        p["bn_m"] = _norm(cfg)
    elif kind == "mlstm":
        H, hd = cfg.num_heads, cfg.resolved_head_dim
        p["cell"] = {"wq": _dense(d, H * hd), "wk": _dense(d, H * hd),
                     "wv": _dense(d, H * hd), "wi": _dense(d, H),
                     "wf": _dense(d, H), "wog": _dense(d, H * hd),
                     "out": _dense(H * hd, d)}
    elif kind == "slstm":
        p["cell"] = {"w": _dense(d, 4 * d),
                     "r": ((4 * d,), ("normal", 0.1)),
                     "out": _dense(d, d)}
    else:
        raise ValueError(f"layer kind {kind!r} has no weight layout here")
    if kind not in ("mlstm", "slstm") and cfg.mlp_type != "none":
        if cfg.moe is not None or cfg.mlp_type not in ("swiglu", "gelu_glu"):
            raise ValueError(f"mlp {cfg.mlp_type!r}/moe has no layout here")
        p["ln2"] = _norm(cfg)
        p["mlp"] = {"wi": _dense(d, cfg.d_ff), "wg": _dense(d, cfg.d_ff),
                    "wo": _dense(cfg.d_ff, d)}
    return p


def layout(cfg) -> dict:
    """Nested dict of leaf specs ``(shape, rule[, dtype])``; decoder
    blocks are stacked over pattern cycles (leading dim ``n_cycles``)
    under slot names ``s<i>_<kind>``."""
    n_cyc = cfg.num_layers // len(cfg.layer_pattern)
    if cfg.pos_embedding not in ("rope", "none") or cfg.is_encoder_decoder:
        raise ValueError("only rope / position-free decoders have a layout")

    def stack(tree):
        if isinstance(tree, dict):
            return {k: stack(v) for k, v in tree.items()}
        return ((n_cyc,) + tree[0],) + tree[1:]

    lay = {"embed": ((cfg.vocab_size, cfg.d_model), ("normal", 0.02)),
           "blocks": {f"s{i}_{k}": stack(_block(cfg, k))
                      for i, k in enumerate(cfg.layer_pattern)},
           "final_norm": _norm(cfg)}
    if not cfg.tie_embeddings:
        lay["lm_head"] = _dense(cfg.d_model, cfg.vocab_size)
    return lay


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) >= 2 and isinstance(x[0], tuple)


def _make_leaf(key, spec, dtype):
    shape, rule = spec[0], spec[1]
    dt = jnp.dtype(spec[2]) if len(spec) > 2 else dtype
    if rule == "ones":
        return jnp.ones(shape, dt)
    if rule == "zeros":
        return jnp.zeros(shape, dt)
    if rule == "a_log":
        st = shape[-1]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, st + 1, dtype=
                                                   jnp.float32)),
                                shape).astype(dt)
    kind, val = rule
    if kind == "const":
        return jnp.full(shape, val, dt)
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * val).astype(dt)
    raise ValueError(rule)


def make_params(cfg, seed: int):
    """All of a node's weights in the dtype they are served in, from one
    jitted call on the default device."""
    lay = layout(cfg)
    leaves, treedef = jax.tree.flatten(lay, is_leaf=_is_leaf)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_make_leaf(k, s, dtype) for k, s in zip(keys, leaves)])

    return jax.jit(build)(jax.random.PRNGKey(seed))
