"""Seeded weights, made on the device in one jitted call per node.

The layout is written out from the configuration (the names and shapes
the serving model reads): the model-level leaves here, each layer's by
its kind's module in ``perfbench/arch/``.  Every leaf is drawn from the
seed by a rule of the benchmark's own: dense matrices N(0, 1/d_in), token
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

from perfbench import arch

Shape = Tuple[int, ...]


def norm(cfg) -> Dict[str, tuple]:
    d = cfg.d_model
    if cfg.norm_type == "rmsnorm":
        return {"scale": ((d,), "ones")}
    if cfg.norm_type == "layernorm":
        return {"scale": ((d,), "ones"), "bias": ((d,), "zeros")}
    return {}


def dense(d_in: int, d_out: int) -> tuple:
    return ((d_in, d_out), ("normal", 1.0 / math.sqrt(d_in)))


def layout(cfg) -> dict:
    """Nested dict of leaf specs ``(shape, rule[, dtype])``; decoder
    blocks are stacked over pattern cycles (leading dim ``n_cycles``)
    under slot names ``s<i>_<kind>``, each laid out by its kind's module
    in ``perfbench/arch/``."""
    n_cyc = cfg.num_layers // len(cfg.layer_pattern)
    if cfg.pos_embedding not in ("rope", "none") or cfg.is_encoder_decoder:
        raise ValueError("only rope / position-free decoders have a layout")

    def stack(tree):
        if isinstance(tree, dict):
            return {k: stack(v) for k, v in tree.items()}
        return ((n_cyc,) + tree[0],) + tree[1:]

    lay = {"embed": ((cfg.vocab_size, cfg.d_model), ("normal", 0.02)),
           "blocks": {f"s{i}_{k}": stack(arch.kind(k).block(cfg))
                      for i, k in enumerate(cfg.layer_pattern)},
           "final_norm": norm(cfg)}
    if not cfg.tie_embeddings:
        lay["lm_head"] = dense(cfg.d_model, cfg.vocab_size)
    extra = arch.model_part(cfg.layer_pattern, "extra_layout")
    if extra is not None:
        lay.update(extra(cfg))
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
