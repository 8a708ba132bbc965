"""New architectures dropped into a copy of the benchmark, run from there.

A family module is a new file in ``perfbench/arch/`` of a copy that
``tiny.make_copy`` made; ``main`` runs in that copy, with ``perfbench``
imported from it, so the benchmark it drives is the copy's own:

    python3 -m perfbench.tests.dropin cell <seed> <nope|rope>
    python3 -m perfbench.tests.dropin parts

``cell`` runs the copy's ``tiny.rag`` cell, traced, and prints the
result's line.  The serving program has no layer kind ``nope``;
``teach_program`` gives it one in this process, as a change to the
program would: its ``attn`` layer with the position rotation left out
(``rope`` leaves it in, a program that the check has to refuse).  A
made-up peak for the CPU device lets ``mfu`` read there; it is no
measurement.

``parts`` checks the model-level parts and a part that a family gives
(``PROBE``) against the plain walk, on the reference alone.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

# The layer kind ``nope``: full causal attention with no position
# rotation in a model whose other layers rotate (SmolLM3's every fourth
# layer; granite-4.0-h's attention layers), then the feed-forward block.
NOPE = '''"""NoPE attention: the ``attn`` layer without the position rotation."""
import dataclasses

from perfbench import reference as R
from perfbench.arch import olmo


def layer(p, x, c, quant):
    h = R.norm(p.get("ln1", {}), x, c["norm_type"])
    a = olmo.attention(p["attn"], h, dict(c, pos_embedding="none"), quant,
                       None)
    return olmo.ffn(p, R.residual(x, a, c), c, quant)


KINDS = {"nope": dataclasses.replace(olmo.KINDS["attn"], layer=layer)}
'''

NOPE_MODEL = {
    "name": "tiny-nope", "arch_type": "dense", "num_layers": 2,
    "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 128,
    "vocab_size": 4096, "head_dim": 16, "mlp_type": "swiglu",
    "norm_type": "rmsnorm", "rope_theta": 10000.0, "pos_embedding": "rope",
    "layer_pattern": ["attn", "nope"], "tie_embeddings": True,
    "dtype": "float32"}

# Every model-level part and a ``moe`` part, each a power of two or a
# renamed leaf, so that the plain walk on rescaled weights must give the
# same logits bit for bit.
PROBE = '''"""A probe of the model-level parts."""
import dataclasses

from perfbench import weights as W
from perfbench.arch import Kind, olmo

KINDS = {"probe": olmo.KINDS["attn"]}


def embed_scale(c):
    return 2.0


def residual_scale(c):
    return 0.5


def logit_scale(c):
    return 0.25


def extra_layout(cfg):
    return {"meta": ((4, cfg.d_model), ("normal", 0.02))}


def _experts(cfg):
    d, f = cfg.d_model, cfg.moe.expert_d_ff
    return {"wi": W.dense(d, f), "wg": W.dense(d, f), "wo": W.dense(f, d)}


PARTS = {"moe": Kind(
    block=_experts, layer=lambda p, h, c, quant: olmo.swiglu(p, h, quant),
    params=lambda m: 3 * m["d_model"] * m["moe"]["expert_d_ff"],
    flops=lambda m, ctx: 0.0)}
'''


def add_family(root: Path, name: str, source: str) -> Path:
    path = root / "perfbench" / "arch" / f"{name}.py"
    path.write_text(source)
    return path


def teach_program(rotate: bool) -> None:
    """Give the serving program the layer kind ``nope`` in this process:
    its ``attn`` layer, with the rotation left out unless ``rotate``."""
    from repro.models import cache, model

    class NoPE(str):
        """The program's ``attn``, marked to run without the rotation."""

    slot_kinds = cache.slot_kinds
    apply_block = model.Model._apply_block

    def nope_slot_kinds(cfg):
        return [(s, NoPE("attn") if k == "nope" else k)
                for s, k in slot_kinds(cfg)]

    def nope_apply_block(self, p, x, kind, ctx, *rest):
        if isinstance(kind, NoPE) and not rotate:
            ctx = dict(ctx, angles=None)
        return apply_block(self, p, x, kind, ctx, *rest)

    cache.slot_kinds = nope_slot_kinds
    model.Model._apply_block = nope_apply_block


def run_cell(root: Path, seed: int, rotate: bool) -> dict:
    from perfbench import harness, peaks
    teach_program(rotate)
    peaks.PEAKS["cpu"] = (1e12, 1e11, "made up, so that mfu reads on the CPU")
    return harness.run(root, "tiny.rag", seed, 3.0, True,
                       time.perf_counter(), require_tpu=False)


def check_parts() -> dict:
    """The probe model against the plain ``attn`` model on its weights
    rescaled and renamed by hand: logits bit for bit, and the counts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perfbench import peaks, reference, spec, weights

    base = dict(NOPE_MODEL, layer_pattern=["attn"], num_layers=2,
                tie_embeddings=False)
    probe = dict(base, layer_pattern=["probe"], moe={
        "num_experts": 1, "num_experts_per_tok": 1, "expert_d_ff": 128})
    params = weights.make_params(spec.model_config({"model": probe}), 5)
    blk = params["blocks"]["s0_probe"]
    half = {"ln1": blk["ln1"], "ln2": blk["ln2"],
            "attn": dict(blk["attn"], wo=blk["attn"]["wo"] * 0.5),
            "mlp": dict(blk["moe"], wo=blk["moe"]["wo"] * 0.5)}
    plain = {"embed": params["embed"] * 2.0, "blocks": {"s0_attn": half},
             "final_norm": params["final_norm"],
             "lm_head": params["lm_head"] * 0.25}
    toks = jnp.asarray(np.arange(7, 47), jnp.int32)
    a = np.asarray(reference.logits(params, toks, probe))
    b = np.asarray(reference.logits(plain, toks, base))
    shapes = jax.tree.map(lambda x: list(x.shape), params)
    return {"logits_equal": bool(np.array_equal(a, b)),
            "logits_spread": float(np.abs(a).max()),
            "meta": shapes.get("meta"),
            "moe_leaves": sorted(shapes["blocks"]["s0_probe"]["moe"]),
            "counts_equal": [peaks.matmul_params(probe),
                             peaks.token_flops(probe, 40)]
            == [peaks.matmul_params(base), peaks.token_flops(base, 40)]}


def main(argv) -> int:
    root = Path.cwd()
    import perfbench
    here = Path(perfbench.__file__).resolve().parent
    if here != (root / "perfbench").resolve():
        raise SystemExit(f"perfbench imported from {here}, not the copy")
    if argv[0] == "cell":
        out = run_cell(root, int(argv[1]), argv[2] == "rope")
    else:
        out = check_parts()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
