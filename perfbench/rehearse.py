"""Compile each cell's engine programs for a described TPU v5e at the real
shapes, without a chip, and print ``memory_analysis`` and a peak
reckoning per configuration.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py --config \\
        edge2-olmo1b-xlstm350m --traffic rag-steady

Programs: the paged prefix prefill at the longest prefix, the fork
refill of a question suffix, the decode segment at its widest block
table, and one layer of the plain reference in float32 for each layer
kind, as its module in ``perfbench/arch/`` lays it out.  Nothing runs.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes")}


def node_programs(node_spec: dict, traffic: dict, one_chip) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.cluster import LiveEdgeNode
    from repro.data.tokenizer import Tokenizer
    from repro.models import Model
    from repro.retrieval.encoder import TextEncoder

    from perfbench import arch, reference, spec, weights

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    cfg = spec.model_config(node_spec)
    cw = int(traffic["corpus"]["chunk_words"])
    max_len = int(traffic["top_k"]) * cw + int(traffic["prompt_overhead"]) \
        + int(traffic["answer_tokens"])
    params = on_chip(jax.eval_shape(lambda k: Model(cfg).init_params(
        k, max_seq=max_len), jax.random.PRNGKey(0)))
    node = LiveEdgeNode(0, node_spec["arch"], cfg, params, [],
                        Tokenizer.build(["a"]), TextEncoder(),
                        max_len=max_len, top_k=int(traffic["top_k"]),
                        max_new_tokens=int(traffic["answer_tokens"]),
                        queue="standing", paged=True, admission="sjf")
    eng, gen = node.engine, node.gen
    B, C = eng.batch_size, eng.prefill_chunk
    i32 = jnp.int32

    def s(shape, dt=i32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cache = on_chip(jax.eval_shape(eng._paged_fresh_cache, s((B,)), s((B,)),
                                   s((B, eng.nb_total))))
    row_state = on_chip(jax.eval_shape(eng._paged_zero_row_state))
    key = s((2,), jnp.uint32)
    l0 = eng.cont_max_prompt_len(gen.max_new_tokens) - C
    out = {"batch": B, "prefill_chunk": C, "block_size": eng.block_size,
           "num_blocks": eng.num_blocks, "max_len": max_len}
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    out["weights_bytes"], out["cache_bytes"] = wbytes, pool
    progs = {
        "prefix_prefill": eng._paged_prefix_prefill.lower(
            params, s((1, l0)), cache, s((eng.nb_total,)), s(()), s(()),
            row_state),
        "fork_refill": eng._paged_refill.lower(
            params, s((1, C)), s((B, 1)), cache, s((B,), jnp.bool_),
            s((B,)), s((B,)), s(()), s(()), key, s((eng.nb_total,)),
            row_state, s(()), s(()), s(()), gp=gen),
        "decode_segment": eng._decode_cont.lower(
            params, s((B, 1)), cache, key, s((B,), jnp.bool_), s((B,)),
            s((B,)), s((B, gen.max_new_tokens)), s(()),
            s((), jnp.bool_), gp=gen, kv_cap=None, nb_cap=eng.nb_total),
    }
    for name, lowered in progs.items():
        compiled = lowered.compile()
        out[name] = _mem(compiled)
        if name == "decode_segment":
            out[name]["tpu_custom_call"] = "tpu_custom_call" in \
                compiled.as_text()
    # one reference layer of each kind, float32, at the reference length,
    # on the benchmark's own layout of that kind's block
    m = dict(node_spec["model"])
    m["head_dim"] = m.get("head_dim") or m["d_model"] // m["num_heads"]
    cj = json.dumps(m, sort_keys=True)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    for kind in arch.kinds(cfg.layer_pattern):
        p = jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf[0], jnp.dtype(leaf[2]) if len(leaf) > 2 else dtype,
            sharding=one_chip), arch.kind(kind).block(cfg),
            is_leaf=weights._is_leaf)
        x = s((max_len, cfg.d_model), jnp.float32)
        out[f"reference_{kind}"] = _mem(reference._layer_jit.lower(
            p, x, kind=kind, cj=cj, quant=None).compile())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="rag-steady")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    config = json.loads((ROOT / "perfbench" / "configs"
                         / f"{args.config}.json").read_text())
    traffic = json.loads((ROOT / "perfbench" / "traffic"
                          / f"{args.traffic}.json").read_text())
    total = 0
    worst_temp = 0
    for node_spec in config["nodes"]:
        r = node_programs(node_spec, traffic, one_chip)
        print(json.dumps({"node": node_spec["arch"], **r}), flush=True)
        total += r["weights_bytes"] + r["cache_bytes"]
        worst_temp = max(worst_temp, max(
            v["temp_size_in_bytes"] for k, v in r.items()
            if isinstance(v, dict) and not k.startswith("reference")))
    print(json.dumps({"config": args.config,
                      "weights_and_caches_bytes": total,
                      "largest_program_temp_bytes": worst_temp,
                      "peak_reckoning_bytes": total + worst_temp}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
