"""Chip smoke: the live RAG cluster's main path on one TPU, at published
widths, through the entry points ``launch/cluster_serve.py`` uses.

    python chip_smoke.py            # on a TPU host; fails on any other backend
    python chip_smoke.py --smoke    # CPU rehearsal: tiny configs, no TPU checks

Phases, each fatal on failure:

1. compile cache on (``repro.launch.compile_cache``), then the backend
   must be ``tpu`` — there is no CPU fallback;
2. build two nodes (olmo-1b, xlstm-350m) at their published configs
   (bf16, random weights from ``--seed``) with standing queues, paged KV
   and SJF admission; profile them (``ClusterRuntime.initialize``) and
   replay 2 slots of 8 RAG requests through PPO identify -> Algorithm-1
   routing -> per-node retrieval -> standing paged ``ServeEngine``;
3. check: every request completed with >= 1 token, all ids inside the
   vocab, none lost; one request's first-step logits per node are
   finite; the olmo node's compiled decode program holds the Pallas
   paged-attention kernel (``tpu_custom_call``), not the jnp oracle;
4. the paged decode kernel against its oracle at olmo-1b widths on the
   same random inputs, within ``KERNEL_TOL``.

The last line of stdout on success is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
from repro.cluster import ClusterRuntime, LiveWorkload, replay_trace  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.cluster_serve import build_cluster  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.rag.pipeline import split_prompt  # noqa: E402

N_NODES = 2          # NODE_ARCHS[:2]: olmo-1b, xlstm-350m
SLOTS = 2
PER_SLOT = 8
SLO_S = 30.0         # a request over it counts as a drop, not a failure
# Kernel vs oracle, both at the engine's dtypes (bf16 query from the bf16
# model, f32 pool) and both rounding the output to bf16: one bf16 ulp of
# an output of magnitude <= 1 is 2**-8 ~ 4e-3, and the f32 softmax adds
# far less.  A misplaced block, a wrong mask or a mis-tiled DMA changes
# outputs by the order of the outputs themselves (~1e-1 here).
KERNEL_TOL = 1e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def first_step_logits(node, prompt):
    """Logits the first token of ``prompt`` is sampled from, through the
    same paged frame programs ``ContinuousSession.begin_frame`` runs."""
    eng = node.engine
    B, C, bs = eng.batch_size, eng.prefill_chunk, eng.block_size
    frame_len = -(-len(prompt) // C) * C
    toks = np.full((B, frame_len), eng.pad_id, np.int32)
    toks[0, frame_len - len(prompt):] = prompt
    first = np.full((B,), frame_len, np.int32)
    first[0] = frame_len - len(prompt)
    tables = np.full((B, eng.nb_total), -1, np.int32)
    n_blk = -(-frame_len // bs)
    tables[0, :n_blk] = np.arange(n_blk)
    cache = eng._paged_fresh_cache(jnp.asarray(first),
                                   jnp.zeros(B, jnp.int32),
                                   jnp.asarray(tables))
    logits = None
    for j in range(frame_len // C):
        logits, cache = eng._paged_prefill_chunk(
            eng.params, jnp.asarray(toks[:, j * C:(j + 1) * C]), cache,
            jnp.int32(frame_len))
    return np.asarray(logits[0], np.float32)


def decode_program_text(node) -> str:
    """Optimized HLO of the node's paged decode segment, compiled from
    shapes at the engine's geometry (nothing runs)."""
    eng = node.engine
    B, gen = eng.batch_size, node.gen
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    cache = jax.eval_shape(eng._paged_fresh_cache, i32, i32,
                           jax.ShapeDtypeStruct((B, eng.nb_total), jnp.int32))
    args = (eng.params, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache,
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct((B,), jnp.bool_),
            i32, i32, jax.ShapeDtypeStruct((B, gen.max_new_tokens), jnp.int32),
            jnp.int32(0), jnp.asarray(False))
    lowered = eng._decode_cont.lower(*args, gp=gen, kv_cap=None,
                                     nb_cap=eng.nb_total)
    return lowered.compile().as_text()


def kernel_vs_oracle(node, seed: int) -> float:
    """Max abs error of the Pallas paged decode kernel against the jnp
    oracle (f32, highest matmul precision) on one random pool at the
    node's head widths and engine pool geometry."""
    cfg, eng = node.engine.cfg, node.engine
    B, H, KV = eng.batch_size, cfg.num_heads, cfg.num_kv_heads
    hd, bs, nb, P = (cfg.resolved_head_dim, eng.block_size, eng.nb_total,
                     eng.num_blocks)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.dtype(cfg.dtype))
    k_pool = jnp.asarray(rng.standard_normal((P, bs, KV, hd)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((P, bs, KV, hd)), jnp.float32)
    # rows own disjoint shuffled block runs of different lengths; the
    # last row leaves its tail unallocated (-1)
    perm = rng.permutation(P).reshape(B, nb).astype(np.int32)
    last = np.array([nb * bs - 1 - 7 * b for b in range(B)], np.int32)
    last[-1] = (nb // 2) * bs - 3
    perm[-1, nb // 2:] = -1
    first = np.array([3 * b for b in range(B)], np.int32)
    args = (q, k_pool, v_pool, jnp.asarray(perm), jnp.asarray(first),
            jnp.asarray(last))
    got = ops.paged_decode_attention(*args, use_pallas=True)
    with jax.default_matmul_precision("highest"):
        want = ops.paged_decode_attention(*args, use_pallas=False)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal: tiny configs, skip the TPU checks")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, weights and traffic")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    backend = jax.default_backend()
    if backend != "tpu" and not args.smoke:
        fail(f"JAX backend is {backend!r}, not 'tpu': this check runs "
             f"only on the chip (--smoke rehearses it on the CPU)")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']}); compile cache {cache_dir}", flush=True)

    t0 = time.perf_counter()
    nodes, qas, tok, encoder, ident, _ = build_cluster(
        N_NODES, smoke=args.smoke, entities=8 if args.smoke else 24,
        seed=args.seed, update_threshold=PER_SLOT, queue="standing",
        paged=True, admission="sjf")
    t_build = time.perf_counter() - t0
    for node in nodes:
        cfg = node.engine.cfg
        print(f"node {node.node_id} [{node.arch}]: {cfg.num_layers} layers, "
              f"d_model {cfg.d_model}, vocab {cfg.vocab_size}, {cfg.dtype}",
              flush=True)
    runtime = ClusterRuntime(nodes, ident, seed=args.seed)
    t0 = time.perf_counter()
    runtime.initialize()
    t_profile = time.perf_counter() - t0
    print(f"set-up: build {t_build:.1f}s, profile+compile {t_profile:.1f}s",
          flush=True)

    tokens = {node.node_id: {} for node in nodes}

    def on_slot(t, m):
        for node in nodes:
            tokens[node.node_id].update(node.last_tokens)

    t0 = time.perf_counter()
    report = replay_trace(runtime, LiveWorkload(qas, encoder,
                                                seed=args.seed + 2),
                          n_slots=SLOTS, slo_s=SLO_S,
                          base_volume=PER_SLOT, trace="uniform",
                          seed=args.seed + 3, verbose=True, on_slot=on_slot)
    t_replay = time.perf_counter() - t0
    lost = sum(node.unfinished() for node in nodes)
    runtime.close()
    submitted = sum(m.n_queries for m in report.slots)
    print(f"replay: {submitted} requests in {t_replay:.1f}s, {lost} lost",
          flush=True)
    for node in nodes:
        st = node.stats
        print(f"node {node.node_id} [{node.arch}]: {st.queries} requests, "
              f"{st.tokens_out} tokens, {st.waves} frames, {st.refills} "
              f"refills, {st.drops} drops", flush=True)

    completed = sum(len(t) for t in tokens.values())
    if lost or completed != submitted:
        fail(f"{completed} of {submitted} requests completed, {lost} lost")
    for node in nodes:
        vocab = node.engine.cfg.vocab_size
        for qid, ids in tokens[node.node_id].items():
            if not ids:
                fail(f"node {node.node_id} request {qid}: no tokens")
            if min(ids) < 0 or max(ids) >= vocab:
                fail(f"node {node.node_id} request {qid}: token ids "
                     f"{ids} outside [0, {vocab})")

    for node in nodes:
        cap = node.engine.cont_max_prompt_len(node.gen.max_new_tokens)
        prompt, _ = split_prompt(qas[0].question, [node.docs[0].text], tok,
                                 cap=cap)
        logits = first_step_logits(node, prompt)
        if logits.shape != (node.engine.cfg.vocab_size,) \
                or not np.isfinite(logits).all():
            fail(f"node {node.node_id}: first-step logits shape "
                 f"{logits.shape}, finite={np.isfinite(logits).all()}")
        print(f"node {node.node_id}: first-step logits finite over "
              f"{logits.shape[0]} ids (prompt {len(prompt)} tokens)",
              flush=True)

    if not args.smoke:
        hlo = decode_program_text(nodes[0])
        if "tpu_custom_call" not in hlo:
            fail(f"node 0 [{nodes[0].arch}] decode program has no "
                 f"tpu_custom_call: the paged kernel did not run")
        print(f"node 0 [{nodes[0].arch}]: decode program holds the Pallas "
              f"paged kernel (tpu_custom_call)", flush=True)

    err = kernel_vs_oracle(nodes[0], args.seed)
    print(f"paged kernel vs oracle [{nodes[0].arch} widths]: max abs err "
          f"{err:.3e} (tol {KERNEL_TOL:g})", flush=True)
    if not err <= KERNEL_TOL:
        fail(f"paged kernel differs from its oracle by {err:.3e}")

    stats = dev.memory_stats()
    peak = stats.get("peak_bytes_in_use") if stats else None
    print("peak device bytes in use: "
          + (f"{peak} ({peak / 1e9:.2f} GB)" if peak is not None
             else "not reported"), flush=True)
    if args.smoke:
        print("rehearsal ok (tiny configs; the TPU checks were skipped)")
        return
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
