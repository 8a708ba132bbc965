"""Serving launcher: request-level scheduling over the compiled decode
loop for any assigned architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
        --batch 4 --prompt-len 16 --new-tokens 16 --requests 12

``--reference`` additionally times the per-token Python loop on the
same requests and reports the speedup of the compiled path.
"""
import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving import GenerationParams, RequestQueue, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--reference", action="store_true",
                    help="also time the per-token Python loop")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key, max_seq=args.max_len)
    eng = ServeEngine(cfg, params, max_len=args.max_len,
                      batch_size=args.batch)
    gen = GenerationParams(max_new_tokens=args.new_tokens,
                           temperature=args.temperature,
                           top_k=args.top_k, top_p=args.top_p)
    rng = jax.random.PRNGKey(1)
    # lengths straddle power-of-two bucket boundaries (L, L/2, L/3) so
    # the queue actually schedules across multiple buckets
    prompts = [
        [int(t) for t in jax.random.randint(
            jax.random.fold_in(rng, i),
            (max(1, args.prompt_len // (1 + i % 3)),), 5, cfg.vocab_size)]
        for i in range(args.requests)]

    queue = RequestQueue(eng, gen)
    rids = queue.submit_all(prompts)
    t0 = time.perf_counter()
    outs = queue.run()
    dt = time.perf_counter() - t0
    toks = sum(len(outs[r]) for r in rids)
    st = queue.stats
    print(f"generated {toks} tokens for {st.requests} requests in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s incl. compile; {st.waves} waves, "
          f"slot utilization {st.slot_utilization:.0%})")
    for i, r in enumerate(rids[:2]):
        print(f"  req{i}: {outs[r]}")

    if args.reference:
        wave = prompts[:args.batch]
        eng.generate(wave, gen=gen)             # warm both paths
        eng.generate_reference(wave, gen=gen)
        t0 = time.perf_counter()
        eng.generate(wave, gen=gen)
        t_new = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.generate_reference(wave, gen=gen)
        t_ref = time.perf_counter() - t0
        n = len(wave) * args.new_tokens
        print(f"compiled loop {n/t_new:.1f} tok/s vs python loop "
              f"{n/t_ref:.1f} tok/s -> {t_ref/t_new:.1f}x")


if __name__ == "__main__":
    main()
