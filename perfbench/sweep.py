"""Find a configuration's knee: its traffic at a list of offered rates.

    python3 perfbench/sweep.py --workload edge2.rag-steady --seed 5 \\
        --seconds 30 --rates 0.5 3 4 5 6 7 8

One process builds the cell and warms it once, then runs one open-loop
window per rate (the cell's traffic mix, its due times regenerated at
that rate, the router reset to its initial policy).  Requests are
judged by the latency and TTFT limits of the cell's traffic file; the
first rate is the unloaded run those limits are chosen from (PERF.md
gives the rule), so its medians and p95s are printed.  Per rate it
prints attainment (share of dispatched requests meeting both limits, a
failed one missing), the backlog (due, not dispatched, at the window's
end), the front door's mean wait in each half of the window, and the
end-to-end numbers.  The knee is the highest rate with attainment
>= 90%, a backlog of at most 2 and no growing front-door wait.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from repro.core.cluster import Query

    from perfbench import harness, spec, timeline, traffic
    if jax.default_backend() != "tpu":
        raise SystemExit(f"JAX backend is {jax.default_backend()!r}, not "
                         f"'tpu'")
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir(ROOT))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(ROOT, args.workload)
    tr = cell.traffic
    w = harness.build(cell, args.seed, args.seconds)
    harness.warm_up(w)
    print(f"set-up {time.perf_counter() - T0:.1f} s", flush=True)
    lim = tr["limits"]
    rows = []
    for k, rate in enumerate(args.rates):
        sched = traffic.schedule(tr, w.corpus, rate, args.seconds,
                                 args.seed + 1000 * (k + 1))
        embs = w.encoder.encode([r.question for r in sched])
        w.schedule = sched
        w.queries = [Query(r.domain, embs[i], qid=r.idx, question=r.question,
                           reference=r.reference)
                     for i, r in enumerate(sched)]
        harness.warm_classes(w)        # this schedule's program families
        # every rate starts from the router's initial state, as a run does
        w.runtime.identifier = harness.new_identifier(
            cell, len(w.nodes), w.encoder.dim)
        probe = harness.Probe(w, time.perf_counter(), False)
        probe.recs = {}
        probe.compiles = 0
        probe.t0 = time.perf_counter()
        win = harness.run_window(w, probe, args.seconds)
        recs = [probe.recs[i] for i in sorted(probe.recs)]
        lat = [r.ret - r.due for r in recs if r.ok]
        ttft = [r.first - r.due for r in recs if r.ok]
        if k == 0:
            print(f"unloaded ({rate} req/s): latency median "
                  f"{np.median(lat):.4f} s, p95 {np.percentile(lat, 95):.4f}"
                  f" s; TTFT median {np.median(ttft):.4f} s, p95 "
                  f"{np.percentile(ttft, 95):.4f} s", flush=True)
        half = args.seconds / 2
        fw1 = [r.dispatch - r.due for r in recs if r.due < half]
        fw2 = [r.dispatch - r.due for r in recs if r.due >= half]
        t = timeline.tails(recs)
        row = {"rate": rate, "dispatched": len(recs),
               "attainment": timeline.attainment(recs, lim["latency_s"],
                                                 lim["ttft_s"]),
               "backlog": win["backlog"],
               "front_wait_1st_half_s": float(np.mean(fw1)) if fw1 else 0,
               "front_wait_2nd_half_s": float(np.mean(fw2)) if fw2 else 0,
               "latency_p50_ms": 1e3 * float(np.median(lat)) if lat else None,
               "latency_p95_ms": t["latency"], "ttft_p95_ms": t["ttft"],
               "tpot_p95_ms": t["tpot"],
               "output_tok_s": timeline.output_tok_s(recs, win["span_s"]),
               "slots": win["slots"], "largest_slot": win["largest_slot"],
               "compiles": probe.compiles}
        probe.uninstall()
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = max([r["rate"] for r in rows[1:] if r["attainment"] >= 0.9
                and r["backlog"] <= 2
                and r["front_wait_2nd_half_s"]
                <= 2 * r["front_wait_1st_half_s"] + 0.25] or [0.0])
    print(json.dumps({"knee_rps": knee, "limits": {
        "latency_s": lim["latency_s"], "ttft_s": lim["ttft_s"]}}), flush=True)
    w.runtime.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
