"""Live edge-cluster serving launcher: hierarchical scheduler over real
per-node engines, end-to-end.

Builds N heterogeneous live nodes (different architecture + private
domain-partitioned corpus each), profiles their measured throughput,
then replays a trace-driven workload through the PPO identifier +
Algorithm-1 inter-node scheduler, printing per-slot measured
latency/quality/drop metrics.

    PYTHONPATH=src python -m repro.launch.cluster_serve --smoke \
        --nodes 2 --slots 3
    ... --no-inter-node          # capacity-unaware routing ablation
    ... --trace uniform          # constant volume instead of diurnal
    ... --standing               # standing engines: frames stay warm
    ... --trace spike --arrival-rate 40   # open-loop saturation replay
    ... --index ivf --nprobe 3   # ANN retrieval instead of the flat scan
    ... --federated --cache      # cross-node retrieval + semantic cache
    ... --ckpt experiments/tiny_lm.npz   # trained generator weights
    ... --metrics-port 0 --dashboard     # /metrics + /health + live rollup
    ... --no-slo-feedback        # monitors report but don't steer routing
"""
import argparse
import json
import os
import time

import jax
import numpy as np

from repro import obs
from repro.cluster import ClusterRuntime, LiveEdgeNode, LiveWorkload, \
    enable_federation, replay_trace
from repro.configs import get_config, get_smoke_config
from repro.core.identifier import OnlineQueryIdentifier
from repro.data.corpus import DOMAINS, generate_corpus
from repro.data.partition import coverage_matrix, partition_edge_data
from repro.data.tokenizer import Tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.retrieval.cache import SemanticQueryCache
from repro.retrieval.encoder import TextEncoder
from repro.train import checkpoint

# heterogeneous architectures, cycled across nodes
NODE_ARCHS = ("olmo-1b", "xlstm-350m", "hymba-1.5b", "qwen2-moe-a2.7b")

# examples/train_tiny.py checkpoint geometry (see its make_dataset/main)
CKPT_D_MODEL = 256


def _load_ckpt_params(ckpt: str, arch: str, vocab: int, max_len: int):
    """Try restoring a ``train_tiny`` checkpoint into this arch; returns
    (cfg, params) or None when the architecture/shape doesn't match."""
    cfg = get_smoke_config(arch, max_d_model=CKPT_D_MODEL, vocab=vocab)
    like = Model(cfg).init_params(jax.random.PRNGKey(0), max_seq=max_len)
    try:
        return cfg, checkpoint.load(ckpt, like)
    except (KeyError, AssertionError, ValueError):
        return None


def node_config(arch: str, *, smoke: bool, vocab: int, d_model: int = 32):
    """A node's model config: the published one (width, depth, vocab,
    bf16; the tokenizer's few hundred ids fall inside its vocab) or, with
    ``smoke``, the reduced CPU-sized variant over the tokenizer's vocab."""
    if smoke:
        return get_smoke_config(arch, max_d_model=d_model, vocab=vocab)
    return get_config(arch)


def param_bytes(cfg, max_len: int) -> int:
    """Bytes of a node's weights, from shapes alone (nothing allocated)."""
    shapes = jax.eval_shape(
        lambda k: Model(cfg).init_params(k, max_seq=max_len),
        jax.random.PRNGKey(0))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def device_bytes_limit():
    """Memory of the device every node shares, where the backend reports
    it (the TPU does; the CPU backend reports nothing)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def check_weights_fit(cfgs, max_len: int) -> None:
    """Refuse, before any weights exist, a cluster whose nodes' weights
    alone exceed the shared device's memory — e.g. qwen2-moe-a2.7b at
    published width (~28 GB of experts and attention in bf16) on one
    16 GB chip — instead of running out of memory halfway through the
    build."""
    limit = device_bytes_limit()
    if limit is None:
        return
    need = [(cfg.name, param_bytes(cfg, max_len)) for cfg in cfgs]
    total = sum(b for _, b in need)
    if total > limit:
        each = ", ".join(f"{name} {b / 1e9:.2f} GB" for name, b in need)
        raise ValueError(
            f"node weights need {total / 1e9:.2f} GB ({each}) but the "
            f"device holds {limit / 1e9:.2f} GB; use fewer nodes or "
            f"--smoke")


def build_cluster(n_nodes: int, *, smoke: bool = True, entities: int = 8,
                  archs=NODE_ARCHS, max_len: int = 192, batch: int = 4,
                  new_tokens: int = 8, top_k: int = 2, d_model: int = 32,
                  seed: int = 0, update_threshold: int = 16,
                  index_kind: str = "flat", nprobe=None,
                  cache: bool = False, federated: bool = False,
                  fanout: int = 2, sketch_centroids: int = 8,
                  ckpt=None, queue: str = "continuous",
                  prefill_chunk: int = 32, paged: bool = False,
                  block_size: int = 16, admission: str = "fifo"):
    """Corpus + tokenizer + N live nodes + PPO identifier.  Returns
    (nodes, workload-ready qas, tokenizer, encoder, identifier,
    coverage matrix).  ``ckpt`` loads ``examples/train_tiny.py``
    weights (and their vocab) into every node whose architecture
    matches the checkpoint; ``federated`` attaches a shared
    ``FederatedRetriever`` to all nodes."""
    docs, qas = generate_corpus(entities, seed=seed)
    if ckpt:
        with open(os.path.splitext(ckpt)[0] + "_vocab.json") as f:
            tok = Tokenizer(json.load(f))
    else:
        tok = Tokenizer.build([d.text for d in docs]
                              + [qa.question for qa in qas]
                              + ["context question answer <sep>"])
    encoder = TextEncoder(seed=seed)
    n_domains = len(DOMAINS)
    primaries = [[d for d in range(n_domains) if d % n_nodes == n]
                 for n in range(n_nodes)]
    node_docs = partition_edge_data(docs, n_nodes, primaries, seed=seed)
    node_archs = [archs[n % len(archs)] for n in range(n_nodes)]
    if not smoke:
        check_weights_fit([get_config(a) for a in node_archs], max_len)
    nodes = []
    for n, arch in enumerate(node_archs):
        loaded = _load_ckpt_params(ckpt, arch, len(tok), max_len) \
            if ckpt else None
        if loaded is not None:
            cfg, params = loaded
            print(f"node {n} [{arch}]: loaded trained weights from {ckpt}",
                  flush=True)
        else:
            if ckpt:
                print(f"node {n} [{arch}]: ckpt arch/shape mismatch — "
                      f"random init", flush=True)
            cfg = node_config(arch, smoke=smoke, vocab=len(tok),
                              d_model=d_model)
            params = Model(cfg).init_params(jax.random.PRNGKey(seed + n),
                                            max_seq=max_len)
        nodes.append(LiveEdgeNode(
            n, arch, cfg, params, node_docs[n], tok, encoder,
            batch_size=batch, max_len=max_len, top_k=top_k,
            max_new_tokens=new_tokens, seed=seed + 10 * n,
            index_kind=index_kind, nprobe=nprobe,
            cache=SemanticQueryCache() if cache else None,
            queue=queue, prefill_chunk=prefill_chunk,
            paged=paged, block_size=block_size, admission=admission))
    if federated:
        enable_federation(nodes, fanout=fanout,
                          n_centroids=sketch_centroids, seed=seed)
    ident = OnlineQueryIdentifier(encoder.dim, n_nodes, seed=seed,
                                  update_threshold=update_threshold)
    cov = coverage_matrix(node_docs, n_domains)
    return nodes, qas, tok, encoder, ident, cov


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--per-slot", type=int, default=48,
                    help="base query volume per slot (trace modulates it)")
    ap.add_argument("--slo", type=float, default=1.5,
                    help="per-slot latency SLO in seconds; the smoke "
                         "default is tight enough that measured "
                         "capacities bind and Algorithm 1 actually "
                         "load-balances")
    ap.add_argument("--trace", default="diurnal",
                    choices=["diurnal", "uniform", "spike", "ramp"])
    ap.add_argument("--arrival-rate", type=float, default=None,
                    metavar="QPS",
                    help="open-loop arrival rate: sets the base per-slot "
                         "volume to QPS * --slot-s (overrides --per-slot)")
    ap.add_argument("--slot-s", type=float, default=1.0,
                    help="nominal slot duration --arrival-rate multiplies")
    ap.add_argument("--require-healthy-exit", action="store_true",
                    help="exit 1 unless every admitted request finished "
                         "and /health recovers to ok after the trace "
                         "(the CI saturation smoke gate)")
    ap.add_argument("--no-inter-node", action="store_true",
                    help="ablation: capacity-unaware identifier sampling")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny models + corpus (CPU CI); without it every "
                         "node runs its published config (bf16)")
    ap.add_argument("--entities", type=int, default=None,
                    help="entities per domain (default 8 smoke / 24 full)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=192)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--index", default="flat", choices=["flat", "ivf"],
                    help="per-node retrieval backend (ivf = ANN probe)")
    ap.add_argument("--nprobe", type=int, default=None,
                    help="IVF lists probed per query (default ~20%%)")
    ap.add_argument("--federated", action="store_true",
                    help="sketch-routed cross-node retrieval")
    ap.add_argument("--fanout", type=int, default=2,
                    help="shards probed per query when --federated")
    ap.add_argument("--cache", action="store_true",
                    help="per-node semantic query cache")
    ap.add_argument("--ckpt", default=None,
                    help="examples/train_tiny.py checkpoint (.npz); "
                         "loads into matching-arch nodes")
    ap.add_argument("--queue", default="continuous",
                    choices=["continuous", "standing", "wave"],
                    help="per-node request scheduler: continuous "
                         "batching fresh per slot, one standing "
                         "queue whose frames stay warm across slots, "
                         "or synchronous waves")
    ap.add_argument("--standing", action="store_true",
                    help="shorthand for --queue standing: one "
                         "long-lived session per node, streamed "
                         "admissions, mid-frame shed")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt chunk size of the continuous prefill "
                         "program")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block-table rows + shared "
                         "retrieved-context prefix forking (continuous "
                         "queue only)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV tokens per pool block (--paged)")
    ap.add_argument("--admission", default="fifo",
                    choices=["fifo", "sjf"],
                    help="continuous-queue admission policy: FIFO-with-"
                         "skip or shortest-prefill-first")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request spans + telemetry and export a "
                         "flight-recorder JSONL dump here at exit "
                         "(read it with tools/trace_report.py)")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="print a metrics-delta rollup every N slots "
                         "(0 = never print)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve /metrics (Prometheus text) and /health "
                         "(SLO verdict JSON) on this port for the whole "
                         "run (0 = pick a free port); the endpoint is "
                         "self-probed before exit")
    ap.add_argument("--dashboard", action="store_true",
                    help="print a live per-node telemetry rollup "
                         "(rates, windowed percentiles, SLO state) "
                         "after every slot")
    ap.add_argument("--no-slo-feedback", action="store_true",
                    help="ablation: keep the SLO monitors (so /health "
                         "still reports) but sever their feedback into "
                         "inter-node routing and admission shedding")
    ap.add_argument("--shed-fraction", type=float, default=0.25,
                    help="fraction of a FIRING node's backlog its queue "
                         "sheds per slot")
    args = ap.parse_args()
    if args.standing:
        args.queue = "standing"
    if args.arrival_rate is not None:
        args.per_slot = max(1, round(args.arrival_rate * args.slot_s))

    enable_compile_cache()
    rec = obs.enable() if args.trace_out else None
    # registry pushes stay on for the whole run: the SLO monitors, the
    # /metrics endpoint, and the dashboard all read from it
    obs.enable_metrics(True)

    t0 = time.perf_counter()
    entities = args.entities or (8 if args.smoke else 24)
    print(f"building {args.nodes} live nodes "
          f"({', '.join(NODE_ARCHS[i % len(NODE_ARCHS)] for i in range(args.nodes))}) "
          f"over {entities * len(DOMAINS)} docs", flush=True)
    nodes, qas, tok, encoder, ident, cov = build_cluster(
        args.nodes, smoke=args.smoke, entities=entities, batch=args.batch,
        max_len=args.max_len, new_tokens=args.new_tokens,
        top_k=args.top_k, seed=args.seed,
        update_threshold=max(4, args.per_slot),
        index_kind=args.index, nprobe=args.nprobe, cache=args.cache,
        federated=args.federated, fanout=args.fanout, ckpt=args.ckpt,
        queue=args.queue, prefill_chunk=args.prefill_chunk,
        paged=args.paged, block_size=args.block_size,
        admission=args.admission)
    print("corpus coverage per node:\n", np.round(cov, 2), flush=True)
    if args.federated:
        fed = nodes[0].federation
        print(f"federation: {len(fed.sketches)} shard sketches published "
              f"({fed.n_centroids} centroids each), fanout {fed.fanout}",
              flush=True)

    runtime = ClusterRuntime(nodes, ident,
                             use_inter_node=not args.no_inter_node,
                             seed=args.seed,
                             slo_feedback=not args.no_slo_feedback,
                             shed_fraction=args.shed_fraction)
    srv = None
    if args.metrics_port is not None:
        srv = obs.TelemetryServer(
            metrics_fn=lambda: obs.to_prometheus(
                obs.registry().snapshot(), obs.registry()),
            health_fn=runtime.health, port=args.metrics_port).start()
        print(f"telemetry: /metrics and /health at {srv.url()}",
              flush=True)
    print("profiling measured node throughput ...", flush=True)
    runtime.initialize()
    for node in nodes:
        print(f"  node {node.node_id} [{node.arch}]: "
              f"{node.capacity.k:.1f} q/s measured -> "
              f"C({args.slo:g}s) = {node.capacity(args.slo):.0f} queries",
              flush=True)

    mode = "identifier-only (no inter-node)" if args.no_inter_node \
        else "PPO + Algorithm-1 inter-node"
    print(f"replaying {args.slots} slots of {args.trace} trace "
          f"(base {args.per_slot}/slot, SLO {args.slo:g}s) under {mode}",
          flush=True)
    workload = LiveWorkload(qas, encoder, seed=args.seed + 2)

    on_slot = None
    if rec is not None or args.metrics_every or args.dashboard:
        reg = obs.registry()
        last_snap = [reg.snapshot()]

        def on_slot(t, m):
            d = reg.delta(last_snap[0])
            last_snap[0] = reg.snapshot()
            if rec is not None:
                rec.record_metrics(last_snap[0], obs.get_tracer().now())
            if args.metrics_every and (t + 1) % args.metrics_every == 0:
                scalars = {k: v for k, v in d.items()
                           if not isinstance(v, dict)}
                line = " ".join(
                    f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in sorted(scalars.items()))
                print(f"  metrics[slot {t}]: {line}", flush=True)
            if args.dashboard and runtime.store is not None:
                print(obs.render_dashboard(runtime.store,
                                           runtime.monitors), flush=True)

    report = replay_trace(runtime, workload, n_slots=args.slots,
                          slo_s=args.slo, base_volume=args.per_slot,
                          trace=args.trace, seed=args.seed + 3,
                          verbose=True, on_slot=on_slot)

    s = report.summary()
    print(f"\nsummary: {s['queries']} queries in {s['slots']} slots | "
          f"quality={s['quality_mean']:.3f} drop={s['drop_rate']:.2f} "
          f"p50={s['latency_p50_s']:.2f}s p95={s['latency_p95_s']:.2f}s "
          f"imbalance={s['load_imbalance']:.2f} "
          f"ppo_updates={s['ppo_updates']}")
    lost = sum(node.unfinished() for node in nodes)
    runtime.close()          # drain + release standing sessions
    for node in nodes:
        st = node.stats
        extra = ""
        if args.cache:
            extra += f", {st.cache_hits} cache hits"
        if args.federated:
            extra += (f", {st.remote_contexts} remote ctx "
                      f"({st.remote_gold} gold)")
        rounds = "waves" if args.queue == "wave" else "frames"
        if args.queue != "wave":
            extra += (f", {st.refills} refills, "
                      f"ttft {st.ttft_mean * 1e3:.0f}ms mean")
        if st.shed:
            extra += f", {st.shed} shed"
        print(f"  node {node.node_id} [{node.arch}]: {st.queries} queries "
              f"in {st.waves} {rounds}, {st.tokens_out} tokens, "
              f"{st.drops} drops, {st.queries_per_s:.1f} q/s measured"
              + extra)
    if args.queue == "standing":
        print(f"standing: {lost} request(s) unfinished at exit")
    if runtime.monitors:
        h = runtime.health()
        print(f"slo: status={h['status']} "
              f"feedback={'on' if runtime.slo_feedback else 'OFF'} "
              f"firing_nodes={h['firing_nodes'] or '[]'}")
        for nid in sorted(runtime.monitors, key=str):
            mon = runtime.monitors[nid]
            trans = sum(s.transitions for s in mon.states.values())
            firing = mon.firing()
            state = "FIRING:" + ",".join(firing) if firing else "OK"
            print(f"  node {nid}: {state} ({trans} objective "
                  f"transition{'s' if trans != 1 else ''})")
    if args.federated:
        fs = nodes[0].federation.stats
        print(f"federation: {fs.shard_probes} shard probes "
              f"({fs.remote_probes} remote) for {fs.queries} queries, "
              f"{fs.remote_contexts} remote contexts merged")
    if rec is not None:
        rec.record_metrics(obs.registry().snapshot(),
                           obs.get_tracer().now())
        obs.disable()
        rec.export_jsonl(args.trace_out)
        print(f"trace: {rec.span_count()} spans "
              f"({len(rec)} events, {rec.dropped} dropped) "
              f"-> {args.trace_out}")
    healthy = True
    if args.require_healthy_exit:
        healthy = _await_recovery(runtime)
        print(f"health at exit: "
              f"{'ok' if healthy else runtime.health()['status']}")
    if srv is not None:
        _probe_endpoint(srv)
        srv.stop()
    print(f"total {time.perf_counter() - t0:.0f}s")
    if args.require_healthy_exit and (lost or not healthy):
        raise SystemExit(f"unhealthy exit: {lost} unfinished request(s), "
                         f"health_ok={healthy}")


def _await_recovery(runtime, timeout_s: float = 20.0) -> bool:
    """Give the SLO monitors time to clear after the trace's spike: bad
    samples age out of the burn-rate windows, burn drops below the
    clear threshold, hysteresis releases.  True once /health says ok."""
    t0 = time.perf_counter()
    while True:
        if runtime.store is not None:
            runtime.store.sample()
        for mon in runtime.monitors.values():
            mon.evaluate()
        if runtime.health()["status"] == "ok":
            return True
        if time.perf_counter() - t0 >= timeout_s:
            return False
        time.sleep(0.5)


def _probe_endpoint(srv) -> None:
    """Self-probe the telemetry endpoint before exit so CI (and any
    scripted run) asserts well-formed exposition without a second
    process: fetch /metrics and round-trip it through the parser, fetch
    /health and check the verdict JSON."""
    import urllib.error
    import urllib.request
    try:
        body = urllib.request.urlopen(srv.url("/metrics"),
                                      timeout=10).read().decode()
        samples = obs.parse_prometheus(body)
        if not samples:
            raise ValueError("empty /metrics exposition")
        try:
            resp = urllib.request.urlopen(srv.url("/health"), timeout=10)
            code, hbody = resp.status, resp.read().decode()
        except urllib.error.HTTPError as e:    # 503 while degraded
            code, hbody = e.code, e.read().decode()
        health = json.loads(hbody)
        if health.get("status") not in ("ok", "degraded", "firing"):
            raise ValueError(f"unexpected /health status: {health!r}")
    except Exception as e:
        print(f"metrics probe: FAILED ({e})")
        raise SystemExit(1)
    print(f"metrics probe: OK ({len(samples)} samples, "
          f"/health {code} status={health['status']})")


if __name__ == "__main__":
    main()
