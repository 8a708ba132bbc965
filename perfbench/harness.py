"""One run of one cell: build the live RAG cluster from the cell's files,
warm every shape its traffic uses, drive the open-loop window through
``ClusterRuntime.run_slot``, and check what the window served against
the plain reference.

The program is used as a deployment would use it: ``LiveEdgeNode``s with
standing, paged ``ContinuousQueue``s (SJF admission, flat retrieval, no
federation or semantic cache) under ``ClusterRuntime``.  The harness
passes only what the deployment and the traffic fix (model, ``max_len``,
answer budget, ``top_k``); batch, prefill chunk and block size stay the
program's defaults.  It reads per-request times around the program's
calls, by wrapping the node's queue and session instances, and changes
no program file.
"""
from __future__ import annotations

import gc
import logging
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from perfbench import check, spec, timeline, traffic

PROGRAM_COUNTERS = ("prefix_hits", "prefix_misses")


def derived_seed(seed: int, *tags: int) -> int:
    """A 31-bit seed for code that takes a signed 32-bit one."""
    return int(np.random.SeedSequence([seed, *tags]).generate_state(1)[0]
               & 0x7FFFFFFF)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class World:
    cell: spec.Cell
    seed: int
    corpus: traffic.Corpus
    tok: object
    encoder: object
    nodes: list
    runtime: object
    max_len: int
    schedule: List[traffic.Request]
    queries: list
    warm_queries: list


# ------------------------------------------------------------------ build


def build(cell: spec.Cell, seed: int, seconds: float) -> World:
    import jax
    from repro.cluster import ClusterRuntime, LiveEdgeNode
    from repro.core.cluster import Query
    from repro.data.corpus import Document
    from repro.data.tokenizer import Tokenizer
    from repro.models import Model
    from repro.retrieval.encoder import TextEncoder

    from perfbench import weights

    tr = cell.traffic
    nodes_spec = cell.config["nodes"]
    corpus = traffic.make_corpus(tr)
    shards = traffic.partition(corpus, len(nodes_spec),
                               int(tr["base_seed"]))
    cw = int(tr["corpus"]["chunk_words"])
    chunks = [[Document(i, d.domain, c, corpus.entities[d.entity])
               for d in shard for i, c in enumerate(
                   traffic.chunk_words(d.text, cw))] for shard in shards]
    rate = traffic.mean_rate(tr, cell.config)
    sched = traffic.schedule(tr, corpus, rate, seconds, seed)
    # The run's seed draws the weights and the order of each period's
    # questions, and nothing else that sets the work: the warm-up
    # questions (whose feedback the router's first update reads) and the
    # router's sampling stream (which node serves each due time) come from
    # the traffic file's base seed.  Drawn from the run's seed, the node
    # split moved with it, and runs of one seed agreed within 1 % on the
    # mean latency where seeds differed by up to 43 %.
    base = int(tr["base_seed"])
    wrng = np.random.default_rng([base, 9])
    warm_ents = wrng.choice(len(corpus.entities),
                            size=int(tr["warmup"]["pool"]), replace=False)
    warm = [traffic._question(corpus, int(e), wrng) + (int(e),)
            for e in warm_ents]
    fact_text = [f"what is the {f.attr} of {corpus.entities[e]} ? "
                 f"the {f.attr} of {corpus.entities[e]} is {f.value} ."
                 for e in range(len(corpus.entities))
                 for f in corpus.facts[e]]
    tok = Tokenizer.build([d.text for d in corpus.docs] + fact_text
                          + ["context question answer <sep>"])
    encoder = TextEncoder(seed=derived_seed(int(tr["base_seed"]), 5))
    max_len = int(tr["top_k"]) * cw + int(tr["prompt_overhead"]) \
        + int(tr["answer_tokens"])
    nodes = []
    for n, ns in enumerate(nodes_spec):
        cfg = spec.model_config(ns)
        shapes = jax.eval_shape(lambda k: Model(cfg).init_params(
            k, max_seq=max_len), jax.random.PRNGKey(0))
        params = weights.make_params(cfg, derived_seed(seed, 100 + n))
        _same_layout(shapes, params, cfg.name)
        nodes.append(LiveEdgeNode(
            n, ns["arch"], cfg, params, chunks[n], tok, encoder,
            max_len=max_len, top_k=int(tr["top_k"]),
            max_new_tokens=int(tr["answer_tokens"]),
            seed=derived_seed(seed, 200 + n), index_kind="flat",
            queue="standing", paged=True, admission="sjf"))
    runtime = ClusterRuntime(nodes, new_identifier(cell, len(nodes),
                                                   encoder.dim),
                             seed=derived_seed(base, 7),
                             slo_feedback=False)
    embs = encoder.encode([r.question for r in sched]) if sched else []
    queries = [Query(r.domain, embs[i], qid=r.idx, question=r.question,
                     reference=r.reference) for i, r in enumerate(sched)]
    wembs = encoder.encode([q for q, _, _ in warm])
    warm_q = [Query(int(corpus.entity_domain[e]), wembs[i],
                    qid=10_000_000 + i, question=q, reference=ref)
              for i, (q, ref, e) in enumerate(warm)]
    return World(cell, seed, corpus, tok, encoder, nodes, runtime, max_len,
                 sched, queries, warm_q)


def new_identifier(cell: spec.Cell, n_nodes: int, dim: int):
    """The router in its initial state.  Its policy is part of the
    deployment, the same for every seed: a seed that reshuffled it would
    change the node split, and so the work, not just its order.  Its
    update threshold is the program's default unless the traffic file
    states one."""
    from repro.core.identifier import OnlineQueryIdentifier
    tr = cell.traffic
    kw = {}
    if "ppo_update_threshold" in tr:
        kw["update_threshold"] = int(tr["ppo_update_threshold"])
    return OnlineQueryIdentifier(
        dim, n_nodes, seed=derived_seed(int(tr["base_seed"]), 6), **kw)


def cache_dir(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` inside the checkout."""
    import os
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")


def _same_layout(shapes, params, name: str) -> None:
    import jax
    a = jax.tree.structure(shapes)
    b = jax.tree.structure(params)
    if a != b:
        raise SystemExit(f"{name}: the benchmark's weight layout differs "
                         f"from the program's ({a} vs {b})")
    for x, y in zip(jax.tree.leaves(shapes), jax.tree.leaves(params)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise SystemExit(f"{name}: leaf {x.shape}/{x.dtype} in the "
                             f"program, {y.shape}/{y.dtype} here")


# ---------------------------------------------------------------- warm-up


def _families(node, w: World) -> dict:
    """The compiled-program families the window's requests land in on
    ``node`` (every request is counted on every node: routing decides
    later): padded prefix length (prefix-prefill program), question
    suffix chunks (fork-refill program), decode block-table width
    (decode-segment program)."""
    from repro.rag.pipeline import split_prompt
    eng = node.engine
    C = eng.prefill_chunk
    budget = node.gen.max_new_tokens
    cap = eng.cont_max_prompt_len(budget)
    l0s, kqs, nbs = set(), set(), set()
    for start in range(0, len(w.queries), 64):
        qs = w.queries[start:start + 64]
        ctxs, _ = node._retrieve(qs)
        for q, c in zip(qs, ctxs):
            toks, plen = split_prompt(q.question, c, w.tok, cap=cap)
            p = max(1, min(plen, len(toks) - 1))
            l0 = p + (-p) % C
            suffix = len(toks) - p
            l0s.add(l0)
            kqs.add(-(-suffix // C))
            nbs.add(eng._cont_nb_cap(l0 + suffix + budget + 2))
    return {"l0": l0s, "kq": kqs, "nb": nbs}


def warm_classes(w: World, seed: int = 0) -> int:
    """Serve, outside the window, synthetic requests through each node's
    standing queue that cover every family of ``_families`` over its
    whole contiguous range (a near-tie in retrieval can move a request
    to a neighbouring family).  Returns the number of requests served."""
    served = 0
    rng = np.random.default_rng([w.seed, seed, 8])
    vocab = len(w.tok)
    for node in w.nodes:
        eng = node.engine
        C = eng.prefill_chunk
        budget = node.gen.max_new_tokens
        fam = _families(node, w)
        # one family of margin each way: a near-tie in retrieval may
        # swap a chunk for a shorter one in the window
        l0s = range(max(C, min(fam["l0"]) - C), max(fam["l0"]) + C + 1, C)
        nbs = {min(eng.nb_total, x) for x in range(
            max(4, min(fam["nb"]) - 4), max(fam["nb"]) + 5, 4)} \
            | set(fam["nb"])
        plan, seen_nb = [], set()
        for l0 in l0s:
            for kq in sorted(fam["kq"]):
                for q in ((kq - 1) * C + 1, kq * C):
                    if l0 + q + budget > eng.max_len:
                        continue
                    nb = eng._cont_nb_cap(l0 + q + budget + 2)
                    if q == (kq - 1) * C + 1 or nb not in seen_nb:
                        plan.append((l0, q))
                        seen_nb.add(nb)
        for nb in sorted(nbs - seen_nb):
            for l0 in l0s:
                hit = [q for kq in fam["kq"] for q in
                       range((kq - 1) * C + 1, kq * C + 1)
                       if l0 + q + budget <= eng.max_len and
                       eng._cont_nb_cap(l0 + q + budget + 2) == nb]
                if hit:
                    plan.append((l0, hit[0]))
                    break
        queue = node._ensure_standing_queue()
        for l0, q in plan:
            toks = rng.integers(5, vocab, size=l0 + q).tolist()
            rid = queue.submit(toks, prefix_len=l0)
            queue.run(wait_for=[rid])
            queue.pop_result(rid)
            served += 1
    return served


def warm_up(w: World) -> dict:
    """Warm every shape family of the window outside it: the program's
    capacity profiling, the engine programs (``warm_classes``), the
    batch-size families of retrieval and routing up to the front door's
    largest slot, the PPO update at every buffered count that can
    trigger it, then whole slots through the runtime.  Those slots'
    feedback stays in the router's buffer, so the window's own feedback
    crosses the update threshold inside it (the traffic file sizes them
    for its rate and the window)."""
    import jax
    from repro.core import ppo

    tr = w.cell.traffic
    slo = float(tr["limits"]["latency_s"])
    n_max = int(tr["warmup"]["max_slot"])
    t = {"t": time.perf_counter()}

    def lap(name):
        now = time.perf_counter()
        t[name] = round(now - t["t"], 3)
        t["t"] = now

    w.runtime.initialize()          # the program's capacity profiling
    lap("profile_s")
    classes_served = warm_classes(w)
    lap("classes_s")
    for node in w.nodes:
        for n in range(1, n_max + 1):
            node.index.search(np.zeros((n, w.encoder.dim), np.float32),
                              node.top_k)
    lap("search_s")
    ident = w.runtime.identifier
    for n in range(1, n_max + 1):
        ident.identify(np.zeros((n, w.encoder.dim), np.float32))
    lap("identify_s")
    # the PPO update program at every buffered count that can trigger
    # it: the buffer crosses the threshold by one slot of at most n_max
    thr = ident.update_threshold
    for b in range(thr, thr + n_max):
        out = ppo.ppo_update(
            ident.params, ident.old_params, ident.opt_state,
            np.zeros((b, w.encoder.dim), np.float32),
            np.zeros((b,), np.int32), np.zeros((b,), np.float32),
            eps=ident.clip_eps, beta=ident.entropy_beta, lr=ident.lr)
        jax.block_until_ready(out)
    lap("ppo_s")
    # whole slots: identify -> route -> dispatch -> feedback
    sizes = [int(s) for s in tr["warmup"]["slots"]]
    pool = list(w.warm_queries)
    i = 0
    for s in sizes:
        w.runtime.run_slot(pool[i:i + s], slo)
        i += s
    lap("slots_s")
    del t["t"]
    return {"requests": classes_served, "ppo_updates":
            int(ident.updates_done), "ppo_buffered": ident.buffered(), **t}


# ---------------------------------------------------------- instruments


class Probe:
    """Caller-side records keyed by request, read around program calls."""

    def __init__(self, w: World, t0: float, traced: bool):
        self.w = w
        self.t0 = t0
        self.traced = traced
        self.recs: Dict[int, timeline.Record] = {}
        self.sessions: Dict[int, object] = {}
        self.seg_time = {n: 0.0 for n in range(len(w.nodes))}
        self.seg_steps = {n: 0 for n in range(len(w.nodes))}
        self.prefix_prefills: List[tuple] = []   # (node, prefix tokens)
        self.compiles = 0
        self._patched: List[tuple] = []
        self.active = True
        self._install()

    def _patch(self, obj, name: str, fn) -> None:
        setattr(obj, name, fn)
        self._patched.append((obj, name))

    def uninstall(self) -> None:
        """Put the program's own methods back (they were shadowed on the
        instances only)."""
        for obj, name in self._patched:
            delattr(obj, name)
        self._patched.clear()
        self.active = False

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _ann(self, name: str):
        import contextlib
        import jax
        if not self.traced:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def _install(self) -> None:
        import jax.monitoring as mon

        def on_event(name, **kw):
            if self.active and name == "/jax/compilation_cache/cache_hits":
                self.compiles += 1

        def on_dur(name, secs, **kw):
            if self.active and \
                    name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_dur)
        for n, node in enumerate(self.w.nodes):
            self._wrap_node(n, node)
        rt = self.w.runtime
        ident_fn, route_fn, fb_fn = (rt.identifier.identify, rt._route,
                                     rt._feedback)

        def identify(*a, **k):
            with self._ann("bench.identify"):
                return ident_fn(*a, **k)

        def route(*a, **k):
            with self._ann("bench.route"):
                return route_fn(*a, **k)

        def feedback(*a, **k):
            with self._ann("bench.feedback"):
                return fb_fn(*a, **k)

        self._patch(rt.identifier, "identify", identify)
        self._patch(rt, "_route", route)
        self._patch(rt, "_feedback", feedback)

    def _wrap_node(self, n: int, node) -> None:
        """process_slot submits its queries to the standing queue in
        order, runs it, then pops each result: so the i-th submit of a
        call is the call's i-th query, and the popped completion carries
        the first-token and last-token offsets from that submit."""
        queue = node._ensure_standing_queue()
        session = queue._ensure_session()
        call: List[int] = []                 # qids of the running call
        by_rid: Dict[int, tuple] = {}        # rid -> (qid, submit time)
        proc, retr = node.process_slot, node._retrieve
        submit, pop, qrun = queue.submit, queue.pop_result, queue.run
        seg, pre = session.run_segment, session._prefill_prefix

        def process_slot(queries, slo_s, scheduler=None):
            t = self.now()
            call[:] = [q.qid for q in queries]
            for q in queries:
                r = self.recs.get(q.qid)
                if r is not None:
                    r.node, r.node_start = n, t
            with self._ann(f"bench.node{n}"):
                out = proc(queries, slo_s, scheduler)
            for q in queries:
                r = self.recs.get(q.qid)
                if r is not None:
                    r.contexts = list(node.last_contexts.get(q.qid, []))
            return out

        def retrieve(queries):
            with self._ann(f"bench.node{n}.retrieve"):
                return retr(queries)

        def q_submit(prompt, max_new_tokens=None, prefix_len=None,
                     trace=None):
            t = self.now()
            rid = submit(prompt, max_new_tokens, prefix_len, trace)
            qid = call.pop(0) if call else None
            by_rid[rid] = (qid, t)
            r = self.recs.get(qid)
            if r is not None:
                r.prompt = list(prompt)
                r.prefix_len = int(prefix_len or 0)
            return rid

        def q_pop(rid):
            c = pop(rid)
            qid, t_sub = by_rid.pop(rid, (None, 0.0))
            r = self.recs.get(qid)
            if r is not None:
                r.first = t_sub + c.ttft_s
                r.done = t_sub + c.done_s
                r.tokens = list(c.tokens)
                r.shed = bool(c.shed)
            return c

        def q_run(*a, **k):
            with self._ann(f"bench.node{n}.engine"):
                return qrun(*a, **k)

        def run_segment(*a, **k):
            t, s0 = time.perf_counter(), session.tstep
            out = seg(*a, **k)
            self.seg_time[n] += time.perf_counter() - t
            self.seg_steps[n] += max(0, session.tstep - s0)
            return out

        def prefill_prefix(prefix):
            self.prefix_prefills.append((n, len(prefix)))
            return pre(prefix)

        for obj, name, fn in ((node, "process_slot", process_slot),
                              (node, "_retrieve", retrieve),
                              (queue, "submit", q_submit),
                              (queue, "pop_result", q_pop),
                              (queue, "run", q_run),
                              (session, "run_segment", run_segment),
                              (session, "_prefill_prefix", prefill_prefix)):
            self._patch(obj, name, fn)
        self.sessions[n] = session

    def same_sessions(self) -> bool:
        """The wrapped sessions are the ones that served the window."""
        return all(node._ensure_standing_queue()._session is
                   self.sessions[n] for n, node in enumerate(self.w.nodes))

    def counters(self) -> Dict[str, int]:
        out = {k: 0 for k in PROGRAM_COUNTERS}
        for node in self.w.nodes:
            st = node._ensure_standing_queue().stats
            for k in PROGRAM_COUNTERS:
                out[k] += int(getattr(st, k))
        return out


# The traced run profiles the end of its window only.  The profiler's
# stop grows with the device ops it kept: a whole 51 s window fills its
# buffer (about 6.2 million ops, the first 20-24 s of a busy v5e), and
# its stop took 185 s written to a file and 88 s handed over in memory
# (at host level 1, without HLO protos), while the reduction reads half
# of those ops.  The last TRACED_S seconds hold about what
# ``devtrace.MAX_DEVICE_OPS`` reads (3.0 million in 12.0 s).
TRACED_S = 12.0
# the front loop's own annotations, entered between two slots: no request
# is in flight there, since ``run_slot`` returns once all its requests
# are answered
BETWEEN_SLOTS = ("bench.front_wait", "bench.run_slot")


class TracedProbe(Probe):
    """The probe of a traced run.  At the first point between two slots
    that lies at most ``TRACED_S`` before the window closes (at once in
    a shorter window) it starts the profiler and enters ``bench.traced``,
    which stays open until the window has closed: the covered part.  It
    starts nowhere else, so every request of the covered part, and every
    ``obs`` annotation its spans enter, is whole in the trace."""

    def __init__(self, w: World, t0: float, seconds: float):
        super().__init__(w, t0, True)
        self.trace_from = max(0.0, seconds - TRACED_S)
        self.session = None
        self.covered = None
        self.traced_at: Optional[float] = None  # harness clock, at start
        self.start_s: Optional[float] = None    # the front loop's stall
        self.stop_s: Optional[float] = None

    def _ann(self, name: str):
        if self.session is None and name in BETWEEN_SLOTS \
                and self.now() >= self.trace_from:
            self._start()
        return super()._ann(name)

    def _start(self) -> None:
        import jax
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        t = time.perf_counter()
        self.session = _profiler.ProfilerSession(opts)
        self.start_s = time.perf_counter() - t
        self.covered = jax.profiler.TraceAnnotation("bench.traced")
        self.covered.__enter__()
        self.traced_at = self.now()
        log(f"trace: the profiler started {self.traced_at:.3f} s into the "
            f"window, in {self.start_s:.4f} s")

    def stop(self):
        """Close the covered part and stop the profiler: its profile, or
        None where it never started."""
        if self.session is None:
            log("trace: the profiler never started")
            return None
        self.covered.__exit__(None, None, None)
        t = time.perf_counter()
        profile = self.session.stop_and_get_profile_data()
        self.stop_s = time.perf_counter() - t
        log(f"trace: stop_trace took {self.stop_s:.1f} s")
        return profile


# ----------------------------------------------------------------- window


def run_window(w: World, probe: Probe, seconds: float) -> dict:
    """Open loop: the requests due by now go into one run_slot call, at
    most the front door's ``max_slot`` of them (the rest go into the
    next call, so no slot has a size that set-up did not warm); the
    caller's clock of each request starts at its due time."""
    sched = w.schedule
    slo = float(w.cell.traffic["limits"]["latency_s"])
    n_max = int(w.cell.traffic["warmup"]["max_slot"])
    i = 0
    lateness = []
    slots = 0
    biggest = 0
    while True:
        now = probe.now()
        if now >= seconds:
            break
        if i < len(sched) and sched[i].due_s <= now:
            j = i
            while j < len(sched) and sched[j].due_s <= now \
                    and j - i < n_max:
                j += 1
            batch = []
            for k in range(i, j):
                r = timeline.Record(sched[k].idx, sched[k].due_s)
                r.dispatch = now
                probe.recs[r.idx] = r
                batch.append(w.queries[k])
            lateness.append(now - sched[i].due_s)
            biggest = max(biggest, j - i)
            with probe._ann("bench.run_slot"):
                w.runtime.run_slot(batch, slo)
            t_ret = probe.now()
            for q in batch:
                probe.recs[q.qid].ret = t_ret
                probe.recs[q.qid].answers += 1
            slots += 1
            i = j
            continue
        nxt = sched[i].due_s if i < len(sched) else seconds
        with probe._ann("bench.front_wait"):
            time.sleep(max(0.0, min(nxt, seconds) - probe.now()))
    span = probe.now()
    backlog = sum(1 for r in sched[i:] if r.due_s < span)
    return {"slots": slots, "span_s": span, "backlog": backlog,
            "dispatched": i, "lateness_p50_s": timeline.pct(lateness, 50),
            "lateness_max_s": max(lateness) if lateness else 0.0,
            "largest_slot": biggest}


# -------------------------------------------------------------------- run


def device_record(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        fault: Optional[str] = None, control: Optional[str] = None) -> dict:
    """One run of ``workload``; returns the result object.  ``fault``
    breaks the timed path underneath, and ``control`` also reads the
    lower-precision control's gaps (the control script and the tests;
    the benchmark's own runs use neither)."""
    import jax
    cell = spec.load_cell(root, workload)
    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise SystemExit(f"JAX backend is {backend!r}, not 'tpu': the "
                         f"benchmark measures the chip only")
    if len(jax.devices()) < cell.chips:
        raise SystemExit(f"cell {workload} needs {cell.chips} chips, JAX "
                         f"sees {len(jax.devices())}")
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", cache_dir(root))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t_b = time.perf_counter()
    w = build(cell, seed, seconds)
    t_w = time.perf_counter()
    warm = warm_up(w)
    warm["build_s"] = round(t_w - t_b, 3)
    warm["warm_s"] = round(time.perf_counter() - t_w, 3)
    if fault is not None:
        check.plant_fault(w, fault)
    rec = None
    if trace:
        from repro import obs
        from repro.obs.recorder import FlightRecorder
        rec = obs.enable(FlightRecorder(1 << 20))
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    probe = TracedProbe(w, t0, seconds) if trace else Probe(w, t0, trace)
    base = probe.counters()
    probe.compiles = 0
    updates0 = w.runtime.identifier.updates_done
    # name any program compiled inside the window on standard error
    quiet = logging.getLogger("jax._src.dispatch")
    level = quiet.level
    quiet.setLevel(logging.ERROR)
    jax.config.update("jax_log_compiles", True)
    try:
        with probe._ann("bench.window"):
            win = run_window(w, probe, seconds)
    finally:
        jax.config.update("jax_log_compiles", False)
        quiet.setLevel(level)
    compiles = probe.compiles
    updates = w.runtime.identifier.updates_done - updates0
    delta = {k: v - base[k] for k, v in probe.counters().items()}
    spans = []
    if trace:
        from repro import obs
        profile = probe.stop()
        obs.disable()
        spans = [e for e in rec.events() if e.get("kind") == "span"]
    recs = [probe.recs[k] for k in sorted(probe.recs)]
    device = device_record(cell.chips)
    log(f"set-up {setup_s:.3f} s; warm-up {warm}")
    lim = cell.traffic["limits"]
    log(f"window: {win['dispatched']} dispatched in {win['slots']} slots "
        f"over {win['span_s']:.3f} s; backlog {win['backlog']} (due, not "
        f"dispatched); generator late p50 {win['lateness_p50_s']} s, max "
        f"{win['lateness_max_s']:.4f} s; largest slot "
        f"{win['largest_slot']}; PPO updates {updates}; attainment "
        f"{timeline.attainment(recs, lim['latency_s'], lim['ttft_s'])}; "
        f"latency mean {timeline.latency_mean_ms(recs)} ms, p50 "
        f"{timeline.tails(recs, 50.0)['latency']} ms; "
        f"compiles in window {compiles}")
    if compiles:
        log(f"WARNING: {compiles} programs compiled or loaded inside the "
            f"window: set-up missed a shape the window used (their names "
            f"are logged above)")
    breakdown = None
    per_layer: Dict[str, float] = {}
    if trace:
        info = dict(win=win, delta=delta, spans=spans, probe=probe,
                    recs=recs, cell=cell, model_dicts=[n["model"] for n in cell.config["nodes"]],
                    device=device, same_sessions=probe.same_sessions(),
                    traced_at_s=probe.traced_at)
        per_layer, dev_extra, breakdown = read_trace(info, profile)
        del profile
        device.update(dev_extra)
    # the program's state goes before the reference runs
    sample = check.collect(w, recs)
    nodes_meta = [(n.node_id, n.arch) for n in w.nodes]
    probe.uninstall()
    probe.sessions.clear()
    w.runtime.close()
    w.nodes.clear()
    w.runtime = None
    gc.collect()
    t_check = time.perf_counter()
    verdict = check.verify(cell, seed, sample, recs, nodes_meta, w,
                           control=control)
    log(f"check: {time.perf_counter() - t_check:.1f} s for "
        f"{sum(len(v) for v in sample.values())} sampled requests")
    if trace:
        t_end = time.perf_counter()
        log(f"trace: phases (s): set-up {setup_s:.1f}, window "
            f"{win['span_s']:.1f}, start stall {probe.start_s or 0.0:.4f}, "
            f"stop {probe.stop_s or 0.0:.1f}, read {info['read_s']:.1f}, "
            f"reduce {info['reduce_s']:.1f}, check {t_end - t_check:.1f}; "
            f"whole run {t_end - t_start:.1f} of the 360 allowed")
    check.print_checked(verdict["checked"])
    out = assemble(cell, recs, win, setup_s, device, verdict, per_layer,
                   trace, breakdown)
    if control is not None:
        out["control"] = verdict["control"]
        out["checked"] = out.pop("checked")     # the compared numbers last
    return out


def read_trace(info: dict, profile):
    """Per-layer metrics, the device's busy and window seconds, and the
    breakdown, from the profile of the covered part and the run's own
    records."""
    # millions of device events become Python objects: the cyclic
    # collector, scanning the whole cluster's objects again and again
    # while they are made, would take most of the reading time
    gc.disable()
    try:
        return _read_trace(info, profile)
    finally:
        gc.enable()


# a start of the profiler that held the front loop longer than this is
# named among the idle gaps (it lies before the covered part)
START_STALL_S = 0.1


def _read_trace(info: dict, profile):
    from perfbench import devtrace
    cell = info["cell"]
    t = time.perf_counter()
    try:
        events = devtrace.read(profile.planes) if profile is not None \
            else []
    except Exception as e:          # an unreadable trace reads as nothing
        log(f"trace: could not read ({e!r})")
        events = []
    t_load = time.perf_counter() - t
    win = devtrace.annotation_window(events, "bench.traced")
    info["events"] = events
    info["trace_window"] = win
    dev_extra, breakdown = {}, None
    if win is not None:
        # device numbers over the part of the window the trace recorded
        cut = devtrace.device_cut(events, *win)
        dwin = win if cut is None else (win[0], cut)
        n_ops = sum(1 for e in events if devtrace.is_device_op(e)
                    and win[0] <= e.start_ns < win[1])
        msg = f"trace: {n_ops} device op events in the " \
            f"{(win[1] - win[0]) / 1e9:.3f} s covered part of the window"
        if cut is not None:
            msg += f"; the device trace stops {(cut - win[0]) / 1e9:.3f} " \
                "s in while the engines still ran: device numbers cover " \
                "that part"
        log(msg)
        info["device_window"] = dwin
        since = info["traced_at_s"]
        until = since + (dwin[1] - win[0]) / 1e9
        nodes = [r.node for r in info["recs"]
                 if r.tokens and since <= r.done <= until]
        by_node = [nodes.count(n) for n in range(len(info["model_dicts"]))]
        log(f"trace: {len(nodes)} requests answered in the device window, "
            f"by node {by_node}")
        busy, merged = devtrace.device_busy(events, *dwin)
        info["busy_s"] = busy
        dev_extra = {"busy_s": busy, "window_s": (dwin[1] - dwin[0]) / 1e9}
        first = sorted(merged)[0] if merged else None
        gaps = devtrace.idle_gaps(events, merged.get(first, []), *dwin,
                                  where=True) if first else []
        log("trace: longest idle gaps (annotation, s, starting s in) "
            f"{[[g[0], round(g[1], 4), round(g[2], 3)] for g in gaps[:4]]}")
        gaps = [g[:2] for g in gaps]
        stall = info["probe"].start_s
        if stall > START_STALL_S:
            gaps = sorted(gaps + [["start_trace", stall]],
                          key=lambda g: -g[1])[:10]
        breakdown = {"device_ops": devtrace.top_ops(events, *dwin),
                     "idle_gaps": gaps}
    out = {}
    for name, reader in cell.readers.items():
        try:
            v = reader(info)
        except Exception as e:      # a reader that fails reads as nothing
            log(f"metric {name}: reader failed ({e!r})")
            v = None
        if v is not None and math.isfinite(v):
            out[name] = float(v)
    info["read_s"] = t_load
    info["reduce_s"] = time.perf_counter() - t - t_load
    return out, dev_extra, breakdown


def assemble(cell, recs, win, setup_s, device, verdict, per_layer, trace,
             breakdown) -> dict:
    failed = sum(1 for r in recs if not r.ok)
    e2e = {"output_tok_s": timeline.output_tok_s(recs, win["span_s"]),
           "latency_mean_ms": timeline.latency_mean_ms(recs),
           "setup_s": setup_s}
    metrics = {}
    if trace:
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        for name, v in per_layer.items():
            metrics[name] = {"value": v, "unit": units[name]}
    else:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(verdict["correct"]), "attempted": len(recs),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = verdict["checked"]
    return out
