"""Request-level serving schedulers over a static-shape ServeEngine.

Two policies, one submit/run/result contract:

``RequestQueue`` — synchronous waves.  Requests are grouped by prompt
bucket (``engine.prompt_bucket``); each ``step()`` runs one *wave* of up
to ``batch_size`` requests through one compiled generate call, and
freed slots are reused by the next wave.  A wave runs to its slowest
row, so short requests queue behind stragglers — kept as the simple,
fully-compiled fallback path.

``ContinuousQueue`` — continuous batching (chunked prefill + per-slot
refill, ``engine.prefill_chunk`` set).  The moment a row finishes, the
next pending request is chunk-prefilled and swapped into the freed slot
(``ContinuousSession``); per-request ``max_new_tokens`` budgets are
honored exactly, and per-request latency / time-to-first-token land in
``ContinuousStats``.  See docs/ARCHITECTURE.md ("Continuous batching").

    queue = RequestQueue(engine, GenerationParams(max_new_tokens=24))
    rids = queue.submit_all(token_prompts)
    outs = queue.run()                    # {rid: [token, ...]}

With ``standing=True`` the ``ContinuousQueue`` keeps ONE long-lived
session across ``run()`` calls: frames stay warm between scheduler
slots, ``run(wait_for=...)`` returns as soon as the named requests
finish (other rows keep decoding next call), and all stats counters
are monotone — callers take ``stats.snapshot()`` / ``stats.delta()``
for per-interval numbers.  ``close()`` drains and releases the frame.
"""
from __future__ import annotations

import time
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence

import jax
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import percentile
from repro.serving.engine import ContinuousSession, ServeEngine
from repro.serving.sampling import GenerationParams


@dataclass
class Request:
    rid: int
    prompt: List[int]


@dataclass
class Completion:
    rid: int
    tokens: List[int]
    prompt_len: int
    bucket: int
    wave: int


@dataclass
class QueueStats:
    waves: int = 0
    requests: int = 0
    tokens_out: int = 0
    slots_run: int = 0        # batch slots dispatched (incl. idle padding)
    slots_used: int = 0       # slots that held a real request
    latency_s: List[float] = field(default_factory=list)  # per request
    # (a wave's requests all finish together, so each request's latency
    # is its wave's wall time)

    @property
    def slot_utilization(self) -> float:
        return self.slots_used / self.slots_run if self.slots_run else 0.0

    @property
    def latency_mean(self) -> float:
        return float(np.mean(self.latency_s)) if self.latency_s else 0.0

    @property
    def latency_p50(self) -> float:
        return percentile(self.latency_s, 50)

    @property
    def latency_p95(self) -> float:
        return percentile(self.latency_s, 95)

    @property
    def latency_p99(self) -> float:
        return percentile(self.latency_s, 99)


class RequestQueue:
    """Packs submitted requests into engine waves; preserves completion
    identity via request ids (results come back in submission order
    regardless of how waves were packed)."""

    def __init__(self, engine: ServeEngine,
                 gen: Optional[GenerationParams] = None, *, key=None):
        self.engine = engine
        self.gen = gen or GenerationParams()
        if self.gen.max_new_tokens >= engine.max_len:
            # reject the impossible (engine, gen) pair up front instead
            # of accepting (and clipping) requests that can never run
            raise ValueError(
                f"max_new_tokens={self.gen.max_new_tokens} does not fit "
                f"the engine cache (max_len={engine.max_len})")
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._pending: List[Request] = []
        self._done: Dict[int, Completion] = {}
        self._next_rid = 0
        self.stats = QueueStats()

    # -------------------------------------------------------------- intake

    def submit(self, prompt: Sequence[int]) -> int:
        rid = self._next_rid
        self._next_rid += 1
        # clip at intake so bucketing and waves see the served length
        # (truncate-left with a warning instead of a shape error in jit)
        prompt, = self.engine.clip_prompts([list(prompt)],
                                           self.gen.max_new_tokens)
        self._pending.append(Request(rid, prompt))
        return rid

    def submit_all(self, prompts: Iterable[Sequence[int]]) -> List[int]:
        return [self.submit(p) for p in prompts]

    def pending(self) -> int:
        return len(self._pending)

    # ------------------------------------------------------------ scheduling

    def _pick_wave(self) -> List[Request]:
        """Fullest-bucket-first: maximizes slot utilization and amortizes
        each prefill compilation over the most requests."""
        by_bucket: Dict[int, List[Request]] = defaultdict(list)
        for r in self._pending:
            b = self.engine.prompt_bucket(len(r.prompt),
                                          self.gen.max_new_tokens)
            by_bucket[b].append(r)
        bucket = max(by_bucket, key=lambda b: (len(by_bucket[b]), -b))
        return by_bucket[bucket][:self.engine.batch_size]

    def step(self) -> List[Completion]:
        """Pack and run one wave; returns its completions (empty list if
        nothing is pending)."""
        if not self._pending:
            return []
        wave = self._pick_wave()
        taken = {r.rid for r in wave}
        self._pending = [r for r in self._pending if r.rid not in taken]
        wave_key = jax.random.fold_in(self._key, self.stats.waves)
        t0 = time.perf_counter()
        outs = self.engine.generate([r.prompt for r in wave], gen=self.gen,
                                    key=wave_key)
        elapsed = time.perf_counter() - t0
        bucket = self.engine.prompt_bucket(
            max(len(r.prompt) for r in wave), self.gen.max_new_tokens)
        completions = []
        for r, toks in zip(wave, outs):
            c = Completion(r.rid, toks, len(r.prompt), bucket,
                           self.stats.waves)
            self._done[r.rid] = c
            completions.append(c)
        self.stats.waves += 1
        self.stats.requests += len(wave)
        self.stats.tokens_out += sum(len(t) for t in outs)
        self.stats.slots_run += self.engine.batch_size
        self.stats.slots_used += len(wave)
        self.stats.latency_s.extend([elapsed] * len(wave))
        return completions

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue; returns {rid: generated tokens} for every
        completed request (including ones finished in earlier steps)."""
        while self._pending:
            self.step()
        return {rid: c.tokens for rid, c in self._done.items()}

    def result(self, rid: int) -> Completion:
        return self._done[rid]


# --------------------------------------------------------------------------
# continuous batching


@dataclass
class ContinuousCompletion:
    rid: int
    tokens: List[int]
    prompt_len: int
    budget: int                   # per-request max_new_tokens
    slot: int                     # engine batch row it decoded in
    frame: int                    # session frame it was admitted into
    ttft_s: float                 # submit -> first token (arrival-anchored)
    done_s: float                 # submit -> last token (arrival-anchored)
    shed: bool = False            # dropped at run() start by a shed hint


@dataclass
class ContinuousStats:
    requests: int = 0
    tokens_out: int = 0
    frames: int = 0               # full batch (re)starts
    segments: int = 0             # compiled decode segments dispatched
    refills: int = 0              # mid-frame per-slot swaps
    prefix_hits: int = 0          # prefix-cache hits (paged sessions)
    prefix_misses: int = 0        # prefix-cache misses (paged sessions)
    prefix_evictions: int = 0     # prefix entries LRU-evicted for space
    admission_skips: int = 0      # pending requests passed over (no fit)
    shed: int = 0                 # requests truncated at intake to fit
    shed_hint_drops: int = 0      # requests dropped by the SLO shed hint
    cow_forks: int = 0            # paged copy-on-write block forks
    kv_exhaustions: int = 0       # paged pool-exhaustion waits
    prefill_passes: int = 0       # model passes of staging prefills
    prefill_tokens: int = 0       # tokens staged by them, pads included
    ttft_s: List[float] = field(default_factory=list)
    latency_s: List[float] = field(default_factory=list)

    # Every scalar above is a monotone counter for the queue's lifetime
    # (standing queues never reset them).  Per-interval numbers come
    # from snapshot()/delta(): take a snapshot before an interval and
    # diff after it — docs/ARCHITECTURE.md, "per-slot stats are deltas
    # of monotonic counters".
    COUNTERS = ("requests", "tokens_out", "frames", "segments", "refills",
                "prefix_hits", "prefix_misses", "prefix_evictions",
                "admission_skips", "shed", "shed_hint_drops",
                "cow_forks", "kv_exhaustions", "prefill_passes",
                "prefill_tokens")

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time copy of the monotone counters (plus the lengths
        of the per-request sample lists)."""
        snap = {k: getattr(self, k) for k in self.COUNTERS}
        snap["ttft_n"] = len(self.ttft_s)
        snap["latency_n"] = len(self.latency_s)
        return snap

    def delta(self, base: Dict[str, int]) -> "ContinuousStats":
        """Stats accumulated since ``base`` (an earlier snapshot()) as a
        fresh ContinuousStats — percentiles/means then cover only the
        interval's requests."""
        d = ContinuousStats()
        for k in self.COUNTERS:
            setattr(d, k, getattr(self, k) - base[k])
        d.ttft_s = self.ttft_s[base["ttft_n"]:]
        d.latency_s = self.latency_s[base["latency_n"]:]
        return d

    # the one shared empty-safe percentile (obs.metrics.percentile)
    _pct = staticmethod(percentile)

    @property
    def ttft_mean(self) -> float:
        return float(np.mean(self.ttft_s)) if self.ttft_s else 0.0

    @property
    def ttft_p50(self) -> float:
        return self._pct(self.ttft_s, 50)

    @property
    def ttft_p95(self) -> float:
        return self._pct(self.ttft_s, 95)

    @property
    def ttft_p99(self) -> float:
        return self._pct(self.ttft_s, 99)

    @property
    def latency_mean(self) -> float:
        return float(np.mean(self.latency_s)) if self.latency_s else 0.0

    @property
    def latency_p50(self) -> float:
        return self._pct(self.latency_s, 50)

    @property
    def latency_p95(self) -> float:
        return self._pct(self.latency_s, 95)

    @property
    def latency_p99(self) -> float:
        return self._pct(self.latency_s, 99)


@dataclass
class _ContRequest:
    rid: int
    prompt: List[int]
    budget: int
    prefix_len: int = 0           # retrieved-context prefix (0 = none)
    trace: Optional[str] = None   # obs trace id (None = untraced)
    t_submit: float = 0.0         # perf_counter at submit (TTFT anchor)
    t_admit: float = 0.0          # perf_counter at admission


class ContinuousQueue:
    """Continuous-batching scheduler with pluggable admission policy.

    ``policy="fifo"`` (default) admits the first pending request that
    fits the live frame (FIFO-with-skip); ``policy="sjf"`` admits the
    fitting request with the fewest prefill chunks (shortest-prefill-
    first), which front-loads cheap admissions and lowers mean TTFT —
    a cached retrieved-context prefix makes a long prompt *cheap*, so
    SJF and the prefix cache compose.

    Requests carry their own ``max_new_tokens`` budget (capped by the
    queue's ``GenerationParams``) and an optional ``prefix_len`` marking
    a shared retrieved-context prefix (paged engines fork its prefilled
    blocks out of the session's ``PrefixCache``).  Completion identity,
    per-request latency and TTFT are preserved via request ids; both are
    arrival-anchored (measured from ``submit()``).

    ``standing=True`` makes the queue a *standing engine*: one
    long-lived session persists across ``run()`` calls, so a stream of
    ``submit()`` + ``run(wait_for=...)`` rounds (one per scheduler
    slot) admits into live frames instead of re-prefilling a cold one,
    requests may straddle a round mid-decode, and ``set_shed`` hints
    take effect at the next refill — mid-frame.  ``close()`` drains and
    releases the frame/KV pool."""

    def __init__(self, engine: ServeEngine,
                 gen: Optional[GenerationParams] = None, *, key=None,
                 policy: str = "fifo", prefix_capacity: int = 8,
                 standing: bool = False):
        self.engine = engine
        self.gen = gen or GenerationParams()
        if engine.prefill_chunk is None:
            raise ValueError("ContinuousQueue needs an engine built with "
                             "prefill_chunk=...; use RequestQueue for "
                             "synchronous waves")
        if policy not in ("fifo", "sjf"):
            raise ValueError(f"unknown admission policy {policy!r}; "
                             "expected 'fifo' or 'sjf'")
        if self.gen.max_new_tokens < 1 \
                or self.gen.max_new_tokens >= engine.max_len \
                or engine.cont_max_prompt_len(self.gen.max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens={self.gen.max_new_tokens} and "
                f"prefill_chunk={engine.prefill_chunk} do not fit the "
                f"engine cache (max_len={engine.max_len})")
        self.policy = policy
        self.prefix_capacity = prefix_capacity
        self.standing = bool(standing)
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._pending: List[_ContRequest] = []
        self._done: Dict[int, ContinuousCompletion] = {}
        self._next_rid = 0
        self._shed_fraction = 0.0
        self._session: Optional[ContinuousSession] = None
        self._owner: Dict[int, _ContRequest] = {}   # slot -> live request
        self._finished: set = set()                 # rids with final tokens
        self.stats = ContinuousStats()

    # -------------------------------------------------------------- intake

    def set_shed(self, fraction: float) -> None:
        """SLO shed hint: drop this fraction of the pending queue (the
        most recently submitted requests) at the next ``run()`` instead
        of serving them late.  Set by ``ClusterRuntime`` when a node's
        SLO monitor is FIRING; 0.0 disables."""
        self._shed_fraction = min(max(float(fraction), 0.0), 1.0)

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               prefix_len: Optional[int] = None,
               trace: Optional[str] = None) -> int:
        rid = self._next_rid
        self._next_rid += 1
        budget = self.gen.max_new_tokens if max_new_tokens is None \
            else min(max_new_tokens, self.gen.max_new_tokens)
        budget = max(1, budget)
        prompt = list(prompt)
        self.stats.requests += 1
        if not prompt:
            # empty prompts condition on nothing -> empty completion
            # (mirrors ServeEngine._route_empty_prompts)
            self._done[rid] = ContinuousCompletion(
                rid, [], 0, budget, -1, -1, 0.0, 0.0)
            self._finished.add(rid)
            return rid
        prefix_len = max(0, min(prefix_len or 0, len(prompt) - 1))
        cap = self.engine.cont_max_prompt_len(self.gen.max_new_tokens)
        if len(prompt) > cap:
            prompt, prefix_len = self._truncate(prompt, prefix_len, cap)
            self.stats.shed += 1
        if self.engine.paged:
            self._check_block_span(prompt, prefix_len, budget)
        self._pending.append(_ContRequest(
            rid, prompt, budget, prefix_len, trace=trace,
            t_submit=time.perf_counter()))
        return rid

    def _truncate(self, prompt: List[int], prefix_len: int,
                  cap: int) -> tuple:
        """Truncate-left an over-long prompt without destabilizing the
        prefix-cache key: the kept prefix length is rounded down to a
        prefill-chunk multiple, so every request against the same
        retrieved context (questions of any length within a chunk
        class) truncates to the *same* prefix tokens and still shares
        one cache entry.  A plain left-truncate would slide the cut
        with the question length and split the context mid-document,
        making each hash unique."""
        n = len(prompt)
        q = n - prefix_len
        keep_p = (cap - min(q, cap)) // self.engine.prefill_chunk \
            * self.engine.prefill_chunk if prefix_len else 0
        if keep_p >= 1:
            kept = keep_p + q
            warnings.warn(
                f"prompt of {n} tokens exceeds the continuous frame "
                f"capacity ({cap}); truncated-left to {kept} tokens at a "
                f"chunk boundary (prefix {prefix_len} -> {keep_p} so the "
                f"shared-prefix cache key stays stable)", stacklevel=3)
            return prompt[prefix_len - keep_p:], keep_p
        warnings.warn(
            f"prompt of {n} tokens exceeds the continuous frame "
            f"capacity ({cap} = chunk-aligned max_len="
            f"{self.engine.max_len} - max_new_tokens="
            f"{self.gen.max_new_tokens}); truncated-left to {cap} "
            f"tokens", stacklevel=3)
        return prompt[-cap:], 0

    def _check_block_span(self, prompt: List[int], prefix_len: int,
                          budget: int) -> None:
        """Reject a request whose block run cannot fit even an *empty*
        pool (it would never become admissible and stall the queue)."""
        C, bs = self.engine.prefill_chunk, self.engine.block_size
        padded = -(-len(prompt) // C) * C
        need = -(-(padded + budget) // bs)
        if prefix_len:
            L0 = prefix_len + (-prefix_len) % C
            tot = -(-(L0 + len(prompt) - prefix_len + budget) // bs)
            need = max(need, -(-L0 // bs) + tot - L0 // bs)
        if need > self.engine.num_blocks:
            raise ValueError(
                f"request needs {need} KV blocks (prompt {len(prompt)}, "
                f"budget {budget}) but the pool only has "
                f"{self.engine.num_blocks}")

    def submit_all(self, prompts: Iterable[Sequence[int]],
                   max_new_tokens: Optional[Iterable[int]] = None,
                   prefix_lens: Optional[Iterable[int]] = None
                   ) -> List[int]:
        budgets = list(max_new_tokens) if max_new_tokens is not None \
            else None
        plens = list(prefix_lens) if prefix_lens is not None else None
        prompts = list(prompts)
        return [self.submit(p, budgets[i] if budgets else None,
                            plens[i] if plens else None)
                for i, p in enumerate(prompts)]

    def pending(self) -> int:
        return len(self._pending)

    def depth(self) -> int:
        """Standing-queue depth: pending + live (admitted, still
        decoding) requests."""
        return len(self._pending) + len(self._owner)

    def oldest_wait_s(self) -> float:
        """Age of the oldest still-pending (not yet admitted) request;
        0.0 when nothing waits."""
        if not self._pending:
            return 0.0
        return time.perf_counter() - min(r.t_submit for r in self._pending)

    def unfinished(self) -> List[int]:
        """Rids submitted but not finished: pending plus mid-decode."""
        return [r.rid for r in self._pending] \
            + [r.rid for r in self._owner.values()]

    # ----------------------------------------------------------- scheduling

    def _admissible(self, session: ContinuousSession
                    ) -> Optional[_ContRequest]:
        """Next pending request that fits the live frame: first fit
        (FIFO-with-skip) or cheapest prefill among the fits (SJF)."""
        def fits(r):
            ok = session.can_refill(len(r.prompt), r.budget,
                                    r.prefix_len or None, r.prompt)
            if not ok:
                self.stats.admission_skips += 1
            return ok
        if self.policy == "fifo":
            for r in self._pending:
                if fits(r):
                    return r
            return None
        best = None
        for r in self._pending:
            if fits(r):
                cost = session.admission_cost(
                    len(r.prompt), r.budget, r.prefix_len or None, r.prompt)
                if best is None or cost < best[0]:
                    best = (cost, r)
        return best[1] if best else None

    def _ensure_session(self) -> ContinuousSession:
        """The live session: standing queues keep one for their whole
        lifetime; per-run queues get a fresh one each ``run()`` (the
        previous was released at run exit)."""
        if self._session is None:
            self._session = ContinuousSession(
                self.engine, self.gen, key=self._key,
                prefix_cache=self.prefix_capacity if self.engine.paged
                else None)
        return self._session

    @staticmethod
    def _session_base(session: ContinuousSession) -> Dict[str, int]:
        """Snapshot of the session/allocator/prefix-cache counters at
        run() entry — a standing session outlives the run, so only the
        run's deltas roll into ``self.stats``."""
        base = {"frames": session.frames, "segments": session.segments,
                "refills": session.refills,
                "prefill_passes": session.prefill_passes,
                "prefill_tokens": session.prefill_tokens,
                "forks": 0, "exhaustions": 0,
                "prefix_hits": 0, "prefix_misses": 0, "prefix_evictions": 0}
        if session.paged:
            base["forks"] = session.allocator.forks
            base["exhaustions"] = session.allocator.exhaustions
        if session.prefix_cache is not None:
            base["prefix_hits"] = session.prefix_cache.hits
            base["prefix_misses"] = session.prefix_cache.misses
            base["prefix_evictions"] = session.prefix_cache.evictions
        return base

    def run(self, wait_for: Optional[Iterable[int]] = None
            ) -> Dict[int, List[int]]:
        """Pump the engine until the target requests finish; returns
        {rid: generated tokens} for every completed request so far.

        By default every submitted request is drained.  A standing
        queue may pass ``wait_for=<rids>``: the call returns as soon as
        those requests finish, leaving other live rows mid-decode for
        the next ``run()`` — a request can straddle scheduler slots
        without a frame restart.  TTFT and latency are arrival-anchored
        (measured from each request's ``submit()``), so they compose
        across runs like a serving trace."""
        if wait_for is not None and not self.standing:
            raise ValueError("run(wait_for=...) needs standing=True: a "
                             "per-run queue releases its session at run "
                             "exit and would drop mid-decode rows")
        tr = obs_trace.get_tracer()
        paged = self.engine.paged
        base = self.stats.snapshot()
        if self._shed_fraction > 0.0 and self._pending:
            # shed the tail (latest arrivals): the oldest requests have
            # already waited longest and would be the first SLO misses
            # if pushed back further
            n_shed = int(len(self._pending) * self._shed_fraction)
            for r in self._pending[len(self._pending) - n_shed:]:
                self._done[r.rid] = ContinuousCompletion(
                    r.rid, [], len(r.prompt), r.budget, -1, -1, 0.0, 0.0,
                    shed=True)
                self._finished.add(r.rid)
                if tr.enabled and r.trace is not None:
                    # terminal span: a shed trace never reaches decode,
                    # so this is what makes its causal tree complete
                    # (trace_report counts `shed` as a terminal stage)
                    tr.emit("shed", r.trace, r.t_submit,
                            time.perf_counter(), reason="slo_hint")
            if n_shed:
                del self._pending[len(self._pending) - n_shed:]
                self.stats.shed_hint_drops += n_shed
        session = self._ensure_session()
        sbase = self._session_base(session)
        owner = self._owner
        targets = set(wait_for) if wait_for is not None else \
            {r.rid for r in self._pending} | {r.rid for r in owner.values()}

        def admit(slot: int, r: _ContRequest) -> None:
            owner[slot] = r
            abs_now = time.perf_counter()
            if tr.enabled:
                session.traces[slot] = r.trace
                if r.trace is not None:
                    # queue wait becomes a retroactive span: admission is
                    # the only point where both endpoints are known
                    tr.emit("queue_wait", r.trace, r.t_submit, abs_now,
                            slot=slot)
            r.t_admit = abs_now
            ttft = abs_now - r.t_submit
            self.stats.ttft_s.append(ttft)
            self._done[r.rid] = ContinuousCompletion(
                r.rid, [], len(r.prompt), r.budget, slot,
                session.frames, ttft, ttft)

        try:
            while not targets <= self._finished:
                if session.active():
                    # drain (run to the last row) only when every live
                    # row is waited for — a straddling straggler keeps
                    # its slot and resumes next run()
                    live = {r.rid for r in owner.values()}
                    for slot, tokens in session.run_segment(
                            drain=not self._pending and live <= targets):
                        r = owner.pop(slot)
                        abs_now = time.perf_counter()
                        c = self._done[r.rid]
                        c.tokens = tokens
                        c.done_s = abs_now - r.t_submit
                        self._finished.add(r.rid)
                        self.stats.tokens_out += len(tokens)
                        self.stats.latency_s.append(c.done_s)
                        if tr.enabled:
                            session.traces.pop(slot, None)
                            if r.trace is not None and r.t_admit:
                                tr.emit("decode", r.trace, r.t_admit,
                                        abs_now, tokens=len(tokens),
                                        slot=slot)
                    if paged and obs_metrics.metrics_enabled():
                        obs_metrics.registry().gauge(
                            "kv_pool_fragmentation").set(
                                session.pool_fragmentation())
                    if targets <= self._finished:
                        break
                admitted = 0
                if session.cache is not None:
                    # refill first: a drained-but-warm frame admits at
                    # its live position (single-row exact-pad prefill)
                    # instead of paying a cold frame restart
                    for slot in session.free_slots():
                        r = self._admissible(session)
                        if r is None:
                            break
                        self._pending.remove(r)
                        if tr.enabled:
                            session.traces[slot] = r.trace
                        passes0 = session.prefill_passes
                        tokens0 = session.prefill_tokens
                        with tr.span("prefill", trace=r.trace,
                                     mode="refill", slot=slot,
                                     prompt_len=len(r.prompt),
                                     prefix_len=r.prefix_len) as sp:
                            session.refill(slot, r.prompt, r.budget,
                                           prefix_len=r.prefix_len or None)
                            # the admission's staging passes, a prefix
                            # prefill's included, and the tokens staged
                            sp.set(
                                passes=session.prefill_passes - passes0,
                                staged_tokens=session.prefill_tokens - tokens0)
                        admitted += 1
                        admit(slot, r)
                if self._pending and not admitted and not session.active():
                    if paged and session.cache is not None:
                        raise RuntimeError(
                            "paged admission stalled: a pending request "
                            "cannot be scheduled even into an idle frame")
                    # open a frame: the session's first, or a non-paged
                    # restart after a drain left nothing refillable (a
                    # paged session only ever opens ONE frame — the pool
                    # persists, so admission continues through refill
                    # above; restarting would drop the prefix cache)
                    n = max(1, session.frame_capacity(
                        [(len(r.prompt), r.budget) for r in self._pending])) \
                        if paged else session.B
                    if paged and any(r.prefix_len for r in self._pending):
                        # frame prefill bypasses the prefix cache (rows are
                        # packed left-padded, not in canonical prefix
                        # layout); open the frame with one row so the rest
                        # admit through cache-aware refill and shared
                        # contexts fork instead of re-prefilling
                        n = 1
                    batch = self._pending[:n]
                    del self._pending[:len(batch)]
                    if tr.enabled:
                        for slot, r in enumerate(batch):
                            session.traces[slot] = r.trace
                    with tr.span("prefill", traces=[r.trace for r in batch],
                                 mode="frame", rows=len(batch)):
                        session.begin_frame([r.prompt for r in batch],
                                            [r.budget for r in batch])
                    for slot, r in enumerate(batch):
                        admit(slot, r)
                if not self._pending and not session.active():
                    break   # wait_for named rids this queue never saw
        finally:
            if not self.standing and targets - self._finished:
                # aborted mid-run (e.g. paged stall): a per-run queue
                # cannot resume a half-drained session on the next run
                session.release()
                self._session = None
                self._owner.clear()
        s, st = session, self.stats
        st.frames += s.frames - sbase["frames"]
        st.segments += s.segments - sbase["segments"]
        st.refills += s.refills - sbase["refills"]
        st.prefill_passes += s.prefill_passes - sbase["prefill_passes"]
        st.prefill_tokens += s.prefill_tokens - sbase["prefill_tokens"]
        if paged:
            st.cow_forks += s.allocator.forks - sbase["forks"]
            st.kv_exhaustions += \
                s.allocator.exhaustions - sbase["exhaustions"]
        if s.prefix_cache is not None:
            st.prefix_hits += s.prefix_cache.hits - sbase["prefix_hits"]
            st.prefix_misses += \
                s.prefix_cache.misses - sbase["prefix_misses"]
            st.prefix_evictions += \
                s.prefix_cache.evictions - sbase["prefix_evictions"]
        if obs_metrics.metrics_enabled():
            self._push_metrics(session, base)
        if not self.standing:
            session.release()
            self._session = None
        return {rid: c.tokens for rid, c in self._done.items()}

    def close(self, drain: bool = True) -> None:
        """Retire a standing queue: finish every unfinished request
        (``drain=True``) or abandon them, then release the session's
        frame and KV pool.  Safe to call twice; the queue stays usable
        (a later submit()+run() opens a fresh session)."""
        if drain and self.unfinished():
            self.run()
        if self._session is not None:
            self._session.release()
            self._session = None
        self._owner.clear()
        self._pending.clear()

    def _push_metrics(self, session: ContinuousSession,
                      base: Dict[str, int]) -> None:
        """Roll this run's deltas into the global metrics registry.
        Host-side and post-segment only — never on the decode hot path.
        ``base`` is the stats snapshot taken at run() entry; a standing
        queue's counters are monotone, so the diff is exactly this
        run's contribution."""
        reg = obs_metrics.registry()
        d = self.stats.delta(base)
        reg.counter("queue_requests_admitted", policy=self.policy).inc(
            len(d.ttft_s))
        reg.counter("queue_admission_skips").inc(d.admission_skips)
        reg.counter("queue_shed").inc(d.shed)
        reg.counter("queue_shed_hint_drops").inc(d.shed_hint_drops)
        reg.counter("queue_tokens_out").inc(d.tokens_out)
        h = reg.histogram("queue_ttft_s")
        for v in d.ttft_s:
            h.observe(v)
        h = reg.histogram("queue_latency_s")
        for v in d.latency_s:
            h.observe(v)
        reg.gauge("queue_depth").set(float(self.depth()))
        reg.gauge("queue_oldest_wait_s").set(self.oldest_wait_s())
        if session.paged:
            alloc = session.allocator
            reg.gauge("kv_pool_utilization").set(alloc.utilization())
            reg.gauge("kv_pool_high_watermark").set(alloc.high_watermark)
            reg.counter("kv_pool_cow_forks").inc(d.cow_forks)
            reg.counter("kv_pool_exhaustion_waits").inc(d.kv_exhaustions)
            if session.prefix_cache is not None:
                reg.counter("prefix_cache_hits").inc(d.prefix_hits)
                reg.counter("prefix_cache_misses").inc(d.prefix_misses)
                reg.counter("prefix_cache_evictions").inc(
                    d.prefix_evictions)

    def result(self, rid: int) -> ContinuousCompletion:
        return self._done[rid]

    def pop_result(self, rid: int) -> ContinuousCompletion:
        """``result()`` that releases the stored completion — standing
        queues live for the node's lifetime, so per-slot consumers pop
        to keep the done-map bounded."""
        return self._done.pop(rid)
