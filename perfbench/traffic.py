"""The one traffic generator: a seeded RAG corpus and an open-loop request
schedule, both described by a traffic file (``perfbench/traffic/<name>.json``).

Copied from the program's generators and extended here, so that a change
to the program cannot change the yardstick:

* ``DOMAINS`` / ``BANKS`` and the fact sentences follow
  ``repro/data/corpus.py``; documents here are long (a heavy-tailed
  200-2,000 words) and hold their entity facts among domain filler.
* ``chunk_words`` is ``repro/retrieval/chunker.py::chunk_text`` at a
  fixed 256 words with its last window aligned to the document's end.
* ``partition`` follows ``repro/data/partition.py::partition_edge_data``.
* the Dirichlet domain mix follows ``repro/data/traces.py``.

The work is the deployment's, drawn from the traffic file's own
``base_seed``: the corpus (every word, so every prompt and its length),
which documents each node holds, the due times, and the questions asked
in each ``mix_period_s`` of the window.  The run's seed draws the order
of those questions within each period, so every seed serves the same
requests at the same times, each question at another due time.  (When
the seed also drew the words and questions, the node split and the
prompts changed with it, and the tails of one seed's two runs agreed
far more closely than those of two seeds.)
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

DOMAINS = ["biomedicine", "finance", "law", "sports", "technology", "travel"]

BANKS: Dict[str, Tuple[List[str], List[str], List[str]]] = {
    # domain: (entity stems, attributes, value words)
    "biomedicine": (
        ["enzyme", "protein", "pathogen", "antibody", "receptor", "genome"],
        ["dosage", "halflife", "target", "pathway", "mutation"],
        ["kinase", "plasma", "membrane", "sequence", "inhibitor", "ligand",
         "antigen", "clinical", "therapeutic", "cellular"]),
    "finance": (
        ["bond", "equity", "fund", "portfolio", "derivative", "index"],
        ["yield", "maturity", "rating", "exposure", "premium"],
        ["basis", "hedge", "liquidity", "dividend", "futures", "margin",
         "treasury", "coupon", "arbitrage", "volatility"]),
    "law": (
        ["statute", "contract", "tribunal", "plaintiff", "clause", "verdict"],
        ["jurisdiction", "liability", "precedent", "remedy", "damages"],
        ["appellate", "binding", "tort", "equity", "injunction", "counsel",
         "discovery", "testimony", "negligence", "covenant"]),
    "sports": (
        ["striker", "league", "marathon", "tournament", "goalkeeper",
         "relay"],
        ["record", "transfer", "ranking", "score", "coach"],
        ["penalty", "sprint", "champion", "stadium", "offside", "podium",
         "fixture", "overtime", "dribble", "medal"]),
    "technology": (
        ["compiler", "protocol", "database", "processor", "router",
         "kernel"],
        ["latency", "throughput", "version", "cache", "bandwidth"],
        ["packet", "thread", "pipeline", "register", "socket", "runtime",
         "buffer", "scheduler", "firmware", "silicon"]),
    "travel": (
        ["airline", "harbor", "monument", "resort", "railway", "museum"],
        ["altitude", "season", "currency", "visa", "route"],
        ["island", "summit", "lagoon", "terminal", "voyage", "heritage",
         "plateau", "carnival", "glacier", "bazaar"]),
}

_FILLER_VERBS = ["was", "is", "remains", "became", "follows", "reports",
                 "shows", "lists", "notes", "records"]
_FILLER_GLUE = ["the", "a", "of", "in", "for", "with", "under", "after",
                "and", "by"]
_WORD = re.compile(r"[a-z0-9]+|[^\sa-z0-9]")


def words(text: str) -> List[str]:
    """The program's word split (``repro/data/tokenizer.py::words``)."""
    return _WORD.findall(text.lower())


def chunk_words(text: str, size: int) -> List[str]:
    """Fixed-length chunks of ``size`` words, the last one aligned to the
    document's end (overlapping its predecessor), so every chunk of a
    document of at least ``size`` words is full; a shorter document is
    one short chunk."""
    ws = words(text)
    starts = list(range(0, max(1, len(ws) - size + 1), size))
    if len(ws) > size and starts[-1] + size < len(ws):
        starts.append(len(ws) - size)
    return [" ".join(ws[i:i + size]) for i in starts]


@dataclass
class Doc:
    doc_id: int
    domain: int
    entity: int                # index into Corpus.entities
    text: str


@dataclass
class Fact:
    attr: str
    value: str


@dataclass
class Corpus:
    docs: List[Doc]
    entities: List[str]        # entity names, one document each
    entity_domain: np.ndarray  # [E]
    facts: List[List[Fact]]    # per entity


def _doc_lengths(c: dict, n: int, base_seed: int) -> np.ndarray:
    """Heavy-tailed document lengths in words: lognormal around the
    median, clipped to [min, max].  Drawn from the file's base seed."""
    rng = np.random.default_rng(base_seed)
    d = c["doc_words"]
    x = np.exp(np.log(d["median"]) + d["sigma"] * rng.standard_normal(n))
    return np.clip(np.round(x), d["min"], d["max"]).astype(np.int64)


def _doc_text(rng, dom: str, name: str, fact_sents: List[str],
              target: int) -> str:
    """``in <domain> ,`` then filler sentences of 8-14 domain words (a
    third of them naming the entity) with the fact sentences at random
    places, until ``target`` words."""
    stems, attrs, values = BANKS[dom]
    pool = np.asarray(values + stems + attrs + _FILLER_GLUE + _FILLER_VERBS
                      + [dom])
    # at least 9 words a sentence: enough sentences to reach the target
    n_sent = max(len(fact_sents) + 1, target // 9 + 2)
    lens = rng.integers(8, 15, size=n_sent)
    flat = pool[rng.integers(len(pool), size=int(lens.sum()))]
    bounds = np.concatenate([[0], np.cumsum(lens)])
    mention = rng.random(n_sent) < 0.3
    where = rng.integers(0, 8, size=n_sent)
    # every sentence has at most 15 words, so the first target // 15
    # sentences are always written: the facts go among them
    fact_at = set(rng.choice(max(len(fact_sents), target // 15),
                             size=len(fact_sents), replace=False).tolist())
    out, n_words, k = [f"in {dom} ,"], 3, 0
    for i in range(n_sent):
        if i in fact_at:
            sent = fact_sents[k]
            k += 1
            n_words += len(words(sent))
        else:
            ws = list(flat[bounds[i]:bounds[i + 1]])
            if mention[i]:
                ws[where[i]] = name
            sent = " ".join(ws) + " ."
            n_words += int(lens[i]) + 1 + (len(name.split()) - 1
                                           if mention[i] else 0)
        out.append(sent)
        if n_words >= target and k == len(fact_sents):
            break
    out += fact_sents[k:]
    # exactly ``target`` words (the facts all lie in the first target//15
    # sentences), so a document's chunk count is fixed by its length
    return " ".join(words(" ".join(out))[:target])


def make_corpus(spec: dict) -> Corpus:
    """``entities`` entities spread evenly over the six domains, one
    document each, of a length fixed by the file; every word, name and
    fact comes from the file's base seed."""
    c = spec["corpus"]
    n_ent = int(c["entities"])
    lengths = _doc_lengths(c, n_ent, int(spec["base_seed"]))
    rng = np.random.default_rng([int(spec["base_seed"]), 1])
    docs, names, doms, facts = [], [], [], []
    for e in range(n_ent):
        d = e % len(DOMAINS)
        dom = DOMAINS[d]
        stems, attrs, values = BANKS[dom]
        name = f"{stems[int(rng.integers(len(stems)))]} {dom[:4]}{e}"
        chosen = rng.choice(len(attrs), size=int(c["facts_per_doc"]),
                            replace=False)
        ef = [Fact(attrs[int(a)], " ".join(
            str(v) for v in rng.choice(values, size=2, replace=False)))
            for a in chosen]
        fact_sents = [f"the {f.attr} of {name} is {f.value} ." for f in ef]
        docs.append(Doc(e, d, e, _doc_text(rng, dom, name, fact_sents,
                                           int(lengths[e]))))
        names.append(name)
        doms.append(d)
        facts.append(ef)
    return Corpus(docs, names, np.asarray(doms), facts)


def partition(corpus: Corpus, n_nodes: int, base_seed: int, *,
              iid_share: float = 0.2, overlap: float = 0.2
              ) -> List[List[Doc]]:
    """Per-node document shards: most of the node's primary domains
    (``d % n_nodes == n``), an i.i.d. slice of every domain, and an
    overlapping share of the others (the program's edge-data split).
    Which entity lies on which node is the deployment's, fixed by the
    traffic file's base seed."""
    rng = np.random.default_rng([base_seed, 2])
    by_domain: Dict[int, List[Doc]] = {}
    for d in corpus.docs:
        by_domain.setdefault(d.domain, []).append(d)
    shards: List[List[Doc]] = []
    for n in range(n_nodes):
        prim = [d for d in range(len(DOMAINS)) if d % n_nodes == n]
        got: List[Doc] = []
        for dom in prim:
            pool = by_domain[dom]
            take = int(len(pool) * (1 - iid_share))
            got += [pool[i] for i in rng.choice(len(pool), size=take,
                                                replace=False)]
        for dom in sorted(by_domain):
            pool = by_domain[dom]
            take = min(len(pool), max(1, int(len(pool) * iid_share
                                             / n_nodes * 2)))
            got += [pool[i] for i in rng.choice(len(pool), size=take,
                                                replace=False)]
        for dom in sorted(by_domain):
            if dom in prim:
                continue
            pool = by_domain[dom]
            take = int(len(pool) * overlap * 0.5)
            if take:
                got += [pool[i] for i in rng.choice(len(pool), size=take,
                                                    replace=False)]
        seen, uniq = set(), []
        for d in got:
            if d.doc_id not in seen:
                seen.add(d.doc_id)
                uniq.append(d)
        shards.append(uniq)
    return shards


# --------------------------------------------------------------- arrivals


def _rate_fn(arr: dict, rate: float, phase: float):
    """(rate(t), period) of the arrival process; ``rate`` is the mean."""
    if arr["kind"] == "poisson":
        return (lambda t: np.full_like(t, rate)), None
    if arr["kind"] == "poisson_burst":
        period, burst = float(arr["period_s"]), float(arr["burst_s"])
        f = float(arr["burst_factor"])
        base = rate * period / (period - burst + f * burst)

        def r(t):
            in_burst = ((t + phase) % period) >= period - burst
            return np.where(in_burst, f * base, base)
        return r, period
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def arrival_times(spec: dict, rate: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of an open-loop arrival process with
    mean ``rate`` requests/s, drawn from the file's base seed: the same
    for every run seed.

    The number of arrivals is ``round(rate * seconds)``; their unit-rate
    gaps are exponential, and times come from inverting the integrated
    rate, so a burst squeezes the same gaps closer together."""
    arr = spec["arrivals"]
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(spec["base_seed"]), 3])
    gaps = rng.exponential(size=n + 1)
    cum = np.cumsum(gaps)
    lam = cum[:n] / cum[n] * rate * seconds       # integrated-rate marks
    phase = float(rng.uniform(0, arr.get("period_s", 1.0)))
    r, _ = _rate_fn(arr, rate, phase)
    grid = np.linspace(0.0, seconds, 20001)
    big = np.concatenate([[0.0], np.cumsum(r(grid[:-1]) * np.diff(grid))])
    big *= rate * seconds / big[-1]
    return np.interp(lam, big, grid)


# ---------------------------------------------------------------- queries


@dataclass
class Request:
    idx: int                   # position in the schedule
    due_s: float               # due time, seconds after the window opens
    domain: int
    entity: int
    question: str
    reference: str


def _question(corpus: Corpus, e: int, rng) -> Tuple[str, str]:
    f = corpus.facts[e][int(rng.integers(len(corpus.facts[e])))]
    name = corpus.entities[e]
    return (f"what is the {f.attr} of {name} ?",
            f"the {f.attr} of {name} is {f.value} .")


def schedule(spec: dict, corpus: Corpus, rate: float, seconds: float,
             seed: int) -> List[Request]:
    """The window's requests in due order.  Domains follow a Dirichlet
    mix redrawn every ``mix_period_s``; the entity is uniform within the
    domain, or, for ``hot`` queries, a Zipf draw over a fixed hot set.
    All of that comes from the file's base seed; the run's seed shuffles
    the questions among the due times of each mix period."""
    times = arrival_times(spec, rate, seconds)
    q = spec["queries"]
    rng = np.random.default_rng([int(spec["base_seed"]), 4])
    n_dom = len(DOMAINS)
    alpha = float(q.get("dirichlet_alpha", 1.0))
    period = float(q.get("mix_period_s", 5.0))
    n_mix = int(np.ceil(seconds / period)) + 1
    mixes = [rng.dirichlet(np.full(n_dom, alpha)) for _ in range(n_mix)]
    by_dom = [np.nonzero(corpus.entity_domain == d)[0] for d in range(n_dom)]
    hot = None
    if q["kind"] == "hot":
        hot = rng.choice(len(corpus.entities), size=int(q["hot_set"]),
                         replace=False)
        z = 1.0 / np.arange(1, len(hot) + 1) ** float(q["zipf"])
        hot_p = z / z.sum()
        # a hot entity keeps one question, so its retrieved context repeats
        hot_q = {int(e): _question(corpus, int(e), rng) for e in hot}
    asked = []                             # (entity, question, reference)
    slot = np.minimum((times // period).astype(np.int64), n_mix - 1)
    for t, m in zip(times, slot):
        if hot is not None and rng.random() < float(q["hot_share"]):
            e = int(hot[rng.choice(len(hot), p=hot_p)])
            asked.append((e, *hot_q[e]))
        else:
            d = int(rng.choice(n_dom, p=mixes[m]))
            e = int(by_dom[d][rng.integers(len(by_dom[d]))])
            asked.append((e, *_question(corpus, e, rng)))
    order = np.arange(len(times))
    shuffle = np.random.default_rng([seed, 4])
    for m in np.unique(slot):
        at = np.nonzero(slot == m)[0]
        order[at] = at[shuffle.permutation(len(at))]
    out = []
    for i, t in enumerate(times):
        e, question, ref = asked[order[i]]
        out.append(Request(i, float(t), int(corpus.entity_domain[e]), e,
                           question, ref))
    return out


def mean_rate(traffic: dict, config: dict) -> float:
    """Offered mean rate (requests/s): the traffic's share of the
    configuration's measured knee."""
    return float(traffic["arrivals"]["share_of_knee"]) \
        * float(config["knee_rps"])
