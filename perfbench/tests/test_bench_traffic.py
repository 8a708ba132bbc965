"""The traffic generator: deterministic per seed, the stated length and
sharing distributions, the same requests at the same times for every
seed, in another order."""
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEEDS = (3, 2**31 + 17)


def _spec(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def steady():
    spec = _spec("rag-steady")
    return spec, traffic.make_corpus(spec)


def test_corpus_is_the_deployments_for_every_seed(steady):
    spec, corpus = steady
    again = traffic.make_corpus(spec)
    assert [d.text for d in again.docs] == [d.text for d in corpus.docs]
    other = dict(spec, base_seed=spec["base_seed"] + 1)
    assert [d.text for d in traffic.make_corpus(other).docs] != \
        [d.text for d in corpus.docs]


def test_document_lengths_heavy_tailed(steady):
    _, corpus = steady
    a = np.asarray(sorted(len(traffic.words(d.text)) for d in corpus.docs))
    assert a.min() >= 180 and a.max() <= 2100
    med, p95 = np.percentile(a, [50, 95])
    assert 350 <= med <= 500 and p95 > 2.5 * med


def test_every_fact_is_in_its_document(steady):
    _, c = steady
    for e in range(0, len(c.docs), 97):
        for f in c.facts[e]:
            assert f"the {f.attr} of {c.entities[e]} is {f.value} ." \
                in c.docs[e].text


def test_chunks_are_full_except_short_documents(steady):
    _, c = steady
    for d in c.docs[:300]:
        n = len(traffic.words(d.text))
        lens = [len(traffic.words(x)) for x in traffic.chunk_words(d.text,
                                                                   256)]
        if n < 256:
            assert lens == [n]
        else:
            assert set(lens) == {256} and len(lens) == -(-n // 256)
    ws = traffic.words(c.docs[0].text)
    chunks = traffic.chunk_words(c.docs[0].text, 256)
    assert chunks[0].split() == ws[:256]
    assert chunks[-1].split() == ws[-256:]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_serves_the_same_requests_in_another_order(steady, seed):
    spec, c = steady
    reqs = traffic.schedule(spec, c, 6.0, 40.0, seed)
    ref = traffic.schedule(spec, c, 6.0, 40.0, 5)
    assert len(reqs) == len(ref) == 240
    t = [r.due_s for r in reqs]
    assert t == [r.due_s for r in ref]
    assert np.all(np.diff(t) >= 0) and 0 <= t[0] and t[-1] < 40.0
    period = spec["queries"]["mix_period_s"]
    for m in range(8):                     # each mix period: one multiset
        a = sorted(r.question for r in reqs if r.due_s // period == m)
        b = sorted(r.question for r in ref if r.due_s // period == m)
        assert a == b and a
    assert [r.question for r in reqs] != [r.question for r in ref]
    again = traffic.schedule(spec, c, 6.0, 40.0, seed)
    assert [(r.due_s, r.question) for r in again] == \
        [(r.due_s, r.question) for r in reqs]


def test_bursts_triple_the_rate():
    spec = _spec("rag-hotdocs-burst")
    arr = spec["arrivals"]
    t = traffic.arrival_times(spec, 5.0, 400.0)
    assert len(t) == 2000
    counts = np.histogram(t, bins=np.arange(0, 400.5, 0.5))[0]
    hi, lo = np.percentile(counts, [95, 30])
    assert hi >= 2.0 * lo
    assert arr["burst_factor"] == 3.0


def test_steady_queries_rarely_repeat(steady):
    spec, c = steady
    reqs = traffic.schedule(spec, c, 8.0, 30.0, SEEDS[0])
    ents = [r.entity for r in reqs]
    assert len(reqs) == 240
    assert 1 - len(set(ents)) / len(ents) < 0.10


def test_hot_queries_repeat_a_small_set(steady):
    spec = _spec("rag-hotdocs-burst")
    c = steady[1]
    reqs = traffic.schedule(spec, c, 6.0, 60.0, SEEDS[1])
    qs = [r.question for r in reqs]
    counts = sorted((qs.count(q) for q in set(qs)), reverse=True)
    hot = sum(counts[:16]) / len(qs)
    assert 0.7 < hot < 0.9
    # Zipf: the most asked question is asked several times the 8th
    assert counts[0] >= 3 * counts[7]


def test_schedule_deterministic(steady):
    spec, c = steady
    a = traffic.schedule(spec, c, 5.0, 20.0, SEEDS[1])
    b = traffic.schedule(spec, c, 5.0, 20.0, SEEDS[1])
    assert [(r.due_s, r.question) for r in a] == \
        [(r.due_s, r.question) for r in b]
