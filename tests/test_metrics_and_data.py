"""Metrics properties (hypothesis) + data substrate."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data.corpus import DOMAINS, generate_corpus
from repro.data.partition import coverage_matrix, partition_edge_data
from repro.data.tokenizer import Tokenizer
from repro.metrics import bertscore, bleu4, meteor, rouge_l, rouge_n
from repro.metrics.text import composite_quality

WORDS = st.lists(st.sampled_from(
    "alpha bravo charlie delta echo foxtrot golf hotel".split()),
    min_size=1, max_size=12)


@given(WORDS)
@settings(max_examples=30, deadline=None)
def test_metrics_identity(ws):
    t = " ".join(ws)
    assert rouge_l(t, t) == pytest.approx(1.0)
    assert rouge_n(t, t, 1) == pytest.approx(1.0)
    assert bleu4(t, t) == pytest.approx(1.0, abs=1e-6)
    # METEOR's fragmentation penalty is 0.5*(chunks/m)^3; for very short
    # texts chunks==m so identical pairs score below 1 by design
    assert meteor(t, t) >= 0.99 if len(ws) >= 4 else meteor(t, t) >= 0.45
    assert bertscore(t, t) == pytest.approx(1.0, abs=1e-5)


@given(WORDS, WORDS)
@settings(max_examples=30, deadline=None)
def test_metrics_bounded(a, b):
    g, r = " ".join(a), " ".join(b)
    for m in (rouge_l(g, r), rouge_n(g, r, 2), bleu4(g, r), meteor(g, r)):
        assert -1e-9 <= m <= 1.0 + 1e-9
    assert -1.0 <= bertscore(g, r) <= 1.0 + 1e-6


def test_rouge_l_paper_norm_matches_definition():
    g, r = "a b c d", "a b x"
    # LCS = 2 ("a b"); paper norm: / max(4, 3) = 0.5
    assert rouge_l(g, r) == pytest.approx(0.5)


def test_composite_quality_weights():
    g = r = "the quick brown fox"
    assert composite_quality(g, r) == pytest.approx(
        1.0 * rouge_l(g, r) + 0.5 * bertscore(g, r))


def test_tokenizer_roundtrip():
    texts = ["the yield of bond x1 is hedge margin .",
             "what is the ranking of league sp2 ?"]
    tok = Tokenizer.build(texts)
    for t in texts:
        assert tok.decode(tok.encode(t)) == t


def test_corpus_and_partition():
    docs, qas = generate_corpus(10, seed=0)
    assert len(docs) == 10 * len(DOMAINS)
    assert len({d.doc_id for d in docs}) == len(docs)
    for qa in qas:
        # answer text is contained verbatim in its source document
        assert qa.answer.rstrip(" .") in docs[qa.doc_id].text
    nd = partition_edge_data(docs, 4, [[0, 1], [2, 3], [4, 5], [0, 1]],
                             seed=0)
    w = coverage_matrix(nd, len(DOMAINS))
    # primary domains have the highest coverage for their nodes
    assert w[1, 2] > w[1, 0] and w[2, 4] > w[2, 1]


def test_retrieval_recall():
    from repro.retrieval.encoder import TextEncoder
    from repro.retrieval.index import FlatIndex
    docs, qas = generate_corpus(15, seed=1)
    enc = TextEncoder(seed=0)
    idx = FlatIndex(256)
    idx.add(enc.encode([d.text for d in docs]), [d.doc_id for d in docs])
    q = enc.encode([qa.question for qa in qas[:40]])
    _, I = idx.search(q, 5)
    recall = np.mean([qas[j].doc_id in idx.payloads(I[j])
                      for j in range(40)])
    assert recall > 0.9
