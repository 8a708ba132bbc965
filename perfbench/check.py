"""The check that decides ``correct``, on what the window itself served.

After the window closes, a sample drawn from the seed of the requests
each node finished (with each node's longest among them) is compared
with the plain reference (``perfbench/reference.py``):

* ``logit_gap.<arch>``: the widest gap, over every served token of the
  node's sampled requests, by which the served token's reference logit
  lies below the reference's best at that position.  Greedy decoding is
  exact up to rounding, so a bf16 program reads a small gap; a token
  produced wrongly reads a gap of the order of the logits' spread.
* ``retrieval_gap``: over the sampled requests, how far a retrieved
  chunk's exact (float64) score lies below the exact k-th best score of
  the node's shard; the search scores at the chip's default matmul
  precision, so near-ties may swap by a little, a wrong chunk by a lot.
* ``unanswered``: requests dispatched in the window that did not come
  back exactly once, from one node, with tokens inside the vocabulary;
  limit 0.

The reference runs after the program's state is freed, one node and
one layer at a time, on weights made again from the seed.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional

import numpy as np

PER_NODE = 5


def retrieval_gap(scores: np.ndarray, got: List[int], k: int) -> float:
    """How far the retrieved chunks' exact scores fall below the exact
    k-th best score of the shard (0 when the top-k is exact; a score
    rounded in the search can swap near-ties by a little).  A missing,
    repeated or unknown chunk reads 2, the widest a cosine gap can be."""
    k = min(k, len(scores))
    if len(got) != k or min(got, default=0) < 0 or len(set(got)) != k:
        return 2.0
    kth = np.sort(scores)[::-1][k - 1]
    return float(max(0.0, kth - min(scores[g] for g in got)))


def _rng(seed: int):
    return np.random.default_rng([seed, 11])


def collect(w, recs) -> Dict[int, List[dict]]:
    """Per node: the sampled requests with what they were served, and
    the retrieval comparison made while the node's shard is at hand."""
    rng = _rng(w.seed)
    top_k = int(w.cell.traffic["top_k"])
    out: Dict[int, List[dict]] = {}
    for node in w.nodes:
        n = node.node_id
        mine = [r for r in recs if r.node == n and r.ok]
        if not mine:
            out[n] = []
            continue
        longest = max(range(len(mine)),
                      key=lambda i: len(mine[i].prompt) + len(mine[i].tokens))
        pick = set(rng.choice(len(mine), size=min(PER_NODE, len(mine)),
                              replace=False).tolist()) | {longest}
        emb = np.asarray(node.index._emb, np.float64)
        payloads = {str(p): i for i, p in enumerate(node.index._payloads)}
        items = []
        for i in sorted(pick):
            r = mine[i]
            q = np.asarray(w.queries[r.idx].embedding, np.float64)
            items.append({"idx": r.idx, "prompt": list(r.prompt),
                          "tokens": list(r.tokens),
                          "retrieval_gap": retrieval_gap(
                              emb @ q, [payloads.get(c, -1)
                                        for c in r.contexts], top_k)})
        out[n] = items
    return out


def gaps(params, model: dict, prompt: List[int], tokens: List[int],
         t_pad: int, control: Optional[str] = None):
    """Per served token: the reference's best logit minus its logit of
    the served token.  With ``control`` (a lower precision), also the
    same gap for the token that the reference computed in that precision
    puts first, at the same positions of the same sequence."""
    import jax.numpy as jnp
    from perfbench import reference
    seq = list(prompt) + list(tokens[:-1])
    if len(seq) > t_pad:
        raise ValueError(f"sequence of {len(seq)} > reference length {t_pad}")
    toks = np.zeros((t_pad,), np.int32)
    toks[:len(seq)] = seq
    toks = jnp.asarray(toks)
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    lg = reference.logits(params, toks, model)[pos]
    best = jnp.max(lg, axis=-1)

    def gap_of(pick):
        return np.asarray(best - jnp.take_along_axis(
            lg, pick[:, None], axis=-1)[:, 0], np.float64)

    served = gap_of(jnp.asarray(np.asarray(tokens, np.int32)))
    if control is None:
        return served, None
    lq = reference.logits(params, toks, model, quant=control)[pos]
    return served, gap_of(jnp.argmax(lq, axis=-1))


def verify(cell, seed: int, sample: Dict[int, List[dict]], recs,
           nodes_meta, w, control: Optional[str] = None) -> dict:
    """Compare and decide ``correct``.  With ``control``, also read the
    control's gap (reported under ``control``, never part of
    ``correct``)."""
    from perfbench import spec, weights
    from perfbench.harness import derived_seed
    limits = cell.config.get("check", {}).get("limits", {})
    checked: Dict[str, dict] = {}
    ctrl: Dict[str, float] = {}
    vocab = {n: int(cell.config["nodes"][n]["model"]["vocab_size"])
             for n, _ in nodes_meta}
    bad = sum(1 for r in recs if r.answers != 1 or r.node < 0 or r.shed
              or not r.tokens or min(r.tokens) < 0
              or max(r.tokens) >= vocab.get(r.node, 0))
    checked["unanswered"] = {"value": bad, "limit": 0}
    checked["retrieval_gap"] = {
        "value": max([it["retrieval_gap"] for its in sample.values()
                      for it in its] or [0.0]),
        "limit": limits.get("retrieval_gap")}
    for n, arch in nodes_meta:
        node_spec = cell.config["nodes"][n]
        cfg = spec.model_config(node_spec)
        params = weights.make_params(cfg, derived_seed(seed, 100 + n))
        worst, worst_c = 0.0, 0.0
        for it in sample.get(n, []):
            g, gc = gaps(params, node_spec["model"], it["prompt"],
                         it["tokens"], w.max_len, control=control)
            worst = max(worst, float(g.max()) if g.size else 0.0)
            if gc is not None and gc.size:
                worst_c = max(worst_c, float(gc.max()))
        name = f"logit_gap.{arch}"
        checked[name] = {"value": worst, "limit": limits.get(name)}
        if control is not None:
            ctrl[name] = worst_c
        del params
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checked.values())
    out = {"correct": correct, "checked": checked}
    if control is not None:
        out["control"] = ctrl
    return out


# ------------------------------------------------------- planted faults


def print_checked(checked: Dict[str, dict]) -> None:
    """Each compared number beside its limit, as the run's last lines
    on standard error."""
    for name, c in checked.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)


def plant_fault(w, kind: str) -> None:
    """Break the timed path underneath (tests and the control script):

    * ``token``: one token of every answer altered where it is produced
      (in the decode segment's output, before the queue sees it);
    * ``drop_half``: half of the requests (odd ids) left out of dispatch;
    * ``retrieval``: each node answers from its lowest-ranked chunks.
    """
    if kind == "token":
        for node in w.nodes:
            sess = node._ensure_standing_queue()._ensure_session()
            seg = sess.run_segment
            vocab = node.engine.cfg.vocab_size

            def run_segment(*a, _seg=seg, _v=vocab, **k):
                ev = _seg(*a, **k)
                return [(s, ([(t[0] + 1) % _v] + list(t[1:])) if t else t)
                        for s, t in ev]
            sess.run_segment = run_segment
    elif kind == "drop_half":
        rt = w.runtime
        dispatch = rt._dispatch

        def half(queries, assign, slo_s):
            keep = [i for i, q in enumerate(queries) if q.qid % 2 == 0]
            res = dispatch([queries[i] for i in keep], assign[keep], slo_s)
            by = {r.qid: r for r in res}
            from repro.core.cluster import QueryResult
            return [by.get(q.qid) or QueryResult(q.qid, -1, "", 0.0, True)
                    for q in queries]
        rt._dispatch = half
    elif kind == "retrieval":
        for node in w.nodes:
            idx = node.index
            search = idx.search

            def worst(queries, k, _s=search, _i=idx):
                s, i = _s(queries, len(_i))
                return s[:, -k:], i[:, -k:]
            idx.search = worst
    else:
        raise ValueError(f"unknown fault {kind!r}")
