"""Metric arithmetic over the caller-side request records of one window.

Every time is on the host's ``perf_counter`` clock, in seconds after the
window opened.  A request's clock starts at its *due* time, so a stall
that delays later dispatches shows in every later request's latency.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Record:
    idx: int                       # schedule position (= qid)
    due: float                     # due time
    dispatch: float = -1.0         # run_slot call that carried it began
    node: int = -1                 # node that answered
    node_start: float = -1.0       # that node's process_slot began
    first: float = -1.0            # first token on the host
    done: float = -1.0             # last token on the host
    ret: float = -1.0              # run_slot returned the answer
    tokens: List[int] = field(default_factory=list)
    prompt: List[int] = field(default_factory=list)
    prefix_len: int = 0
    contexts: List[str] = field(default_factory=list)
    shed: bool = False
    answers: int = 0               # results returned for this request

    @property
    def ok(self) -> bool:
        return self.answers == 1 and not self.shed and len(self.tokens) > 0


def pct(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile over all values (None if empty)."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def tails(recs: Sequence[Record], q: float = 95.0) -> dict:
    """Tails over every completed request of the window, in ms."""
    done = [r for r in recs if r.ok]
    tpot = [(r.done - r.first) / (len(r.tokens) - 1) for r in done
            if len(r.tokens) > 1]
    return {
        "latency": _ms(pct([r.ret - r.due for r in done], q)),
        "ttft": _ms(pct([r.first - r.due for r in done], q)),
        "tpot": _ms(pct(tpot, q)),
    }


def latency_mean_ms(recs: Sequence[Record]) -> Optional[float]:
    """Mean over every completed request of the window of due time ->
    the answer returned by ``run_slot``, in ms."""
    lat = [r.ret - r.due for r in recs if r.ok]
    return 1e3 * sum(lat) / len(lat) if lat else None


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def output_tok_s(recs: Sequence[Record], span: float) -> float:
    """Tokens of every request dispatched in the window over the span
    from the window's opening until the last of them was answered."""
    return sum(len(r.tokens) for r in recs if r.ok) / span


def attainment(recs: Sequence[Record], latency_s: float, ttft_s: float
               ) -> float:
    """Share of dispatched requests that met both limits (a failed
    request misses)."""
    if not recs:
        return 0.0
    met = sum(1 for r in recs if r.ok and r.ret - r.due <= latency_s
              and r.first - r.due <= ttft_s)
    return met / len(recs)
