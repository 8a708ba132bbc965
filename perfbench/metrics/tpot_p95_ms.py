"""Caller side (the benchmark's open-loop front loop): the 95th
percentile over every completed request of the window of
(last token - first token) / (tokens - 1), in ms."""
from perfbench import timeline


def read(run):
    return timeline.tails(run["recs"], 95.0)["tpot"]
