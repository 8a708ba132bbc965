"""Caller side (the benchmark's open-loop front loop): the 95th
percentile over every completed request of the window of
due time -> the answer returned by ``run_slot``, in ms."""
from perfbench import timeline


def read(run):
    return timeline.tails(run["recs"], 95.0)["latency"]
