"""KV pool and prefix cache (``models/cache.py``,
``serving/prefix_cache.py``): prefix-cache hits over lookups in the
window, from the queues' ``ContinuousStats`` counters, in %."""


def read(run):
    d = run["delta"]
    look = d["prefix_hits"] + d["prefix_misses"]
    return 100.0 * d["prefix_hits"] / look if look else None
