"""Plan cache: hits over hits + misses, as deltas over the window."""


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    hits = a.get("plan_cache_hits", 0) - b.get("plan_cache_hits", 0)
    miss = a.get("plan_cache_misses", 0) - b.get("plan_cache_misses", 0)
    return 100.0 * hits / (hits + miss) if hits + miss > 0 else None
