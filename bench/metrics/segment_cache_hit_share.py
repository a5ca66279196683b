"""Cross-session segment cache: hits / (hits + misses), in %."""


def read(r):
    c = r.counters
    total = c.get("cache_hits_total", 0.0) + c.get("cache_misses_total", 0.0)
    return 100.0 * c["cache_hits_total"] / total if total else None
