"""Compilations during set-up that found their program in JAX's persistent
cache, over those that looked (the program's compile-cache counters)."""


def read(rec):
    c = rec["counters"]
    if not c.get("setup.cache_requests"):
        return None
    return 100.0 * c["setup.cache_hits"] / c["setup.cache_requests"]
