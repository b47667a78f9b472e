"""Percent of the tiles that region reads asked of the store's decoded
tile cache that it held (TileCache hits over hits and misses)."""


def read(r):
    hits, misses = r.counters.get("cache_hits", 0), r.counters.get(
        "cache_misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None
