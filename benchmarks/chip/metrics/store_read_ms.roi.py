"""Mean milliseconds of a store.read span: the batched read of the
cache-miss tiles of the region reads in one service batch."""


def read(r):
    reads = r.spans_named("store.read", op="roi")
    return sum(s.dur_us for s in reads) / 1e3 / len(reads) if reads else None
