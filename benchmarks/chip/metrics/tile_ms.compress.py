"""Host milliseconds per field building the haloed tile batch: the
engine.tile span (NaN-bordered padding, halo tiles, per-tile bounds)
plus exec.pack (halo index tables and padding to the batch capacity).
Nothing where the program has no such spans."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    if not r.spans_named("engine.tile"):
        return None
    return per_field_ms(r, "compress", ("engine.tile", "exec.pack"))
