"""Host milliseconds per field parsing containers for a decode batch:
the engine.parse spans (container headers and tile tables, then the
per-tile work list with its bounds).  Nothing where the program has no
such span."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    if not r.spans_named("engine.parse"):
        return None
    return per_field_ms(r, "decompress", ("engine.parse",))
