"""Host milliseconds per field admitting compress requests: the
engine.admit span (input checks, the finite scan, the bound and the
value range of each field).  Nothing where the program has no such
span."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    if not r.spans_named("engine.admit"):
        return None
    return per_field_ms(r, "compress", ("engine.admit",))
