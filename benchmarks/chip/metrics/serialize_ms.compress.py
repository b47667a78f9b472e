"""Host milliseconds per field serializing tile sections and writing
the containers: the engine.serialize span.  Nothing where the program
has no such span."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    if not r.spans_named("engine.serialize"):
        return None
    return per_field_ms(r, "compress", ("engine.serialize",))
