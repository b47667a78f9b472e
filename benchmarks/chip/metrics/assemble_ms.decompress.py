"""Host milliseconds per field scattering decoded tile interiors back
into whole fields: the engine.assemble span.  Nothing where the
program has no such span."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    if not r.spans_named("engine.assemble"):
        return None
    return per_field_ms(r, "decompress", ("engine.assemble",))
