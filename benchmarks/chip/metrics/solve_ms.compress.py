"""Milliseconds per field in the fenced exec.solve stage: quantize,
order flags and the subbin solve."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    return per_field_ms(r, "compress", ("exec.solve",))
