"""Milliseconds per field in the fenced exec.decode stage: stream
upload and the fused or staged decode."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    return per_field_ms(r, "decompress", ("exec.decode",))
