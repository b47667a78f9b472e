"""Host milliseconds per field in the engine call of a compress
batch (request checks, plan, haloed tiling, serialization): the
service.group span less the executor stages under it."""
from benchmarks.chip.readers import per_field_ms

STAGES = ("exec.upload", "exec.solve", "exec.encode", "exec.download")


def read(r):
    return per_field_ms(r, "compress", STAGES, self_time=True)
