"""Host milliseconds per field in the engine call of a decode batch
(container parse, stream prep, assembly): the service.group span less
the executor's decode and download under it."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    return per_field_ms(r, "decompress", ("exec.decode", "exec.download"),
                        self_time=True)
