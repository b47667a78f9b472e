"""Milliseconds per field in the executor's upload and download of a
compress batch (the download includes the on-device compaction)."""
from benchmarks.chip.readers import per_field_ms


def read(r):
    return per_field_ms(r, "compress", ("exec.upload", "exec.download"))
