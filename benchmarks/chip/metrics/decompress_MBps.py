"""Output MB of the full decodes completed in the window, over the
window."""
from benchmarks.chip.readers import mb_per_s


def read(r):
    return mb_per_s(r, "out_bytes")
