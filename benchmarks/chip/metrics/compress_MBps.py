"""Input MB of the compress requests completed in the window, over
the window."""
from benchmarks.chip.readers import mb_per_s


def read(r):
    return mb_per_s(r, "in_bytes")
