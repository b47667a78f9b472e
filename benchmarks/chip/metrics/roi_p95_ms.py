"""95th percentile latency of every region read due in the window,
timed from its due time."""
from benchmarks.chip.readers import percentile_ms


def read(r):
    return percentile_ms(r, 95)
