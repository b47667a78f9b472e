"""Percent of the HBM roofline of the whole decode step: container and
output bytes over 819 GB/s, against the device busy time."""
from benchmarks.chip.readers import step_roofline as read  # noqa: F401
