"""Median milliseconds a region read waits in the service before its
batch starts (the queue_ms tag of its service.request span)."""
import statistics


def read(r):
    waits = [s.tags["queue_ms"] for s in r.spans_named("service.request",
                                                        kind="store_roi")
             if "queue_ms" in s.tags]
    return statistics.median(waits) if waits else None
