"""Arithmetic shared by the metric readers in ``metrics/``."""
from __future__ import annotations

FAILED_MS = 1e9   # latency of a request that failed: beyond every limit


def per_field_ms(r, kind: str, stages: tuple[str, ...],
                 self_time: bool = False) -> float | None:
    """Milliseconds per request of the service's ``kind`` groups spent
    in ``stages`` (or, with ``self_time``, outside them)."""
    groups = r.spans_named("service.group", kind=kind)
    n = sum(int(g.tags.get("n_requests", 1)) for g in groups)
    if not n:
        return None
    inside = sum(s.dur_us for g in groups for s in r.descendants(g, stages))
    total = sum(g.dur_us for g in groups) - inside if self_time else inside
    return total / 1e3 / n


def step_roofline(r) -> float | None:
    """Percent of the HBM roofline: the bytes every implementation must
    read and write for the work done in the traced window, over the
    chip's bandwidth, against the device's busy time."""
    if r.trace is None or not r.trace["busy_s"] or not r.traced_bytes:
        return None
    least_s = r.traced_bytes / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / r.trace["busy_s"]


def idle_share(r) -> float | None:
    if r.trace is None or not r.trace["window_s"]:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])


def mb_per_s(r, direction: str) -> float | None:
    done = r.completed()
    if not done or r.window_s <= 0:
        return None
    nbytes = sum(getattr(q, direction) for q in done)
    return nbytes / 1e6 / r.window_s


def percentile_ms(r, q: float) -> float | None:
    """Nearest-rank percentile of every request's latency from its due
    time; a failed or lost request counts as ``FAILED_MS``."""
    reqs = r.window.requests
    if not reqs:
        return None
    lat = sorted((x.t_done - x.t_due) * 1e3 if x.ok else FAILED_MS
                 for x in reqs)
    return lat[max(0, -(-len(lat) * q // 100) - 1)]
