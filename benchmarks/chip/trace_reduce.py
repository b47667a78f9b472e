"""Reduce a ``jax.profiler`` trace to device busy time and a breakdown.

Busy time is the union of the intervals in which an operation ran on
a device (the ``XLA Ops`` line of each ``/device:...`` plane), clipped
to the traced window and averaged over the devices.  The idle gaps are
the rest of the window; each is labelled by the program span that was
innermost on the host at its midpoint, so the breakdown says what the
host was doing while the device waited.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"/device:[A-Z]+:\d+")
DEVICE_OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
TOP = 10


def load(profile_dir: str):
    """The ``ProfileData`` of the one trace written under ``profile_dir``."""
    import jax

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"want one trace under {profile_dir}, found "
                         f"{len(paths)}")
    return jax.profiler.ProfileData.from_file(paths[0])


def events(profile):
    """Every event as ``(plane, line, name, start_ns, end_ns)``."""
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, e.start_ns,
                       e.start_ns + e.duration_ns)


def device_ops(evs) -> dict[str, list[tuple[str, float, float]]]:
    """Device plane -> its operations as ``(name, start, end)``.

    An operation is named ``module/op``: the program (the ``XLA
    Modules`` event around it, without its fingerprint) and the HLO
    instruction's name (without its shapes), so the breakdown reads
    across programs.  A loop and the operations in its body are both
    operations: their times overlap."""
    ops: dict[str, list] = defaultdict(list)
    modules: dict[str, list] = defaultdict(list)
    for plane, line, name, a, b in evs:
        if not DEVICE_PLANE.fullmatch(plane):
            continue
        if line in DEVICE_OP_LINES:
            ops[plane].append((name.split(" = ")[0], a, b))
        elif line == MODULE_LINE:
            modules[plane].append((a, b, name.split("(")[0]))
    out = {}
    for plane, found in ops.items():
        mods = sorted(modules.get(plane, []))
        starts = [m[0] for m in mods]
        named = []
        for op, a, b in found:
            k = bisect.bisect_right(starts, a) - 1
            inside = k >= 0 and mods[k][0] <= a <= mods[k][1]
            named.append((f"{mods[k][2]}/{op}" if inside else op, a, b))
        out[plane] = named
    return out


def annotation(evs, name: str) -> tuple[float, float]:
    """``(start, end)`` of the first host event called ``name``."""
    for plane, _line, ev, a, b in evs:
        if ev == name and not DEVICE_PLANE.fullmatch(plane):
            return a, b
    raise ValueError(f"no event {name!r} in the trace")


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, clipped to [lo, hi], as
    sorted disjoint intervals."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(intervals, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for a, b in merged(intervals, lo, hi):
        if a > t:
            out.append((t, a))
        t = b
    if hi > t:
        out.append((t, hi))
    return out


def top_ops(ops, lo: float, hi: float, n_devices: int = 1,
            k: int = TOP) -> list[list]:
    """The ``k`` operation names with the most device time in [lo, hi],
    as ``[name, seconds]`` averaged over the devices."""
    total: dict[str, float] = defaultdict(float)
    for name, a, b in ops:
        total[name] += max(0.0, min(b, hi) - max(a, lo))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / n_devices] for name, ns in ranked]


def label_gaps(gap_list, spans, k: int = TOP) -> list[list]:
    """Idle seconds summed by what the host was doing, the ``k`` largest.

    ``spans`` are ``(name, start, end)`` on the trace's clock; a gap
    takes the name of the shortest span covering its midpoint (the
    innermost one), or ``"no span"`` where none does."""
    names = [name for name, _, _ in spans]
    starts = np.array([s for _, s, _ in spans], np.float64)
    ends = np.array([e for _, _, e in spans], np.float64)
    total: dict[str, float] = defaultdict(float)
    for a, b in gap_list:
        mid = (a + b) / 2
        cover = np.flatnonzero((starts <= mid) & (mid <= ends))
        label = (names[cover[np.argmin(ends[cover] - starts[cover])]]
                 if cover.size else "no span")
        total[label] += b - a
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def summarize(evs, window: tuple[float, float], spans=()) -> dict | None:
    """Busy and window seconds and the breakdown of one traced window,
    or ``None`` where the trace holds no device operation."""
    evs = list(evs)
    per_device = device_ops(evs)
    if not per_device:
        return None
    lo, hi = window
    n = len(per_device)
    busy_ns = sum(busy([(a, b) for _, a, b in ops], lo, hi)
                  for ops in per_device.values()) / n
    all_ops = [op for ops in per_device.values() for op in ops]
    # idle gaps of the first device: on one chip, the only one
    first = per_device[sorted(per_device)[0]]
    idle = gaps([(a, b) for _, a, b in first], lo, hi)
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": top_ops(all_ops, lo, hi, n),
        "idle_gaps": label_gaps(idle, spans),
    }
