"""The numbers the ops compare against the plain reference."""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import census


def bound(x: np.ndarray, eb: float, mode: str) -> float:
    """The pointwise bound a container promises: ``eb`` itself, or
    ``eb`` times the value range (``noa``)."""
    if mode == "abs":
        return float(eb)
    if mode == "noa":
        return float(eb) * (float(x.max()) - float(x.min()))
    raise ValueError(f"unknown error-bound mode {mode!r}")


def bound_ratio(x: np.ndarray, y: np.ndarray, limit: float) -> float:
    """Largest pointwise error over the bound (at most 1 when it holds)."""
    if x.shape != y.shape:
        return float("inf")
    err = np.abs(x.astype(np.float64) - y.astype(np.float64)).max()
    return float(err) / limit


def bits_differ(got: np.ndarray, want: np.ndarray) -> int:
    """Values whose bits differ (every value, where shapes differ)."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size)
    w = f"u{want.dtype.itemsize}"
    return int(np.count_nonzero(got.view(w) != want.view(w)))


def field_numbers(x: np.ndarray, y: np.ndarray, limit: float) -> dict:
    """The guarantees a decoded field is held to, against its input."""
    return {"bound_ratio": bound_ratio(x, y, limit),
            "order_flips": census.order_flips(x, y) if x.shape == y.shape
            else int(x.size)}


def worst(rows: list[dict], limits: dict) -> list[tuple[str, float, float]]:
    """``(name, value, limit)``: counts summed, ratios at their largest."""
    out = []
    for name, limit in limits.items():
        vals = [r[name] for r in rows]
        if name.endswith("_ratio"):
            value = max(vals) if vals else 0.0
        else:
            value = int(sum(vals))
        out.append((name, value, limit))
    return out

