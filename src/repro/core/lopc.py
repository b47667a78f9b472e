"""LOPC public API (paper Algorithm 1, end to end).

    blob  = compress(field, eb=1e-2, mode="noa")
    field2 = decompress(blob)

Guarantees (tested):
  * |field - field2| <= eb (point-wise; NOA bounds are relative to range)
  * full local order under SoS => all critical points, exact locations
    and types, no spurious critical points
  * deterministic, schedule-independent bytes (CPU/GPU bit parity)

``preserve_order=False`` degrades LOPC to its underlying guaranteed-bound
quantizer + PFPL lossless pipeline (the paper's non-topology baseline
configuration; subbins all zero and skipped in the stream).

This module is a thin single-field wrapper over the tiled, batched
``repro.engine`` subsystem: ``compress`` writes v2 (tiled) containers
through the engine's shape-stable device programs, and ``decompress``
reads both container versions — v1 blobs written by earlier releases
decode unchanged through the retained legacy path.  Pass
``container_version=1`` to emit the legacy whole-field format.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..codecs import pipeline
from . import bitstream
from .quantize import (
    abs_bound_from_mode,
    bin_dtype_for,
    check_eps,
    dequantize,
    quantize,
)
from .subbin import solve_subbins

TAG_BINS = bitstream.TAG_BINS
TAG_SUBBINS = bitstream.TAG_SUBBINS
TAG_NONFINITE = bitstream.TAG_NONFINITE

FLAG_ORDER_PRESERVING = bitstream.FLAG_ORDER_PRESERVING
FLAG_HAS_NONFINITE = bitstream.FLAG_HAS_NONFINITE

__all__ = ["CompressStats", "compress", "decompress", "compression_ratio"]


@dataclass
class CompressStats:
    raw_bytes: int
    total_bytes: int
    bin_bytes: int
    subbin_bytes: int
    header_bytes: int
    n_sweeps: int
    eps_abs: float

    @property
    def ratio(self) -> float:
        return self.raw_bytes / self.total_bytes


def encode_nonfinite(x: np.ndarray):
    """Sidecar for NaN/Inf cells (real scientific data uses NaN fill
    values — climate ocean masks etc). Cells are replaced by the finite
    mean for compression and restored BIT-EXACTLY on decode. The paper's
    order/critical-point guarantees apply to the finite-filled field
    (comparisons with NaN are undefined in the source data anyway)."""
    mask = ~np.isfinite(x)
    finite = x[~mask]
    fill = finite.mean() if finite.size else 0.0
    w = bitstream.Writer()
    packed = np.packbits(mask.reshape(-1))
    w.lp(packed.tobytes())
    w.lp(np.ascontiguousarray(x[mask]).tobytes())  # exact payloads
    filled = x.copy()
    filled[mask] = fill
    return filled, w.getvalue()


def decode_nonfinite(payload: bytes, out: np.ndarray) -> np.ndarray:
    r = bitstream.Reader(payload)
    packed = np.frombuffer(r.lp(), np.uint8)
    vals = np.frombuffer(r.lp(), out.dtype)
    mask = np.unpackbits(packed, count=out.size).astype(bool).reshape(out.shape)
    out = out.copy()
    out[mask] = vals
    return out


# the engine is imported lazily inside compress/decompress: core.lopc is
# a leaf module the engine itself depends on (stats + sidecar helpers)

def compress(
    field: np.ndarray,
    eb: float,
    mode: str = "noa",
    preserve_order: bool = True,
    solver: str = "auto",
    return_stats: bool = False,
    container_version: int = bitstream.VERSION_TILED,
    plan=None,
):
    """Compress a 1/2/3-D scalar field. Returns bytes (and stats)."""
    if container_version == bitstream.VERSION_TILED:
        from .. import engine as _engine

        return _engine.compress(
            field, eb, mode, preserve_order, solver,
            plan=plan, return_stats=return_stats,
        )
    if container_version != bitstream.VERSION:
        raise ValueError(f"unknown container version {container_version}")
    return _compress_v1(field, eb, mode, preserve_order, solver, return_stats)


def _compress_v1(field, eb, mode, preserve_order, solver, return_stats):
    """Legacy whole-field v1 writer (kept for byte compatibility and as
    the reference implementation the engine is tested bit-identical to).
    """
    import jax.numpy as jnp

    x = np.asarray(field)
    if x.dtype not in (np.float32, np.float64):
        raise ValueError(f"LOPC compresses float32/float64 fields, got {x.dtype}")
    if x.ndim not in (1, 2, 3):
        raise ValueError(f"LOPC supports 1D/2D/3D grids, got ndim={x.ndim}")
    if eb <= 0:
        raise ValueError("error bound must be positive")
    nonfinite_payload = None
    if not np.isfinite(x).all():
        x, nonfinite_payload = encode_nonfinite(x)

    eps_abs = abs_bound_from_mode(x, eb, mode)
    check_eps(x, eps_abs)

    xj = jnp.asarray(x)
    bins = quantize(xj, eps_abs)
    n_sweeps = 0
    flags = 0
    sections = {}
    if preserve_order:
        subbins, sweeps = solve_subbins(bins, xj, method=solver)
        n_sweeps = int(sweeps)
        flags |= FLAG_ORDER_PRESERVING
        sections[TAG_SUBBINS] = pipeline.encode_subbins(subbins)
    sections[TAG_BINS] = pipeline.encode_bins(bins)
    if nonfinite_payload is not None:
        flags |= FLAG_HAS_NONFINITE
        sections[TAG_NONFINITE] = nonfinite_payload

    header = bitstream.Header(
        dtype=x.dtype,
        shape=x.shape,
        eb_mode=mode,
        eb=float(eb),
        eps_abs=float(eps_abs),
        flags=flags,
    )
    blob = bitstream.write_container(header, sections)
    if not return_stats:
        return blob
    stats = CompressStats(
        raw_bytes=x.nbytes,
        total_bytes=len(blob),
        bin_bytes=len(sections[TAG_BINS]),
        subbin_bytes=len(sections.get(TAG_SUBBINS, b"")),
        header_bytes=len(blob) - sum(len(s) for s in sections.values()),
        n_sweeps=n_sweeps,
        eps_abs=eps_abs,
    )
    return blob, stats


def decompress(blob: bytes) -> np.ndarray:
    """Reconstruct the field; embarrassingly parallel (paper §IV-D).

    Dispatches on the container version byte: v2 (tiled) decodes through
    the engine's per-tile section table; v1 through the legacy
    whole-field path.
    """
    version = bitstream.container_version(blob)
    if version == bitstream.VERSION_TILED:
        from .. import engine as _engine

        return _engine.decompress(blob)
    if version == bitstream.VERSION_CHAIN:
        from .. import temporal as _temporal

        return _temporal.decompress_chain(blob)  # (n_frames, *shape)
    return _decompress_v1(blob)


def _decompress_v1(blob: bytes) -> np.ndarray:
    import jax.numpy as jnp

    header, sections = bitstream.read_container(blob)
    n = int(np.prod(header.shape))
    bdt = bin_dtype_for(header.dtype)
    bins = pipeline.decode_bins(sections[TAG_BINS], n, header.shape, bdt)
    if header.flags & FLAG_ORDER_PRESERVING:
        subbins = pipeline.decode_subbins(sections[TAG_SUBBINS], n, header.shape, bdt)
    else:
        subbins = np.zeros(header.shape, bdt)
    out = np.asarray(
        dequantize(jnp.asarray(bins), jnp.asarray(subbins), header.eps_abs, header.dtype)
    )
    if header.flags & FLAG_HAS_NONFINITE:
        out = decode_nonfinite(sections[TAG_NONFINITE], out)
    return out


def compression_ratio(field: np.ndarray, eb: float, mode: str = "noa", **kw) -> float:
    _, stats = compress(field, eb, mode, return_stats=True, **kw)
    return stats.ratio
