"""Topology-adaptive per-tile error-bound assignment (ROADMAP item).

The compressor preserves full local order at *any* eps (the ordered-
space solve enforces every SoS neighbor relation exactly, independent of
the bound), so the eb budget is purely a rate/distortion knob: TopoSZp
and Soler et al. spend bits where they buy resolvable structure and
loosen the bound where they cannot.  This module scores each tile of a
field and maps it to a rung of the quantized eb ladder ``{eb_user *
2**(k_max - k), k in 0..k_max}`` serialized per tile in the container
(``core.bitstream``, TAG_EB_LADDER; rung ``k_max`` is the user bound,
rung 0 is ``2**k_max`` times looser):

- *noise-dominated* tiles (critical-cell density >= DENSE_TOPOLOGY)
  hold dense low-persistence topology — pure sampling noise to the
  pointwise metric, yet preserved exactly by the solve regardless of
  eps.  Bits spent resolving below the tile's noise scale (the median
  absolute second difference) buy nothing, so the bound may grow to
  ``NOISE_FRACTION`` of that scale;
- *featureless* tiles (no critical cell at all) carry monotone flow
  with no topology to pin down; the bound may grow to
  ``RELIEF_FRACTION`` of the tile's own relief;
- tiles with *sparse* criticality (isolated persistent extrema in
  otherwise smooth data — the features the paper protects) get half
  the featureless allowance: one ladder rung of extra protection,
  scaled to the relief the feature actually spans.

Each doubling of the resulting per-tile floor over the user bound
loosens one rung.  A final *boundary-tightening* pass re-runs the exact
quantizer arithmetic (the same ``core.quantize`` op sequence the device
executes) and tightens any tile whose looser grid would place a decode
anchor on the wrong side of a neighboring tile's anchor: with mixed
rungs, two adjacent cells can straddle tile boundaries whose bases
interleave against their SoS order, and the ordered-space solve would
then have to move a value by up to one coarse bin — a float-space
nudge, but an *ordered-space* jump of ~eps/ulp (>2**40 for f64) that
forces the whole subbin stream to a wide word.  Tightening the looser
tile until every cross-eps boundary pair's anchors agree with SoS order
keeps every subbin a tie-separation counter, exactly as in uniform
mode.

The scorer is pure host-side numpy plus jitted calls into the *same*
``core.quantize``/``tda.critpoints`` kernels the compressor uses — a
deterministic function of (field, layout, eb) alone, so ladder indices
(and hence container bytes) are invariant to batch composition, solver
schedule, and encode path.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import numpy as np

from ..core.bitstream import EB_LADDER_K_MAX
from ..core.quantize import (
    decode_base,
    effective_eps,
    eps_operand,
    quantize_broadcast,
)
from ..core.topology import offsets
from .critpoints import CLASS_REGULAR, classify_critical_points

ADAPTIVE_EB_MODES = ("off", "tda")

# Critical-cell density at or above which a tile's topology is treated
# as noise-dominated (iid noise classifies ~half of all cells critical;
# real isolated features sit orders of magnitude below this).
DENSE_TOPOLOGY = 0.02
# Noise-dominated tiles: eps may grow to this fraction of the tile's
# noise scale (median |second difference|; ~1.65 sigma for iid noise).
NOISE_FRACTION = 0.6
# Featureless tiles: eps may grow to this fraction of the tile relief.
RELIEF_FRACTION = 1.0 / 16.0
# Tiles holding sparse persistent criticals: half the featureless
# allowance — one ladder rung of extra protection for real features.
CRITICAL_RELIEF_FRACTION = RELIEF_FRACTION / 2.0


def _tile_ids(layout) -> np.ndarray:
    """(canonical) int array: row-major tile id of every real cell."""
    g, t = layout.grid, layout.tile
    c = layout.canonical
    i0 = np.arange(c[0]) // t[0]
    i1 = np.arange(c[1]) // t[1]
    i2 = np.arange(c[2]) // t[2]
    return (i0[:, None, None] * g[1] + i1[None, :, None]) * g[2] \
        + i2[None, None, :]


def _tile_blocks(vol: np.ndarray, layout) -> np.ndarray:
    """(n_tiles, -1) view of ``vol`` (leading-dim stack over canonical
    shape), NaN-padded so partial edge tiles pool only real cells."""
    lead = vol.shape[0]
    g, t = layout.grid, layout.tile
    c = layout.canonical
    pad = np.full((lead,) + tuple(layout.padded), np.nan)
    pad[:, : c[0], : c[1], : c[2]] = vol
    b = pad.reshape(lead, g[0], t[0], g[1], t[1], g[2], t[2])
    return b.transpose(1, 3, 5, 0, 2, 4, 6).reshape(layout.n_tiles, -1)


def tile_relief(x3: np.ndarray, layout) -> tuple[np.ndarray, np.ndarray]:
    """Per-tile (min, max) over real (in-field) cells, row-major grid
    order.  ``x3`` is the canonical 3-D view of the field."""
    blocks = _tile_blocks(x3[None], layout)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tmin = np.nanmin(blocks, axis=1)
        tmax = np.nanmax(blocks, axis=1)
    return tmin, tmax


def tile_noise_scale(x3: np.ndarray, layout) -> np.ndarray:
    """Per-tile noise scale: median |second difference| pooled over all
    axes.  Robust to the tile's large-scale structure (a front or bump
    occupies few cells, the median sees the noise floor around it)."""
    c = layout.canonical
    d2 = np.full((3,) + tuple(c), np.nan)
    for ax in range(3):
        if c[ax] >= 3:
            sl = [slice(None)] * 3
            sl[ax] = slice(1, c[ax] - 1)
            d2[(ax, *sl)] = np.abs(np.diff(x3, 2, axis=ax))
    blocks = _tile_blocks(d2, layout)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        med = np.nanmedian(blocks, axis=1)
    return np.nan_to_num(med)


def critical_counts(x: np.ndarray, layout) -> tuple[np.ndarray, np.ndarray]:
    """Per-tile (critical-cell count, real-cell count).

    Classification runs on the field at its *original* ndim (the
    canonical-3D unit axes would add no constraints, but the native form
    keeps this identical to the TDA quality metrics' view).
    """
    cls = np.asarray(classify_critical_points(np.asarray(x)))
    crit3 = (cls != CLASS_REGULAR).reshape(layout.canonical)
    tid = _tile_ids(layout)
    counts = np.bincount(tid[crit3].ravel(), minlength=layout.n_tiles)
    cells = np.bincount(tid.ravel(), minlength=layout.n_tiles)
    return counts, cells


def critical_tiles(x: np.ndarray, layout) -> np.ndarray:
    """(n_tiles,) bool: does the tile contain any critical cell?"""
    counts, _ = critical_counts(x, layout)
    return counts > 0


@partial(jax.jit, static_argnames=("dtype",))
def _anchor_impl(x, eps_cell, dtype):
    """Bins + decode anchors under the exact device op sequence."""
    b = quantize_broadcast(x, eps_cell, dtype)
    return decode_base(b, eps_cell, dtype)


def _boundary_violation_tiles(base: np.ndarray, x3: np.ndarray,
                              eps_cell: np.ndarray,
                              tid: np.ndarray) -> np.ndarray:
    """Tiles whose looser grid breaks SoS anchor order at a boundary.

    For every Freudenthal neighbor pair straddling tiles with different
    eps: the pair's decode anchors must not be ordered against the
    values (a strict inversion), and exact value ties must share an
    anchor (else the tie-break forces an eps-scale move).  Returns a
    bool mask over tiles — for each offending pair, the looser side.
    """
    n_tiles = int(tid.max()) + 1
    tighten = np.zeros(n_tiles, bool)
    offs = offsets(3)
    for off in offs[: len(offs) // 2]:  # each unordered pair once
        sa = tuple(slice(None) if d == 0
                   else (slice(None, -d) if d > 0 else slice(-d, None))
                   for d in off)
        sb = tuple(slice(None) if d == 0
                   else (slice(d, None) if d > 0 else slice(None, d))
                   for d in off)
        ea, eb_ = eps_cell[sa], eps_cell[sb]
        cross = ea != eb_
        if not cross.any():
            continue
        xa, xb = x3[sa], x3[sb]
        ba, bb = base[sa], base[sb]
        viol = cross & (((xa < xb) & (ba > bb)) | ((xa > xb) & (ba < bb))
                        | ((xa == xb) & (ba != bb)))
        if not viol.any():
            continue
        loose_tid = np.where(ea > eb_, tid[sa], tid[sb])[viol]
        tighten |= np.bincount(loose_tid, minlength=n_tiles) > 0
    return tighten


def tighten_ladder(x: np.ndarray, layout, ladder: np.ndarray,
                   eps_abs: float,
                   k_max: int = EB_LADDER_K_MAX) -> np.ndarray:
    """Raise rungs until no cross-eps tile boundary inverts anchors.

    ``eps_abs`` is the user's absolute bound (the tightest rung).  Runs
    the exact quantize/decode-base arithmetic of the device pipeline, so
    "no violation" here is "no violation" there.  Monotone (never
    loosens) and convergent: all-equal rungs have no cross-eps pairs.
    """
    ladder = np.asarray(ladder, np.uint8).copy()
    x3 = np.ascontiguousarray(np.asarray(x).reshape(layout.canonical))
    tid = _tile_ids(layout)
    eps_tight = effective_eps(eps_abs)
    for _ in range(k_max + 1):
        if (ladder == ladder[0]).all():
            break
        eps_tiles = eps_tight * np.exp2(k_max - ladder.astype(np.float64))
        eps_cell = eps_tiles[tid]
        base = np.asarray(_anchor_impl(x3, eps_operand(eps_cell), x3.dtype))
        tighten = _boundary_violation_tiles(base, x3, eps_cell, tid)
        tighten &= ladder < k_max
        if not tighten.any():
            break
        ladder[tighten] += 1
    return ladder


def ladder_indices(x: np.ndarray, layout, eps_abs: float,
                   k_max: int = EB_LADDER_K_MAX) -> np.ndarray:
    """(n_tiles,) uint8 eb-ladder index per tile (k_max = tightest).

    ``eps_abs`` is the user's absolute bound — rung ``k_max`` exactly.
    Constant fields (zero range) take rung 0 everywhere: there is no
    topology to protect, so the loosest bound is safe by construction.
    """
    x = np.asarray(x)
    if not np.all(np.isfinite(x)):
        raise ValueError("adaptive-eb scoring requires a finite field "
                         "(strip non-finite cells first)")
    x3 = np.asarray(x, np.float64).reshape(layout.canonical)
    rng = float(x3.max()) - float(x3.min())
    idx = np.zeros(layout.n_tiles, np.uint8)
    if rng == 0.0 or k_max == 0:
        return idx
    tmin, tmax = tile_relief(x3, layout)
    counts, cells = critical_counts(x, layout)
    sigma = tile_noise_scale(x3, layout)

    relief = tmax - tmin
    dense = counts >= DENSE_TOPOLOGY * cells
    flat = counts == 0
    floor = np.where(flat, RELIEF_FRACTION * relief,
                     CRITICAL_RELIEF_FRACTION * relief)
    floor[dense] = np.maximum(floor[dense], NOISE_FRACTION * sigma[dense])

    loose = np.zeros(layout.n_tiles, np.int64)
    grows = floor > float(eps_abs)
    loose[grows] = np.clip(
        np.floor(np.log2(floor[grows] / float(eps_abs))).astype(np.int64),
        0, k_max)
    idx[:] = (k_max - loose).astype(np.uint8)
    return tighten_ladder(x, layout, idx, eps_abs, k_max)
