"""Device-side halo exchange: per-tile face-neighbour tables.

The solve's halo rounds rebuild every tile's haloed view from the
current interiors of a resident batch (``device._halo_build``).  All it
needs to know is which tile lies across each face: a
:class:`~repro.engine.plan.TileLayout`'s padded extent is exactly
``grid × tile``, so a halo cell lies outside the padded field (the zero
border the legacy host path materialized) exactly where the
neighbouring tile does not exist.  Every halo cell is a face, edge or
corner of one of the 26 neighbouring tiles, and an edge or corner is
reached by following face neighbours one axis at a time, so a table of
six face-neighbour tile ids per tile is the whole plan constant.

Table layout, one int32 row per tile::

    (lo0, hi0, lo1, hi1, lo2, hi2, self)

the tile ids of the low and high face neighbour along axes 0, 1 and 2,
``-1`` where there is none, and the tile's own id, ``-1`` on the pad
rows that fill a group up to its resident capacity (their interiors
read 0, so pad tiles stay at the zero state).

Group tables: a compress group holds the concatenated tiles of several
fields.  Fields are independent (halos never cross fields), so the group
table is each field's table shifted by its tile offset, padded with
dead rows up to the group's resident capacity.  Tables depend only on
(layout sequence, capacity), so steady-state serving reuses them from an
LRU cache — they are plan constants, not per-request data, and take 28
bytes a tile (57 KB at capacity 2,048).
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from .plan import TileLayout

# Columns of a neighbour table: face neighbours, then the tile itself.
LO = (0, 2, 4)
HI = (1, 3, 5)
SELF = 6
N_COLS = 7


@lru_cache(maxsize=32)
def neighbor_tiles(layout: TileLayout) -> np.ndarray:
    """-> int32 (n_tiles, 7): face-neighbour tile ids of each tile (row-
    major grid order) along axes 0, 1, 2, low then high, ``-1`` at the
    padded field's faces, and the tile's own id."""
    g = layout.grid
    ids = np.arange(layout.n_tiles, dtype=np.int64).reshape(g)
    out = np.full(g + (N_COLS,), -1, np.int64)
    for a in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[a], hi[a] = slice(1, None), slice(None, -1)
        # the low neighbour of tile i along a is tile i - 1 there
        out[tuple(lo) + (LO[a],)] = ids[tuple(hi)]
        out[tuple(hi) + (HI[a],)] = ids[tuple(lo)]
    out[..., SELF] = ids
    return out.reshape(layout.n_tiles, N_COLS).astype(np.int32)


@lru_cache(maxsize=32)
def group_neighbors(layouts: tuple[TileLayout, ...],
                    capacity: int) -> np.ndarray:
    """Concatenated per-field tables padded to ``capacity`` rows.

    All layouts in a group share one tile shape (the engine groups by
    it); each field's ids are shifted by its tile offset so the build
    never crosses fields.  Pad rows are all ``-1``: pad tiles read 0
    everywhere, which keeps their subbins at 0.  The solve keeps subbins
    in int32, so a group is refused past 2^31 cells.
    """
    tile = layouts[0].tile
    tables = []
    off = 0
    for lay in layouts:
        if lay.tile != tile:
            raise ValueError("group layouts must share one tile shape")
        t = neighbor_tiles(lay)
        tables.append(np.where(t >= 0, t + off, -1))
        off += lay.n_tiles
    if off > capacity:
        raise ValueError(f"group of {off} tiles exceeds capacity {capacity}")
    if capacity * layouts[0].tile_elems > np.iinfo(np.int32).max:
        raise ValueError("resident group too large for int32 subbins")
    tables.append(np.full((capacity - off, N_COLS), -1, np.int32))
    return np.ascontiguousarray(np.concatenate(tables), dtype=np.int32)
