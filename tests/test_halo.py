"""The solve's halo build against the host tile oracle.

``device._halo_build`` rebuilds every resident tile's haloed view from
the batch's interiors and the per-tile neighbour table of
``engine.halo``.  The oracle is the legacy host path: scatter the
interiors into a zero-bordered padded field, then cut the haloed tiles
back out (``plan.scatter_interiors`` + ``plan.extract_halo_tiles``).
Pad rows up to the resident capacity must read 0 everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import device, executor, halo
from repro.engine.plan import (
    CompressionPlan,
    TileLayout,
    extract_halo_tiles,
    scatter_interiors,
)


def _canonical(shape, tile=(4, 4, 8)):
    return (CompressionPlan(tile_shape=tile).layout_for(shape),)


def _raw(grid, tile=(2, 3, 4)):
    padded = tuple(g * t for g, t in zip(grid, tile))
    return TileLayout(padded, padded, tile, grid)


GROUPS = {
    "1d": _canonical((37,), tile=(1, 1, 8)),
    "2d": _canonical((13, 22)),
    "3d": _canonical((9, 10, 21)),
    "single-tile": _canonical((3, 4, 5)),
    "multi-field": (_raw((3, 2, 4)), _raw((1, 1, 1)), _raw((2, 5, 1))),
}


def _oracle(cur: np.ndarray, layouts, capacity: int) -> np.ndarray:
    out = np.zeros((capacity,) + layouts[0].halo_tile, cur.dtype)
    off = 0
    for lay in layouts:
        padded_b = np.zeros(tuple(p + 2 for p in lay.padded), cur.dtype)
        scatter_interiors(cur[off:off + lay.n_tiles], lay, padded_b)
        out[off:off + lay.n_tiles] = extract_halo_tiles(padded_b, lay)
        off += lay.n_tiles
    return out


def _state(rng, layouts, capacity, dtype):
    shape = (capacity,) + layouts[0].tile
    top = np.iinfo(dtype).max
    # pad rows get non-zero state too: the build must zero them
    return rng.integers(1, top, size=shape, dtype=dtype, endpoint=True)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.uint64])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_halo_build_matches_host_oracle(rng, group, dtype):
    layouts = GROUPS[group]
    n = sum(lay.n_tiles for lay in layouts)
    capacity = executor.resident_capacity(n, 4) + 3
    cur = _state(rng, layouts, capacity, dtype)
    nbr = halo.group_neighbors(layouts, capacity)
    got = jax.jit(device._halo_build)(jnp.asarray(cur), jnp.asarray(nbr))
    assert got.dtype == cur.dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  _oracle(cur, layouts, capacity))


def test_halo_build_matches_host_oracle_on_a_mesh(rng):
    """The placement ``compress_fields_sharded`` gives every upload."""
    from repro.distributed.compression import make_tile_put

    put = make_tile_put(jax.make_mesh((jax.device_count(),), ("data",)))
    layouts = GROUPS["multi-field"]
    capacity = 40 * jax.device_count()
    cur = _state(rng, layouts, capacity, np.int32)
    nbr = halo.group_neighbors(layouts, capacity)
    with jax.set_mesh(put.mesh):
        got = jax.jit(device._halo_build)(put(cur), put(nbr))
    np.testing.assert_array_equal(np.asarray(got),
                                  _oracle(cur, layouts, capacity))


def test_neighbor_table_is_per_tile_not_per_cell():
    layouts = GROUPS["3d"]
    nbr = halo.group_neighbors(layouts, 2048)
    assert nbr.shape == (2048, halo.N_COLS) and nbr.dtype == np.int32
    n = layouts[0].n_tiles
    assert (nbr[n:] == -1).all()
    np.testing.assert_array_equal(nbr[:n, halo.SELF], np.arange(n))


def test_group_neighbors_refuses_mixed_tiles_and_overflow():
    with pytest.raises(ValueError, match="tile shape"):
        halo.group_neighbors((_raw((1, 1, 2)), _raw((1, 1, 2), (2, 2, 2))), 8)
    with pytest.raises(ValueError, match="exceeds capacity"):
        halo.group_neighbors((_raw((2, 2, 2)),), 4)
    with pytest.raises(ValueError, match="int32"):
        halo.group_neighbors((_raw((1, 1, 1), (1024, 1024, 1024)),), 2)
