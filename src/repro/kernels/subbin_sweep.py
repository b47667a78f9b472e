"""Pallas TPU kernel: block-local subbin fixed-point sweep.

This is the TPU-native replacement for the paper's GPU worklist
(§IV-D).  A GPU raises one subbin per thread per barrier interval; a
worklist keeps later iterations sparse.  On TPU we instead pull a whole
X-band of the field into VMEM and iterate it to *local* convergence
before writing back — one global sweep then advances constraint chains
by an entire band instead of one hop, so global sweeps needed drop from
O(chain length) to O(chain length / band extent).  The fixed point is
unchanged: updates are monotone raises toward the same least solution,
so any schedule (paper Theorem, §IV-E) yields identical integers.

Halo mechanics: band i reads its neighbors' bands through two extra
BlockSpecs whose index_map clamps to [0, G-1].  Out-of-grid neighbor
constraints carry flag bit 0, so the garbage rows a clamped halo fetches
are provably never consumed.

Fields of any rank run through the canonical 3D view (ref.py): the
Freudenthal 2D/1D links are exactly the in-plane subsets of the 14-link.

Two entry points share the band machinery:

- :func:`solve_blockwise` — whole-field form (X-bands of one field),
  the kernels/ops.py public path;
- :func:`solve_tiles_blockwise` — batched (B, tile) form consumed by the
  engine's device-resident executor as the ``solver="blockwise"``
  backend: one grid step iterates one haloed tile to local convergence,
  so the executor's halo-exchange rounds only pay for constraint chains
  that genuinely cross tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import topology

from . import planes

BAND = 8  # X-rows per band; (BAND+2, Y, Z) int32 x 4 arrays must fit VMEM

_OFFS3 = topology.offsets(3)
_TIES3 = topology.tie_breaker(3)


def _shift_yz(arr, oy: int, oz: int):
    """Shift in the (fully resident) Y/Z plane with zero fill."""
    pads = [(0, 0), (max(0, -oy), max(0, oy)), (max(0, -oz), max(0, oz))]
    sl = (
        slice(None),
        slice(max(0, oy), max(0, oy) + arr.shape[1]),
        slice(max(0, oz), max(0, oz) + arr.shape[2]),
    )
    return jnp.pad(arr, pads, constant_values=0)[sl]


def _relax_band(padded, flags):
    """One relaxation of the band interior given (BAND+2, Y, Z) padded subbins."""
    new = padded[1:-1]
    for k, (ox, oy, oz) in enumerate(_OFFS3):
        nsub = _shift_yz(padded[1 + ox : 1 + ox + new.shape[0]], int(oy), int(oz))
        need = ((flags >> np.uint32(k)) & np.uint32(1)).astype(jnp.bool_)
        cand = nsub + jnp.int32(int(_TIES3[k]))
        new = jnp.maximum(new, jnp.where(need, cand, 0))
    return new


def _sweep_kernel(prev_ref, cur_ref, nxt_ref, flags_ref, out_ref, changed_ref):
    prev_band = prev_ref[...]
    cur0 = cur_ref[...]
    nxt_band = nxt_ref[...]
    flags = flags_ref[...]

    halo_lo = prev_band[-1:]
    halo_hi = nxt_band[:1]

    def relax(cur):
        padded = jnp.concatenate([halo_lo, cur, halo_hi], axis=0)
        return _relax_band(padded, flags)

    first = relax(cur0)

    def cond(c):
        return c[1]

    def body(c):
        cur, _ = c
        new = relax(cur)
        return new, jnp.any(new != cur)

    final, _ = jax.lax.while_loop(cond, body, (first, jnp.any(first != cur0)))
    out_ref[...] = final
    changed_ref[...] = jnp.any(final != cur0).astype(jnp.int32).reshape(1, 1)


# ------------------------------------------------- batched (B, tile) form
#
# Layout: a haloed tile (t0+2, t1+2, t2+2) sits in VMEM as
# (t0+2, S, L) with its Y extent zero-padded up to a sublane multiple S
# and its Z extent up to a lane multiple L, and the flags ride the same
# grid (zero outside the tile interior).  Y/Z neighbor reads are then
# native vreg rotations (``pltpu.roll``) instead of unaligned slices: a
# rotation wraps only into halo or pad cells, whose flags are 0, so the
# relax there is max(cur, 0) = cur (states are non-negative) and the
# wrapped values are never consumed.  X neighbors are static slices of
# the untiled leading axis.

SUBLANE = 8
LANE = 128


def _make_tile_kernel(max_iters: int, t0: int):
    def _tile_kernel(sub_ref, flags_ref, out_ref, iters_ref):
        sub = sub_ref[0]      # (t0+2, S, L): halos held fixed, pad zero
        flags = flags_ref[0]  # (t0, S, L): zero outside the interior
        # numpy scalars of the state's own dtype: a weak Python int would
        # become a 64-bit literal under x64, which Mosaic cannot lower.
        # The relax neutral is the dtype minimum: equal to max(cur, 0)
        # for the non-negative signed lane, and the bottom of the biased
        # lane the wrapper maps unsigned states into.
        floor = sub.dtype.type(jnp.iinfo(sub.dtype).min)
        ties = [sub.dtype.type(int(t)) for t in _TIES3]

        def relax(rows):
            """One sweep over the interior rows (t0, S, L)."""
            full = jnp.concatenate([sub[:1], rows, sub[-1:]], axis=0)
            new = rows
            for k, (ox, oy, oz) in enumerate(_OFFS3):
                nsub = full[1 + int(ox): 1 + int(ox) + t0]
                nsub = planes.roll(nsub, -int(oy), 1)
                nsub = planes.roll(nsub, -int(oz), 2)
                need = ((flags >> np.uint32(k)) & np.uint32(1)) != 0
                new = jnp.maximum(new, jnp.where(need, nsub + ties[k], floor))
            return new

        def moved(a, b):
            return jnp.max((a != b).astype(jnp.int32)) > 0

        int0 = sub[1:1 + t0]
        first = relax(int0)
        ch1 = moved(first, int0)

        def cond(c):
            return c[1] & (c[2] < max_iters)

        def body(c):
            cur, _, it, last = c
            new = relax(cur)
            ch = moved(new, cur)
            it = it + 1
            return new, ch, it, jnp.where(ch, it, last)

        final, _, _, last = jax.lax.while_loop(
            cond, body,
            (first, ch1, jnp.int32(1), jnp.where(ch1, jnp.int32(1), jnp.int32(0))),
        )
        out_ref[0] = final
        iters_ref[...] = jnp.full(iters_ref.shape, last, jnp.int32)

    return _tile_kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def solve_tiles_blockwise(sub_h: jnp.ndarray, flags: jnp.ndarray,
                          interpret: bool = False):
    """Batched-tile band solver: iterate every tile of a (B, t0+2, t1+2,
    t2+2) haloed batch to *local* convergence, halos held fixed.

    This is the engine-facing form of the band kernel above: one grid
    step pulls one tile (plus halo) into VMEM and relaxes it until no
    interior subbin moves, so a single call collapses every in-tile
    constraint chain — the executor's halo-exchange rounds then only pay
    for chains that genuinely cross tiles.  Returns ``(interiors
    (B, t0, t1, t2) in sub_h's dtype — int32 for the staged subbin lane,
    uint32/uint64 for the adaptive ordered-space lane —
    last_changed_sweep (B,) int32)`` where the
    per-tile sweep index is 0 for tiles already at their fixed point.

    The fixed point is schedule-independent (monotone raises, §IV-E), so
    the interiors are bit-identical to the jnp Jacobi/frontier schedules.
    """
    return planes.per_device(
        functools.partial(_solve_tiles, interpret=interpret))(sub_h, flags)


def _solve_tiles(sub_h, flags, interpret: bool):
    state_dtype = sub_h.dtype
    biased = state_dtype == jnp.uint32
    if biased:
        # Mosaic has no unsigned max: flip the top bit, an order-preserving
        # map onto int32 that commutes with the +tie wrap-around adds
        sub_h = jax.lax.bitcast_convert_type(sub_h ^ np.uint32(1 << 31),
                                             jnp.int32)
    b = sub_h.shape[0]
    h0, h1, h2 = sub_h.shape[1:]
    t0, t1, t2 = h0 - 2, h1 - 2, h2 - 2
    s = -(-h1 // SUBLANE) * SUBLANE
    lanes = -(-h2 // LANE) * LANE
    sub_p = jnp.pad(sub_h, ((0, 0), (0, 0), (0, s - h1), (0, lanes - h2)))
    flags_p = jnp.pad(flags, ((0, 0), (0, 0), (1, s - h1 + 1),
                              (1, lanes - h2 + 1)))
    max_iters = t0 * t1 * t2 + 2
    out, iters = pl.pallas_call(
        _make_tile_kernel(max_iters, t0),
        grid=(b,),
        in_specs=[planes.chunk_block((1, h0, s, lanes)), planes.chunk_block((1, t0, s, lanes))],
        out_specs=[planes.chunk_block((1, t0, s, lanes)), planes.chunk_block((1, 1, LANE))],
        out_shape=[
            jax.ShapeDtypeStruct((b, t0, s, lanes), sub_h.dtype),
            jax.ShapeDtypeStruct((b, 1, LANE), jnp.int32),
        ],
        interpret=interpret,
    )(sub_p, flags_p)
    out = out[:, :, 1:1 + t1, 1:1 + t2]
    if biased:
        out = jax.lax.bitcast_convert_type(out, jnp.uint32) ^ np.uint32(1 << 31)
    return out, iters[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _one_global_sweep(sub, flags, interpret: bool = False):
    x, y, z = sub.shape
    grid_n = x // BAND
    band_spec = lambda fn: pl.BlockSpec((BAND, y, z), fn)  # noqa: E731
    new, changed = pl.pallas_call(
        _sweep_kernel,
        grid=(grid_n,),
        in_specs=[
            band_spec(lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
            band_spec(lambda i: (i, 0, 0)),
            band_spec(lambda i: (jnp.minimum(i + 1, grid_n - 1), 0, 0)),
            band_spec(lambda i: (i, 0, 0)),
        ],
        out_specs=[
            band_spec(lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x, y, z), jnp.int32),
            jax.ShapeDtypeStruct((grid_n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(sub, sub, sub, flags)
    return new, jnp.any(changed != 0)


def solve_blockwise(flags3: jnp.ndarray, interpret: bool = False):
    """Drive global sweeps to the fixed point. flags3: (X, Y, Z) uint32.

    Returns (subbins int32 (X, Y, Z), n_global_sweeps). X is padded to a
    BAND multiple internally (pad cells have flag 0 => stay 0).
    """
    x, y, z = flags3.shape
    xp = -(-x // BAND) * BAND
    flags_p = jnp.pad(flags3, ((0, xp - x), (0, 0), (0, 0)))
    sub = jnp.zeros((xp, y, z), jnp.int32)
    sweeps = 0
    while True:
        sub, changed = _one_global_sweep(sub, flags_p, interpret=interpret)
        sweeps += 1
        if not bool(changed):
            break
    return sub[:x], sweeps
