"""Pallas kernel: fused LOPC decode (paper §IV-D "embarrassingly
parallel" decompression path).

Two entry points share the file:

``decode_tiles_fused``
    The engine's fused decompress backend (``decode_path="fused"``):
    RZE-expand -> bitshuffle-undo -> dezigzag/undelta -> dequantize as
    one jitted program.  The word-level steps run as a Pallas kernel in
    the bit-plane layout of ``kernels.planes`` (one bit-matrix transpose
    undoes BIT_w, a rotate-and-add scan undoes the delta), gridded over
    chunk blocks; the RZE expansion (a gather) and the exact-integer
    dequantize run in XLA around it.  Every step is integer-exact, so
    values equal the staged chain's bit for bit; tests pin it against
    the determinism manifest.  The executor routes f32 ordered decode
    with <= 4-byte sections here; other cases stay staged.

``dequantize_ff32``
    The original FF32-contract dequantize microkernel (reconstruct =
    k-th representable float above base(bin), k = subbin, as ordered-int
    bit arithmetic per ref.py).  Kept as the minimal on-TPU exemplar and
    for the kernel-vs-oracle tests; any row count works (rows pad to
    BLOCK_ROWS internally and the result slices back).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..codecs.rze import rze_decode
from ..core.floatbits import int_dtype_for, ordered_to_float
from ..core.quantize import Eps, decode_base_ordered
from . import planes

LANE = 128
BLOCK_ROWS = 256


# ------------------------------------------------- fused decode pipeline

def _inverse(a, w: int, transform: str):
    """Bit-plane rows of one stream -> sign-extended signed ints."""
    words = planes.bit_transpose(a, w)
    if transform == "delta":
        return planes.sign_extend(planes.running_sum(
            planes.sign_extend(planes.unzigzag(words), w)), w)
    return planes.sign_extend(words, w)   # "raw"


def decode_stream(bitmap, packed, batch: int, tile_elems: int,
                  transform: str, interpret: bool):
    """(batch * cpt, ...) RZE section rows -> (batch, tile_elems) ints in
    the container type (int32 for <= 4-byte words)."""
    shuffled = rze_decode(bitmap, packed)
    n, length = shuffled.shape
    w = shuffled.dtype.itemsize * 8
    cdt = planes.container(w)
    if cdt.itemsize == shuffled.dtype.itemsize:
        m = shuffled.view(cdt)
    else:
        m = shuffled.astype(cdt)
    out = planes.plane_call(lambda a: _inverse(a, w, transform),
                            m.reshape(n, w, length // w), [], cdt,
                            interpret)
    return planes.from_planes(out, batch, tile_elems)


def decode_tiles_fused(bitmap, packed, sub_bitmap, sub_packed, eps: Eps,
                       tile_elems: int, dtype, interpret: bool = False):
    """Fused ordered decode of a tile batch -> (batch, tile_elems).

    Inputs mirror ``device.resident_decode_order``: RZE sections as
    (batch * cpt, ...) bitmap/packed word arrays (bins delta-coded,
    subbins raw) and the per-tile :class:`~repro.core.quantize.Eps`.
    """
    dtype = jnp.dtype(dtype)
    batch = eps.value.shape[0]
    bins = decode_stream(bitmap, packed, batch, tile_elems, "delta",
                         interpret)
    subs = decode_stream(sub_bitmap, sub_packed, batch, tile_elems, "raw",
                         interpret)
    base = decode_base_ordered(bins, eps.expand(1), dtype)
    return ordered_to_float(base + subs.astype(int_dtype_for(dtype)), dtype)


# ------------------------------------------- FF32 dequantize microkernel

def _decode_kernel(eps_ref, bins_ref, sub_ref, out_ref):
    eps = eps_ref[0]
    b = bins_ref[...]
    s = sub_ref[...]
    base = (b.astype(jnp.float32) - jnp.float32(0.5)) * eps
    bits = lax.bitcast_convert_type(base, jnp.int32)
    imin = jnp.int32(np.iinfo(np.int32).min)
    m = jnp.where(bits >= 0, bits, imin - bits) + s
    out_bits = jnp.where(m >= 0, m, imin - m)
    out_ref[...] = lax.bitcast_convert_type(out_bits, jnp.float32)


def dequantize_ff32(bins2d, sub2d, eps32, interpret: bool = False):
    """(R, 128) int32 bins + subbins -> f32 reconstruction.

    Any row count works: rows pad up to a BLOCK_ROWS multiple (pad rows
    decode garbage nobody reads) and the result slices back to R.
    """
    rows = bins2d.shape[0]
    assert bins2d.shape == sub2d.shape and bins2d.shape[1] == LANE
    pad = -rows % BLOCK_ROWS
    if pad:
        bins2d = jnp.concatenate(
            [bins2d, jnp.zeros((pad, LANE), bins2d.dtype)])
        sub2d = jnp.concatenate([sub2d, jnp.zeros((pad, LANE), sub2d.dtype)])
    grid = ((rows + pad) // BLOCK_ROWS,)
    spec = pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows + pad, LANE), jnp.float32),
        interpret=interpret,
    )(eps32.reshape(1).astype(jnp.float32), bins2d, sub2d)
    return out[:rows]
