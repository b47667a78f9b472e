"""Pallas kernel: fused LOPC encode (the compress mirror of
``fused_decode``).

Two entry points share the file:

``encode_ints_fused``
    The lossless encode stage over a resident integer batch: [delta ->]
    [zigzag|reinterpret] -> BIT_w in one kernel gridded over chunk
    blocks, in the bit-plane layout of ``kernels.planes``, with the
    RZE bitmap and counts taken from the kernel's shuffled words in the
    same jitted program.  Drives the bins stream after the staged
    frontend (and the subs stream after the solve, and temporal
    residual streams via the same ``transform`` modes the staged
    ``encode_tiles`` takes).  Every step is integer-exact, so the
    streams equal the staged stage programs' bit for bit; tests pin it
    against the staged chain and the determinism manifest.

``encode_values_fused``
    The full compress fusion for the plain (preserve_order=False) f32
    path with 16-bit bin streams: NaN-validity -> guaranteed-bound
    quantize -> delta/zigzag -> BIT in the kernel.  Quantize is the
    shared ``quantize_broadcast`` sequence with the exact integer decode
    anchors.  Its first guess is an f32 quotient (Mosaic has no f64) on
    every backend; a 16-bit stream means |bin| < 2**14, where that guess
    is within one bin of the exact one, so the verify-and-correct pass
    lands on the staged frontend's bins.

Any row count works: chunk blocks pad internally (pad chunks encode as
all-zero streams) and the outputs slice back, because encode batches
can arrive at odd sizes from callers outside the bucketed executor.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..codecs.rze import rze_bitmap
from ..core.quantize import Eps, quantize_broadcast
from . import planes


def _word_dtype(ints_dtype) -> jnp.dtype:
    return jnp.dtype(jnp.dtype(ints_dtype).str.replace("i", "u"))


def _forward(m, w: int, transform: str):
    """(n, w, Q) sign-extended ints -> bit-plane rows of the words."""
    if transform == "delta":
        words = planes.zigzag(
            planes.sign_extend(m - planes.previous_element(m), w), w)
    elif transform == "zigzag":
        words = planes.zigzag(m, w)
    elif transform == "raw":
        words = planes.low_bits(m, w)
    else:
        raise ValueError(f"unknown transform {transform!r}")
    return planes.bit_transpose(words, w)


def _streams(shuffled, batch: int, cpt: int, wdt):
    """Kernel output -> (bitmap, shuffled words, counts) chunk rows."""
    n = batch * cpt
    words = shuffled[:n].reshape(n, -1)
    if jnp.dtype(wdt).itemsize == words.dtype.itemsize:
        words = words.view(wdt)
    else:
        words = words.astype(wdt)   # low 16 bits of int32 containers
    bitmap, counts = rze_bitmap(words)
    return bitmap, words, counts


def _step(block_tiles, cpt: int):
    return None if block_tiles is None else block_tiles * cpt


def encode_ints_fused(ints, chunk_len: int, transform: str,
                      interpret: bool = False,
                      block_tiles: int | None = None):
    """Fused lossless encode of (batch, E) signed ints ->
    (bitmap, shuffled words, counts) chunk rows.

    Output shapes and values equal ``device.encode_tiles`` exactly.
    ``block_tiles`` sets tiles per grid step (default: the whole batch
    in interpret mode, ``planes.CHUNKS_PER_STEP`` chunks on the chip).
    """
    batch, elems = ints.shape
    w = jnp.dtype(ints.dtype).itemsize * 8
    cpt = -(-elems // chunk_len)
    m = planes.to_planes(ints, chunk_len, w)
    out = planes.plane_call(lambda a: _forward(a, w, transform), m, [],
                            planes.container(w), interpret,
                            _step(block_tiles, cpt))
    return _streams(out, batch, cpt, _word_dtype(ints.dtype))


def encode_values_fused(x_int, eps: Eps, chunk_len: int, dtype, bins_store,
                        interpret: bool = False,
                        block_tiles: int | None = None):
    """Fused full encode of (batch, E) NaN-marked f32 interiors ->
    the bins stream's (bitmap, shuffled words, counts).

    NaN cells (tile pad, pad tiles) encode as bin 0 exactly like the
    staged frontend's validity masking; ``eps`` is the per-tile
    :class:`~repro.core.quantize.Eps`.  Only valid for
    preserve_order=False float32 batches whose bins the host stores in
    16 bits (see module docstring).
    """
    dtype = jnp.dtype(dtype)
    bins_store = jnp.dtype(bins_store)
    if bins_store.itemsize != 2:
        raise ValueError(f"fused values encode needs 16-bit bins, got "
                         f"{bins_store}: the f32 first guess is exact "
                         "only for |bin| < 2**14")
    batch, elems = x_int.shape
    w = bins_store.itemsize * 8
    cpt = -(-elems // chunk_len)
    x = jnp.pad(x_int, ((0, 0), (0, cpt * chunk_len - elems)),
                constant_values=jnp.nan)
    m = planes.to_planes(x, chunk_len, w)
    guess = eps.value.astype(jnp.float32)
    per_chunk = [jnp.repeat(a, cpt) for a in (guess,) + tuple(eps[1:])]

    def body(xm, *eps_leaves):
        valid = jnp.isfinite(xm)
        x0 = jnp.where(valid, xm, planes.const(0, xm.dtype))
        bins = quantize_broadcast(x0, Eps(*eps_leaves), dtype)
        bins = planes.sign_extend(
            jnp.where(valid, bins, planes.const(0, bins.dtype)), w)
        return _forward(bins, w, "delta")

    out = planes.plane_call(body, m, per_chunk, planes.container(w),
                            interpret, _step(block_tiles, cpt))
    return _streams(out, batch, cpt, _word_dtype(bins_store))
