"""Shared TPU layout for the fused encode/decode kernels: bit-plane form.

A lossless-stage chunk of ``L`` words of ``w`` bits is held as a
``(w, L/w)`` array ``M`` with ``M[r, q] = word[q*w + r]`` — the same
bytes the BIT_w stage writes, read as rows: row ``b`` of the *shuffled*
chunk is bit-plane ``b`` (``codecs.bitshuffle``).  In this form every
lossless step is a Mosaic-native operation on (8, 128)-tiled vregs:

- BIT_w and its inverse are one bit-matrix transpose per lane column
  (:func:`bit_transpose`, an involution): five masked row exchanges via
  sublane rotations for w = 32, instead of a lane-splitting reshape;
- delta/zigzag and their inverses are elementwise, with the previous
  element reached by sublane/lane rotations and the running sum by
  log-step rotate-and-add scans;
- per-tile scalars ride (chunks, 1, 1) blocks broadcast over the chunk.

16-bit words compute in int32 containers holding the low 16 bits (v5e's
vector unit has no 16-bit integer ops); 64-bit words compute in int64,
which runs in interpret mode and is refused by Mosaic (no 64-bit vector
types) — callers on the chip keep 8-byte streams on the staged chain.
Moving between natural element order and this form is an XLA transpose
outside the kernel (:func:`to_planes`, :func:`from_planes`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# Chunks per grid step on the chip (~16 KiB each at any word width).
CHUNKS_PER_STEP = 16

_TRANSPOSE_MASKS = {
    16: (0x00FF, 0x0F0F, 0x3333, 0x5555),
    32: (0x0000FFFF, 0x00FF00FF, 0x0F0F0F0F, 0x33333333, 0x55555555),
    64: (0x00000000FFFFFFFF, 0x0000FFFF0000FFFF, 0x00FF00FF00FF00FF,
         0x0F0F0F0F0F0F0F0F, 0x3333333333333333, 0x5555555555555555),
}


def container(w: int) -> jnp.dtype:
    """Signed integer type the kernels compute ``w``-bit words in."""
    return jnp.dtype(jnp.int64 if w == 64 else jnp.int32)


def const(value: int, dtype) -> np.generic:
    """A literal of the container's own type: a weak Python int would be
    a 64-bit constant under x64, which Mosaic cannot lower."""
    return np.dtype(dtype).type(value)


def roll(x, shift: int, axis: int):
    """``jnp.roll`` semantics as the Mosaic rotate (interpretable too)."""
    shift %= x.shape[axis]
    if shift == 0:
        return x
    return pltpu.roll(x, np.int32(shift), axis)


def srl(x, n: int):
    """Logical right shift by a static amount."""
    return lax.shift_right_logical(x, const(n, x.dtype))


def sign_extend(v, w: int):
    """Low ``w`` bits of container ints as a signed ``w``-bit value."""
    bits = 8 * v.dtype.itemsize
    if w == bits:
        return v
    s = const(bits - w, v.dtype)
    return (v << s) >> s


def low_bits(v, w: int):
    """Low ``w`` bits of container ints, zero-extended."""
    if w == 8 * v.dtype.itemsize:
        return v
    return v & const((1 << w) - 1, v.dtype)


def bit_transpose(a, w: int):
    """Transpose the w x w bit matrix of every lane column of (n, w, Q).

    Row ``i`` of the result holds, MSB first, bit ``w-1-i`` of rows
    0..w-1 — so on a chunk's word rows it is BIT_w, and on bit-plane
    rows its inverse.  Log-step block exchanges (Hacker's Delight
    ``transpose32``) with partner rows reached by sublane rotation.
    """
    rows = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    j = w // 2
    for m in _TRANSPOSE_MASKS[w]:
        partner = roll(a, w - j, 1)                       # a[k + j]
        t = (a ^ srl(partner, j)) & const(m, a.dtype)
        back = roll(t, j, 1) << const(j, a.dtype)         # t[k - j] << j
        a = jnp.where((rows & j) == 0, a ^ t, a ^ back)
        j //= 2
    return a


def previous_element(a):
    """Natural-order predecessor of every element of (n, w, Q) chunks
    (0 before a chunk's first element)."""
    rows = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    lanes = lax.broadcasted_iota(jnp.int32, a.shape, 2)
    up = roll(a, 1, 1)              # a[r-1, q]; row 0 wraps to a[w-1, q]
    prev_col = roll(up, 1, 2)       # row 0: a[w-1, q-1]
    zero = const(0, a.dtype)
    return jnp.where(rows == 0, jnp.where(lanes == 0, zero, prev_col), up)


def running_sum(a):
    """Inclusive natural-order prefix sum within every (w, Q) chunk,
    wrapping in the container type (log-step rotate-and-add scans)."""
    n, w, q = a.shape
    rows = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    lanes = lax.broadcasted_iota(jnp.int32, a.shape, 2)
    zero = const(0, a.dtype)
    s = 1
    while s < w:                    # down each column
        a = a + jnp.where(rows >= s, roll(a, s, 1), zero)
        s *= 2
    total = jnp.broadcast_to(a[:, w - 1:, :], a.shape)   # column sums
    c = total
    s = 1
    while s < q:                    # across columns
        c = c + jnp.where(lanes >= s, roll(c, s, 2), zero)
        s *= 2
    return a + (c - total)


def zigzag(v, w: int):
    return low_bits((v << const(1, v.dtype)) ^ (v >> const(w - 1, v.dtype)), w)


def unzigzag(z):
    return srl(z, 1) ^ (const(0, z.dtype) - (z & const(1, z.dtype)))


def to_planes(ints, chunk_len: int, w: int):
    """(batch, E) ints -> (batch*cpt, w, chunk_len/w) container ints,
    sign-extended, chunks zero-padded (XLA, outside the kernel)."""
    b, e = ints.shape
    cpt = -(-e // chunk_len)
    x = jnp.pad(ints, ((0, 0), (0, cpt * chunk_len - e)))
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(container(w))
    x = x.reshape(b * cpt, chunk_len // w, w)
    return jnp.swapaxes(x, 1, 2)


def from_planes(m, batch: int, elems: int):
    """Inverse of :func:`to_planes` -> (batch, elems)."""
    n, w, q = m.shape
    return jnp.swapaxes(m, 1, 2).reshape(batch, -1)[:, :elems]


def per_device(fn):
    """Run ``fn`` on each device's share of its operands' leading axis
    when traced under a mesh (``jax.set_mesh``): the compiler cannot
    partition a Mosaic kernel, so a sharded tile batch reaches it through
    ``shard_map``.  ``fn`` must treat leading-axis rows independently
    (tiles, chunks).  Off a mesh, or where the rows do not split evenly,
    ``fn`` runs whole."""
    def run(*args):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or any(
                a.ndim == 0 or a.shape[0] % mesh.size
                for a in jax.tree.leaves(args)):
            return fn(*args)
        spec = P(mesh.axis_names)
        return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False)(*args)
    return run


def chunk_block(shape):
    """BlockSpec stepping the leading (chunk) axis with the grid index;
    int32 zeros, since Python ints would be 64-bit under x64."""
    zeros = (np.int32(0),) * (len(shape) - 1)
    return pl.BlockSpec(shape, lambda i: (i,) + zeros)


def plane_call(body, planes, extras, out_dtype, interpret: bool,
               step: int | None = None):
    """Grid ``body(planes_block, *extra_blocks) -> block`` over chunk
    blocks of (n, w, Q) ``planes``; ``extras`` are per-chunk (n,) scalars
    riding (chunks, 1, 1) blocks.  ``step`` chunks per grid step default
    to all of them in interpret mode (one dispatch) and
    ``CHUNKS_PER_STEP`` on the chip.  Pads the chunk count to the step
    (pad chunks are all-zero and sliced off)."""
    w, q = planes.shape[1:]

    def call(planes, *extras):
        n = planes.shape[0]      # this device's chunks under a mesh
        step_ = step or (n if interpret else CHUNKS_PER_STEP)
        pad = -n % step_
        if pad:
            planes = jnp.pad(planes, ((0, pad), (0, 0), (0, 0)))
            extras = [jnp.pad(e, (0, pad)) for e in extras]
        extras = [e.reshape(-1, 1, 1) for e in extras]

        def kernel(*refs):
            out_ref = refs[-1]
            out_ref[...] = body(refs[0][...], *(r[...] for r in refs[1:-1]))

        out = pl.pallas_call(
            kernel,
            grid=((n + pad) // step_,),
            in_specs=[chunk_block((step_, w, q))]
            + [chunk_block((step_, 1, 1)) for _ in extras],
            out_specs=chunk_block((step_, w, q)),
            out_shape=jax.ShapeDtypeStruct((n + pad, w, q), out_dtype),
            interpret=interpret,
        )(planes, *extras)
        return out[:n] if pad else out

    return per_device(call)(planes, *extras)
