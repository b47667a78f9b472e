"""SLEEK-adapted guaranteed-bound quantization (paper §IV-A).

LOPC halves the usual 2*eps bin width so the subbin mechanism can move a
reconstructed value anywhere inside its bin without violating the user's
point-wise bound:

    bin(x)        = round(x / eps)               (f64 intermediate math)
    base(b)       = (b - 0.5) * eps              (bottom of bin b)
    x in bin b  <=>  base(b) <= x < base(b+1)

A *verify-and-correct* pass nudges any bin whose containment check fails
(floating-point rounding in the division can misplace a value by one
bin).  This reproduces SLEEK's "no outlier path" property: every finite
value is representable and the bound holds for every point, which we
property-test with hypothesis.  ``eps`` is shrunk by 2^-20 relative so
that the realized bin width (computed in floating point) never exceeds
the user's bound even after rounding.

Monotonicity of ``bin`` + containment of the decode interval is what the
subbin solver builds on: cross-bin neighbor order is automatically
correct, so only same-bin pairs ever need correction.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .floatbits import float_to_ordered, int_dtype_for, ordered_to_float

# Relative shrink applied to the user's bound. Covers the worst-case
# accumulation of rounding in base(b) across f64 math + cast to f32/f64.
EPS_SHRINK = 1.0 - 2.0**-20

# f32 fields use i32 bins (PFPL convention); f64 fields use i64 bins.
_BIN_DTYPE = {jnp.dtype(jnp.float32): jnp.int32, jnp.dtype(jnp.float64): jnp.int64}


def bin_dtype_for(dtype) -> jnp.dtype:
    return _BIN_DTYPE[jnp.dtype(dtype)]


def effective_eps(eb_abs: float) -> float:
    """The internally used (slightly shrunk) absolute bound."""
    return float(eb_abs) * EPS_SHRINK


def abs_bound_from_mode(x, eb: float, mode: str) -> float:
    """Resolve an ABS or NOA (range-normalized) bound to absolute."""
    if mode == "abs":
        return float(eb)
    if mode == "noa":
        lo = float(np.min(x))
        hi = float(np.max(x))
        rng = hi - lo
        if rng == 0.0:
            rng = 1.0  # constant field: any positive eps preserves it
        return float(eb) * rng
    raise ValueError(f"unknown error-bound mode {mode!r} (want 'abs'|'noa')")


class Eps(NamedTuple):
    """An f64 bin width in the two forms the device programs use.

    ``value`` feeds the first bin guess ``round(x / eps)``, which the
    verify-and-correct pass makes exact (see :func:`quantize_broadcast`).
    The decode anchor of an f32 field must be exact on a backend without
    IEEE f64 arithmetic (a TPU emulates f64 with f32 pairs), so it is
    computed from the integer form: ``eps == mantissa * 2**exp`` with the
    53-bit ``mantissa`` split into int32 words ``m_lo`` (low 32 bits,
    two's-complement wrapped) and ``m_hi``.  Leaves broadcast alike, so a
    per-tile ``Eps`` indexes and reshapes like one array.
    """

    value: jnp.ndarray
    m_lo: jnp.ndarray
    m_hi: jnp.ndarray
    exp: jnp.ndarray

    def expand(self, ndim: int) -> "Eps":
        """Per-tile leaves (C,) -> (C, 1, ..., 1) for broadcasting."""
        return Eps(*(a.reshape(a.shape + (1,) * ndim) for a in self))


def eps_operand(eps) -> Eps:
    """Host-side (numpy) :class:`Eps` of positive finite f64 bound(s)."""
    v = np.asarray(eps, np.float64)
    frac, e = np.frexp(v)
    mant = np.ldexp(frac, 53).astype(np.int64)  # exact: frac has <= 53 bits
    return Eps(v, (mant & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
               (mant >> 32).astype(np.int32), (e - 53).astype(np.int32))


_I0 = np.int32(0)  # typed: a Python 0 is an int64 operand under x64


def _srl(x, n):
    """Logical right shift of int32 words."""
    return lax.shift_right_logical(x, jnp.asarray(n, jnp.int32))


def _field(limbs, lo: int, width: int):
    """Bits [lo, lo + width) of a 16-bit-limbed integer (static range)."""
    out = jnp.int32(0)
    for i, limb in enumerate(limbs):
        a, c = max(lo - 16 * i, 0), min(lo + width - 16 * i, 16)
        if a < c:
            part = _srl(limb, a) & ((1 << (c - a)) - 1)
            out = out | (part << (16 * i + a - lo))
    return out


# f32 anchors need eps in [2**-125, 2**960]: check_eps enforces the floor
# (the half-bin anchor -eps/2 must be a normal f32); above the ceiling
# every f32 anchor saturates to +-inf/max, and products could overflow f64.
F32_EPS_RANGE = (2.0**-125, 2.0**960)


@jax.jit
def f32_base_ordered(bins: jnp.ndarray, eps: Eps) -> jnp.ndarray:
    """Ordered int32 of the f32 decode anchor, in 32-bit integer math.

    Equals ``float_to_ordered(decode_base(bins, eps, float32))`` as the
    IEEE-f64 definition computes it — ``t = RN64((b - 0.5) * eps)``, then
    the smallest f32 (or inf) >= t — bit for bit, for every int32 bin and
    every eps in :data:`F32_EPS_RANGE`, on any backend (a TPU's f64 is
    an inexact f32-pair emulation).  ``|2b - 1|`` is normalized to 33
    bits and multiplied by the 53-bit mantissa in 16-bit limbs, so the
    86-bit product's f64 rounding bits and f32 grid sit at fixed
    positions: round to 53 bits (ties to even), then up (positive t) or
    toward zero (negative t) onto the f32 grid.
    """
    b = bins.astype(jnp.int32)
    pos = b >= 1
    # |2b - 1| = 2u + 1 with u < 2**32; shifted left by clz(u) it lies in
    # [2**32, 2**33): limbs (a0, a1, 1)
    u = jnp.where(pos, b - 1, -b)
    sa = lax.clz(u)
    sh = jnp.minimum(sa, 31)
    low = jnp.where(u == 0, _I0, ((u << sh) << 1) | (1 << sh))
    a = [low & 0xFFFF, _srl(low, 16)]
    m = [eps.m_lo & 0xFFFF, _srl(eps.m_lo, 16), eps.m_hi & 0xFFFF,
         _srl(eps.m_hi, 16)]
    cols = [jnp.int32(0)] * 6
    for i, ai in enumerate(a):
        for j, mj in enumerate(m):
            p = ai * mj  # < 2**32: wraps in int32, split logically
            cols[i + j] = cols[i + j] + (p & 0xFFFF)
            cols[i + j + 1] = cols[i + j + 1] + _srl(p, 16)
    for j, mj in enumerate(m):  # the top limb of A is 1
        cols[2 + j] = cols[2 + j] + mj
    limbs, carry = [], jnp.int32(0)
    for c in cols:
        t = c + carry
        limbs.append(t & 0xFFFF)
        carry = _srl(t, 16)
    # the product lies in [2**84, 2**86): shift the top bit to bit 85
    short = limbs[5] < (1 << 5)
    limbs = [jnp.where(short, ((limb << 1) | (_srl(lower, 15) if k else 0))
                       & 0xFFFF, limb)
             for k, (limb, lower) in enumerate(zip(limbs, [0] + limbs))]
    lead = 85 - sa - short.astype(jnp.int32) + eps.exp - 1
    # f64 keeps bits [33, 86); the f32 grid keeps [62, 86)
    rb = _field(limbs, 32, 1) != 0
    sticky = (limbs[0] | limbs[1]) != 0
    up = rb & (sticky | (_field(limbs, 33, 1) != 0))
    mid = _field(limbs, 33, 29)
    carry_in = up & (mid == (1 << 29) - 1)
    k_floor = _field(limbs, 62, 24) + carry_in.astype(jnp.int32)
    rem = ((mid != 0) | up) & ~carry_in
    exp_bits = (jnp.clip(lead, np.int32(-126), np.int32(127)) + 126) << 23
    mag_up = jnp.where(lead >= 128, np.int32(0x7F800000),
                       exp_bits + k_floor + rem.astype(jnp.int32))
    mag_dn = jnp.where(lead >= 128, np.int32(0x7F7FFFFF),
                       jnp.minimum(exp_bits + k_floor, 0x7F7FFFFF))
    return jnp.where(pos, mag_up, -mag_dn)


def _as_eps(eps) -> Eps:
    """Concrete host bounds (float / numpy) -> :class:`Eps`."""
    return eps if isinstance(eps, Eps) else eps_operand(eps)


def decode_base_ordered(bins: jnp.ndarray, eps: Eps, dtype) -> jnp.ndarray:
    """Ordered int of :func:`decode_base` (the form decoders add to)."""
    eps = _as_eps(eps)
    if jnp.dtype(dtype) == jnp.float32:
        return f32_base_ordered(bins, eps)
    t = (bins.astype(jnp.float64) - 0.5) * eps.value
    return float_to_ordered(t)  # t is already the representable used


def decode_base(bins: jnp.ndarray, eps: Eps, dtype) -> jnp.ndarray:
    """Smallest *representable* dtype value >= (b - 0.5) * eps.

    This is the paper's decode anchor ("subbin 0 decodes to the lowest
    representable value within the bin", §IV-E).  Using the representable
    bottom — not a round-to-nearest cast — keeps bin decode intervals
    disjoint even when eps is smaller than one ulp of the data, so
    cross-bin order can never collapse.  Monotone in b by construction.
    ``(b - 0.5) * eps`` is the IEEE-f64 product; for f32 it is evaluated
    exactly in integer arithmetic (:func:`f32_base_ordered`).
    """
    eps = _as_eps(eps)
    if jnp.dtype(dtype) == jnp.float64:
        return (bins.astype(jnp.float64) - 0.5) * eps.value
    return ordered_to_float(f32_base_ordered(bins, eps), dtype)


def quantize_broadcast(x: jnp.ndarray, eps_b: Eps, dtype) -> jnp.ndarray:
    """The quantize op sequence with a broadcastable (e.g. per-tile) eps.

    Not jitted: callers are themselves traced programs — the engine's
    resident quantize stage and the fused Pallas encode kernel — and
    inline this exact op sequence, so bins are bit-identical whichever
    entry point runs.  The first guess is computed at ``eps_b.value``'s
    precision: f64 in XLA programs, f32 in the fused kernel (Mosaic has
    no f64).  Both lie within one bin of the exact quotient for the bins
    their callers admit (the fused kernel takes only 16-bit bin streams,
    |bin| < 2**14), and the correction passes then land on the same
    bins.
    """
    bdt = bin_dtype_for(dtype)
    gdt = eps_b.value.dtype
    b = jnp.round(x.astype(gdt) / eps_b.value).astype(bdt)
    # Verify-and-correct: containment in [base(b), base(b+1)) under the
    # *same* comparisons the decoder's anchors imply. Two passes cover
    # the worst realizable misplacement (|round error| <= 1 bin).
    if jnp.dtype(dtype) == jnp.float32:
        ox = float_to_ordered(x)

        def correct(_, b):
            # both anchors from one (2, ...) evaluation, and the pass as
            # a loop body: the integer anchor is long, and inlining it
            # four times costs the TPU compiler ~4x
            base = f32_base_ordered(jnp.stack([b, b + 1]), eps_b)
            return (b - (ox < base[0]).astype(bdt)
                    + (ox >= base[1]).astype(bdt))

        return lax.fori_loop(0, 2, correct, b)
    for _ in range(2):
        too_high = x < decode_base(b, eps_b, dtype)
        too_low = x >= decode_base(b + 1, eps_b, dtype)
        b = b - too_high.astype(bdt) + too_low.astype(bdt)
    return b


@partial(jax.jit, static_argnames=("dtype",))
def _quantize_impl(x: jnp.ndarray, eps: Eps, dtype) -> jnp.ndarray:
    return quantize_broadcast(x, eps, dtype)


def quantize(x: jnp.ndarray, eps_abs: float) -> jnp.ndarray:
    """Map values to bins of width ``effective_eps(eps_abs)``.

    Guarantees: monotone in x, and base(b) <= x < base(b+1) exactly
    (under IEEE comparisons), hence any decode inside the bin is within
    +-eps_abs of x.
    """
    eps = eps_operand(effective_eps(eps_abs))
    return _quantize_impl(x, eps, jnp.dtype(x.dtype))


@partial(jax.jit, static_argnames=("dtype",))
def _dequantize_impl(bins, subbins, eps, dtype):
    idt = int_dtype_for(dtype)
    return ordered_to_float(
        decode_base_ordered(bins, eps, dtype) + subbins.astype(idt), dtype)


def dequantize(bins: jnp.ndarray, subbins: jnp.ndarray, eps_abs: float, dtype) -> jnp.ndarray:
    """Reconstruct: subbin k -> k-th lowest representable float in the bin."""
    check_backend(dtype, "decompress")
    eps = eps_operand(effective_eps(eps_abs))
    return _dequantize_impl(bins, subbins, eps, jnp.dtype(dtype))


# f64 bins beyond 2^51 lose exactness in the (b - 0.5) * eps decode-base
# math (b - 0.5 needs a half-ulp at |b| <= 2^51), which silently breaks
# the point-wise bound near the int64 bin limit.  The bin domain is
# therefore capped at the float-exact range, not the integer range.
F64_EXACT_BIN_LIMIT = 2.0**51


def min_eps_abs(dtype) -> float:
    """Smallest user bound a ``dtype`` field accepts.

    XLA flushes denormals (FTZ), so a bin width below the smallest
    normal cannot be honored; for f32 the half-bin anchor
    ``base(0) = -eps/2`` must be normal as well, which keeps every
    anchor the exact integer path computes equal to the f64 definition's
    flushed one.
    """
    tiny = float(np.finfo(dtype).tiny)
    if jnp.dtype(dtype) == jnp.float32:
        return 2.0 * tiny / EPS_SHRINK
    return tiny


def max_abs_bin(dtype) -> float:
    """Largest |bin| for which the error-bound guarantee holds."""
    int_limit = float(jnp.iinfo(bin_dtype_for(dtype)).max) * 0.5
    return min(int_limit, F64_EXACT_BIN_LIMIT)


def check_eps(x: np.ndarray, eps_abs: float) -> None:
    """Reject bounds below :func:`min_eps_abs` or whose bins overflow."""
    if eps_abs < min_eps_abs(x.dtype):
        raise ValueError(
            f"error bound {eps_abs:.3e} is below the smallest normal "
            f"{x.dtype} bin width ({min_eps_abs(x.dtype):.3e}); XLA "
            "flushes denormals (FTZ), so sub-denormal bin widths cannot "
            "be honored"
        )
    check_bin_range(x, eps_abs)
    check_backend(x.dtype, "compress")


class BackendUnsupported(NotImplementedError):
    """A stage whose bytes this backend cannot reproduce exactly."""


# float64 stages a TPU cannot run bit-exactly: its f64 arithmetic is an
# inexact emulation, and 64-bit float bitcasts do not lower at all.
_TPU_F64_STAGES = {
    "compress": "quantize: the bin anchors (b - 0.5) * eps need IEEE "
                "float64 multiplies, which the TPU emulates inexactly",
    "decompress": "dequantize: float_to_ordered needs a float64 -> int64 "
                  "bitcast, which the TPU compiler does not lower",
}


def check_backend(dtype, op: str) -> None:
    """Refuse ``op`` ("compress" | "decompress") of a ``dtype`` field
    where the default backend would not give the CPU's bytes."""
    if jnp.dtype(dtype) == jnp.float64 and jax.default_backend() == "tpu":
        raise BackendUnsupported(
            f"float64 {op} is not supported on a TPU backend: stage "
            f"{_TPU_F64_STAGES[op]}")


def check_bin_range(x: np.ndarray, eps_abs: float) -> None:
    """Reject inputs whose bins would overflow the exact-math domain."""
    dtype = jnp.dtype(x.dtype)
    eps = effective_eps(eps_abs)
    max_bin = float(np.max(np.abs(np.asarray(x, np.float64)))) / eps
    limit = max_abs_bin(dtype)
    if max_bin > limit:
        raise ValueError(
            f"|x|/eps = {max_bin:.3g} overflows {bin_dtype_for(dtype)} bins; "
            "use a looser bound or float64 input"
        )
