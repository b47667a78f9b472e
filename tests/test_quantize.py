"""Quantizer guarantees (paper §IV-A): strict error bound, monotonicity,
containment — property-tested with hypothesis on adversarial floats."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import assume, given, strategies as st

from repro.core.floatbits import float_to_ordered, nextafter_k, ordered_to_float
from repro.core.quantize import (
    EPS_SHRINK,
    F32_EPS_RANGE,
    Eps,
    abs_bound_from_mode,
    check_eps,
    decode_base,
    dequantize,
    effective_eps,
    eps_operand,
    f32_base_ordered,
    max_abs_bin,
    min_eps_abs,
    quantize,
    quantize_broadcast,
)


@given(
    st.lists(
        st.floats(min_value=-1e6, max_value=1e6, allow_nan=False, width=32),
        min_size=1,
        max_size=64,
    ),
    st.floats(min_value=1e-6, max_value=10.0),
)
def test_f32_bound_and_containment(vals, eb):
    x = np.array(vals, np.float32)
    # public-API contract: f32 uses i32 bins; compress() rejects overflow
    assume(np.abs(x).max() / effective_eps(eb) < np.iinfo(np.int32).max * 0.5)
    b = quantize(jnp.asarray(x), eb)
    eps = effective_eps(eb)
    base = decode_base(b, eps, jnp.float32)
    top = decode_base(b + 1, eps, jnp.float32)
    assert bool(jnp.all(jnp.asarray(x) >= base)), "containment (bottom)"
    assert bool(jnp.all(jnp.asarray(x) < top)), "containment (top)"
    # decode at subbin 0 is within the user bound
    y = dequantize(b, jnp.zeros_like(b), eb, jnp.float32)
    assert np.all(np.abs(x.astype(np.float64) - np.asarray(y, np.float64)) <= eb)


@given(
    st.lists(
        st.floats(min_value=-1e12, max_value=1e12, allow_nan=False),
        min_size=1,
        max_size=64,
    ),
    st.floats(min_value=1e-9, max_value=100.0),
)
def test_f64_bound_and_containment(vals, eb):
    x = np.array(vals, np.float64)
    # public-API contract: bins must stay in the f64-exact domain
    # (compress() rejects anything beyond via check_bin_range)
    assume(np.abs(x).max() / effective_eps(eb) < max_abs_bin(np.float64))
    b = quantize(jnp.asarray(x), eb)
    y = dequantize(b, jnp.zeros_like(b), eb, jnp.float64)
    assert np.all(np.abs(x - np.asarray(y)) <= eb)


def test_monotone(rng):
    x = np.sort(rng.standard_normal(1000)).astype(np.float64)
    b = np.asarray(quantize(jnp.asarray(x), 1e-3))
    assert np.all(np.diff(b) >= 0), "quantization must be monotone increasing"


@pytest.mark.parametrize("mode,expected", [("abs", 0.5), ("noa", 0.5 * 3.0)])
def test_bound_modes(mode, expected):
    x = np.array([0.0, 1.0, 3.0])
    assert abs_bound_from_mode(x, 0.5, mode) == pytest.approx(expected)


def test_noa_constant_field():
    x = np.zeros(10)
    assert abs_bound_from_mode(x, 0.5, "noa") == pytest.approx(0.5)


@given(
    st.floats(min_value=-1e30, max_value=1e30, allow_nan=False),
    st.integers(min_value=0, max_value=100),
)
def test_ordered_int_roundtrip_and_nextafter(v, k):
    for dtype in (np.float32, np.float64):
        x = jnp.asarray(np.array([v], dtype))
        m = float_to_ordered(x)
        back = ordered_to_float(m, dtype)
        assert np.asarray(back == x).all() or (float(x[0]) == 0.0)
        stepped = np.asarray(nextafter_k(x, jnp.asarray([k])))[0]
        expect = float(x[0])
        for _ in range(k):
            expect = np.nextafter(np.array(expect, dtype), np.array(np.inf, dtype))
        assert stepped == expect


def test_ordered_int_is_monotone(rng):
    for dtype in (np.float32, np.float64):
        x = np.sort(rng.standard_normal(500).astype(dtype))
        m = np.asarray(float_to_ordered(jnp.asarray(x)))
        assert np.all(np.diff(m) >= 0)


# ------------------------------------------- exact f32 decode anchors

I32 = np.iinfo(np.int32)
EPS_LO, EPS_HI = F32_EPS_RANGE


def _anchor_reference(b, eps):
    """Ordered int32 of the smallest f32 (or +inf) >= RN64((b - 0.5) *
    eps), from numpy's IEEE f64 multiply and ``nextafter`` alone."""
    t = (np.asarray(b, np.int64).astype(np.float64) - 0.5) * eps
    with np.errstate(over="ignore"):
        f = t.astype(np.float32)
        f = np.where(f.astype(np.float64) < t,
                     np.nextafter(f, np.float32(np.inf)), f)
    bits = f.view(np.int32)
    return np.where(bits >= 0, bits, I32.min - bits)


def _random_eps(rng, n):
    """Random 53-bit mantissas at exponents across F32_EPS_RANGE."""
    e = rng.integers(int(np.log2(EPS_LO)), int(np.log2(EPS_HI)), n)
    return np.clip(np.ldexp(rng.uniform(1.0, 2.0, n), e), EPS_LO, EPS_HI)


def _edge_cases(rng):
    bins = np.array([0, 1, -1, 2, -2, 3, -3, 2**14, -2**14, 2**24, -2**24,
                     2**30, -2**30, 2**30 + 1, -2**30 - 1, I32.max, I32.min,
                     I32.max - 1, I32.min + 1], np.int64)
    eps = np.array([EPS_LO, np.nextafter(EPS_LO, np.inf), EPS_HI,
                    np.nextafter(EPS_HI, 0), 1.0, 0.5, 1.5,
                    effective_eps(1e-2), effective_eps(min_eps_abs(np.float32)),
                    np.ldexp(2.0 - 2.0**-52, -60), np.ldexp(1.0, 127),
                    np.ldexp(1.0, 128), 3.4e38])
    b, e = np.meshgrid(bins, eps)
    return b.ravel(), e.ravel()


def _tie_cases(rng, n=4096):
    """(b, eps) whose f64 product (b - 0.5) * eps is a tie or a near tie
    next to the f32 grid, where the rounding bits decide the anchor.
    With mantissa ``m_odd * 2**t`` and ``A = |2b - 1|``, the odd product
    ``A * m_odd`` has 54 + k bits: rounding to 53 drops its low k + 1
    bits, the top one of them set (a tie when k == 0, else just above
    one, with sticky bits as low as the product's lowest 16), and the 29
    bits between the f64 and the f32 precision are all zeros (even: a
    tie rounds down onto an f32 value) or all ones (odd: it carries onto
    one)."""
    bins, eps = [], []
    while len(bins) < n:
        k = 0 if rng.integers(2) else int(rng.integers(1, 21))
        t = int(rng.integers(1, 22 - k))
        a = int(rng.integers(1 << (t + k), 1 << (t + k + 1))) | 1
        sticky = int(rng.integers(0, 1 << int(rng.integers(0, k)))) | 1 \
            if k else 0
        mid = 0 if rng.integers(2) else (1 << 29) - 1
        want = (((mid << 1) | 1) << k) | sticky
        mod = 1 << (k + 30)
        r = want * pow(a, -1, mod) % mod
        lo = max(-(-(1 << (53 + k)) // a), 1 << (52 - t))
        hi = min(((1 << (54 + k)) - 1) // a, (1 << (53 - t)) - 1)
        j_lo, j_hi = -(-(lo - r) // mod), (hi - r) // mod
        if j_lo > j_hi:
            continue
        m_odd = r + int(rng.integers(j_lo, j_hi + 1)) * mod
        p = a * m_odd
        assert lo <= m_odd <= hi and p.bit_length() == 54 + k
        assert p % mod == want and m_odd & 1
        bins.append((a + 1) // 2 if rng.integers(2) else (1 - a) // 2)
        # |t| = a * m_odd * 2**(t + e - 1) lands at 2**[-100, 100)
        e = int(rng.integers(-100, 100)) - 54 - k - t
        eps.append(np.ldexp(float(m_odd << t), e))
    return np.array(bins, np.int64), np.array(eps)


def _saturating_cases(rng, n=1 << 14):
    """eps placing (b - 0.5) * eps around +-f32 max: anchors that round
    onto max, saturate to +inf (positive) or clamp to -max (negative)."""
    b = rng.integers(-2**31, 2**31, n)
    b[b == 0] = 1
    big = float(np.finfo(np.float32).max)
    eps = big / np.abs(b - 0.5) * (1 + rng.integers(-4, 5, n) * 2.0**-30)
    eps *= 1 + rng.integers(-3, 4, n) * 2.0**-52
    return b, np.clip(eps, EPS_LO, EPS_HI)


def _near_grid_cases(rng, n=1 << 16):
    """eps placing (b - 0.5) * eps within a few f64 ulps of an f32 value,
    where the round-up onto the f32 grid decides the anchor."""
    b = rng.integers(-2**20, 2**20, n)
    v = (rng.standard_normal(n) * 10.0 ** rng.uniform(-30, 30, n))
    v = v.astype(np.float32).astype(np.float64)
    eps = np.abs(v * 2 / (2 * b - 1)) * (1 + rng.integers(-3, 4, n) * 2.0**-52)
    return b, np.clip(np.where(eps > 0, eps, 1.0), EPS_LO, EPS_HI)


def _random_cases(rng, n=1 << 17):
    b = np.concatenate([
        rng.integers(-2**31, 2**31, n),
        (rng.choice([-1, 1], n) * 2.0 ** rng.uniform(0, 31, n)).astype(np.int64)
        .clip(I32.min, I32.max)])
    return b, _random_eps(rng, 2 * n)


@pytest.mark.parametrize("family", [_random_cases, _edge_cases, _tie_cases,
                                    _saturating_cases, _near_grid_cases],
                         ids=lambda f: f.__name__.strip("_"))
def test_f32_anchor_equals_ieee_reference(rng, family):
    """The integer-limb anchor equals RN64((b - 0.5) * eps) rounded up
    onto the f32 grid (numpy's IEEE f64) for every case of the family."""
    b, eps = family(rng)
    assert np.all((eps >= EPS_LO) & (eps <= EPS_HI))
    got = np.asarray(f32_base_ordered(jnp.asarray(b.astype(np.int32)),
                                      eps_operand(eps)))
    want = _anchor_reference(b, eps)
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, (b[bad[:5]], eps[bad[:5]], got[bad[:5]],
                           want[bad[:5]])


def test_f32_eps_floor():
    """The smallest accepted f32 bound keeps the half-bin anchor -eps/2
    normal (the anchor equals the f64 definition there), and anything
    below it is refused."""
    floor = min_eps_abs(np.float32)
    tiny = float(np.finfo(np.float32).tiny)
    assert floor == 2 * tiny / EPS_SHRINK
    assert effective_eps(floor) / 2 >= tiny
    assert effective_eps(floor) >= EPS_LO
    assert min_eps_abs(np.float64) == float(np.finfo(np.float64).tiny)
    x = np.array([0.0, 3 * tiny, -5 * tiny, 1e-36], np.float32)
    check_eps(x, floor)
    with pytest.raises(ValueError, match="smallest normal"):
        check_eps(x, float(np.nextafter(floor, 0)))
    y = np.asarray(dequantize(quantize(jnp.asarray(x), floor),
                              jnp.zeros(4, jnp.int32), floor, jnp.float32))
    assert np.all(np.abs(x.astype(np.float64) - y) <= floor)


@pytest.mark.parametrize("eps", [effective_eps(1e-2), 1e-3, 0.37,
                                 np.ldexp(1.0, -100), np.ldexp(1.7, 90)])
def test_f32_first_guess_lands_on_f64_bins(eps):
    """For |bin| < 2**14 (16-bit bin streams) the fused kernel's f32
    quotient and the staged frontend's f64 quotient correct to the same
    bins, on every anchor and its f32 neighbours."""
    b = np.arange(-2**14 + 1, 2**14, dtype=np.int32)
    e = eps_operand(np.float64(eps))
    anchors = np.asarray(decode_base(jnp.asarray(b), e, jnp.float32))
    x = np.concatenate([anchors, np.nextafter(anchors, np.float32(np.inf)),
                        np.nextafter(anchors, np.float32(-np.inf))])
    x = x[np.isfinite(x)]
    f64 = quantize_broadcast(jnp.asarray(x), e, jnp.float32)
    e32 = Eps(np.float32(eps), *e[1:])
    f32 = quantize_broadcast(jnp.asarray(x), e32, jnp.float32)
    assert np.array_equal(np.asarray(f64), np.asarray(f32))
