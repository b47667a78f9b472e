"""Per-kernel validation: interpret-mode Pallas vs ref.py oracles.

All LOPC kernels are integer/f32-exact, so comparisons are strict
equality across shape/dtype sweeps (brief requirement (c))."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.quantize import effective_eps
from repro.core.subbin import solve_subbins
from repro.core.quantize import quantize as quantize_f64
from repro.kernels import ops, ref
from repro.kernels.ref import (
    dequantize_ff32_ref,
    quantize_ff32_ref,
    rze_bitmap_ref,
    solve_subbins_ref,
)


@pytest.mark.parametrize("n", [5, 128, 4096, 100_000])
@pytest.mark.parametrize("scale", [1e-3, 1.0, 1e3])
def test_quantize_kernel_matches_ref(rng, n, scale):
    x = (rng.standard_normal(n) * scale).astype(np.float32)
    eps = np.float32(scale * 1e-3)
    got = np.asarray(ops.quantize_ff32(jnp.asarray(x), eps))
    want = np.asarray(quantize_ff32_ref(jnp.asarray(x), jnp.float32(eps)))
    assert np.array_equal(got, want)


@given(
    st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32), min_size=1, max_size=300),
    st.floats(1e-3, 10.0),
)
def test_quantize_kernel_property(vals, eb):
    x = np.array(vals, np.float32)
    eps = np.float32(effective_eps(eb))
    if not ops.ff32_domain_ok(x, eps):
        return
    bins = ops.quantize_ff32(jnp.asarray(x), eps)
    # containment under the FF32 base (same predicate the decoder uses)
    base = np.asarray(ref.decode_base_ff32(bins, jnp.float32(eps)))
    top = np.asarray(ref.decode_base_ff32(bins + 1, jnp.float32(eps)))
    assert (x >= base).all() and (x < top).all()
    # user bound
    y = np.asarray(ops.dequantize_ff32(bins, jnp.zeros_like(bins), eps))
    assert np.abs(x.astype(np.float64) - y.astype(np.float64)).max() <= eb


@pytest.mark.parametrize("n", [7, 4096, 33_000])
def test_decode_kernel_matches_ref(rng, n):
    bins = rng.integers(-(2**22), 2**22, n).astype(np.int32)
    sub = rng.integers(0, 5, n).astype(np.int32)
    eps = np.float32(1e-2)
    got = np.asarray(ops.dequantize_ff32(jnp.asarray(bins), jnp.asarray(sub), eps))
    want = np.asarray(dequantize_ff32_ref(jnp.asarray(bins), jnp.asarray(sub), jnp.float32(eps)))
    assert np.array_equal(got, want)


def test_ff32_end_to_end_order_preservation(rng):
    """FF32 path preserves order + bound on its own decode chain."""
    from repro.core.subbin import solve_subbins as solve
    from repro.tda.critpoints import local_order_violations

    x = (np.cumsum(rng.standard_normal((24, 18, 12)), 0) * 0.1).astype(np.float32)
    eb = 0.05
    eps = np.float32(effective_eps(eb))
    assert ops.ff32_domain_ok(x, eps)
    bins = ops.quantize_ff32(jnp.asarray(x), eps)
    sub, _ = solve(bins, jnp.asarray(x), method="jacobi")
    y = np.asarray(ops.dequantize_ff32(bins, sub, eps))
    assert np.abs(x.astype(np.float64) - y.astype(np.float64)).max() <= eb
    assert local_order_violations(x, y) == 0


@pytest.mark.parametrize("n_chunks", [1, 4, 9])
def test_bitshuffle_kernel_matches_ref(rng, n_chunks):
    words = rng.integers(0, 2**32, (n_chunks, 4096), dtype=np.uint32)
    words[0] &= np.uint32(0xFF)
    got = np.asarray(ops.bitshuffle_u32(jnp.asarray(words)))
    want = np.asarray(ref.bitshuffle_ref(jnp.asarray(words)))
    assert np.array_equal(got, want)
    back = np.asarray(ops.bitunshuffle_u32(jnp.asarray(got)))
    assert np.array_equal(back, words)


@pytest.mark.parametrize("n_chunks", [1, 4, 11])
def test_rze_kernel_matches_ref(rng, n_chunks):
    words = rng.integers(0, 50, (n_chunks, 4096), dtype=np.uint32)
    words[words < 40] = 0
    bitmap, counts = ops.rze_bitmap_u32(jnp.asarray(words))
    bitmap_ref_, counts_ref_ = rze_bitmap_ref(jnp.asarray(words))
    assert np.array_equal(np.asarray(bitmap), np.asarray(bitmap_ref_))
    assert np.array_equal(np.asarray(counts), np.asarray(counts_ref_))


@pytest.mark.parametrize("shape", [(40,), (17, 23), (9, 11, 13), (64, 8, 4)])
def test_subbin_sweep_matches_jacobi(rng, shape):
    """Blockwise kernel == jacobi == canonical-3D ref (schedule
    independence of the least fixed point across all three solvers)."""
    x = rng.uniform(-1, 1, shape)
    xj = jnp.asarray(x)
    bins = quantize_f64(xj, 0.5)
    s_jacobi, _ = solve_subbins(bins, xj, method="jacobi")
    s_block, _ = ops.solve_subbins_blockwise(bins, xj)
    s_ref, _ = solve_subbins_ref(bins, xj)
    assert np.array_equal(np.asarray(s_jacobi), np.asarray(s_block))
    assert np.array_equal(np.asarray(s_jacobi), np.asarray(s_ref))


@pytest.mark.parametrize("rows", [1, 5, 255, 257, 300])
def test_dequantize_ff32_any_row_count(rng, rows):
    """The microkernel pads odd row counts internally (no BLOCK_ROWS
    divisibility requirement on callers) and slices the result back."""
    from repro.kernels import fused_decode

    bins = rng.integers(-(2**20), 2**20,
                        (rows, fused_decode.LANE)).astype(np.int32)
    sub = rng.integers(0, 5, (rows, fused_decode.LANE)).astype(np.int32)
    eps = jnp.float32(1e-2)
    got = fused_decode.dequantize_ff32(jnp.asarray(bins), jnp.asarray(sub),
                                       eps, interpret=True)
    assert got.shape == (rows, fused_decode.LANE)
    want = dequantize_ff32_ref(jnp.asarray(bins), jnp.asarray(sub), eps)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_fused_decode_matches_staged_on_determinism_cases():
    """decode_path="fused" (and "auto") must reproduce the staged chain
    bit-for-bit on every case the determinism manifest pins — the same
    24 generator/shape/dtype combinations whose container hashes CI
    compares, so fused-vs-staged identity is checked exactly where a
    numerics drift would also break the archived-bytes claim."""
    from benchmarks.check_determinism import DTYPES, EB, SHAPES
    from repro import engine
    from repro.data.fields import FIELD_GENERATORS, make_scientific_field

    for name in sorted(FIELD_GENERATORS):
        for shape in SHAPES:
            for dtype in DTYPES:
                x = make_scientific_field(name, shape, np.dtype(dtype),
                                          seed=5)
                blob = engine.compress(x, EB)
                case = (name, shape, dtype)
                staged = engine.decompress(blob, decode_path="staged")
                for path in ("fused", "auto"):
                    y = engine.decompress(blob, decode_path=path)
                    assert y.dtype == staged.dtype, case
                    assert y.tobytes() == staged.tobytes(), \
                        f"decode_path={path} diverged from staged on {case}"


def test_subbin_sweep_long_chain_fewer_sweeps():
    """The point of block-local convergence: a chain spanning the whole
    X extent converges in ~X/BAND global sweeps, not ~X."""
    n = 128
    x = -np.cumsum(np.full((n, 4, 4), 1e-9), axis=0)  # descending in x
    xj = jnp.asarray(x)
    bins = quantize_f64(xj, 1.0)
    sub_j, it_j = solve_subbins(bins, xj, method="jacobi")
    sub_b, it_b = ops.solve_subbins_blockwise(bins, xj)
    assert np.array_equal(np.asarray(sub_j), np.asarray(sub_b))
    assert int(it_b) < int(it_j) / 3, (int(it_b), int(it_j))


# ------------------------------------------------------- fused encode

def test_fused_encode_ints_matches_staged(rng):
    """The fused encode kernel's streams must equal the staged
    ``device.encode_tiles`` programs exactly, across word widths and
    transform modes (the bins/subs/temporal-residual cases)."""
    from repro.engine import device
    from repro.kernels import fused_encode

    for dtype, chunk_len in ((np.int16, 8192), (np.int32, 4096)):
        for transform in ("delta", "zigzag", "raw"):
            ints = rng.integers(-50, 50, (4, 1000)).astype(dtype)
            ints[0, :37] = 0  # leading zero run -> dead bitmap words
            got = fused_encode.encode_ints_fused(
                jnp.asarray(ints), chunk_len, transform, interpret=True)
            want = device.encode_tiles(jnp.asarray(ints), chunk_len,
                                       transform)
            for g, w in zip(got, want):
                assert np.array_equal(np.asarray(g), np.asarray(w)), \
                    (dtype, transform)


@pytest.mark.parametrize("batch,block_tiles", [(1, 4), (3, 2), (5, 4),
                                               (7, 3)])
def test_fused_encode_pads_odd_batches(rng, batch, block_tiles):
    """Batches that don't divide ``block_tiles`` pad internally (zero
    rows -> all-zero streams) and slice back to exactly the staged
    output — odd row counts arrive from callers outside the bucketed
    executor."""
    from repro.engine import device
    from repro.kernels import fused_encode

    ints = rng.integers(-9, 9, (batch, 600)).astype(np.int32)
    got = fused_encode.encode_ints_fused(
        jnp.asarray(ints), 4096, "delta", interpret=True,
        block_tiles=block_tiles)
    want = device.encode_tiles(jnp.asarray(ints), 4096, "delta")
    for g, w in zip(got, want):
        assert g.shape == w.shape, (batch, block_tiles)
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_fused_encode_values_handles_dead_tiles(rng):
    """The full-fusion values kernel: NaN cells (dead pad tiles, in-tile
    pad) must encode as bin 0 exactly like the staged frontend's
    validity masking, and live cells as the shared quantize sequence."""
    from repro.engine import device
    from repro.kernels import fused_encode

    batch, elems = 5, 700
    x = (rng.standard_normal((batch, elems)) * 3).astype(np.float32)
    x[1] = np.nan          # fully dead tile (capacity pad)
    x[3, 600:] = np.nan    # in-tile pad cells
    from repro.core.quantize import eps_operand, quantize_broadcast

    eps = eps_operand(np.full(batch, 1e-3, np.float64))
    got = fused_encode.encode_values_fused(
        jnp.asarray(x), eps, 8192, jnp.float32, jnp.int16,
        interpret=True)
    # the staged equivalent: quantize valid cells (f64 first guess),
    # zero the rest, encode
    valid = np.isfinite(x)
    bins = np.asarray(quantize_broadcast(
        jnp.asarray(np.where(valid, x, 0)), eps.expand(1), jnp.float32))
    bins = np.where(valid, bins, 0).astype(np.int16)
    want = device.encode_tiles(jnp.asarray(bins), 8192, "delta")
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert np.asarray(got[2])[1] == 0  # dead tile -> zero-count chunk


def test_fused_encode_values_refuses_wide_bins():
    """The kernel's f32 first guess is exact only for 16-bit bins; wider
    streams stay on the staged quantize."""
    from repro.core.quantize import eps_operand
    from repro.kernels import fused_encode

    eps = eps_operand(np.full(2, 1e-3, np.float64))
    with pytest.raises(ValueError, match="16-bit bins"):
        fused_encode.encode_values_fused(
            jnp.zeros((2, 64), jnp.float32), eps, 4096, jnp.float32,
            jnp.int32, interpret=True)


def test_fused_encode_matches_staged_on_determinism_cases():
    """encode_path="fused" must emit byte-identical containers to the
    staged chain on every snapshot case the determinism manifest pins —
    across both solver schedules — and those bytes must still hash to
    the committed manifest, so the fused path is held to the same
    archived-bytes contract as the staged one."""
    import hashlib
    import json

    from benchmarks.check_determinism import (
        DTYPES,
        EB,
        MANIFEST_PATH,
        SHAPES,
    )
    from repro import engine
    from repro.data.fields import FIELD_GENERATORS, make_scientific_field

    manifest = json.loads(MANIFEST_PATH.read_text())
    for name in sorted(FIELD_GENERATORS):
        for shape in SHAPES:
            for dtype in DTYPES:
                x = make_scientific_field(name, shape, np.dtype(dtype),
                                          seed=5)
                case = f"{name}/{'x'.join(map(str, shape))}/{dtype}"
                for solver in ("jacobi", "blockwise"):
                    staged = engine.compress(x, EB, solver=solver,
                                             encode_path="staged")
                    fused = engine.compress(x, EB, solver=solver,
                                            encode_path="fused")
                    assert fused == staged, \
                        f"encode_path=fused diverged on {case}/{solver}"
                    assert (hashlib.sha256(fused).hexdigest()
                            == manifest[case]), case
