"""The benchmark's plain reference, on the CPU at tiny shapes: the
spec-only decoder agrees with the library's decode and with the
committed fixtures, a region equals the slice of the full decode, and
the local-order census agrees with ``repro.tda.critpoints`` and catches
one swapped neighbour pair."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import checks
from benchmarks.chip.reference import census, spec_decode

ROOT = Path(__file__).resolve().parents[3]
FIXTURES = ROOT / "tests" / "data"
V2 = {"v2": "fixture_v2.lopc", "v2_wide": "fixture_v2_wide.lopc",
      "v2_adaptive": "fixture_v2_adaptive.lopc"}


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    axes = np.meshgrid(*[np.linspace(0, 3 * np.pi, n) for n in shape],
                       indexing="ij")
    x = np.sin(axes[0]) * np.cos(axes[1] + 0.3) * np.sin(axes[2] + 0.7)
    return (x + 0.05 * rng.standard_normal(shape)).astype(np.float32)


@pytest.mark.parametrize("name", sorted(V2))
def test_reference_decodes_committed_fixtures(name):
    blob = (FIXTURES / V2[name]).read_bytes()
    want = np.load(FIXTURES / "expected.npz")[name]
    got = spec_decode.decode(blob)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want, equal_nan=True)


def test_reference_matches_library_decode_and_regions():
    from repro import engine

    x = _field((20, 40, 140))
    blob = engine.compress(x, 1e-2)
    full = spec_decode.decode(blob)
    assert checks.bits_differ(full, engine.decompress(blob)) == 0
    for region in [(slice(3, 17), slice(0, 40), slice(60, 139)),
                   (slice(0, 1), slice(39, 40), slice(0, 140)),
                   (slice(5, 6), slice(7, 8), slice(9, 10))]:
        got = spec_decode.decode_region(blob, region)
        assert checks.bits_differ(got, full[region]) == 0
        assert checks.bits_differ(
            got, engine.decompress_roi(blob, region)) == 0


def test_bfloat16_control_breaks_the_decode():
    from repro import engine

    x = _field((16, 24, 70), seed=3)
    blob = engine.compress(x, 1e-2)
    exact = spec_decode.decode(blob)
    low = spec_decode.decode(blob, "bfloat16")
    assert checks.bits_differ(low, exact) > exact.size // 10


def test_census_signatures_match_critpoints():
    from repro.tda.critpoints import critical_signature

    x = _field((9, 10, 11), seed=1)
    x[2, 3, 4] = x[2, 3, 5]          # a tie, broken by index
    lo, up = census.signatures(x)
    want_lo, want_up = (np.asarray(a) for a in critical_signature(x))
    inner = (slice(1, -1),) * 3       # whole links only
    assert np.array_equal(lo[inner], want_lo[inner])
    assert np.array_equal(up[inner], want_up[inner])


def test_census_counts_order_like_critpoints():
    from repro.tda.critpoints import order_violation_counts

    x = _field((12, 13, 14), seed=2)
    y = np.round(x * 8) / 8           # a coarse quantizer breaks order
    want = int(np.asarray(order_violation_counts(x, y)).sum())
    assert census.order_flips(x, y) == want > 0
    assert census.order_flips(x, x.copy()) == 0


def test_census_catches_one_swapped_neighbour_pair():
    x = _field((10, 12, 14), seed=4)
    y = x.copy()
    y[5, 6, 7], y[5, 6, 8] = x[5, 6, 8], x[5, 6, 7]
    assert x[5, 6, 7] != x[5, 6, 8]
    assert census.order_flips(x, y) > 0
    numbers = checks.field_numbers(x, y, checks.bound(x, 1e-2, "noa"))
    assert numbers["order_flips"] > 0
