"""Device-resident executor acceptance (PR-2 contract):

1. Transfer probe: exactly one field-tile upload and one encoded-stream
   download per compress group, whatever the solver or round count.
2. Trace probe: the resident path costs a constant number of traces
   across mixed shapes/dtypes once each (tile, capacity, dtype) bucket
   is warm — and zero growth in steady state.
3. Cross-solver bit-identity: jacobi / frontier / blockwise (Pallas,
   interpret on CPU) emit byte-identical v2 containers, and all decode
   bit-identical to the legacy whole-field ``core.lopc`` path, over all
   field generators, f32+f64, including nonfinite inputs.
4. Adaptive section widths: bins/subbins store at the narrowest word
   the values need (self-described; wide values fall back losslessly).
5. Empty-input guards and trailing-chunk trimming.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro import engine
from repro.core import bitstream, compress, decompress
from repro.data.fields import FIELD_GENERATORS, make_scientific_field
from repro.engine import device, executor
from repro.engine.plan import CompressionPlan

GENERATORS = sorted(FIELD_GENERATORS)
SOLVERS = ("jacobi", "frontier", "blockwise")


# ------------------------------------------------------- transfer probe

def test_one_upload_one_download_per_compress_group(rng):
    fields = [rng.standard_normal((12, 11, 10)) for _ in range(3)]
    executor.reset_transfer_counts()
    blobs = engine.compress_many(fields, 1e-2)
    # identical shapes -> one (dtype, tile) group -> one tile upload and
    # one stream download, regardless of field count or halo rounds
    assert executor.TRANSFER_COUNTS["h2d_tiles"] == 1
    assert executor.TRANSFER_COUNTS["d2h_sections"] == 1

    executor.reset_transfer_counts()
    engine.compress_many(
        [rng.standard_normal((10, 10, 10)),
         rng.standard_normal((10, 10, 10)).astype(np.float32)], 1e-2,
    )
    assert executor.TRANSFER_COUNTS["h2d_tiles"] == 2  # two dtype groups
    assert executor.TRANSFER_COUNTS["d2h_sections"] == 2

    executor.reset_transfer_counts()
    engine.decompress_many(blobs)
    assert executor.TRANSFER_COUNTS["h2d_sections"] == 1
    assert executor.TRANSFER_COUNTS["d2h_values"] == 1


def test_compress_aux_upload_is_eps_and_neighbour_table(rng):
    """Beside its tiles, a compress group uploads the f64 eps vector and
    the (capacity, 7) int32 halo neighbour table: per tile, not per
    cell."""
    from repro.engine import halo

    x = rng.standard_normal((40, 36, 70))
    layout = CompressionPlan().layout_for(x.shape)
    capacity = executor.resident_capacity(layout.n_tiles)
    executor.reset_transfer_counts()
    engine.compress(x, 1e-2)
    assert executor.TRANSFER_COUNTS["h2d_aux"] == 2
    tiles = capacity * int(np.prod(layout.halo_tile)) * x.itemsize
    aux = capacity * (8 + 4 * halo.N_COLS)
    assert executor.TRANSFER_COUNTS["bytes_h2d"] == tiles + aux


# ---------------------------------------------------------- trace probe

def test_resident_traces_constant_across_mixed_shapes_dtypes(rng):
    """Shapes sharing one (tile, capacity) bucket must share every
    resident trace — across dtypes too, once each dtype is warm."""
    plan = CompressionPlan(tile_shape=(8, 8, 8), batch_tiles=4)
    # all of these shrink to tile (8,8,8), single tile, floor capacity
    shapes = [(8, 8, 8), (7, 8, 8), (8, 7, 6), (6, 7, 8), (5, 8, 8)]
    for dtype in (np.float64, np.float32):  # warm both dtype buckets
        x = rng.standard_normal(shapes[0]).astype(dtype)
        engine.decompress(engine.compress(x, 1e-2, plan=plan), plan=plan)
    snapshot = dict(device.TRACE_COUNTS)
    for shape in shapes[1:]:
        for dtype in (np.float64, np.float32):
            x = rng.standard_normal(shape).astype(dtype)
            y = engine.decompress(engine.compress(x, 1e-2, plan=plan),
                                  plan=plan)
            assert np.abs(x - y).max() <= 1e-2 * (x.max() - x.min())
    assert dict(device.TRACE_COUNTS) == snapshot, \
        "resident path retraced within a warm (tile, capacity) bucket"


# ------------------------------------------------ cross-solver identity

@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("name", GENERATORS)
def test_cross_solver_bit_identity(name, dtype):
    x = make_scientific_field(name, (13, 11, 9), dtype, seed=5)
    blobs = {s: engine.compress(x, 1e-2, solver=s) for s in SOLVERS}
    ref = blobs["jacobi"]
    for s, b in blobs.items():
        assert b == ref, f"solver {s} produced different bytes ({name})"
    y_legacy = decompress(compress(x, 1e-2, "noa", container_version=1))
    assert np.array_equal(engine.decompress(ref), y_legacy), (name, dtype)


def test_cross_solver_bit_identity_nonfinite(rng):
    x = rng.standard_normal((14, 12, 10))
    x[rng.random(x.shape) < 0.07] = np.nan
    x[2, 3, 4] = np.inf
    x[5, 6, 7] = -np.inf
    blobs = {s: engine.compress(x, 1e-2, solver=s) for s in SOLVERS}
    assert len(set(blobs.values())) == 1
    y_legacy = decompress(compress(x, 1e-2, "noa", container_version=1))
    assert np.array_equal(engine.decompress(blobs["jacobi"]), y_legacy,
                          equal_nan=True)


def test_cross_solver_low_rank(rng):
    for shape in [(250,), (21, 17)]:
        x = rng.standard_normal(shape)
        blobs = {s: engine.compress(x, 5e-3, solver=s) for s in SOLVERS}
        assert len(set(blobs.values())) == 1
        assert np.array_equal(
            engine.decompress(blobs["jacobi"]),
            decompress(compress(x, 5e-3, "noa", container_version=1)),
        )


# ------------------------------------------------ adaptive stream width

def test_sections_narrow_to_value_range(rng):
    x = rng.standard_normal((12, 11, 10))
    c = bitstream.read_container_v2(engine.compress(x, 1e-2))
    # eb=1e-2 NOA: |bin| <~ 50, short chains -> both streams fit int16
    assert c.stream_words() == (2, 2)
    y = engine.decompress(engine.compress(x, 1e-2))
    assert np.array_equal(y, decompress(compress(x, 1e-2, "noa",
                                                 container_version=1)))


def test_sections_widen_when_values_demand_it(rng):
    # bins: tight absolute bound on wide-range f64 data -> beyond int16
    x = rng.standard_normal((10, 10, 10)) * 1e4
    c = bitstream.read_container_v2(engine.compress(x, 1e-4, "abs"))
    assert c.stream_words()[0] >= 4
    assert np.array_equal(
        engine.decompress(engine.compress(x, 1e-4, "abs")),
        decompress(compress(x, 1e-4, "abs", container_version=1)),
    )
    # subbins: one monotone chain longer than int16 -> int32 sub stream
    hard = -np.cumsum(np.full(40_000, 1e-9))
    blob = engine.compress(hard, 1.0, "abs")
    assert bitstream.read_container_v2(blob).stream_words()[1] == 4
    assert np.array_equal(
        engine.decompress(blob),
        decompress(compress(hard, 1.0, "abs", container_version=1)),
    )


# ------------------------------------------------- trimming + tolerance

def test_trailing_zero_chunks_are_trimmed(rng):
    plan = CompressionPlan(tile_shape=(1, 1, 16384))
    x = np.zeros(9000)
    x[:100] = rng.standard_normal(100)
    blob = engine.compress(x, 1e-2, plan=plan)
    c = bitstream.read_container_v2(blob)
    assert c.n_tiles == 1
    bins_b, _ = c.tile_payloads(0)
    bm, _ = bitstream.deserialize_rze_section(bins_b)
    tile_elems = int(np.prod(c.tile_shape))
    word = c.stream_words()[0]
    cpt = -(-tile_elems // {2: 8192, 4: 4096, 8: 2048}[word])
    assert bm.shape[0] < cpt, "all-zero trailing chunks were not trimmed"
    assert np.array_equal(engine.decompress(blob, plan=plan), np.asarray(
        decompress(compress(x, 1e-2, "noa", container_version=1))))


def test_small_field_in_big_plan_tile_ratio(rng):
    """The PR-1 regression: a field much smaller than the plan tile must
    not serialize pad — tile shrink + trim keep the ratio near legacy's."""
    plan = CompressionPlan(tile_shape=(16, 16, 64), batch_tiles=8)
    x = make_scientific_field("gaussians", (40, 28, 12), seed=3)
    blob, stats = engine.compress(x, 1e-2, plan=plan, return_stats=True)
    _, legacy_stats = compress(x, 1e-2, "noa", container_version=1,
                               return_stats=True)
    assert np.array_equal(engine.decompress(blob, plan=plan),
                          decompress(compress(x, 1e-2, "noa",
                                              container_version=1)))
    assert stats.ratio >= 0.85 * legacy_stats.ratio


# ----------------------------------------------------- empty-input guards

def test_compress_many_empty():
    assert engine.compress_many([], 1e-2) == []
    blobs, stats = engine.compress_many([], 1e-2, return_stats=True)
    assert blobs == [] and stats == []
    assert engine.decompress_many([]) == []


def test_decompress_roi_zero_volume(rng):
    x = rng.standard_normal((12, 10, 8))
    blob = engine.compress(x, 1e-2)
    out = engine.decompress_roi(blob, (slice(5, 2), slice(0, 5), slice(0, 5)))
    assert out.shape == (0, 5, 5) and out.dtype == x.dtype
    assert engine.decompress_roi(blob, (slice(3, 3), slice(0, 2),
                                        slice(0, 8))).size == 0
    assert engine.decompress_roi(blob, (slice(0, 0),)
                                 + (slice(None),) * 2).size == 0


# ----------------------------------------------------- executor plumbing

def test_resident_capacity_buckets():
    from repro.engine import buckets

    assert executor.resident_capacity(1) == executor.CAPACITY_FLOOR
    assert executor.resident_capacity(8) == 8
    assert executor.resident_capacity(9) == 16
    assert executor.resident_capacity(36) == 64
    assert executor.resident_capacity(37) == 64
    assert executor.resident_capacity(3, floor=4) == 4
    # every capacity a packed batch can take is in the closed class set
    classes = buckets.capacity_classes(8)
    assert classes == (8, 16, 32, 64, 128)
    for n in range(1, buckets.packing_cap(8) + 1):
        assert executor.resident_capacity(n) in classes


def test_bucket_chunk_planning():
    from repro.engine import buckets

    cap = buckets.packing_cap(8)  # 128
    # compress chunks split at request boundaries, never above the cap
    sizes = [63, 63, 63, 4, 100, 1]
    spans = buckets.plan_request_chunks(sizes, 8)
    assert [tuple(s) for s in spans] == [(0, 2), (2, 4), (4, 6)]
    assert all(sum(sizes[lo:hi]) <= cap for lo, hi in spans)
    # an oversized single request rides a chunk of its own
    assert buckets.plan_request_chunks([300, 2], 8) == [(0, 1), (1, 2)]
    assert buckets.plan_request_chunks([], 8) == [(0, 0)]
    # decode chunks balance: every chunk of an overflowing batch lands
    # in the top two classes, so no small-residue classes appear under
    # load that a prewarm pass didn't see
    for n in (129, 200, 257, 1000):
        chunks = buckets.plan_tile_chunks(n, 8)
        assert sum(chunks) == n
        assert all(cap // 2 <= c <= cap for c in chunks)
    assert buckets.plan_tile_chunks(5, 8) == [5]
    assert buckets.plan_tile_chunks(0, 8) == []


def test_bucket_company_never_changes_bytes(rng):
    """The bucket byte contract: the SAME request compressed alone, in
    a half-full bucket, and in an exactly-full bucket (and beyond, into
    chunk-split territory) emits identical container bytes — capacity
    classes only pad device batches with masked dead tiles."""
    from repro.engine import buckets

    plan = CompressionPlan(tile_shape=(8, 8, 8), batch_tiles=4)
    floor = max(buckets.CAPACITY_FLOOR, plan.batch_tiles)
    x = rng.standard_normal((16, 8, 8))          # 2 tiles
    mate = rng.standard_normal((8, 8, 8))        # 1 tile
    alone = engine.compress(x, 1e-2, plan=plan)
    # half-full bucket (3 of 8 tiles), exactly-full (x + 6 mates), and a
    # group big enough to split into multiple capacity-class chunks
    for n_mates in (1, floor - 2, 4 * buckets.packing_cap(floor)):
        group = [x] + [mate] * n_mates
        blobs = engine.compress_many(group, 1e-2, plan=plan)
        assert blobs[0] == alone, f"bytes changed with {n_mates} mates"
        assert all(b == blobs[1] for b in blobs[2:])
    # decode side: the padded/chunked batches reproduce the same values
    # whichever company the containers decode in
    alone_y = engine.decompress(alone, plan=plan)
    group_y = engine.decompress_many([alone] + [blobs[1]] * 5, plan=plan)
    assert np.array_equal(group_y[0], alone_y)


def test_decode_path_flag_is_value_identical(rng):
    """staged / fused / auto decode paths return identical values (the
    fused Pallas kernel is the same math, fused); unknown paths fail
    fast."""
    x = rng.standard_normal((24, 18, 12)).astype(np.float32)
    blob = engine.compress(x, 1e-2)
    outs = {p: engine.decompress(blob, decode_path=p)
            for p in executor.DECODE_PATHS}
    assert outs["fused"].tobytes() == outs["staged"].tobytes()
    assert outs["auto"].tobytes() == outs["staged"].tobytes()
    with pytest.raises(ValueError, match="decode path"):
        executor.Executor(engine.CompressionPlan(), decode_path="warp")


def test_sharded_executor_is_byte_identical(rng):
    import jax

    from repro.distributed.compression import compress_fields_sharded

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    fields = [rng.standard_normal((15, 12, 9)), rng.standard_normal((8, 50))]
    # placement must not change bytes
    assert compress_fields_sharded(fields, 1e-2, mesh) == \
        engine.compress_many(fields, 1e-2)


# ------------------------------------------------- encode-path contract

def test_encode_path_flag_is_byte_identical(rng):
    """encode_path staged/fused/auto must emit identical containers —
    f32 and f64 (this file also runs under the x64 CI leg), plain and
    order-preserving."""
    for dtype in (np.float32, np.float64):
        x = rng.standard_normal((20, 18, 16)).astype(dtype)
        for order in (False, True):
            staged = engine.compress(x, 1e-2, preserve_order=order,
                                     encode_path="staged")
            for path in ("fused", "auto"):
                b = engine.compress(x, 1e-2, preserve_order=order,
                                    encode_path=path)
                assert b == staged, (np.dtype(dtype), order, path)


def test_unknown_encode_path_rejected():
    with pytest.raises(ValueError, match="encode path"):
        executor.Executor(CompressionPlan(), encode_path="nope")
    with pytest.raises(ValueError, match="unknown decode path"):
        executor.Executor(CompressionPlan(), decode_path="nope")


def test_fused_encode_download_is_near_payload_size(rng):
    """The tentpole's transfer claim: with the fused path, compress-side
    D2H bytes stay within 1.1x of the serialized container (vs the
    capacity-padded staged download, a multiple of it)."""
    x = np.cumsum(rng.standard_normal((40, 40, 40)), axis=0).astype(
        np.float32)
    executor.reset_transfer_counts()
    blob = engine.compress(x, 1e-3, encode_path="fused")
    d2h = executor.TRANSFER_COUNTS["bytes_d2h"]
    assert 0 < d2h <= 1.1 * len(blob), (d2h, len(blob))

    executor.reset_transfer_counts()
    staged = engine.compress(x, 1e-3, encode_path="staged")
    assert staged == blob
    assert executor.TRANSFER_COUNTS["bytes_d2h"] > d2h


def test_fused_encode_steady_state_zero_retrace(rng):
    """A second fused-path compress in a warm bucket must add no jit
    traces: the compacted download's variable-size fetches are eager
    granule slices, never traced programs."""
    plan = CompressionPlan(tile_shape=(8, 8, 8), batch_tiles=4)
    engine.compress(rng.standard_normal((8, 8, 8)).astype(np.float32),
                    1e-2, plan=plan, encode_path="fused")
    snapshot = dict(device.TRACE_COUNTS)
    for _ in range(2):
        x = rng.standard_normal((7, 8, 6)).astype(np.float32)
        engine.compress(x, 1e-2, plan=plan, encode_path="fused")
    assert dict(device.TRACE_COUNTS) == snapshot, \
        "fused encode path retraced within a warm bucket"
