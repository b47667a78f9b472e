"""Device-resident executor: upload once, run fused programs, download once.

The PR-1 engine orchestrated execution from the host: per-batch
``np.asarray`` syncs after the frontend, numpy halo scatter/gather per
relax round, and a re-upload of bins for the lossless stage.  The
executor inverts that: a compress group's tiles are uploaded to the
device once (padded to a bucketed *resident capacity* so programs stay
shape-stable), the entire quantize → flags → solve → halo rounds →
delta/zigzag/BIT/RZE pipeline runs as device-resident stage programs
over the batch (``device.resident_compress``), and one download drains
the fixed-shape encoded streams for host serialization.

Transfer accounting
-------------------
``TRANSFER_COUNTS`` counts every host↔device crossing the executor
makes, by category:

  h2d_tiles      field-tile uploads (one per compress group)
  h2d_aux        small operands: eps vector + halo neighbour table
  d2h_aux        tiny mid-pipeline fetches: the sub-max scalar (subbin
                 width pick, at the solve's natural sync point) and the
                 fused path's compacted-stream totals
  d2h_sections   encoded-stream downloads (one per compress group)
  h2d_sections   decode-side stream uploads (one per decode batch)
  d2h_values     decoded-value downloads (one per decode batch)

plus two byte totals, ``bytes_h2d`` and ``bytes_d2h``, accumulating the
payload sizes of every counted crossing — the proof that the fused
encode path's compacted download actually shrinks the transfer to
~compressed size (asserted against the serialized payload in tests and
gated by ``benchmarks/check_regression.py``).

Tests assert the compress invariant — exactly one ``h2d_tiles`` and one
``d2h_sections`` per group — and ``benchmarks/engine_bench.py`` records
the counters next to MB/s so the resident path's win stays visible.

Resident capacity
-----------------
Group tile counts pad up to a *capacity class* ``floor * 2**k`` from the
closed bucket registry (``engine.buckets``): batches larger than the
packing cap split into chunks at request boundaries (compress) or tile
boundaries (decode), so the set of trace keys a deployment can touch is
enumerable and prewarmable — steady-state serving is zero-retrace at
any load mix.  Chunking never changes bytes: halo exchange only spans a
single request's tiles and decode tiles are independent, so a chunk
boundary between requests is invisible to the streams.  The probe tests
push mixed shapes/dtypes through one bucket and assert the trace
counter does not move, and push varied shapes through many and assert
steady state adds nothing.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..codecs import rze
from ..core import bitstream
from ..core.quantize import bin_dtype_for
from ..obs import CounterView, REGISTRY, fence, span
from . import buckets, device, halo
from .plan import CompressionPlan, TileLayout

# Counter idioms (zero-default [], dict(), clear()) are preserved, but
# the storage is a registry counter family with locked increments, so
# two services incrementing from their worker threads never lose
# updates.  Writers here use the atomic ``.add`` — ``c[k] += n`` on a
# view is the old racy read-modify-write, kept only for external code.
TRANSFER_COUNTS: CounterView = CounterView(REGISTRY.counter(
    "lopc_transfers_total", "host-device crossings by category"))

# Decode-work probe, the partial-read analogue of TRANSFER_COUNTS:
# ``tiles`` counts tile sections actually decoded (every decode path
# funnels through ``decode_items``), ``batches`` the device batches they
# rode.  A region read that claims to be tile-addressable proves it here
# — tests and the store bench assert the delta equals the tiles
# overlapping the region, and a cache hit adds zero.
DECODE_COUNTS: CounterView = CounterView(REGISTRY.counter(
    "lopc_decodes_total", "tile sections decoded and their device batches"))

_CHUNK_WORDS = {2: 8192, 4: 4096, 8: 2048}  # word bytes -> words / 16 KiB

# Section word widths adapt to the stored values (self-described by the
# section header, so readers never guess): bins pick theirs host-side
# from the value-range bound (engine._store_bin_dtype); subbins pick
# int16 when the solved maximum fits, else int32 — values are < 2^31 by
# the group-size guard of ``halo.group_neighbors``, so the legacy int64
# width is never needed.
# Every halved width halves the chunk rows and bit-planes of the
# dominant BIT/RZE stage on both ends of the pipeline.

CAPACITY_FLOOR = buckets.CAPACITY_FLOOR

DECODE_PATHS = ("staged", "fused", "auto")

# decode_path="auto" picks the fused kernel once a batch clears this
# many padded elements (capacity * tile_elems); below it the staged
# chain's per-dispatch overhead is already amortized and its larger
# per-op batches win on CPU.  Crossover bracketed via engine_bench:
# 512k-elem batches still favor staged, 768k+ favor fused.
FUSED_AUTO_MIN_ELEMS = 768 * 1024

ENCODE_PATHS = ("staged", "fused", "auto")

# encode_path="auto" crossover (padded batch elements above which a real
# accelerator takes the fused kernel + compacted download).  Measured on
# CPU interpret via the encode_paths block of BENCH_engine.json: there
# is NO crossover off-TPU — the compaction's prefix-sum scatter runs
# 0.4-0.6x the staged path's wall clock at every size (XLA CPU scatter
# is serial, while the staged download's host-side boolean index is a
# vectorized memcpy) — so ``auto`` additionally requires a non-interpret
# backend, where the dispatch fold and the ~5x smaller D2H are the
# whole point.  Explicit ``fused`` is always honored (the byte-identity
# and transfer-contract tests, and CPU users who want the download
# shrink regardless of wall clock).
FUSED_ENCODE_AUTO_MIN_ELEMS = 1024 * 1024

# Compacted downloads fetch dense-buffer prefixes rounded up to this
# many words, so the set of eager slice shapes the download dispatches
# stays small while the padding tail stays well under a KiB per stream.
# Measured bytes_d2h on the paper fields is ≤ 1.097x payload (worst:
# qmcpack, the smallest container) vs the 1.1x acceptance gate; the
# overhead floor is the repeat-eliminated bitmap transport (keepmap +
# kept words run ~7x the bitmap's serialized form), NOT the tails, so
# shrinking the granule further buys nothing.
_DL_GRANULE_WORDS = 32


def use_fused_encode(encode_path: str, padded_elems: int,
                     interpret: bool) -> bool:
    """Does this compress group take the fused encode kernel?

    Unlike the decode pick, this is dtype-independent: the fused encode
    kernel covers every (transform, word width) the staged
    ``encode_tiles`` does, and the f64-sensitive quantize stage stays in
    the staged frontend except for the plain-f32 full fusion (decided
    inside ``device.resident_compress``).  Both paths are bit-identical,
    so path choice is purely a speed pick; ``auto`` requires a real
    accelerator (``not interpret``) AND the group's largest batch to
    clear ``FUSED_ENCODE_AUTO_MIN_ELEMS`` — interpret-mode measurement
    (see the constant's comment) shows the compaction scatter never
    beats the staged download off-TPU.
    """
    if encode_path == "staged":
        return False
    if encode_path == "fused":
        return True
    return not interpret and padded_elems >= FUSED_ENCODE_AUTO_MIN_ELEMS


def stream_encoder(encode_path: str, fused: bool, interpret: bool):
    """-> ``encode(ints, chunk_len, transform)`` for a group's streams.

    The fused kernel has no 64-bit form on the chip (Mosaic has no
    64-bit vector types): under ``auto`` an 8-byte stream there takes
    the staged encode, whose outputs the compacted download takes
    alike; an explicit ``fused`` request gets the compiler's refusal.
    """
    if not fused:
        return device.encode_tiles
    if interpret or encode_path == "fused":
        return device.encode_tiles_fused

    def encode(ints, chunk_len: int, transform: str):
        wide = jnp.dtype(ints.dtype).itemsize == 8
        return (device.encode_tiles if wide else device.encode_tiles_fused)(
            ints, chunk_len, transform)

    return encode


def reset_transfer_counts() -> None:
    TRANSFER_COUNTS.clear()


def reset_decode_counts() -> None:
    DECODE_COUNTS.clear()


def decode_count(key: str = "tiles") -> int:
    return DECODE_COUNTS[key]


def transfer_count(*keys: str) -> int:
    return sum(TRANSFER_COUNTS[k] for k in keys) if keys else sum(
        TRANSFER_COUNTS.values()
    )


def resident_capacity(n_tiles: int, floor: int = CAPACITY_FLOOR) -> int:
    """Resident-batch capacity class for a group of ``n_tiles`` tiles.

    Everything at or below ``floor`` shares one class (the shape-mix
    serving case: mixed small fields never retrace); above it, classes
    double — ``floor * 2**k`` — so the registry is *closed* under the
    executor's packing cap and each class is one trace of the fused
    programs, paid once (or prewarmed) and then warm for every group
    that lands in it.  Pad-tile compute waste is bounded at 2x and is
    reported by the service metrics (``bucket_pad_waste``).
    """
    return buckets.bucket_capacity(n_tiles, floor)


def chunks_per_tile(layout: TileLayout, bdt) -> tuple[int, int]:
    """-> (chunks per tile, chunk length in words)."""
    chunk_len = _CHUNK_WORDS[np.dtype(bdt).itemsize]
    return -(-layout.tile_elems // chunk_len), chunk_len


@dataclass
class GroupStreams:
    """One compress group's encoded streams + solver diagnostics (host
    arrays; the single download of the group)."""

    bins: tuple[np.ndarray, np.ndarray, np.ndarray]   # bitmap, packed, counts
    subs: tuple[np.ndarray, np.ndarray, np.ndarray] | None
    local_sweeps: np.ndarray                          # (n_tiles,) int32
    last_round: np.ndarray                            # (n_tiles,) int32
    bins_cpt: int
    subs_cpt: int
    chunk_tiles: tuple[int, ...]                      # real tiles per chunk

    def halo_rounds(self) -> int:
        """Iterations the solve's halo-round loop ran, summed over the
        group's device chunks: a chunk's loop stops after the first
        round in which no tile moved, so it ran 1 + its last round."""
        bounds = np.cumsum((0, *self.chunk_tiles))
        return sum(1 + int(self.last_round[lo:hi].max(initial=0))
                   for lo, hi in zip(bounds[:-1], bounds[1:]))


class Executor:
    """Execute half of the engine for one plan: fused, device-resident.

    ``solver`` selects the subbin schedule (``auto``/``jacobi``/
    ``frontier``/``blockwise``) — schedules differ in speed only; the
    least fixed point is schedule-independent, so all of them emit
    byte-identical containers (tested).  ``decode_path`` selects the
    decompress backend the same way: ``staged`` runs the PR-2 chain of
    jitted stage programs, ``fused`` the single-dispatch Pallas kernel
    (``kernels.fused_decode``; f32 ordered decode only — other cases
    fall back to staged), ``auto`` picks per batch.  ``encode_path`` is
    the compress-side twin: ``fused`` runs the lossless stage as one
    Pallas kernel (``kernels.fused_encode``) and downloads the streams
    device-compacted (~payload-size D2H instead of capacity-padded
    arrays), ``staged`` keeps the PR-2 stage chain with host-side
    compaction, ``auto`` picks per group.  All paths are bit-identical
    (tested against the determinism manifest).  ``put`` optionally
    places each uploaded array (e.g. a NamedSharding put from
    distributed.compression); placement never changes bytes either.
    """

    def __init__(self, plan: CompressionPlan, solver: str = "auto",
                 put=None, decode_path: str = "auto",
                 encode_path: str = "auto"):
        if solver not in device.SOLVERS:
            raise ValueError(f"unknown solver method {solver!r}")
        if decode_path not in DECODE_PATHS:
            raise ValueError(f"unknown decode path {decode_path!r}")
        if encode_path not in ENCODE_PATHS:
            raise ValueError(f"unknown encode path {encode_path!r}")
        self.plan = plan
        self.solver = solver
        self.decode_path = decode_path
        self.encode_path = encode_path
        self.put = put or (lambda a: jnp.asarray(a))
        mesh = getattr(put, "mesh", None)
        self._placement = (partial(jax.set_mesh, mesh) if mesh is not None
                           else contextlib.nullcontext)

    # ------------------------------------------------------------ compress

    def compress_tiles(self, x_tiles: np.ndarray, eps_tiles: np.ndarray,
                       layouts: tuple[TileLayout, ...], dtype,
                       preserve_order: bool,
                       bins_store=None, adaptive: bool = False) -> GroupStreams:
        """Run one compress group device-resident.

        ``x_tiles`` is the group's concatenated haloed tiles with NaN
        marking every cell outside a field (pad, border); ``eps_tiles``
        the per-tile effective bounds; ``bins_store`` the (possibly
        narrowed) section word dtype for the bins stream.  ``adaptive``
        routes order-preserving groups through the ordered-space solve
        (per-tile eb ladders — see ``device.resident_frontend_adaptive``);
        it is part of the compress group key, so mixed-mode batches never
        share a group.  Exactly one tile upload and one stream download
        happen here, whatever the solver or round count.
        """
        with self._placement():
            return self._compress_tiles(x_tiles, eps_tiles, layouts, dtype,
                                        preserve_order, bins_store, adaptive)

    def _compress_tiles(self, x_tiles, eps_tiles, layouts, dtype,
                        preserve_order, bins_store, adaptive) -> GroupStreams:
        layout0 = layouts[0]
        n_total = x_tiles.shape[0]
        floor = max(CAPACITY_FLOOR, self.plan.batch_tiles)
        bins_store = np.dtype(bins_store or bin_dtype_for(dtype))
        bins_cpt, bins_chunk = chunks_per_tile(layout0, bins_store)
        sizes = tuple(lay.n_tiles for lay in layouts)
        offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(int)
        spans = buckets.plan_request_chunks(sizes, floor)
        # one path pick per *group* (largest chunk decides) so the whole
        # group's streams share one form through serialization
        max_capacity = max(
            resident_capacity(int(offsets[hi] - offsets[lo]), floor)
            for lo, hi in spans)
        solver, interpret = device.resolve_solver(self.solver)
        fused = use_fused_encode(self.encode_path,
                                 max_capacity * layout0.tile_elems, interpret)
        chunks = []
        for lo, hi in spans:
            r0, r1 = int(offsets[lo]), int(offsets[hi])
            n_chunk = r1 - r0
            capacity = resident_capacity(n_chunk, floor)
            with span("exec.pack", tiles=n_chunk, capacity=capacity):
                nbr = halo.group_neighbors(layouts[lo:hi], capacity)
                xc, ec = x_tiles[r0:r1], eps_tiles[r0:r1]
                pad = capacity - n_chunk
                if pad:
                    xc = np.concatenate([
                        xc, np.full((pad,) + xc.shape[1:], np.nan, xc.dtype),
                    ])
                    ec = np.concatenate([ec, np.ones(pad, np.float64)])
            TRANSFER_COUNTS.add("h2d_tiles")
            TRANSFER_COUNTS.add("bytes_h2d", xc.nbytes)
            TRANSFER_COUNTS.add("h2d_aux", 2)
            TRANSFER_COUNTS.add("bytes_h2d", ec.nbytes + nbr.nbytes)
            with span("exec.upload", tiles=n_chunk, capacity=capacity,
                      nbytes=xc.nbytes):
                x_dev = self.put(xc)
                eps_dev = device.put_eps(self.put, ec)
                nbr_dev = self.put(nbr)
                fence(x_dev, eps_dev, nbr_dev)
            max_rounds = jnp.asarray(n_chunk * layout0.tile_elems + 2,
                                     jnp.int64)
            with span("exec.solve", tiles=n_chunk, capacity=capacity,
                      solver=solver, adaptive=adaptive):
                bins_s, sub_dev, local1, last_round, sub_max = \
                    device.resident_compress(
                        x_dev, eps_dev, nbr_dev, max_rounds,
                        dtype=jnp.dtype(dtype), preserve_order=preserve_order,
                        solver=solver, interpret=interpret,
                        local_max_iters=layout0.tile_elems + 2,
                        bins_store=jnp.dtype(bins_store),
                        bins_chunk=bins_chunk,
                        encode_fused=fused, adaptive=adaptive,
                    )
                fence(bins_s, sub_dev, local1, last_round, sub_max)
            chunks.append([n_chunk, capacity, bins_s, sub_dev, local1,
                           last_round, sub_max])

        subs_cpt = 0
        if preserve_order:
            # one scalar sync per chunk; the width is picked from the
            # *group* maximum so chunking never changes the sub stream
            TRANSFER_COUNTS.add("d2h_aux", len(chunks))
            TRANSFER_COUNTS.add("bytes_d2h", sum(c[6].nbytes for c in chunks))
            sub_top = max(int(c[6]) for c in chunks)
            if sub_top < 2**15:
                sub_store = np.dtype(np.int16)
            elif sub_top < 2**31:
                sub_store = np.dtype(np.int32)
            else:
                # adaptive ordered-space deltas (f64, or an f32 bin
                # straddling zero at a huge eb) can exceed int32
                sub_store = np.dtype(np.int64)
            subs_cpt, subs_chunk = chunks_per_tile(layout0, sub_store)
            encode = stream_encoder(self.encode_path, fused, interpret)
            with span("exec.encode", chunks=len(chunks), fused=fused,
                      sub_word=sub_store.itemsize):
                for c in chunks:
                    c.append(encode(
                        c[3].astype(jnp.dtype(sub_store)).reshape(c[1], -1),
                        subs_chunk, "raw",
                    ))
                fence([c[7] for c in chunks])
        else:
            for c in chunks:
                c.append(None)
        ns = [c[0] for c in chunks]
        with span("exec.download", fused=fused, tiles=n_total) as dl_sp:
            if fused:
                streams = []
                for c in chunks:
                    streams.append(c[2])
                    streams.append(c[7])
                restored, extras = fetch_compacted_streams(
                    streams, [(c[4], c[5]) for c in chunks])
                bins_s = _cat_streams_flat(restored[0::2], ns, bins_cpt)
                subs_s = (_cat_streams_flat(restored[1::2], ns, subs_cpt)
                          if preserve_order else None)
                local1 = np.concatenate(
                    [e[0][:n] for e, n in zip(extras, ns)])
                last_round = np.concatenate(
                    [e[1][:n] for e, n in zip(extras, ns)])
            else:
                TRANSFER_COUNTS.add("d2h_sections")
                host = jax.device_get(
                    [(c[2], c[7], c[4], c[5]) for c in chunks])
                nbytes = _nbytes(host)
                TRANSFER_COUNTS.add("bytes_d2h", nbytes)
                dl_sp.set_tag("nbytes", nbytes)
                bins_s = _cat_streams([h[0] for h in host], ns, bins_cpt)
                subs_s = (_cat_streams([h[1] for h in host], ns, subs_cpt)
                          if preserve_order else None)
                local1 = np.concatenate([h[2][:n] for h, n in zip(host, ns)])
                last_round = np.concatenate(
                    [h[3][:n] for h, n in zip(host, ns)])
        return GroupStreams(bins_s, subs_s, local1, last_round, bins_cpt,
                            subs_cpt, tuple(ns))

    # ------------------------------------------------------------- decode

    def use_fused(self, dtype, order: bool) -> bool:
        """Can this (dtype, order) signature take the fused kernel?

        The fused kernel covers the hot serving case — f32 ordered
        decode — and falls back to the staged chain elsewhere (f64
        needs x64-dependent base math, plain decode is rare).  Both
        paths are bit-identical, so path choice is purely a speed pick:
        ``auto`` additionally requires the batch to clear
        ``FUSED_AUTO_MIN_ELEMS`` (below it, per-dispatch overhead beats
        the staged chain's three dispatches on CPU interpret runs).
        """
        if self.decode_path == "staged" or not order:
            return False
        if np.dtype(dtype) != np.float32:
            return False
        return True

    def decode_items(self, items, tile: tuple[int, int, int], dtype,
                     order: bool, words: tuple[int, int]) -> np.ndarray:
        """Decode a mixed tile work-list -> values (n, *tile).

        ``items`` is a list of (container, tile_id, eps_eff) sharing one
        (tile shape, dtype, order, section words) signature — tiles of
        *different blobs* ride the same fixed-shape device batches,
        mirroring the compress side's request coalescing.  ``words`` is
        the (bins, subs) section word width in bytes, read from the
        containers (old int64-width blobs decode through the same path).
        Work-lists larger than the packing cap split into balanced
        chunks (tiles are independent); each chunk is one stream upload,
        one resident decode — staged or fused per ``decode_path`` — and
        one value download.
        """
        dtype = np.dtype(dtype)
        tile_elems = int(np.prod(tile))
        if order and words[1] not in _CHUNK_WORDS:
            # header flags promise a subbin stream the sections lack
            raise ValueError("corrupt LOPC container (missing subbin stream)")
        n = len(items)
        if not n:
            return np.zeros((0,) + tuple(tile), dtype)
        DECODE_COUNTS.add("tiles", n)
        floor = max(CAPACITY_FLOOR, self.plan.batch_tiles)
        # the fused kernel accumulates subbins in f32's ordered-int width;
        # 8-byte subbin sections (wide adaptive deltas) stay staged
        fusable = self.use_fused(dtype, order) and words[1] in (2, 4)
        parts = []
        pos = 0
        for n_chunk in buckets.plan_tile_chunks(n, floor):
            batch = resident_capacity(n_chunk, floor)
            fused = fusable and (self.decode_path == "fused"
                                 or batch * tile_elems
                                 >= FUSED_AUTO_MIN_ELEMS)
            parts.append(self._decode_chunk(
                items[pos : pos + n_chunk], tile_elems, dtype, order,
                words, batch, fused,
            ))
            pos += n_chunk
        out = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return out.reshape((n,) + tuple(tile))

    def _decode_chunk(self, items, tile_elems: int, dtype, order: bool,
                      words: tuple[int, int], batch: int,
                      fused: bool) -> np.ndarray:
        n = len(items)
        DECODE_COUNTS.add("batches")

        def alloc(word):
            chunk_len = _CHUNK_WORDS[word]
            cpt = -(-tile_elems // chunk_len)
            udt = f"<u{word}"
            bitmap = np.zeros((batch * cpt, chunk_len // (word * 8)), udt)
            packed = np.zeros((batch * cpt, chunk_len), udt)
            return bitmap, packed, cpt

        with span("exec.stream_prep", tiles=n, batch=batch):
            bitmap, packed, bins_cpt = alloc(words[0])
            if order:
                sub_bitmap, sub_packed, subs_cpt = alloc(words[1])
            eps = np.ones(batch, np.float64)
            for j, (c, t, eps_eff) in enumerate(items):
                eps[j] = eps_eff
                bins_b, sub_b = c.tile_payloads(t)
                _fill_rows(bitmap, packed, bins_b, j * bins_cpt, bins_cpt)
                if order:
                    _fill_rows(sub_bitmap, sub_packed, sub_b, j * subs_cpt,
                               subs_cpt)
        TRANSFER_COUNTS.add("h2d_sections")
        up = bitmap.nbytes + packed.nbytes + eps.nbytes
        if order:
            up += sub_bitmap.nbytes + sub_packed.nbytes
        TRANSFER_COUNTS.add("bytes_h2d", up)
        with span("exec.decode", tiles=n, batch=batch, fused=fused,
                  nbytes_h2d=up):
            if order and fused:
                out = device.resident_decode_fused(
                    self.put(bitmap), self.put(packed),
                    self.put(sub_bitmap), self.put(sub_packed),
                    device.put_eps(self.put, eps), tile_elems=tile_elems,
                    dtype=jnp.dtype(dtype),
                )
            elif order:
                out = device.resident_decode_order(
                    self.put(bitmap), self.put(packed),
                    self.put(sub_bitmap), self.put(sub_packed),
                    device.put_eps(self.put, eps), tile_elems=tile_elems,
                    dtype=jnp.dtype(dtype),
                )
            else:
                out = device.resident_decode_plain(
                    self.put(bitmap), self.put(packed),
                    device.put_eps(self.put, eps),
                    tile_elems=tile_elems, dtype=jnp.dtype(dtype),
                )
            fence(out)
        TRANSFER_COUNTS.add("d2h_values")
        with span("exec.download", tiles=n) as dl_sp:
            out_h = np.asarray(out)
            dl_sp.set_tag("nbytes", out_h.nbytes)
        TRANSFER_COUNTS.add("bytes_d2h", out_h.nbytes)
        return out_h[:n]


def _fill_rows(bitmap: np.ndarray, packed: np.ndarray, section: bytes,
               row0: int, cpt: int) -> None:
    """Deserialize one tile section into its chunk-row span.

    Sections may carry *fewer* than ``cpt`` chunks: the serializer trims
    trailing all-zero chunks (pad-cell waste), and missing rows decode as
    zero words — exactly the zeros the trim removed.
    """
    bm, pk = bitstream.deserialize_rze_section(section)
    if bm.shape[0] > cpt:
        raise ValueError("corrupt LOPC container (tile section too long)")
    bitmap[row0 : row0 + bm.shape[0]] = bm
    packed[row0 : row0 + pk.shape[0]] = pk


def _cat_streams(parts, ns, cpt):
    """Concatenate per-chunk encoded streams, keeping only real-tile
    chunk rows so downstream ``j * cpt`` section slicing is unchanged."""
    sliced = [tuple(a[: n * cpt] for a in p) for p, n in zip(parts, ns)]
    if len(sliced) == 1:
        return sliced[0]
    return tuple(np.concatenate(cols) for cols in zip(*sliced))


def _nbytes(tree) -> int:
    """Total payload bytes of every array in a pytree of fetched hosts."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "nbytes"))


def _granule_len(total: int, size: int) -> int:
    """Granule-rounded dense-prefix length (capped at the buffer)."""
    return min(size, -(-total // _DL_GRANULE_WORDS) * _DL_GRANULE_WORDS)


def fetch_compacted_streams(streams, extras=()):
    """Download device (bitmap, packed, counts) streams at ~payload size.

    Each non-``None`` stream is compacted on device
    (``device.compact_streams``: front-packed nonzero words +
    repeat-eliminated bitmap), the per-stream totals come back as one
    tiny ``d2h_aux`` fetch, and one ``d2h_sections`` crossing drains
    only granule-rounded dense prefixes (plus ``extras``, e.g. solver
    diagnostics riding the same sync).  Streams are restored host-side
    to the flat form the serializer consumes: ``(bitmap rows,
    front-packed nonzero words, counts)`` with counts derived exactly
    from the bitmap popcount.  ``None`` entries pass through (the plain
    path's empty subs slots).
    """
    with span("exec.compaction", streams=sum(
            1 for s in streams if s is not None)):
        live = [(i, device.compact_streams(s[0], s[1]))
                for i, s in enumerate(streams) if s is not None]
        shapes = [(streams[i][0].shape, np.dtype(streams[i][0].dtype),
                   int(np.prod(streams[i][1].shape)))
                  for i, _ in live]
        TRANSFER_COUNTS.add("d2h_aux")
        totals = jax.device_get([c[3] for _, c in live])
        TRANSFER_COUNTS.add("bytes_d2h", _nbytes(totals))
    fetch = []
    for (_, c), (bshape, _, wsize), tot in zip(live, shapes, totals):
        bsize = int(np.prod(bshape))
        fetch.append((c[0], c[1][: _granule_len(int(tot[1]), bsize)],
                      c[2][: _granule_len(int(tot[0]), wsize)]))
    TRANSFER_COUNTS.add("d2h_sections")
    fetch_h, extras_h = jax.device_get((fetch, list(extras)))
    TRANSFER_COUNTS.add("bytes_d2h", _nbytes((fetch_h, extras_h)))
    restored = [None] * len(streams)
    for (i, _), (bshape, bdt, _), tot, (keepmap, kept, words) in zip(
            live, shapes, totals, fetch_h):
        restored[i] = _restore_stream(keepmap, kept, words, int(tot[0]),
                                      int(tot[1]), bshape, bdt)
    return restored, extras_h


def _restore_stream(keepmap, kept, words, total_words: int,
                    total_kept: int, bitmap_shape, bitmap_dtype):
    """Undo the transport compaction of one stream (exact inverses:
    repeat-restore for the bitmap, popcount for the counts)."""
    rows, bwords = bitmap_shape
    bitmap = rze.np_repeat_restore(
        np.asarray(keepmap), np.asarray(kept[:total_kept]), rows * bwords,
        bitmap_dtype,
    ).reshape(rows, bwords)
    word = bitmap_dtype.itemsize
    bits = np.unpackbits(
        bitmap.astype(f">u{word}").view(np.uint8).reshape(rows, -1), axis=1)
    counts = bits.sum(axis=1).astype(np.int32)
    return bitmap, np.asarray(words[:total_words]), counts


def _cat_streams_flat(parts, ns, cpt):
    """``_cat_streams`` for restored compacted streams: keep each
    chunk's real-tile bitmap/counts rows and exactly those rows' words
    (front-pack order is row-major, so a prefix of the dense words)."""
    sliced = []
    for (bitmap, data, counts), n in zip(parts, ns):
        k = n * cpt
        sliced.append((bitmap[:k], data[: int(counts[:k].sum())],
                       counts[:k]))
    if len(sliced) == 1:
        return sliced[0]
    return tuple(np.concatenate(cols) for cols in zip(*sliced))


@lru_cache(maxsize=64)
def default_executor(plan: CompressionPlan, solver: str,
                     decode_path: str = "auto",
                     encode_path: str = "auto") -> Executor:
    """Shared executors for the common no-custom-put case."""
    return Executor(plan, solver, decode_path=decode_path,
                    encode_path=encode_path)
