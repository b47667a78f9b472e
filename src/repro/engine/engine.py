"""Plan/execute compression engine (batched tiled LOPC).

``compress_many`` turns any mix of concurrent 1/2/3-D field requests
into shared fixed-shape tile batches:

  plan      pad + partition each field into one canonical tile shape,
            with a one-cell halo so order constraints crossing tile
            boundaries stay visible to the subbin solver
  execute   the device-resident executor (engine/executor.py): tiles are
            uploaded once per group, then quantize -> order flags ->
            tile-local subbin solve -> on-device halo-exchange rounds ->
            delta/zigzag/BIT/RZE run as a chain of resident stage
            programs whose intermediates never leave the device; one
            download returns the encoded streams
  serialize the v2 container: an indexed per-tile section table that
            decodes embarrassingly parallel, including partial
            region-of-interest reads (``decompress_roi``)

Because the subbin solution is the least fixed point of a monotone
system, tile-local convergence plus halo exchange lands on exactly the
same integers as the legacy whole-field solve — the engine is
bit-identical to ``core.lopc`` on every input (tested), it just gets
there with shape-stable programs and without the host round-trips the
PR-1 engine paid between every stage.

``solver`` selects the subbin schedule the executor runs — ``jacobi``
(dense jnp sweeps; ``frontier`` is an accepted alias here, see
engine/device.py), ``blockwise`` (the Pallas band kernel, batched-tile
form), or ``auto`` (blockwise on TPU, jacobi elsewhere).  Schedules
differ in speed only; all of them emit byte-identical containers
(paper §IV-E, tested).
"""
from __future__ import annotations

import numpy as np

from .. import obs
from ..core import bitstream
from ..core.lopc import CompressStats, decode_nonfinite, encode_nonfinite
from ..core.quantize import (
    abs_bound_from_mode,
    bin_dtype_for,
    check_backend,
    check_eps,
    effective_eps,
)
from . import device
from . import buckets
from .executor import Executor, default_executor
from .plan import (
    HALO,
    CompressionPlan,
    TileLayout,
    canonical3d_shape,
    extract_halo_tiles,
    padded_with_border,
    scatter_interiors,
    tiles_for_region,
)

FLAG_ORDER_PRESERVING = bitstream.FLAG_ORDER_PRESERVING
FLAG_HAS_NONFINITE = bitstream.FLAG_HAS_NONFINITE
FLAG_ADAPTIVE_EB = bitstream.FLAG_ADAPTIVE_EB

_SOLVERS = device.SOLVERS

ADAPTIVE_EB_MODES = ("off", "tda")

DEFAULT_PLAN = CompressionPlan()


# -------------------------------------------- nonfinite sidecar (ROI form)

def decode_nonfinite_region(payload: bytes, out_region: np.ndarray,
                            full_shape: tuple[int, ...],
                            region: tuple[slice, ...]) -> np.ndarray:
    """ROI variant: the sidecar indexes the full grid, so the mask and
    value streams are sliced down to the requested region."""
    r = bitstream.Reader(payload)
    packed = np.frombuffer(r.lp(), np.uint8)
    vals = np.frombuffer(r.lp(), out_region.dtype)
    n = int(np.prod(full_shape))
    mask = np.unpackbits(packed, count=n).astype(bool).reshape(full_shape)
    # value k of the sidecar belongs to the k-th masked cell in C order
    pos = np.cumsum(mask.reshape(-1)).reshape(full_shape) - 1
    m = mask[region]
    out_region = out_region.copy()
    out_region[m] = vals[pos[region][m]]
    return out_region


# ------------------------------------------------------------ validation

def _validate(x: np.ndarray, eb: float):
    if x.dtype not in (np.float32, np.float64):
        raise ValueError(f"LOPC compresses float32/float64 fields, got {x.dtype}")
    if x.ndim not in (1, 2, 3):
        raise ValueError(f"LOPC supports 1D/2D/3D grids, got ndim={x.ndim}")
    if eb <= 0:
        raise ValueError("error bound must be positive")


# -------------------------------------------------------------- compress

class _Request:
    """One field moving through a compress_many call."""

    def __init__(self, x, eb, mode, plan, adaptive_eb: str = "off"):
        x = np.asarray(x)
        _validate(x, eb)
        self.nonfinite = None
        if not np.isfinite(x).all():
            x, self.nonfinite = encode_nonfinite(x)
        self.x = x
        self.eb = float(eb)
        self.mode = mode
        self.adaptive = adaptive_eb == "tda"
        self.eps_abs = abs_bound_from_mode(x, eb, mode)
        check_eps(x, self.eps_abs)  # the tightest rung is the user bound
        self.layout = plan.layout_for(x.shape)
        self.ladder = None
        if self.adaptive:
            from ..tda.adaptive import ladder_indices

            # header bound = loosest rung (eb_base); rung k_max is the
            # user bound exactly (power-of-2 scaling is float-exact)
            self.ladder = ladder_indices(x, self.layout, self.eps_abs)
            self.eps_abs = float(self.eps_abs
                                 * 2.0**bitstream.EB_LADDER_K_MAX)
        self.eps_eff = effective_eps(self.eps_abs)
        # bound on |bin| (quantize = round + <=2 correction steps), known
        # before any device work — it picks the narrowest section width.
        # Adaptive requests bound it at the *tightest* rung (the largest
        # bins any tile can produce).
        eps_tight = self.eps_eff * (
            2.0**-bitstream.EB_LADDER_K_MAX if self.adaptive else 1.0)
        self.max_bin = float(np.max(np.abs(x), initial=0.0)) / eps_tight + 4
        self.bins_store = _store_bin_dtype(self.max_bin, np.dtype(x.dtype))
        self.sweeps = 0

    def eps_tiles(self) -> np.ndarray:
        """(n_tiles,) effective eps — the ladder-scaled per-tile bounds
        for adaptive requests, the uniform bound otherwise."""
        if not self.adaptive:
            return np.full(self.layout.n_tiles, self.eps_eff, np.float64)
        return self.eps_eff * np.exp2(-self.ladder.astype(np.float64))


def _store_bin_dtype(max_bin: float, dtype) -> np.dtype:
    """Narrowest section word width whose bins (and their deltas) fit.

    The v2 tile sections are self-describing (word size in the header),
    so the writer is free to store bins at the width the *values* need
    rather than the conservative quantizer dtype: an eb=1e-2 NOA field
    has |bin| <~ 50 and fits int16 regardless of being f64 data.  Every
    halved width halves the chunk rows and bit-planes of the dominant
    BIT/RZE stage on both ends of the pipeline.  The bound is doubled so
    per-chunk deltas cannot wrap (wrapping would still decode exactly —
    two's complement cumsum inverts it — but costs ratio).

    The width is a *per-request* property (computed from the request's
    own value bound) and part of the compress group key, so batching a
    request with wider-valued neighbors never changes its bytes — the
    service layer's coalescing is byte-transparent.
    """
    native = np.dtype(bin_dtype_for(dtype))
    bound = 2 * max_bin + 4
    for cand in (np.dtype(np.int16), np.dtype(np.int32)):
        if cand.itemsize < native.itemsize and bound < np.iinfo(cand).max:
            return cand
    return native


def _serialize_tile_sections(streams, n_tiles: int, cpt: int):
    """Split batched chunk rows into per-tile RZE sections.

    Trailing all-zero chunks of a tile are trimmed before serialization:
    small fields routed through a large canonical tile would otherwise
    pay for rows of pure pad in every tile (the PR-1 per-tile ratio
    regression).  A zero chunk is exactly a zero count — decode
    reconstructs missing rows as zeros, so trimming is lossless.

    Streams arrive in one of two forms, emitting identical bytes: raw
    chunk rows from the staged download (``packed.ndim == 2``), or the
    fused path's compacted transport form — front-packed nonzero words
    plus popcount-derived counts — where each tile's words are a
    prefix-sum slice of the flat data.
    """
    bitmap, packed, counts = (np.asarray(a) for a in streams)
    out = []
    if packed.ndim == 1:
        word = packed.dtype.itemsize
        chunk_len = bitmap.shape[1] * word * 8
        offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        for j in range(n_tiles):
            rows = slice(j * cpt, (j + 1) * cpt)
            nz = np.flatnonzero(counts[rows])
            keep = int(nz[-1]) + 1 if nz.size else 0
            out.append(bitstream.serialize_rze_section_flat(
                bitmap[j * cpt : j * cpt + keep],
                packed[offsets[j * cpt] : offsets[j * cpt + keep]],
                chunk_len,
            ))
        return out
    for j in range(n_tiles):
        rows = slice(j * cpt, (j + 1) * cpt)
        nz = np.flatnonzero(counts[rows])
        keep = int(nz[-1]) + 1 if nz.size else 0
        rows = slice(j * cpt, j * cpt + keep)
        out.append(
            bitstream.serialize_rze_section(
                bitmap[rows], packed[rows], counts[rows], compacted=False
            )
        )
    return out


def compress_many(
    fields,
    eb,
    mode: str = "noa",
    preserve_order: bool = True,
    solver: str = "auto",
    plan: CompressionPlan | None = None,
    return_stats: bool = False,
    put=None,
    group_cb=None,
    encode_path: str = "auto",
    adaptive_eb: str = "off",
):
    """Compress a batch of scalar fields into v2 containers.

    ``fields`` may mix shapes, ranks, and dtypes; ``eb`` is one bound or
    a per-field sequence.  Tiles of all requests are coalesced into
    shared device-resident batches (grouped by (dtype, tile_shape,
    bins_store) — the stored bins width is a per-request property, so
    group composition never changes a request's bytes) — both the
    throughput path and what keeps jit traces constant across arbitrary
    request mixes.  ``put`` optionally places each uploaded array (e.g.
    a NamedSharding put from distributed.compression).  ``group_cb``,
    when given, is called once per device group with a summary dict
    (``kind``/``dtype``/``tile``/``n_requests``/``n_tiles``) — the hook
    the service layer uses to report per-batch device occupancy without
    re-deriving the grouping.  ``encode_path`` selects the compress
    backend (``staged``/``fused``/``auto``, see ``executor.Executor``) —
    paths are byte-identical, so it is purely a speed/transfer pick.

    Returns a list of blobs, or (blobs, stats) when ``return_stats``.
    """
    if solver not in _SOLVERS:
        raise ValueError(f"unknown solver method {solver!r}")
    if adaptive_eb not in ADAPTIVE_EB_MODES:
        raise ValueError(f"unknown adaptive_eb mode {adaptive_eb!r} "
                         f"(expected one of {ADAPTIVE_EB_MODES})")
    if adaptive_eb != "off" and not preserve_order:
        raise ValueError("adaptive_eb requires preserve_order=True (the "
                         "ladder exists to protect topology)")
    plan = plan or DEFAULT_PLAN
    fields = list(fields)
    if not fields:
        return ([], []) if return_stats else []
    ebs = list(eb) if np.ndim(eb) else [eb] * len(fields)
    if len(ebs) != len(fields):
        raise ValueError("eb must be a scalar or one bound per field")
    with obs.span("engine.admit", n_requests=len(fields)):
        reqs = [_Request(x, e, mode, plan, adaptive_eb)
                for x, e in zip(fields, ebs)]
    ex = (Executor(plan, solver, put, encode_path=encode_path) if put
          else default_executor(plan, solver, encode_path=encode_path))

    groups: dict[tuple, list[int]] = {}
    for i, r in enumerate(reqs):
        groups.setdefault(
            (np.dtype(r.x.dtype), r.layout.tile, r.bins_store, r.adaptive), []
        ).append(i)

    blobs: list[bytes | None] = [None] * len(reqs)
    stats: list[CompressStats | None] = [None] * len(reqs)
    for (dtype, tile, _store, _adaptive), members in groups.items():
        if group_cb is not None:
            sizes = [reqs[i].layout.n_tiles for i in members]
            group_cb({
                "kind": "compress", "dtype": str(dtype), "tile": tile,
                "n_requests": len(members),
                "n_tiles": sum(sizes),
                "tile_batches": _compress_batches(sizes, plan),
            })
        with obs.span("engine.compress_group", dtype=str(dtype),
                      tile=list(tile), n_requests=len(members),
                      n_tiles=sum(reqs[i].layout.n_tiles for i in members)
                      ) as group_span:
            gs = _compress_group(
                [reqs[i] for i in members], dtype, ex, preserve_order,
                [blobs, stats], members, return_stats,
            )
            if preserve_order and obs.enabled():
                group_span.set_tag("halo_rounds", gs.halo_rounds())
                group_span.set_tag("local_sweeps",
                                   int(gs.local_sweeps.max(initial=0)))
    if return_stats:
        return blobs, stats
    return blobs


def _compress_group(reqs, dtype, ex: Executor, preserve_order, out, members,
                    return_stats):
    """Plan-side assembly for one (dtype, tile_shape) group: build the
    NaN-marked haloed tile batch, run the executor, serialize per-tile
    sections into one v2 container per request.  Returns the group's
    :class:`~.executor.GroupStreams` (its solver diagnostics)."""
    blobs, stats = out
    nan = np.asarray(np.nan, dtype)

    # ---- plan: tiles of every request, concatenated (shared batches).
    # NaN marks every cell outside a field (in-tile pad, halo border), so
    # validity rides inside the single tile upload.
    with obs.span("engine.tile"):
        x_tiles, eps_tiles, ranges = [], [], []
        n_total = 0
        for r in reqs:
            arr3 = r.x.reshape(r.layout.canonical)
            x_pb = padded_with_border(arr3, r.layout, nan)
            x_tiles.append(extract_halo_tiles(x_pb, r.layout))
            eps_tiles.append(r.eps_tiles())
            ranges.append((n_total, n_total + r.layout.n_tiles))
            n_total += r.layout.n_tiles
        x_tiles = np.concatenate(x_tiles)
        eps_tiles = np.concatenate(eps_tiles)

    # ---- execute: the whole pipeline, device-resident
    gs = ex.compress_tiles(
        x_tiles, eps_tiles,
        tuple(r.layout for r in reqs), dtype, preserve_order,
        bins_store=reqs[0].bins_store,  # identical across the group (key)
        adaptive=reqs[0].adaptive,      # ditto — part of the group key
    )

    # ---- per-request solver diagnostics (sweeps are never serialized)
    if preserve_order:
        for r, (lo, hi) in zip(reqs, ranges):
            local = int(gs.local_sweeps[lo:hi].max(initial=0))
            rounds = int(gs.last_round[lo:hi].max(initial=0))
            r.sweeps = local + max(0, rounds - 1)

    # ---- per-tile serialization, then one v2 container per request
    with obs.span("engine.serialize", n_tiles=n_total):
        bins_sections = _serialize_tile_sections(gs.bins, n_total,
                                                 gs.bins_cpt)
        if preserve_order:
            sub_sections = _serialize_tile_sections(gs.subs, n_total,
                                                    gs.subs_cpt)
        else:
            sub_sections = [b""] * n_total

        for r, (lo, hi), i in zip(reqs, ranges, members):
            flags = FLAG_ORDER_PRESERVING if preserve_order else 0
            extra = {}
            if r.nonfinite is not None:
                flags |= FLAG_HAS_NONFINITE
                extra[bitstream.TAG_NONFINITE] = r.nonfinite
            if r.adaptive:
                flags |= FLAG_ADAPTIVE_EB
                extra[bitstream.TAG_EB_LADDER] = \
                    bitstream.serialize_eb_ladder(r.ladder)
            header = bitstream.Header(
                dtype=np.dtype(dtype), shape=r.x.shape, eb_mode=r.mode,
                eb=r.eb, eps_abs=float(r.eps_abs), flags=flags,
            )
            tiles = list(zip(bins_sections[lo:hi], sub_sections[lo:hi]))
            blob = bitstream.write_container_v2(
                header, r.layout.tile, r.layout.grid, tiles, extra
            )
            blobs[i] = blob
            if return_stats:
                bin_bytes = sum(len(b) for b, _ in tiles)
                subbin_bytes = sum(len(s) for _, s in tiles)
                stats[i] = CompressStats(
                    raw_bytes=r.x.nbytes,
                    total_bytes=len(blob),
                    bin_bytes=bin_bytes,
                    subbin_bytes=subbin_bytes,
                    header_bytes=len(blob) - bin_bytes - subbin_bytes,
                    n_sweeps=r.sweeps,
                    eps_abs=float(r.eps_abs),
                )

    return gs

def compress(field, eb, mode="noa", preserve_order=True, solver="auto",
             plan=None, return_stats=False, put=None, encode_path="auto",
             adaptive_eb="off"):
    """Single-field convenience wrapper over :func:`compress_many`."""
    out = compress_many([field], eb, mode, preserve_order, solver, plan,
                        return_stats, put, encode_path=encode_path,
                        adaptive_eb=adaptive_eb)
    if return_stats:
        blobs, stats = out
        return blobs[0], stats[0]
    return out[0]


# ------------------------------------------------------------ decompress

def container_layout(c) -> TileLayout:
    """TileLayout of a parsed tiled container (v2 snapshot or v3 chain —
    both expose header/tile_shape/grid/n_tiles), validating that the
    stored geometry is consistent with the field shape."""
    canonical = canonical3d_shape(c.header.shape)
    layout = TileLayout(tuple(c.header.shape), canonical,
                        tuple(int(t) for t in c.tile_shape),
                        tuple(int(g) for g in c.grid))
    expected = tuple(-(-cd // t) for cd, t in zip(canonical, layout.tile))
    if layout.grid != expected or layout.n_tiles != c.n_tiles:
        raise ValueError("corrupt LOPC container (grid/shape mismatch)")
    return layout


def _as_container(reader) -> bitstream.ContainerV2:
    """Accept a parsed v2 reader or raw blob bytes (the blob caller)."""
    if isinstance(reader, (bytes, bytearray, memoryview)):
        return bitstream.read_container_v2(bytes(reader))
    return reader


def _compress_batches(sizes, plan):
    """Device batches a compress group will run as -> [(real, capacity)].

    The same ``buckets`` planning the executor uses, so ``group_cb``
    consumers (the service's pad-waste metrics) see exactly the batches
    that execute."""
    floor = max(buckets.CAPACITY_FLOOR, plan.batch_tiles)
    out = []
    for lo, hi in buckets.plan_request_chunks(tuple(sizes), floor):
        n = int(sum(sizes[lo:hi]))
        out.append((n, buckets.bucket_capacity(n, floor)))
    return out


def _decode_batches(n_tiles, plan):
    """Decode-side twin of :func:`_compress_batches`."""
    floor = max(buckets.CAPACITY_FLOOR, plan.batch_tiles)
    return [(n, buckets.bucket_capacity(n, floor))
            for n in buckets.plan_tile_chunks(n_tiles, floor)]


def _decode_runs(runs, plan, group_cb=None, decode_path: str = "auto"):
    """Decode a list of tile runs sharing device batches across readers.

    ``runs`` holds ``(container, layout, tile_ids)`` triples; tiles of
    every run with one (dtype, tile_shape, order, section words)
    signature ride the same fixed-shape device batches — the shared
    grouping under ``decompress_many``, ``decompress_roi``, and the
    store's batched reads.  Returns one ``(len(tile_ids), *tile)`` value
    array per run.  ``group_cb`` mirrors :func:`compress_many`'s
    per-device-group reporting hook; ``decode_path`` selects the staged
    or fused decompress backend (see :class:`~.executor.Executor`).
    """
    groups: dict[tuple, list[int]] = {}
    for i, (c, layout, tile_ids) in enumerate(runs):
        if not tile_ids:
            continue
        order = bool(c.header.flags & FLAG_ORDER_PRESERVING)
        groups.setdefault((np.dtype(c.header.dtype), layout.tile, order,
                           c.stream_words()), []).append(i)
    outs: list[np.ndarray | None] = [
        np.empty((0,) + tuple(layout.tile), np.dtype(c.header.dtype))
        for c, layout, _ in runs
    ]
    for dtype, _, _, _ in groups:
        check_backend(dtype, "decompress")
    ex = default_executor(plan, "auto", decode_path)
    for (dtype, tile, order, words), members in groups.items():
        if group_cb is not None:
            n_tiles = sum(len(runs[i][2]) for i in members)
            group_cb({
                "kind": "decompress", "dtype": str(dtype), "tile": tile,
                "n_requests": len(members),
                "n_tiles": n_tiles,
                "tile_batches": _decode_batches(n_tiles, plan),
            })
        with obs.span("engine.parse", n_requests=len(members)):
            items, spans = [], []
            for i in members:
                c, layout, tile_ids = runs[i]
                eps_eff = effective_eps(c.header.eps_abs)
                # per-tile eb-ladder scaling (all-zero for non-adaptive
                # containers); eps is decode *data*, not program shape,
                # so adaptive and uniform tiles share device batches here.
                ladder = c.eb_ladder()
                start = len(items)
                items.extend((c, t, eps_eff * 2.0 ** -int(ladder[t]))
                             for t in tile_ids)
                spans.append((i, start, len(items)))
        with obs.span("engine.decode_group", dtype=str(dtype),
                      tile=list(tile), n_requests=len(members),
                      n_tiles=len(items)):
            values = ex.decode_items(items, tile, dtype, order, words)
        for i, lo, hi in spans:
            outs[i] = values[lo:hi]
    return outs


def decode_tiles_for_region(reader, tile_ids,
                            plan: CompressionPlan | None = None,
                            decode_path: str = "auto") -> np.ndarray:
    """Tile-granular decode entry point -> values ``(len(tile_ids), *tile)``.

    ``reader`` is a parsed :class:`~repro.core.bitstream.ContainerV2`
    over any byte source (in-memory blob, ``FileSource`` into a store
    payload file) or raw blob bytes.  Decodes exactly the requested
    tiles — the shared primitive behind ``decompress_roi``, the store's
    ``read_roi``, and the service's batched store reads; the
    ``executor.DECODE_COUNTS`` probe counts every tile that passes
    through here.
    """
    plan = plan or DEFAULT_PLAN
    c = _as_container(reader)
    layout = container_layout(c)
    return _decode_runs([(c, layout, list(tile_ids))], plan,
                        decode_path=decode_path)[0]


def decode_tiles_many(runs, plan: CompressionPlan | None = None,
                      group_cb=None, decode_path: str = "auto",
                      ) -> list[np.ndarray]:
    """Batched form of :func:`decode_tiles_for_region`.

    ``runs`` is a list of ``(reader, tile_ids)`` pairs; tiles of all
    runs sharing one (dtype, tile, order, words) signature are decoded
    in shared device batches, exactly like ``decompress_many`` coalesces
    full decodes.  The store's ``read_roi_many`` rides this to batch
    cache-miss tiles across concurrent readers.
    """
    plan = plan or DEFAULT_PLAN
    parsed = []
    for reader, tile_ids in runs:
        c = _as_container(reader)
        parsed.append((c, container_layout(c), list(tile_ids)))
    return _decode_runs(parsed, plan, group_cb, decode_path)


def decompress(blob: bytes, plan: CompressionPlan | None = None,
               decode_path: str = "auto") -> np.ndarray:
    """Reconstruct a full field from a v2 container.

    Tiles are independent sections (own crc, own RZE streams), so this
    decode is embarrassingly parallel; here they run as fixed-shape
    fused device batches.  ``decode_path`` selects the staged stage
    programs or the fused Pallas kernel (bit-identical; speed only).
    """
    plan = plan or DEFAULT_PLAN
    c = bitstream.read_container_v2(blob)
    layout = container_layout(c)
    values = _decode_runs([(c, layout, list(range(layout.n_tiles)))], plan,
                          decode_path=decode_path)[0]
    return _assemble_field(values, c, layout)


def assemble_interiors(values: np.ndarray, layout: TileLayout,
                       shape) -> np.ndarray:
    """Scatter decoded (n_tiles, *tile) interiors back into a field of
    the original ``shape`` (shared by v2 snapshot and v3 chain decode)."""
    pb = np.zeros(tuple(d + 2 * HALO for d in layout.padded), values.dtype)
    scatter_interiors(values, layout, pb)
    padded = pb[HALO:-HALO, HALO:-HALO, HALO:-HALO]
    cn = layout.canonical
    return np.ascontiguousarray(
        padded[: cn[0], : cn[1], : cn[2]]
    ).reshape(shape)


def _assemble_field(values, c: bitstream.ContainerV2, layout: TileLayout):
    out = assemble_interiors(values, layout, c.header.shape)
    if c.header.flags & FLAG_HAS_NONFINITE:
        out = decode_nonfinite(c.extra_section(bitstream.TAG_NONFINITE), out)
    return out


def decompress_many(blobs, plan: CompressionPlan | None = None,
                    group_cb=None, decode_path: str = "auto"):
    """Batched decode: tiles of all containers with one (tile_shape,
    dtype, order) signature share device batches — the decode-side
    mirror of compress_many's request coalescing.  ``group_cb`` mirrors
    :func:`compress_many`'s per-device-group reporting hook."""
    plan = plan or DEFAULT_PLAN
    parsed = []
    with obs.span("engine.parse"):
        for b in blobs:
            c = bitstream.read_container_v2(b)
            layout = container_layout(c)
            parsed.append((c, layout, list(range(layout.n_tiles))))
    values = _decode_runs(parsed, plan, group_cb, decode_path)
    with obs.span("engine.assemble", n_fields=len(parsed)):
        return [_assemble_field(v, c, layout)
                for v, (c, layout, _) in zip(values, parsed)]


def decompress_roi(blob: bytes, region: tuple[slice, ...],
                   plan: CompressionPlan | None = None,
                   decode_path: str = "auto") -> np.ndarray:
    """Partial decode: reconstruct only ``region`` of the field.

    ``region`` has exactly one slice per *original* field dimension
    (1/2/3-D fields take 1/2/3 slices — canonicalization to 3-D is an
    internal detail and never appears in the API).  Slice semantics are
    numpy's: negative indices count from the field end, out-of-range
    stops clamp to the field extent, and the result equals
    ``decompress(blob)[region]`` exactly.  Steps must be 1 (validated on
    every axis, even when another axis is empty); zero-volume regions
    (empty or reversed slices) return an empty array without touching
    the device.  Non-finite cells inside the region restore bit-exactly
    from the sidecar.

    Touches exactly the tiles intersecting the region (the v2 index
    makes them addressable without scanning the stream).  A v3 *chain*
    blob is detected by version: a single-frame chain routes through
    ``temporal.decompress_frame(0)`` (its one frame is a snapshot in
    all but framing), a multi-frame chain raises a ValueError naming
    the container version — pick a frame first.
    """
    plan = plan or DEFAULT_PLAN
    if bitstream.container_version(blob) == bitstream.VERSION_CHAIN:
        return _roi_from_chain(blob, region, plan)
    c = bitstream.read_container_v2(blob)
    layout = container_layout(c)
    tile_ids = tiles_for_region(layout, region)
    values = decode_tiles_for_region(c, tile_ids, plan, decode_path)
    return region_from_tiles(c, layout, region, dict(zip(tile_ids, values)))


def region_from_tiles(c, layout: TileLayout, region: tuple[slice, ...],
                      tiles: dict[int, np.ndarray]) -> np.ndarray:
    """Assemble ``region`` of a field from decoded tile interiors.

    ``tiles`` maps tile id -> decoded ``(*tile,)`` values and must cover
    every tile intersecting the region (a mix of freshly decoded and
    cached interiors — the store's read path — assembles identically to
    a cold decode).  Region semantics match :func:`decompress_roi`.
    """
    shape = c.header.shape
    tile_ids = tiles_for_region(layout, region)  # validates the region
    # empty/reversed slices clamp to zero extent (numpy slicing semantics)
    canon_region = (slice(0, 1),) * (3 - len(region)) + tuple(
        slice(sl.indices(n)[0], max(sl.indices(n)[0], sl.indices(n)[1]))
        for sl, n in zip(region, shape)
    )
    out_shape = tuple(sl.stop - sl.start for sl in canon_region)
    final_shape = out_shape[3 - len(region):]
    if not tile_ids or 0 in out_shape:
        return np.empty(final_shape, np.dtype(c.header.dtype))
    out = np.empty(out_shape, np.dtype(c.header.dtype))
    g1, g2 = layout.grid[1], layout.grid[2]
    t = layout.tile
    for tid in tile_ids:
        v = tiles[tid]
        gi, rem = divmod(tid, g1 * g2)
        gj, gk = divmod(rem, g2)
        t0, t1, t2 = gi * t[0], gj * t[1], gk * t[2]
        src, dst = [], []
        for base, extent, sl in zip((t0, t1, t2), t, canon_region):
            lo = max(base, sl.start)
            hi = min(base + extent, sl.stop)
            src.append(slice(lo - base, hi - base))
            dst.append(slice(lo - sl.start, hi - sl.start))
        out[tuple(dst)] = v[tuple(src)]
    out = out.reshape(final_shape)
    if c.header.flags & FLAG_HAS_NONFINITE:
        out = decode_nonfinite_region(
            c.extra_section(bitstream.TAG_NONFINITE), out, shape,
            tuple(slice(*sl.indices(n)[:2]) for sl, n in zip(region, shape)),
        )
    return out


def _roi_from_chain(blob: bytes, region: tuple[slice, ...],
                    plan: CompressionPlan) -> np.ndarray:
    """ROI over a v3 chain blob: decode frame 0 when the chain is a
    single frame (its sections are a v2 snapshot's), else refuse with
    the container version spelled out."""
    from ..temporal import decompress_frame  # lazy: temporal imports engine

    c = bitstream.read_container_v3(blob)
    if c.n_frames != 1:
        raise ValueError(
            f"decompress_roi expects a v2 snapshot container, got a "
            f"version {bitstream.VERSION_CHAIN} chain with {c.n_frames} "
            "frames; pick a frame with temporal.decompress_frame first"
        )
    layout = container_layout(c)
    tiles_for_region(layout, region)  # validate slices before decoding
    full = decompress_frame(blob, 0, plan=plan)
    return np.ascontiguousarray(full[tuple(region)])
