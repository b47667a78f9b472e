"""Frame-chain compression: temporal bin residuals over the engine.

Scientific codes emit *time series* of fields, and consecutive frames
are strongly correlated — but a snapshot compressor pays for the full
spatial signal every frame.  A :func:`compress_chain` call instead
predicts frame ``t``'s quantized bin grid from the **decoded bins of
frame t-1** (which are the encoder's own bins — the bins stream is
lossless, so predictor state never drifts) and encodes only the bin
residual through the engine's existing zigzag/BIT/RZE stages.  The
subbin local-order solve still runs **per frame** on that frame's own
bins and values, so every decoded frame independently preserves full
local order — the paper's guarantee is per frame, not amortized across
the chain.  Like everything else in the engine, chain bytes are
byte-identical across subbin solver schedules.

Residency: the predictor state (previous frame's bin grid) lives on the
device between frames (``device.residual_tiles`` /
``device.accumulate_bins``), so a chain costs one tile upload and one
stream download per frame per group — bins never round-trip through the
host between frames.  Frames at the same time step of *concurrent*
chains are coalesced into shared resident batches, mirroring
``compress_many``'s request grouping (and with the same byte contract:
group composition never changes a chain's bytes).

Quantization grid: all frames of a chain share ONE effective bin width.
``mode="abs"`` trivially does; for ``mode="noa"`` the chain bound is the
*minimum* of the per-frame NOA bounds, so every frame's point-wise error
stays within its own range-relative budget while bins remain comparable
across frames (a per-frame grid would turn slow range drift into a
global bin shift and destroy the residuals).

Random access: the v3 container's frame index marks keyframes (encoded
exactly like v2 snapshots) every ``keyframe_interval`` frames, so
``decompress_frame(t)`` replays at most one keyframe plus
``keyframe_interval - 1`` bin-residual accumulations — and intermediate
frames only pay the (cheap) bins decode, never the subbin/dequantize
stages.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bitstream
from ..core.lopc import decode_nonfinite, encode_nonfinite
from ..core.quantize import (
    abs_bound_from_mode,
    bin_dtype_for,
    check_backend,
    check_eps,
    effective_eps,
)
from ..engine import device, halo
from ..engine.engine import (
    DEFAULT_PLAN,
    _serialize_tile_sections,
    _store_bin_dtype,
    _validate,
    assemble_interiors,
    container_layout,
)
from ..engine.executor import (
    CAPACITY_FLOOR,
    TRANSFER_COUNTS,
    _fill_rows,
    _nbytes,
    chunks_per_tile,
    fetch_compacted_streams,
    resident_capacity,
    stream_encoder,
    use_fused_encode,
)
from ..engine.plan import (
    CompressionPlan,
    TileLayout,
    extract_halo_tiles,
    padded_with_border,
)

FLAG_ORDER_PRESERVING = bitstream.FLAG_ORDER_PRESERVING
FLAG_HAS_NONFINITE = bitstream.FLAG_HAS_NONFINITE
FLAG_ADAPTIVE_EB = bitstream.FLAG_ADAPTIVE_EB

DEFAULT_KEYFRAME_INTERVAL = 8


@dataclass
class ChainStats:
    """Size accounting for one compressed chain."""

    raw_bytes: int
    total_bytes: int
    bins_bytes: int
    subbin_bytes: int
    header_bytes: int
    n_frames: int
    n_keyframes: int
    n_sweeps: int
    eps_abs: float

    @property
    def ratio(self) -> float:
        return self.raw_bytes / self.total_bytes


def _normalize_interval(keyframe_interval) -> int:
    """None/0 -> 0 (only frame 0 is a keyframe); else the stride."""
    if keyframe_interval is None:
        return 0
    k = int(keyframe_interval)
    if k < 0:
        raise ValueError("keyframe_interval must be >= 0 (0/None = only "
                         "frame 0)")
    return k


def _frame_kind(t: int, interval: int) -> int:
    if t == 0 or (interval and t % interval == 0):
        return bitstream.FRAME_KEY
    return bitstream.FRAME_RESIDUAL


class _Chain:
    """One chain moving through a compress_chains call."""

    def __init__(self, frames, eb, mode, plan, keyframe_interval,
                 adaptive_eb: str = "off"):
        frames = [np.asarray(f) for f in frames]
        if not frames:
            raise ValueError("a chain needs at least one frame")
        shape, dtype = frames[0].shape, frames[0].dtype
        for f in frames:
            _validate(f, eb)
            if f.shape != shape or f.dtype != dtype:
                raise ValueError(
                    "all frames of a chain must share one shape and dtype "
                    f"(got {f.shape}/{f.dtype} after {shape}/{dtype})"
                )
        self.eb = float(eb)
        self.mode = mode
        self.interval = _normalize_interval(keyframe_interval)
        self.filled: list[np.ndarray] = []
        self.nonfinite: list[bytes | None] = []
        for f in frames:
            nf = None
            if not np.isfinite(f).all():
                f, nf = encode_nonfinite(f)
            self.filled.append(f)
            self.nonfinite.append(nf)
        # one bin width for the whole chain: the tightest per-frame bound
        # (per-frame NOA semantics hold for every frame; see module doc)
        self.eps_abs = min(abs_bound_from_mode(f, eb, mode)
                           for f in self.filled)
        for f in self.filled:
            check_eps(f, self.eps_abs)
        self.layout: TileLayout = plan.layout_for(shape)
        self.adaptive = adaptive_eb == "tda"
        self.ladder = None
        if self.adaptive:
            from ..tda.adaptive import ladder_indices, tighten_ladder

            # chain-wide ladder: the tightest rung any frame needs, so
            # every frame's topology gets its protection while bins stay
            # comparable across frames (one grid per tile per chain);
            # then re-tighten against every frame to a fixpoint — the
            # elementwise max can introduce cross-eps tile boundaries no
            # single frame's own ladder had
            ladder = np.maximum.reduce(
                [ladder_indices(f, self.layout, self.eps_abs)
                 for f in self.filled])
            for _ in range(bitstream.EB_LADDER_K_MAX + 1):
                prev = ladder
                for f in self.filled:
                    ladder = tighten_ladder(f, self.layout, ladder,
                                            self.eps_abs)
                if np.array_equal(ladder, prev):
                    break
            self.ladder = ladder
            self.eps_abs = float(self.eps_abs
                                 * 2.0**bitstream.EB_LADDER_K_MAX)
        self.eps_eff = effective_eps(self.eps_abs)
        eps_tight = self.eps_eff * (
            2.0**-bitstream.EB_LADDER_K_MAX if self.adaptive else 1.0)
        self.max_bin = [
            float(np.max(np.abs(f), initial=0.0)) / eps_tight + 4
            for f in self.filled
        ]
        self.dtype = np.dtype(dtype)
        self.shape = shape
        self.prev_bins = None          # device (n_tiles, *tile), bin dtype
        self.sections: list[list[tuple[bytes, bytes]]] = [None] * len(frames)
        self.sweeps = 0

    @property
    def n_frames(self) -> int:
        return len(self.filled)

    def kind(self, t: int) -> int:
        return _frame_kind(t, self.interval)

    def bins_store(self, t: int) -> np.dtype:
        """Stored word width of frame t's bins stream (host-side bound,
        so widths — and therefore bytes — are independent of batch
        composition and solver schedule).  Residual values are bounded
        by the two adjacent frames' bin bounds."""
        if self.kind(t) == bitstream.FRAME_KEY:
            return _store_bin_dtype(self.max_bin[t], self.dtype)
        return _store_bin_dtype(self.max_bin[t] + self.max_bin[t - 1],
                                self.dtype)

    def eps_tiles(self) -> np.ndarray:
        """(n_tiles,) effective per-tile eps (ladder-scaled when
        adaptive, uniform otherwise) — shared by every frame."""
        if not self.adaptive:
            return np.full(self.layout.n_tiles, self.eps_eff, np.float64)
        return self.eps_eff * np.exp2(-self.ladder.astype(np.float64))


def compress_chains(
    chains,
    eb,
    mode: str = "noa",
    preserve_order: bool = True,
    solver: str = "auto",
    plan: CompressionPlan | None = None,
    keyframe_interval=DEFAULT_KEYFRAME_INTERVAL,
    return_stats: bool = False,
    put=None,
    group_cb=None,
    encode_path: str = "auto",
    adaptive_eb: str = "off",
):
    """Compress a batch of frame sequences into v3 chain containers.

    ``chains`` is a sequence of frame sequences (each frame a 1/2/3-D
    float32/float64 array; all frames of one chain share shape and
    dtype, different chains may mix freely).  ``eb`` and
    ``keyframe_interval`` are scalars or per-chain sequences.  Frames at
    the same time step of concurrent chains are coalesced into shared
    device-resident batches, grouped by (dtype, tile shape, frame kind,
    stored width) — group composition never changes a chain's bytes.
    ``encode_path`` selects the lossless-stage backend per step
    (``staged``/``fused``/``auto``, see ``executor.Executor``); paths
    are byte-identical.

    Returns a list of blobs, or (blobs, stats) when ``return_stats``.
    """
    if solver not in device.SOLVERS:
        raise ValueError(f"unknown solver method {solver!r}")
    from ..engine.engine import ADAPTIVE_EB_MODES

    if adaptive_eb not in ADAPTIVE_EB_MODES:
        raise ValueError(f"unknown adaptive_eb mode {adaptive_eb!r} "
                         f"(expected one of {ADAPTIVE_EB_MODES})")
    if adaptive_eb != "off" and not preserve_order:
        raise ValueError("adaptive_eb requires preserve_order=True (the "
                         "ladder exists to protect topology)")
    plan = plan or DEFAULT_PLAN
    chains = list(chains)
    if not chains:
        return ([], []) if return_stats else []
    ebs = list(eb) if np.ndim(eb) else [eb] * len(chains)
    if len(ebs) != len(chains):
        raise ValueError("eb must be a scalar or one bound per chain")
    if isinstance(keyframe_interval, (list, tuple)):
        intervals = list(keyframe_interval)
        if len(intervals) != len(chains):
            raise ValueError("keyframe_interval must be a scalar or one "
                             "stride per chain")
    else:
        intervals = [keyframe_interval] * len(chains)
    reqs = [_Chain(c, e, mode, plan, k, adaptive_eb)
            for c, e, k in zip(chains, ebs, intervals)]
    put = put or (lambda a: jnp.asarray(a))

    for t in range(max(r.n_frames for r in reqs)):
        active = [r for r in reqs if t < r.n_frames]
        groups: dict[tuple, list[_Chain]] = {}
        for r in active:
            groups.setdefault(
                (r.dtype, r.layout.tile, r.kind(t), r.bins_store(t),
                 r.adaptive), []
            ).append(r)
        for (dtype, tile, kind, store, _adaptive), members in groups.items():
            if group_cb is not None:
                group_cb({
                    "kind": "chain_step", "t": t,
                    "frame_kind": ("key" if kind == bitstream.FRAME_KEY
                                   else "residual"),
                    "dtype": str(dtype), "tile": tile,
                    "n_requests": len(members),
                    "n_tiles": sum(r.layout.n_tiles for r in members),
                })
            _compress_chain_step(members, t, kind, store, dtype,
                                 preserve_order, solver, plan, put,
                                 encode_path)

    blobs = [_serialize_chain(r, preserve_order) for r in reqs]
    if return_stats:
        return blobs, [_chain_stats(r, b) for r, b in zip(reqs, blobs)]
    return blobs


def _compress_chain_step(members, t, kind, store, dtype, preserve_order,
                         solver, plan, put, encode_path: str = "auto"):
    """One resident step: frame ``t`` of every chain in one group.

    Mirrors the executor's compress group (one tile upload, one stream
    download), plus the temporal stages: the previous step's resident
    bins predict this frame, and this frame's bins stay resident as the
    next step's predictor.  ``encode_path`` routes the lossless stage
    through the fused Pallas kernel + compacted download exactly like a
    snapshot group (the quantize frontend always runs staged here — the
    resident predictor needs the bin grid as an array either way).
    """
    layout0 = members[0].layout
    adaptive = members[0].adaptive  # identical across the group (key)
    nan = np.asarray(np.nan, dtype)
    x_tiles, eps_tiles, ranges = [], [], []
    n_total = 0
    for r in members:
        arr3 = r.filled[t].reshape(r.layout.canonical)
        x_pb = padded_with_border(arr3, r.layout, nan)
        x_tiles.append(extract_halo_tiles(x_pb, r.layout))
        eps_tiles.append(r.eps_tiles())
        ranges.append((n_total, n_total + r.layout.n_tiles))
        n_total += r.layout.n_tiles
    x_tiles = np.concatenate(x_tiles)
    eps_tiles = np.concatenate(eps_tiles)

    capacity = resident_capacity(n_total, max(CAPACITY_FLOOR,
                                              plan.batch_tiles))
    pad = capacity - n_total
    if pad:
        x_tiles = np.concatenate([
            x_tiles, np.full((pad,) + x_tiles.shape[1:], np.nan,
                             x_tiles.dtype),
        ])
        eps_tiles = np.concatenate([eps_tiles, np.ones(pad, np.float64)])

    solver_c, interpret = device.resolve_solver(solver)
    fused = use_fused_encode(encode_path, capacity * layout0.tile_elems,
                             interpret)
    encode = stream_encoder(encode_path, fused, interpret)
    TRANSFER_COUNTS.add("h2d_tiles")
    TRANSFER_COUNTS.add("bytes_h2d", x_tiles.nbytes)
    x_dev = put(x_tiles)
    TRANSFER_COUNTS.add("h2d_aux")
    TRANSFER_COUNTS.add("bytes_h2d", eps_tiles.nbytes)
    eps_dev = device.put_eps(put, eps_tiles)

    if adaptive and preserve_order:
        bins_enc, u_init, flags = device.resident_frontend_adaptive(
            x_dev, eps_dev, jnp.dtype(dtype)
        )
    else:
        u_init = None
        bins_enc, flags = device.resident_frontend(
            x_dev, eps_dev, jnp.dtype(dtype), preserve_order
        )

    bins_store = np.dtype(store)
    bins_cpt, bins_chunk = chunks_per_tile(layout0, bins_store)
    if kind == bitstream.FRAME_KEY:
        stream_ints, transform = bins_enc, "delta"
    else:
        prevs = [r.prev_bins for r in members]
        if pad:
            prevs.append(jnp.zeros((pad,) + layout0.tile, bins_enc.dtype))
        stream_ints = device.residual_tiles(bins_enc, jnp.concatenate(prevs))
        transform = "zigzag"
    bins_s = encode(
        stream_ints.astype(bins_store).reshape(capacity, -1),
        bins_chunk, transform,
    )

    subs_s = None
    subs_cpt = 0
    if preserve_order:
        layouts = tuple(r.layout for r in members)
        nbr = halo.group_neighbors(layouts, capacity)
        TRANSFER_COUNTS.add("h2d_aux")
        TRANSFER_COUNTS.add("bytes_h2d", nbr.nbytes)
        nbr_dev = put(nbr)
        max_rounds = jnp.asarray(n_total * layout0.tile_elems + 2, jnp.int64)
        sub, local1, last_round = device.resident_solve(
            flags, nbr_dev, max_rounds, solver=solver_c,
            interpret=interpret, local_max_iters=layout0.tile_elems + 2,
            sub0=u_init,
        )
        if adaptive:
            sub = device._ordered_delta(sub, u_init)
        TRANSFER_COUNTS.add("d2h_aux")  # one scalar at the solve sync
        sub_max = device._sub_max(sub)
        TRANSFER_COUNTS.add("bytes_d2h", sub_max.nbytes)
        sub_top = int(sub_max)
        sub_store = (np.dtype(np.int16) if sub_top < 2**15
                     else np.dtype(np.int32) if sub_top < 2**31
                     else np.dtype(np.int64))
        subs_cpt, subs_chunk = chunks_per_tile(layout0, sub_store)
        subs_s = encode(
            sub.astype(jnp.dtype(sub_store)).reshape(capacity, -1),
            subs_chunk, "raw",
        )

    if fused:
        streams = [bins_s, subs_s] if preserve_order else [bins_s]
        restored, extras = fetch_compacted_streams(
            streams, (local1, last_round) if preserve_order else ())
        bins_s = restored[0]
        if preserve_order:
            subs_s = restored[1]
            local1, last_round = extras
    else:
        TRANSFER_COUNTS.add("d2h_sections")
        if preserve_order:
            bins_s, subs_s, local1, last_round = jax.device_get(
                (bins_s, subs_s, local1, last_round)
            )
            TRANSFER_COUNTS.add("bytes_d2h", _nbytes(
                (bins_s, subs_s, local1, last_round)))
        else:
            bins_s = jax.device_get(bins_s)
            TRANSFER_COUNTS.add("bytes_d2h", _nbytes(bins_s))

    bins_sections = _serialize_tile_sections(bins_s, n_total, bins_cpt)
    if preserve_order:
        sub_sections = _serialize_tile_sections(subs_s, n_total, subs_cpt)
    else:
        sub_sections = [b""] * n_total

    for r, (lo, hi) in zip(members, ranges):
        r.prev_bins = bins_enc[lo:hi]  # stays resident for frame t+1
        r.sections[t] = list(zip(bins_sections[lo:hi], sub_sections[lo:hi]))
        if preserve_order:
            local = int(np.asarray(local1)[lo:hi].max(initial=0))
            rounds = int(np.asarray(last_round)[lo:hi].max(initial=0))
            r.sweeps += local + max(0, rounds - 1)


def _serialize_chain(r: _Chain, preserve_order: bool) -> bytes:
    flags = FLAG_ORDER_PRESERVING if preserve_order else 0
    extra = {}
    if r.adaptive:
        flags |= FLAG_ADAPTIVE_EB
        extra[bitstream.TAG_EB_LADDER] = \
            bitstream.serialize_eb_ladder(r.ladder)
    frames = []
    for t in range(r.n_frames):
        fflags = FLAG_HAS_NONFINITE if r.nonfinite[t] is not None else 0
        payload = bitstream.serialize_frame_payload(
            r.sections[t], r.nonfinite[t] or b""
        )
        frames.append((r.kind(t), fflags, payload))
    header = bitstream.Header(
        dtype=r.dtype, shape=r.shape, eb_mode=r.mode, eb=r.eb,
        eps_abs=float(r.eps_abs), flags=flags,
    )
    return bitstream.write_container_v3(
        header, r.layout.tile, r.layout.grid, r.interval, frames, extra
    )


def _chain_stats(r: _Chain, blob: bytes) -> ChainStats:
    bins_bytes = sum(len(b) for tiles in r.sections for b, _ in tiles)
    subbin_bytes = sum(len(s) for tiles in r.sections for _, s in tiles)
    return ChainStats(
        raw_bytes=sum(f.nbytes for f in r.filled),
        total_bytes=len(blob),
        bins_bytes=bins_bytes,
        subbin_bytes=subbin_bytes,
        header_bytes=len(blob) - bins_bytes - subbin_bytes,
        n_frames=r.n_frames,
        n_keyframes=sum(1 for t in range(r.n_frames)
                        if r.kind(t) == bitstream.FRAME_KEY),
        n_sweeps=r.sweeps,
        eps_abs=float(r.eps_abs),
    )


def compress_chain(frames, eb, mode="noa", preserve_order=True, solver="auto",
                   plan=None, keyframe_interval=DEFAULT_KEYFRAME_INTERVAL,
                   return_stats=False, put=None, encode_path="auto",
                   adaptive_eb="off"):
    """Single-chain convenience wrapper over :func:`compress_chains`."""
    out = compress_chains([frames], eb, mode, preserve_order, solver, plan,
                          keyframe_interval, return_stats, put,
                          encode_path=encode_path, adaptive_eb=adaptive_eb)
    if return_stats:
        blobs, stats = out
        return blobs[0], stats[0]
    return out[0]


# ------------------------------------------------------- appended frames

class _AppendStep:
    """Single-frame shim presenting the ``_Chain`` surface that
    :func:`_compress_chain_step` consumes, so an appended frame runs the
    exact same resident step as a frame inside ``compress_chains`` — the
    basis of the store's append-vs-whole-chain byte identity."""

    def __init__(self, filled, eps_eff, layout, prev_bins, ladder=None):
        self.filled = [filled]
        self.eps_eff = eps_eff
        self.layout = layout
        self.prev_bins = prev_bins
        self.adaptive = ladder is not None
        self.ladder = ladder
        self.sections: list = [None]
        self.sweeps = 0

    def eps_tiles(self) -> np.ndarray:
        if not self.adaptive:
            return np.full(self.layout.n_tiles, self.eps_eff, np.float64)
        return self.eps_eff * np.exp2(-self.ladder.astype(np.float64))


def encode_appended_frame(
    frame,
    *,
    eps_abs: float,
    kind: int,
    prev_bins=None,
    prev_max_bin: float = 0.0,
    preserve_order: bool = True,
    solver: str = "auto",
    plan: CompressionPlan | None = None,
    encode_path: str = "auto",
    ladder: np.ndarray | None = None,
):
    """Encode ONE frame as if it were the next step of an existing chain.

    ``eps_abs`` is the chain's pinned bin width, ``kind`` the frame kind
    (``bitstream.FRAME_KEY``/``FRAME_RESIDUAL``), and — for residual
    frames — ``prev_bins`` is the previous frame's decoded bin tiles in
    the engine layout (:meth:`ChainDecoder.resident_bins`) with
    ``prev_max_bin`` its recorded host-side bin bound (the stored width
    is picked by the same rule as :meth:`_Chain.bins_store`, so an
    appended frame's bytes equal the ones a whole-chain compress would
    emit for the same position — tested).  Returns ``(tile_sections,
    nonfinite_sidecar | None, max_bin, sweeps)``; the caller persists
    the sections as one more v3 frame payload and keeps ``max_bin`` for
    the next append.

    ``ladder`` is the chain's committed eb-ladder indices for an
    adaptive chain (``ContainerV3.eb_ladder()``): appended frames run
    at the chain's per-tile bounds so the whole chain stays decodable
    with one ladder.  ``eps_abs`` is then the chain header's (loosest)
    bound, exactly as stored.
    """
    if solver not in device.SOLVERS:
        raise ValueError(f"unknown solver method {solver!r}")
    if kind == bitstream.FRAME_RESIDUAL and prev_bins is None:
        raise ValueError("a residual frame needs the previous frame's bins")
    plan = plan or DEFAULT_PLAN
    x = np.asarray(frame)
    _validate(x, 1.0)  # eb sign is the chain's concern; validate shape/dtype
    nonfinite = None
    if not np.isfinite(x).all():
        x, nonfinite = encode_nonfinite(x)
    adaptive = ladder is not None
    eps_tight_abs = eps_abs * (
        2.0**-bitstream.EB_LADDER_K_MAX if adaptive else 1.0)
    check_eps(x, eps_tight_abs)
    eps_eff = effective_eps(eps_abs)
    eps_tight = eps_eff * (
        2.0**-bitstream.EB_LADDER_K_MAX if adaptive else 1.0)
    max_bin = float(np.max(np.abs(x), initial=0.0)) / eps_tight + 4
    if kind == bitstream.FRAME_KEY:
        store = _store_bin_dtype(max_bin, np.dtype(x.dtype))
    else:
        store = _store_bin_dtype(max_bin + prev_max_bin, np.dtype(x.dtype))
    layout = plan.layout_for(x.shape)
    if adaptive and len(ladder) != layout.n_tiles:
        raise ValueError("ladder length does not match the frame's tile grid")
    step = _AppendStep(x, eps_eff, layout, prev_bins,
                       np.asarray(ladder, np.uint8) if adaptive else None)
    _compress_chain_step(
        [step], 0, kind, store, np.dtype(x.dtype),
        preserve_order, solver, plan, lambda a: jnp.asarray(a), encode_path,
    )
    return step.sections[0], nonfinite, max_bin, step.sweeps


# ------------------------------------------------------------ decompress

def _section_word(section: bytes) -> int:
    if len(section) < 9:
        raise ValueError("truncated stream")
    w = section[8]
    if w not in (2, 4, 8):
        raise ValueError("corrupt LOPC container (bad section word size)")
    return int(w)


class ChainDecoder:
    """Sequential bins accumulator over a chain's frame run.

    ``step(t)`` decodes frame ``t``'s bins stream and folds it into the
    resident bin state (cheap: no subbin decode, no dequantize);
    ``values(t)`` additionally decodes frame ``t``'s subbins and
    reconstructs the frame's values on the host.

    ``c`` is anything exposing the :class:`~repro.core.bitstream.
    ContainerV3` reading surface (header, tile_shape/grid, entries,
    frame_tiles) — a parsed v3 blob, or the store layer's manifest-built
    view whose frame payloads are pread from a payload file, which is
    how ``LopcStore.read_frame`` replays only the needed frame bytes
    from disk.  ``resident_bins`` exposes the accumulated predictor
    state in the engine's ``(n_tiles, *tile)`` layout — the store's
    ``append_frame`` reads it to seed :func:`encode_appended_frame`.
    """

    def __init__(self, c: bitstream.ContainerV3, plan: CompressionPlan):
        self.c = c
        self.layout = container_layout(c)
        self.order = bool(c.header.flags & FLAG_ORDER_PRESERVING)
        self.eps_eff = effective_eps(c.header.eps_abs)
        self.dtype = np.dtype(c.header.dtype)
        check_backend(self.dtype, "decompress")
        self.bdt = jnp.dtype(bin_dtype_for(self.dtype))
        self.capacity = resident_capacity(
            self.layout.n_tiles, max(CAPACITY_FLOOR, plan.batch_tiles)
        )
        self.bins = None     # device (capacity, tile_elems) bin ints
        self.pos = -1        # index of the frame self.bins describes

    def resident_bins(self):
        """Device ``(n_tiles, *tile)`` bins of the frame ``pos`` points
        at — the predictor state :func:`encode_appended_frame` takes."""
        n = self.layout.n_tiles
        return self.bins[:n].reshape((n,) + self.layout.tile)

    def _upload_sections(self, sections, word):
        """Fixed-shape (bitmap, packed) batch of one frame's sections."""
        from ..engine.executor import _CHUNK_WORDS

        chunk_len = _CHUNK_WORDS[word]
        cpt = -(-self.layout.tile_elems // chunk_len)
        udt = f"<u{word}"
        bitmap = np.zeros((self.capacity * cpt, chunk_len // (word * 8)), udt)
        packed = np.zeros((self.capacity * cpt, chunk_len), udt)
        for j, section in enumerate(sections):
            _fill_rows(bitmap, packed, section, j * cpt, cpt)
        TRANSFER_COUNTS.add("h2d_sections")
        TRANSFER_COUNTS.add("bytes_h2d", bitmap.nbytes + packed.nbytes)
        return jnp.asarray(bitmap), jnp.asarray(packed)

    def step(self, t: int):
        """Fold frame ``t``'s bins into the resident state."""
        kind = self.c.entries[t].kind
        if kind == bitstream.FRAME_RESIDUAL and self.pos != t - 1:
            raise ValueError(
                f"chain decode out of order (frame {t} follows {self.pos})"
            )
        tiles, nonfinite = self.c.frame_tiles(t)
        bins_sections = [b for b, _ in tiles]
        word = _section_word(bins_sections[0])
        bitmap, packed = self._upload_sections(bins_sections, word)
        if kind == bitstream.FRAME_KEY:
            self.bins = device.decode_tiles(
                bitmap, packed, self.layout.tile_elems, "delta", self.bdt
            )
        else:
            residual = device.decode_tiles(
                bitmap, packed, self.layout.tile_elems, "zigzag", self.bdt
            )
            self.bins = device.accumulate_bins(self.bins, residual)
        self.pos = t
        return tiles, nonfinite

    def values(self, t: int) -> np.ndarray:
        """Decode frame ``t`` fully (assumes step() has reached it)."""
        tiles, nonfinite = self.step(t) if self.pos < t else \
            self.c.frame_tiles(t)
        if self.pos != t:
            raise ValueError(
                f"chain decode out of order (frame {t} follows {self.pos})"
            )
        n = self.layout.n_tiles
        eps = np.full(self.capacity, self.eps_eff, np.float64)
        ladder = self.c.eb_ladder()
        eps[:n] = self.eps_eff * np.exp2(-np.asarray(ladder, np.float64))
        if self.order:
            sub_sections = [s for _, s in tiles]
            word = _section_word(sub_sections[0])
            sbitmap, spacked = self._upload_sections(sub_sections, word)
            subs = device.decode_tiles(
                sbitmap, spacked, self.layout.tile_elems, "raw",
                jnp.dtype(f"i{word}"),
            )
        else:
            subs = jnp.zeros_like(self.bins)
        out = device.dequantize_tiles(
            self.bins, subs, device.put_eps(jnp.asarray, eps),
            jnp.dtype(self.dtype)
        )
        TRANSFER_COUNTS.add("d2h_values")
        out_h = np.asarray(out)
        TRANSFER_COUNTS.add("bytes_d2h", out_h.nbytes)
        values = out_h[:n].reshape((n,) + self.layout.tile)
        field = assemble_interiors(values, self.layout, self.c.header.shape)
        if self.c.entries[t].flags & FLAG_HAS_NONFINITE:
            field = decode_nonfinite(nonfinite, field)
        return field


def decompress_chain(blob: bytes,
                     plan: CompressionPlan | None = None) -> np.ndarray:
    """Reconstruct every frame of a v3 chain -> (n_frames, *shape)."""
    plan = plan or DEFAULT_PLAN
    c = bitstream.read_container_v3(blob)
    dec = ChainDecoder(c, plan)
    return np.stack([dec.values(t) for t in range(c.n_frames)])


def decompress_frame(blob: bytes, t: int,
                     plan: CompressionPlan | None = None) -> np.ndarray:
    """Random-access decode of frame ``t``.

    Replays at most one keyframe plus the bin-residual run from it to
    ``t`` (bounded by the chain's ``keyframe_interval``); intermediate
    frames only pay the bins decode, and only frame ``t`` runs the
    subbin decode and dequantize stages.
    """
    plan = plan or DEFAULT_PLAN
    c = bitstream.read_container_v3(blob)
    dec = ChainDecoder(c, plan)
    for k in range(c.keyframe_before(t), t):
        dec.step(k)
    return dec.values(t)
