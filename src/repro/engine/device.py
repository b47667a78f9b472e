"""Fixed-shape device programs of the execute half of the engine.

Every function here is jitted over arrays whose shapes depend only on
(resident capacity, tile_shape, dtype) — never on a field's shape — so
the engine costs a constant number of traces no matter how many distinct
field shapes flow through it (asserted by the trace-count probe in
tests).  All math reuses the exact elementwise op sequences of
core/quantize.py and core/subbin.py, which is what makes the engine
bit-identical to the legacy whole-field path.

The centerpiece is :func:`resident_compress`: it takes the uploaded
tile batch and runs quantize → order flags → subbin solve (tile-local
solves + on-device halo-exchange rounds built from the per-tile
face-neighbour table of engine/halo.py) → delta/zigzag/BIT/RZE as a
short chain of jitted stage programs whose intermediates never leave
the device; the halo-round ``while_loop`` carries its state in place
(XLA buffer reuse — no per-round host scatter/gather, no per-round
re-upload, not even a per-round scalar readback).

Solver backends (all converge to the same least fixed point, so the
output bytes are identical — the paper's schedule independence, §IV-E):

  jacobi     dense synchronous jnp sweeps per tile-local solve
  frontier   accepted alias of jacobi here (the dense worklist's active
             mask cannot fire under capped rounds — see _resident_solve;
             core.subbin keeps the reference schedule)
  blockwise  the Pallas band kernel, batched-tile form
             (kernels/subbin_sweep.solve_tiles_blockwise); lowers via
             Mosaic on TPU, runs in interpret mode elsewhere

Per-tile error bounds ride along as a (C,) :class:`~repro.core.quantize.Eps`
operand (broadcast to (C,1,1,1) inside), so one traced program serves
tiles of *different fields with different bounds* in the same resident
batch — the core of ``compress_many``'s request coalescing.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..codecs.bitshuffle import bitshuffle, bitunshuffle
from ..codecs.rze import rze_bitmap, rze_decode
from ..codecs.transforms import delta_decode, delta_encode, zigzag_decode, zigzag_encode
from ..core import topology
from ..core.floatbits import int_dtype_for, ordered_to_float
from ..core.quantize import Eps, decode_base_ordered, eps_operand, quantize_broadcast
from ..obs import CounterView, REGISTRY
from . import halo

# Incremented inside traced function bodies: Python side effects run only
# while tracing, so this counts jit traces, not executions.  Tests use it
# to assert shape stability across many field shapes.  A view over the
# registry's ``lopc_traces_total`` family (label ``program``), so the
# metrics exposition shows it too.
TRACE_COUNTS: CounterView = CounterView(REGISTRY.counter(
    "lopc_traces_total", "jit traces of the engine's device programs"),
    label="program")

SOLVERS = ("auto", "jacobi", "frontier", "blockwise")


def trace_count() -> int:
    return sum(TRACE_COUNTS.values())


def resolve_solver(solver: str) -> tuple[str, bool]:
    """-> (concrete schedule, interpret flag) for the current backend.

    ``auto`` picks the Pallas blockwise kernel on TPU (native Mosaic
    lowering) and the jnp Jacobi schedule elsewhere; an explicit
    ``blockwise`` off-TPU runs the kernel in interpret mode, which is
    also what the CI kernel job exercises.
    """
    if solver not in SOLVERS:
        raise ValueError(f"unknown solver method {solver!r}")
    on_tpu = jax.default_backend() == "tpu"
    if solver == "auto":
        solver = "blockwise" if on_tpu else "jacobi"
    return solver, not on_tpu


def _interior(x: jnp.ndarray) -> jnp.ndarray:
    return x[:, 1:-1, 1:-1, 1:-1]


# The merged-3D layout
# --------------------
# A (C, t0+2, t1+2, t2+2) haloed tile batch is computed on as the 3-D
# array (C*(t0+2), t1+2, t2+2): tile i owns the contiguous row span
# [i*(t0+2), (i+1)*(t0+2)).  An interior cell's 14 Freudenthal neighbors
# all lie within its own tile's halo span, so plain zero-fill shifts
# (core.subbin's exact op sequence) read the right cells for every
# interior; a shift crossing a span boundary only feeds *halo* rows,
# whose flags are 0 — their relax update is max(cur, 0) = cur, so halos
# self-preserve and their (garbage) neighbor reads are never consumed.
# This matters because XLA lowers 3-D pad+slice+elementwise far better
# than the batched 4-D interior-slice formulation (~17x on CPU), and it
# lets the jnp schedules share core.subbin's sweep code verbatim.

def _merge(x4: jnp.ndarray) -> jnp.ndarray:
    c, h0, h1, h2 = x4.shape
    return x4.reshape(c * h0, h1, h2)


def _split_interior(x_m: jnp.ndarray, c: int) -> jnp.ndarray:
    h0 = x_m.shape[0] // c
    return x_m.reshape(c, h0, *x_m.shape[1:])[:, 1:-1, 1:-1, 1:-1]


def _pad_halo(x4: jnp.ndarray, fill=0) -> jnp.ndarray:
    """(C, t0, t1, t2) -> (C, t0+2, t1+2, t2+2), `fill` in the halo."""
    return jnp.pad(x4, ((0, 0),) + ((1, 1),) * 3, constant_values=fill)


def _halo_build(cur: jnp.ndarray, nbr: jnp.ndarray) -> jnp.ndarray:
    """(C, t0, t1, t2) interiors -> (C, t0+2, t1+2, t2+2) haloed tiles.

    ``nbr`` is a (C, 7) table of ``engine.halo``: face-neighbour tile
    ids per axis (low, high), ``-1`` where none, then the tile's own id
    (``-1`` on pad rows, whose interiors read 0).  Three passes, axis 2
    then 1 then 0: each takes the first and last plane along its axis
    from every tile of the previous pass's output, pulls them row-wise
    from the neighbour, zeroes them where there is none, and
    concatenates them on either side.  Later passes copy faces that
    already carry the earlier passes' halos, so edges and corners land
    too, and a halo cell is non-zero only where the neighbour along
    every axis it crosses exists: exactly the zero border beyond the
    padded field.  Only dense slices, selects, concatenates and gathers
    of whole planes — no per-cell index.
    """
    def pull(planes, col):
        """Row ``col[i]`` of ``planes`` for each tile i, 0 where -1."""
        got = jnp.take(planes, col, axis=0, mode="clip")
        return jnp.where(_live(col), got, 0)

    x = jnp.where(_live(nbr[:, halo.SELF]), cur, 0)
    for a in (3, 2, 1):   # array axes of tile axes 2, 1, 0
        n = x.shape[a]
        lo = pull(jax.lax.slice_in_dim(x, n - 1, n, axis=a),
                  nbr[:, halo.LO[a - 1]])
        hi = pull(jax.lax.slice_in_dim(x, 0, 1, axis=a),
                  nbr[:, halo.HI[a - 1]])
        x = jnp.concatenate([lo, x, hi], axis=a)
    return x


def _live(col: jnp.ndarray) -> jnp.ndarray:
    """(C,) tile ids -> (C, 1, 1, 1) mask of the ones that exist."""
    return (col >= 0)[:, None, None, None]


def _local_solve_jacobi(sub_m, flags_m, c: int, max_iters: int):
    """Tile-local Jacobi solve on the merged layout, halos fixed.
    Returns ``(solved merged state, last_changed_sweep (C,) int32)`` —
    the per-tile sweep index at which the tile last moved (0 if it was
    already at its fixed point).

    Tiles are independent given fixed halos, so the per-tile counter is
    invariant to batch composition (a field's diagnostics never inherit a
    batch-mate's solver cost).
    """

    def cond(s):
        return s[1] & (s[2] < max_iters)

    def body(s):
        cur, _, it, last = s
        new, ch = _relax_merged(cur, flags_m)
        ch_t = jnp.any(ch.reshape(c, -1), axis=1)  # reuse the sweep's mask
        it = it + 1
        return new, jnp.any(ch_t), it, jnp.where(ch_t, it, last)

    first, ch = _relax_merged(sub_m, flags_m)
    ch_t = jnp.any(ch.reshape(c, -1), axis=1)
    final, _, _, last = jax.lax.while_loop(
        cond, body,
        (first, jnp.any(ch_t), jnp.int32(1),
         jnp.where(ch_t, jnp.int32(1), jnp.int32(0))),
    )
    return final, last


def _relax_merged(sub_m, flags_m):
    """One Jacobi sweep on the merged layout (core.subbin's update)."""
    from ..core.subbin import _relax_once

    return _relax_once(sub_m, flags_m, 3)


# How many sweeps a jnp schedule runs between halo refreshes.  The
# refresh is cheap (``_halo_build``: dense plane copies, about one pass
# over the haloed batch), so a small cap keeps total sweeps pinned near
# the global chain length: unbounded local convergence re-propagates
# snaking in-tile chains after every halo update (measured ~3x the
# sweeps of the legacy global schedule), while cap 1 pays a refresh per
# sweep.  8 amortizes the refresh to noise with <10% extra sweeps on the
# paper fields.  The Pallas blockwise schedule intentionally ignores the
# cap: its tile lives in VMEM, where iterating to full local convergence
# is the whole point (§IV-D).
ROUND_SWEEP_CAP = 8


def _resident_solve(flags, nbr, solver: str, interpret: bool,
                    local_max_iters: int, max_rounds, sub0=None):
    """Subbin least fixed point over a resident tile batch.

    Rounds alternate (a) a halo build that rebuilds every tile's haloed
    view from the *current* interiors via the per-tile face-neighbour
    table ``nbr`` (``_halo_build``: three passes of whole-plane copies,
    about one pass over the haloed batch) and (b) a tile-local solve to
    local convergence.  Round 1 sees all-zero halos, so it reproduces a
    per-tile frontend solve; the loop exits when a full round moves
    nothing, which by monotonicity is exactly the global least fixed
    point (docs/engine.md).

    Subbins are computed in int32 throughout: a chain cannot exceed the
    group's cell count, and groups are < 2^31 cells (enforced by
    ``halo.group_neighbors``), so values are identical to an int64 solve.

    ``sub0`` overrides the all-zero initial state: the adaptive-eb path
    seeds it with the *biased-ordered decoded base* of every cell (see
    ``resident_frontend_adaptive``) and runs the same monotone relax in
    that absolute space — unsigned bias makes 0 the global minimum, so
    the relax's ``max(cur, 0)`` neutral and the zero-filled halo build
    stay correct unchanged.

    Returns (interiors (C, *t) in sub0's dtype, local1 (C,),
    last_round (C,)): per-tile sweeps of the first local solve, and the
    last round index in which the tile still moved — the per-request
    diagnostics that replace the old host-side round bookkeeping.
    """
    c = flags.shape[0]
    tile = flags.shape[1:]
    if sub0 is None:
        sub0 = jnp.zeros((c,) + tuple(tile), jnp.int32)
    zeros_c = jnp.zeros((c,), jnp.int32)
    blockwise = solver == "blockwise"
    if not blockwise:
        flags_m = _merge(_pad_halo(flags))

    cap_iters = min(ROUND_SWEEP_CAP, local_max_iters)

    def local_solve(haloed):
        if blockwise:
            from ..kernels import subbin_sweep  # lazy: pallas import

            return subbin_sweep.solve_tiles_blockwise(haloed, flags,
                                                      interpret=interpret)
        # "frontier" runs the jacobi schedule here: with capped sweeps
        # per round, the dense worklist's active mask provably never
        # suppresses an update (a cell only moves when a needed neighbor
        # moved last sweep), so a separate mask-carrying loop would be
        # identical work plus 14 shifted-mask ops per sweep.  The true
        # dense-worklist reference schedule lives in core.subbin for the
        # whole-field path.
        solved_m, last = _local_solve_jacobi(_merge(haloed), flags_m, c,
                                             cap_iters)
        return _split_interior(solved_m, c), last

    def cond(s):
        return s[1] & (s[2] <= max_rounds)

    def body(s):
        cur, _, rnd, local1, last_round = s
        new, iters = local_solve(_halo_build(cur, nbr))
        ch_t = jnp.any((new != cur).reshape(c, -1), axis=1)
        local1 = jnp.where(rnd == 1, iters, local1)
        last_round = jnp.where(ch_t, rnd.astype(jnp.int32), last_round)
        return new, jnp.any(ch_t), rnd + 1, local1, last_round

    final, _, _, local1, last_round = jax.lax.while_loop(
        cond, body, (sub0, jnp.bool_(True), jnp.int64(1), zeros_c, zeros_c)
    )
    return final, local1, last_round


def put_eps(put, eps: np.ndarray) -> Eps:
    """Upload per-tile f64 bounds as the device's :class:`Eps` operand."""
    return Eps(*(put(a) for a in eps_operand(eps)))


# ------------------------------------------------ lossless stage (shared)

# How integers become unsigned words ahead of BIT/RZE:
#   delta    spatial delta + zigzag   (snapshot/keyframe bins: the field
#                                      itself carries the smooth signal)
#   zigzag   zigzag only              (temporal bin residuals: the
#                                      previous-frame prediction already
#                                      removed the smooth component, so a
#                                      second spatial delta only adds
#                                      noise)
#   raw      reinterpret as unsigned  (subbins: non-negative counts)
TRANSFORMS = ("delta", "zigzag", "raw")


def _encode_ints(ints: jnp.ndarray, chunk_len: int, transform: str):
    """(C, E) ints -> (bitmap, raw shuffled words, counts) per chunk.

    Each tile occupies ceil(E/chunk_len) consecutive chunk rows, so the
    host can slice out independent per-tile sections (the v2 container's
    unit of parallel decode).  Same stage order as codecs.pipeline
    ([delta ->] [zigzag|reinterpret] -> BIT_w -> RZE_w), except the RZE
    word compaction stays on the host: the serializer compacts the raw
    words with one boolean index (identical bytes, identical download
    size), which beats XLA's CPU scatter lowering by an order of
    magnitude.
    """
    b, e = ints.shape
    n_chunks = -(-e // chunk_len)
    padded = jnp.pad(ints, ((0, 0), (0, n_chunks * chunk_len - e)))
    chunks = padded.reshape(b * n_chunks, chunk_len)
    if transform == "delta":
        words = zigzag_encode(delta_encode(chunks))
    elif transform == "zigzag":
        words = zigzag_encode(chunks)
    elif transform == "raw":
        words = chunks.astype(
            jnp.dtype(jnp.dtype(chunks.dtype).str.replace("i", "u"))
        )
    else:
        raise ValueError(f"unknown transform {transform!r} (want {TRANSFORMS})")
    shuffled = bitshuffle(words)
    bitmap, counts = rze_bitmap(shuffled)
    return bitmap, shuffled, counts


def _decode_ints(bitmap, packed, tile_elems: int, transform: str, out_dtype):
    """Inverse of _encode_ints -> (C, tile_elems) ints."""
    shuffled = rze_decode(bitmap, packed)
    words = bitunshuffle(shuffled)
    if transform == "delta":
        chunks = delta_decode(zigzag_decode(words))
    elif transform == "zigzag":
        chunks = zigzag_decode(words)
    elif transform == "raw":
        chunks = words.astype(out_dtype)
    else:
        raise ValueError(f"unknown transform {transform!r} (want {TRANSFORMS})")
    rows, chunk_len = chunks.shape
    n_chunks = -(-tile_elems // chunk_len)
    b = rows // n_chunks
    return chunks.astype(out_dtype).reshape(b, n_chunks * chunk_len)[:, :tile_elems]


# --------------------------------------------- resident stage programs
#
# The resident pipeline is a handful of jitted stage programs rather
# than one mega-jit: every intermediate stays a device array between
# calls (still exactly one tile upload and one stream download per
# group), but XLA compiles each stage in isolation — its fusion
# heuristics generate ~3x slower code when quantize, the solve loop, and
# the 32/64-plane bitshuffle land in a single computation.  Splitting
# also shares traces harder: the encode program is keyed only by the
# chunk-row count, so compress groups with different tile shapes but
# equal row counts reuse it.

@partial(jax.jit, static_argnames=("dtype", "preserve_order"))
def _resident_quantize(x_h, eps, dtype, preserve_order: bool):
    """Quantize one resident tile batch; NaN in x_h marks cells outside
    the field (tile pad, halo border, pad tiles), so validity travels
    *inside* the one tile upload instead of as a second array."""
    TRACE_COUNTS.add("resident_quantize")
    valid_h = jnp.isfinite(x_h)
    x0 = jnp.where(valid_h, x_h, jnp.asarray(0, x_h.dtype))
    bins_h = quantize_broadcast(x0, eps.expand(3), dtype)
    sentinel = jnp.iinfo(bins_h.dtype).min
    bins_h = jnp.where(valid_h, bins_h, sentinel)
    bins_enc = jnp.where(_interior(valid_h), _interior(bins_h), 0)
    if not preserve_order:
        return bins_enc, None, None
    vals_m = _merge(jnp.where(valid_h, x0, jnp.asarray(jnp.inf, x0.dtype)))
    return bins_enc, _merge(bins_h), vals_m


@jax.jit
def _resident_flags(bins_m, vals_m):
    """Order flags on the merged layout: interior cells only see their
    own tile's halo span and halo-row results are sliced away, so the
    flags equal the whole-field computation (sentinel bins / +inf values
    at invalid cells kill every out-of-field constraint).

    A separate jit from quantize on purpose: fused, XLA rematerializes
    the quantize chain into every one of the 14 offset terms (~10x
    slower on CPU, and optimization_barrier does not stop it).
    """
    TRACE_COUNTS.add("resident_flags")
    return topology.order_flags(bins_m, vals_m)


def resident_frontend(x_h, eps, dtype, preserve_order: bool):
    """Quantize + order flags over one resident tile batch.

    Returns (bins_enc (C, *t), flags (C, *t) uint32 | None), both
    device-resident.
    """
    capacity = x_h.shape[0]
    bins_enc, bins_m, vals_m = _resident_quantize(x_h, eps, jnp.dtype(dtype),
                                                  preserve_order)
    if not preserve_order:
        return bins_enc, None
    flags_m = _resident_flags(bins_m, vals_m)
    return bins_enc, _split_interior(flags_m, capacity)


# ------------------------------------------- adaptive-eb ordered-space path
#
# With per-tile eb *ladders* (engine adaptive_eb="tda"), neighboring
# tiles quantize at different eps, so "same bin" is no longer meaningful
# across tile boundaries and the relative subbin count cannot express
# cross-eps constraints.  The adaptive path therefore solves in
# *absolute biased-ordered space*: the state of a cell is the unsigned
# biased ordered-int of its decoded value, initialized at its bin's
# decode base, and EVERY in-field SoS-less Freudenthal pair carries a
# constraint (topology.order_flags_all).  The least fixed point of the
# identical monotone relax then reproduces the full SoS order globally —
# including across mixed-eps boundaries — and the stored subbin is
# simply ``u_final - u_init``, which the unchanged decode
# (``float_to_ordered(base) + sub``) inverts exactly.  Per-tile error
# bounds still hold: the original field is itself a prefixed point of
# the constraint system that dominates the init, so the least fixed
# point never raises a cell above its original value.

def _bias_ordered(o: jnp.ndarray) -> jnp.ndarray:
    """Signed ordered ints -> unsigned biased (order-preserving) space
    where 0 is the global minimum — the neutral element the relax's
    ``max(cur, 0)`` and the zero-filled halo build assume."""
    ut = jnp.dtype(jnp.dtype(o.dtype).str.replace("i", "u"))
    bias = ut.type(ut.type(1) << ut.type(8 * ut.itemsize - 1))
    return o.astype(ut) ^ bias


@partial(jax.jit, static_argnames=("dtype",))
def _resident_quantize_adaptive(x_h, eps, dtype):
    """Adaptive frontend: quantize at per-tile eps and seed the
    ordered-space solve state at each cell's decoded bin base."""
    TRACE_COUNTS.add("resident_quantize_adaptive")
    valid_h = jnp.isfinite(x_h)
    x0 = jnp.where(valid_h, x_h, jnp.asarray(0, x_h.dtype))
    eps_b = eps.expand(3)
    bins_h = quantize_broadcast(x0, eps_b, dtype)
    bins_enc = jnp.where(_interior(valid_h), _interior(bins_h), 0)
    u_init = _bias_ordered(decode_base_ordered(_interior(bins_h), eps_b,
                                               dtype))
    u_init = jnp.where(_interior(valid_h), u_init,
                       jnp.asarray(0, u_init.dtype))
    vals_m = _merge(jnp.where(valid_h, x0, jnp.asarray(jnp.inf, x0.dtype)))
    return bins_enc, u_init, vals_m


@jax.jit
def _resident_flags_adaptive(vals_m):
    """All-pairs order flags on the merged layout (see _resident_flags
    for why this is a separate jit from quantize)."""
    TRACE_COUNTS.add("resident_flags_adaptive")
    return topology.order_flags_all(vals_m)


def resident_frontend_adaptive(x_h, eps, dtype):
    """Quantize + ordered-space init + all-pairs flags for one resident
    batch.  Returns (bins_enc (C, *t), u_init (C, *t) unsigned,
    flags (C, *t) uint32), all device-resident."""
    capacity = x_h.shape[0]
    bins_enc, u_init, vals_m = _resident_quantize_adaptive(
        x_h, eps, jnp.dtype(dtype))
    flags_m = _resident_flags_adaptive(vals_m)
    return bins_enc, u_init, _split_interior(flags_m, capacity)


@jax.jit
def _ordered_delta(u_final, u_init):
    """Stored adaptive subbin: ordered distance climbed above the bin
    base (non-negative; 0 at invalid cells, whose state never moves)."""
    TRACE_COUNTS.add("ordered_delta")
    st = jnp.dtype(jnp.dtype(u_final.dtype).str.replace("u", "i"))
    return (u_final - u_init).astype(st)


@partial(jax.jit, static_argnames=("solver", "interpret", "local_max_iters"))
def resident_solve(flags, nbr, max_rounds, solver: str,
                   interpret: bool, local_max_iters: int, sub0=None):
    """Jitted wrapper of the halo-round solve (see _resident_solve).
    ``max_rounds`` is traced, so it never forces a retrace."""
    TRACE_COUNTS.add("resident_solve")
    return _resident_solve(flags, nbr, solver, interpret, local_max_iters,
                           max_rounds, sub0)


@partial(jax.jit, static_argnames=("chunk_len", "transform"))
def encode_tiles(ints, chunk_len: int, transform: str):
    """Jitted lossless stage over (C, tile_elems) resident integers."""
    TRACE_COUNTS.add("encode")
    return _encode_ints(ints, chunk_len, transform)


@partial(jax.jit, static_argnames=("chunk_len", "transform", "interpret"))
def _fused_encode_ints_program(ints, chunk_len: int, transform: str,
                               interpret: bool):
    TRACE_COUNTS.add("fused_encode")
    from ..kernels.fused_encode import encode_ints_fused

    return encode_ints_fused(ints, chunk_len, transform,
                             interpret=interpret)


def encode_tiles_fused(ints, chunk_len: int, transform: str):
    """Single-dispatch alternative to ``encode_tiles``: the whole
    transform -> BIT -> RZE-bitmap chain as one Pallas kernel gridded
    over tiles (``kernels.fused_encode``).  Bit-identical to the staged
    stage programs; interpret mode off-TPU like every kernel."""
    _, interpret = resolve_solver("auto")
    return _fused_encode_ints_program(ints, chunk_len, transform,
                                      interpret)


@partial(jax.jit,
         static_argnames=("dtype", "bins_store", "bins_chunk", "interpret"))
def _fused_encode_values_program(x_h, eps, dtype, bins_store,
                                 bins_chunk: int, interpret: bool):
    TRACE_COUNTS.add("fused_encode_values")
    from ..kernels.fused_encode import encode_values_fused

    capacity = x_h.shape[0]
    x_int = _interior(x_h).reshape(capacity, -1)
    return encode_values_fused(x_int, eps, bins_chunk, dtype, bins_store,
                               interpret=interpret)


def resident_encode_fused(x_h, eps, dtype, bins_store, bins_chunk: int):
    """Full compress fusion for the plain (preserve_order=False) f32
    path with 16-bit bins: NaN-validity -> quantize -> delta/zigzag ->
    BIT -> RZE-bitmap as ONE Pallas kernel over the haloed tile batch.
    Quantize is the shared ``quantize_broadcast`` op sequence, so the
    bins — and hence the streams — equal the staged frontend's
    bit-for-bit."""
    _, interpret = resolve_solver("auto")
    return _fused_encode_values_program(x_h, eps, jnp.dtype(dtype),
                                        jnp.dtype(bins_store), bins_chunk,
                                        interpret)


@jax.jit
def compact_streams(bitmap, words):
    """Device-side stream compaction for the fused-encode download.

    Packs the transfer-relevant content of one encoded stream into dense
    buffers so the executor can download ~compressed-size bytes instead
    of capacity-padded arrays:

    - ``words_dense``: every nonzero word of ``words``, front-packed
      globally in row-major order via the RZE prefix-sum scatter (one
      unique-index scatter over the flat buffer).  Row-major global
      order equals per-row compaction concatenated, so the host can
      slice per-chunk runs back out with the per-row counts.
    - ``kept_dense`` + ``keepmap``: the bitmap repeat-eliminated (the
      serializer's ``np_repeat_eliminate`` on device, as one flat run —
      transport-only: the host restores the exact bitmap, so downstream
      bytes are unchanged) with the keep mask packed MSB-first.
    - ``totals``: (total nonzero words, total kept bitmap words) int32 —
      the one tiny fetch that sizes the real download.

    Per-row counts are NOT transferred: they equal the bitmap rows'
    popcount exactly (``rze_bitmap`` construction), which the host
    recomputes from the restored bitmap.
    """
    TRACE_COUNTS.add("compact")

    def front_pack(flat, live):
        cum = jnp.cumsum(live, dtype=jnp.int32)
        total = cum[-1]
        cum_dead = jnp.cumsum(~live, dtype=jnp.int32)
        dest = jnp.where(live, cum - 1, total + cum_dead - 1)
        dense = jnp.zeros_like(flat).at[dest].set(flat,
                                                  unique_indices=True)
        return dense, total

    flat_w = words.reshape(-1)
    words_dense, total_words = front_pack(flat_w, flat_w != 0)
    flat_b = bitmap.reshape(-1)
    keep = jnp.concatenate(
        [jnp.ones((1,), bool), flat_b[1:] != flat_b[:-1]])
    kept_dense, total_kept = front_pack(flat_b, keep)
    weights = jnp.array([128, 64, 32, 16, 8, 4, 2, 1], jnp.uint8)
    keepmap = jnp.sum(keep.reshape(-1, 8).astype(jnp.uint8) * weights,
                      axis=1, dtype=jnp.uint8)
    totals = jnp.stack([total_words, total_kept]).astype(jnp.int32)
    return keepmap, kept_dense, words_dense, totals


def resident_compress(x_h, eps, nbr, max_rounds, dtype,
                      preserve_order: bool, solver: str, interpret: bool,
                      local_max_iters: int, bins_store, bins_chunk: int,
                      encode_fused: bool = False, adaptive: bool = False):
    """Quantize -> flags -> solve -> bins encode over one resident batch.

    Chains the stage programs above; every intermediate is a device
    array, so nothing crosses the host boundary between quantize and the
    encoded RZE streams.  ``bins_store`` is the (host-chosen, possibly
    narrowed) section word dtype for bins.  ``encode_fused`` routes the
    lossless stage through the fused Pallas encode kernel (and, for the
    plain f32 case, fuses quantize into it too) — bit-identical either
    way.  Returns ``((bins bitmap, packed, counts), sub | None, local1,
    last_round, sub_max | None)`` with the *unencoded* subbins still
    resident — the executor reads the ``sub_max`` scalar to pick the
    narrowest subbin width, then runs the sub encode as one more device
    stage.
    """
    capacity = x_h.shape[0]
    # the fused quantize guesses in f32 (Mosaic has no f64), which lands
    # on the exact bins only while |bin| < 2**14: 16-bit streams
    if (encode_fused and not preserve_order
            and jnp.dtype(dtype) == jnp.float32
            and jnp.dtype(bins_store).itemsize == 2):
        bins_streams = resident_encode_fused(x_h, eps, dtype, bins_store,
                                             bins_chunk)
        zc = jnp.zeros((capacity,), jnp.int32)
        return bins_streams, None, zc, zc, None
    encode = encode_tiles_fused if encode_fused else encode_tiles
    if adaptive and preserve_order:
        bins_enc, u_init, flags = resident_frontend_adaptive(
            x_h, eps, jnp.dtype(dtype))
        bins_streams = encode(
            bins_enc.astype(bins_store).reshape(capacity, -1), bins_chunk,
            "delta"
        )
        u_final, local1, last_round = resident_solve(
            flags, nbr, max_rounds, solver=solver,
            interpret=interpret, local_max_iters=local_max_iters,
            sub0=u_init,
        )
        sub = _ordered_delta(u_final, u_init)
        return bins_streams, sub, local1, last_round, _sub_max(sub)
    bins_enc, flags = resident_frontend(x_h, eps, jnp.dtype(dtype),
                                        preserve_order)
    bins_streams = encode(
        bins_enc.astype(bins_store).reshape(capacity, -1), bins_chunk, "delta"
    )
    if not preserve_order:
        zc = jnp.zeros((capacity,), jnp.int32)
        return bins_streams, None, zc, zc, None
    sub, local1, last_round = resident_solve(
        flags, nbr, max_rounds, solver=solver, interpret=interpret,
        local_max_iters=local_max_iters,
    )
    return bins_streams, sub, local1, last_round, _sub_max(sub)


@jax.jit
def _sub_max(sub):
    """Largest subbin of the batch — the one scalar the executor reads
    back mid-pipeline, to pick the narrowest subbin section width (the
    solve must finish before the sub encode anyway, so this readback
    rides the natural synchronization point)."""
    TRACE_COUNTS.add("sub_max")
    return jnp.max(sub)


@partial(jax.jit, static_argnames=("tile_elems", "transform", "out_dtype"))
def decode_tiles(bitmap, packed, tile_elems: int, transform: str, out_dtype):
    """Jitted inverse of encode_tiles -> (C, tile_elems) resident ints."""
    TRACE_COUNTS.add("decode")
    return _decode_ints(bitmap, packed, tile_elems, transform, out_dtype)


# --------------------------------------------- temporal chain stages
#
# Frame chains (src/repro/temporal/) predict frame t's bins from the
# previous frame's bins.  Both stages are trivially elementwise; they
# are jitted separately so the predictor state (the previous frame's
# bin grid) stays a device array between frames — the chain never
# round-trips bins through the host.

@jax.jit
def residual_tiles(bins_enc, prev_bins):
    """Temporal bin residual of one resident frame batch vs the decoded
    previous-frame bins (identical integers, since the bins stream is
    lossless)."""
    TRACE_COUNTS.add("residual")
    return bins_enc - prev_bins


@jax.jit
def accumulate_bins(prev_bins, residual):
    """Decode-side inverse of :func:`residual_tiles`."""
    TRACE_COUNTS.add("accumulate")
    return prev_bins + residual.astype(prev_bins.dtype)


@partial(jax.jit, static_argnames=("dtype",))
def dequantize_tiles(bins, subbins, eps, dtype):
    """(C, E) resident bins+subbins -> reconstructed values, per-tile
    eps (mirroring the compress side's per-tile bounds)."""
    TRACE_COUNTS.add("dequantize")
    base = decode_base_ordered(bins, eps.expand(1), dtype)
    idt = int_dtype_for(dtype)
    # an adaptive f32 subbin stream can be wider than f32's ordered-int
    # width (ordered distances across a zero-straddling bin): accumulate
    # in the wider type — the final ordered value always fits idt
    if jnp.dtype(subbins.dtype).itemsize > jnp.dtype(idt).itemsize:
        o = base.astype(subbins.dtype) + subbins
        return ordered_to_float(o.astype(idt), dtype)
    return ordered_to_float(base + subbins.astype(idt), dtype)


def _signed_twin(arr) -> jnp.dtype:
    return jnp.dtype(jnp.dtype(arr.dtype).str.replace("u", "i"))


def resident_decode_order(bitmap, packed, sub_bitmap, sub_packed, eps,
                          tile_elems: int, dtype):
    """Decode an order-preserving tile batch: RZE -> BIT -> zigzag/delta
    -> dequantize; intermediates stay device-resident between stages.
    Stream word widths come from the arrays themselves (the section
    header dictated them), so narrowed and legacy widths share a path."""
    bins = decode_tiles(bitmap, packed, tile_elems, "delta",
                        _signed_twin(packed))
    subs = decode_tiles(sub_bitmap, sub_packed, tile_elems, "raw",
                        _signed_twin(sub_packed))
    return dequantize_tiles(bins, subs, eps, jnp.dtype(dtype))


def resident_decode_plain(bitmap, packed, eps, tile_elems: int, dtype):
    """Decode without a subbin stream (preserve_order=False)."""
    bins = decode_tiles(bitmap, packed, tile_elems, "delta",
                        _signed_twin(packed))
    return dequantize_tiles(bins, jnp.zeros_like(bins), eps, jnp.dtype(dtype))


@partial(jax.jit, static_argnames=("tile_elems", "dtype", "interpret"))
def _fused_decode_program(bitmap, packed, sub_bitmap, sub_packed, eps,
                          tile_elems: int, dtype, interpret: bool):
    TRACE_COUNTS.add("fused_decode")
    from ..kernels.fused_decode import decode_tiles_fused

    return decode_tiles_fused(bitmap, packed, sub_bitmap, sub_packed, eps,
                              tile_elems=tile_elems, dtype=dtype,
                              interpret=interpret)


def resident_decode_fused(bitmap, packed, sub_bitmap, sub_packed, eps,
                          tile_elems: int, dtype):
    """Single-dispatch alternative to ``resident_decode_order``: the
    whole RZE -> BIT -> transform -> dequantize chain as one Pallas
    kernel gridded over tiles (``kernels.fused_decode``).  Bit-identical
    to the staged chain; interpret mode off-TPU like every kernel."""
    _, interpret = resolve_solver("auto")
    return _fused_decode_program(bitmap, packed, sub_bitmap, sub_packed,
                                 eps, tile_elems=tile_elems,
                                 dtype=jnp.dtype(dtype),
                                 interpret=interpret)
