"""Plain reference decoder of LOPC v2 containers, from docs/format.md.

Copied from the spec-only decoder of ``tests/test_format_spec.py`` and
kept apart from it: nothing here imports ``repro``, so what the
benchmark compares against is independent of the code under test.
Per-tile decoding and a region decode were added for the benchmark's
reads; a region equals the same slice of the full decode because every
tile decodes on its own (docs/format.md).

``precision="bfloat16"`` computes the bin anchors ``(b - 0.5) * eps``
in bfloat16 instead of float64: the benchmark's control, the reference
one precision below what a float32 container states.
"""
from __future__ import annotations

import struct
import zlib

import numpy as np

MAGIC = b"LOPC"
VERSION_TILED = 2
DTYPES = {0: np.dtype(np.float32), 1: np.dtype(np.float64)}
EB_MODES = {0: "abs", 1: "noa"}
TAG_NONFINITE = 3
TAG_EB_LADDER = 4
FLAG_ORDER_PRESERVING = 1
FLAG_HAS_NONFINITE = 2
FLAG_ADAPTIVE_EB = 4
EB_LADDER_VERSION = 1
TILE_ENTRY = "QQQQI"
CHUNK_WORDS = {2: 8192, 4: 4096, 8: 2048}   # word bytes -> words / chunk
EPS_SHRINK = 1.0 - 2.0**-20
PRECISIONS = ("exact", "bfloat16")


def _require(cond, msg: str) -> None:
    if not cond:
        raise ValueError(f"corrupt LOPC container: {msg}")


class _Cursor:
    """Minimal little-endian cursor."""

    def __init__(self, buf: bytes, off: int = 0):
        self.buf, self.off = buf, off

    def take(self, fmt: str):
        vals = struct.unpack_from("<" + fmt, self.buf, self.off)
        self.off += struct.calcsize("<" + fmt)
        return vals if len(vals) > 1 else vals[0]

    def raw(self, n: int) -> bytes:
        b = self.buf[self.off : self.off + n]
        _require(len(b) == n, "truncated")
        self.off += n
        return b

    def lp(self) -> bytes:
        return self.raw(self.take("Q"))


# -------------------------------------------------- RZE section decode

def _undo_final_rze(payload: bytes) -> bytes:
    r = _Cursor(payload)
    n = r.take("Q")
    bitmap = np.frombuffer(r.lp(), np.uint8)
    nonzero = np.frombuffer(payload, np.uint8, offset=r.off)
    nz = np.unpackbits(bitmap, count=n).astype(bool)
    out = np.zeros(n, np.uint8)
    out[nz] = nonzero
    return out.tobytes()


def _bit_untranspose(shuffled: np.ndarray) -> np.ndarray:
    """Invert BIT_w: plane b (0 = MSB) words -> original words."""
    n_chunks, chunk_len = shuffled.shape
    w = shuffled.dtype.itemsize * 8
    be = shuffled.astype(f">u{shuffled.dtype.itemsize}")
    bits = np.unpackbits(be.view(np.uint8).reshape(n_chunks, -1), axis=1)
    planes = bits.reshape(n_chunks, w, chunk_len)       # [chunk, b, j]
    wordbits = planes.transpose(0, 2, 1)                # [chunk, j, b]
    packed = np.packbits(wordbits.reshape(n_chunks, chunk_len, w), axis=2)
    return (
        packed.reshape(n_chunks, -1)
        .view(f">u{shuffled.dtype.itemsize}")
        .astype(shuffled.dtype)
    )


def decode_rze_section(section: bytes, tile_elems: int,
                       transform: str) -> np.ndarray:
    """One RZE section -> the tile's signed integer stream."""
    r = _Cursor(section)
    n_chunks, chunk_len, word, final = r.take("IIBB")
    _require(CHUNK_WORDS.get(word) == chunk_len, "chunk length")
    udt = np.dtype(f"<u{word}")
    payload = section[r.off:]
    if final:
        payload = _undo_final_rze(payload)
    r2 = _Cursor(payload)
    keepmap = np.frombuffer(r2.lp(), np.uint8)
    kept = np.frombuffer(r2.lp(), udt)
    data = np.frombuffer(r2.lp(), udt)
    sdt = np.dtype(f"<i{word}")
    if n_chunks == 0:  # fully trimmed: every chunk was all-zero
        return np.zeros(tile_elems, sdt)

    w = word * 8
    n_bitmap_words = n_chunks * (chunk_len // w)
    keep = np.unpackbits(keepmap, count=n_bitmap_words).astype(bool)
    bitmap = (kept[np.cumsum(keep) - 1] if n_bitmap_words
              else np.zeros(0, udt))
    # bitmap bit j (MSB-first) = data word j nonzero
    nzbits = np.unpackbits(
        bitmap.astype(f">u{word}").view(np.uint8), count=n_chunks * chunk_len
    ).astype(bool).reshape(n_chunks, chunk_len)
    shuffled = np.zeros((n_chunks, chunk_len), udt)
    shuffled[nzbits] = data

    words = _bit_untranspose(shuffled)
    if transform == "raw":
        ints = words.astype(sdt)
    else:
        # zigzag^-1: (z >> 1) ^ -(z & 1), in the signed twin
        z = words
        ints = ((z >> 1) ^ (-(z & 1).astype(sdt)).astype(udt)).astype(sdt)
        if transform == "delta":
            # per-chunk cumsum in the STORED width (wrap is intentional)
            ints = np.cumsum(ints, axis=1, dtype=sdt)
    # trailing all-zero chunks were trimmed; missing rows are zero
    cpt = -(-tile_elems // chunk_len)
    full = np.zeros((cpt, chunk_len), sdt)
    full[:n_chunks] = ints
    return full.reshape(-1)[:tile_elems]


# ------------------------------------------------- value reconstruction

def _ordered(f: np.ndarray) -> np.ndarray:
    idt = np.dtype(f"i{f.dtype.itemsize}")
    bits = f.view(idt)
    imin = np.iinfo(idt).min
    return np.where(bits >= 0, bits, imin - bits)


def _ordered_inv(m: np.ndarray, dtype) -> np.ndarray:
    idt = np.dtype(f"i{np.dtype(dtype).itemsize}")
    m = m.astype(idt)
    imin = np.iinfo(idt).min
    bits = np.where(m >= 0, m, imin - m).astype(idt)
    return bits.view(dtype)


def dequantize(bins: np.ndarray, subs: np.ndarray, eps_abs: float,
               dtype, precision: str = "exact") -> np.ndarray:
    eps = eps_abs * EPS_SHRINK
    if precision == "bfloat16":
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        t = ((bins.astype(np.float32) - 0.5).astype(bf16)
             * np.asarray(eps, np.float32).astype(bf16)).astype(np.float64)
    else:
        t = (bins.astype(np.float64) - 0.5) * eps
    if np.dtype(dtype) == np.float64:
        base = t
    else:
        v = t.astype(np.float32)
        bumped = _ordered_inv(_ordered(v) + 1, np.float32)
        base = np.where(v.astype(np.float64) < t, bumped, v)
    base = base.astype(dtype)
    return _ordered_inv(_ordered(base) + subs.astype(np.int64), dtype)


def _apply_nonfinite(payload: bytes, out: np.ndarray) -> np.ndarray:
    r = _Cursor(payload)
    packed = np.frombuffer(r.lp(), np.uint8)
    vals = np.frombuffer(r.lp(), out.dtype)
    mask = np.unpackbits(packed, count=out.size).astype(bool).reshape(out.shape)
    out = out.copy()
    out[mask] = vals
    return out


def _parse_eb_ladder(payload: bytes, n_tiles: int) -> np.ndarray:
    """eb-ladder section: [u8 version][u8 k_max][u16 reserved=0]
    [u32 n_tiles][u8 index * n_tiles]; every rule is strict."""
    r = _Cursor(payload)
    version, k_max, reserved = r.take("BBH")
    _require(version == EB_LADDER_VERSION and reserved == 0 and k_max <= 7,
             "eb ladder header")
    n = r.take("I")
    _require(n == n_tiles, "eb ladder length")
    idx = np.frombuffer(r.raw(n), np.uint8)
    _require(r.off == len(payload) and (n == 0 or idx.max() <= k_max),
             "eb ladder body")
    return idx


# --------------------------------------------------- container decoder

class Container:
    """A parsed v2 container: header, tile index and per-tile bounds."""

    def __init__(self, blob: bytes):
        r = _Cursor(blob)
        _require(r.raw(4) == MAGIC, "magic")
        version, flags, dtc, ndim = r.take("BBBB")
        _require(version == VERSION_TILED, f"version {version}")
        self.shape = tuple(np.atleast_1d(r.take("Q" * ndim)).tolist()) \
            if ndim > 1 else (r.take("Q"),)
        self.mode = EB_MODES[r.take("B")]
        self.eb, eps_abs = r.take("dd")
        self.dtype = DTYPES[dtc]
        self.flags = flags
        self.tile_shape = tuple(r.take("QQQ"))
        self.grid = tuple(r.take("QQQ"))
        n_tiles, n_extra = r.take("IB")
        _require(n_tiles == int(np.prod(self.grid)), "tile count")
        self.extras = {}
        for _ in range(n_extra):
            tag, off, n = r.take("BQQ")
            self.extras[tag] = (off, n)
        self.entries = [r.take(TILE_ENTRY) for _ in range(n_tiles)]
        _require(r.take("I") == zlib.crc32(blob[: r.off - 4]) & 0xFFFFFFFF,
                 "header crc")
        self.blob = blob
        self.data_off = r.off
        self.order = bool(flags & FLAG_ORDER_PRESERVING)
        self.tile_elems = int(np.prod(self.tile_shape))
        has_flag = bool(flags & FLAG_ADAPTIVE_EB)
        _require(has_flag == (TAG_EB_LADDER in self.extras), "eb ladder flag")
        if has_flag:
            idx = _parse_eb_ladder(self._extra(TAG_EB_LADDER), n_tiles)
            self.eps_tiles = eps_abs * np.exp2(-idx.astype(np.float64))
        else:
            self.eps_tiles = np.full(n_tiles, eps_abs)

    def _extra(self, tag: int) -> bytes:
        off, n = self.extras[tag]
        return self.blob[self.data_off + off : self.data_off + off + n]

    def tile(self, i: int, precision: str = "exact") -> np.ndarray:
        """Decoded values of tile ``i`` as a ``tile_shape`` array."""
        boff, blen, soff, slen, crc = self.entries[i]
        d = self.data_off
        bins_b = self.blob[d + boff : d + boff + blen]
        sub_b = self.blob[d + soff : d + soff + slen]
        _require(zlib.crc32(sub_b, zlib.crc32(bins_b)) & 0xFFFFFFFF == crc,
                 f"tile {i} crc")
        bins = decode_rze_section(bins_b, self.tile_elems, "delta")
        subs = (decode_rze_section(sub_b, self.tile_elems, "raw")
                if self.order else np.zeros_like(bins))
        return dequantize(bins, subs, self.eps_tiles[i], self.dtype,
                          precision).reshape(self.tile_shape)

    def canonical(self) -> tuple[int, int, int]:
        return (1,) * (3 - len(self.shape)) + tuple(self.shape)


def decode(blob: bytes, precision: str = "exact") -> np.ndarray:
    """The whole field of a v2 container."""
    c = Container(blob)
    return decode_region(c, tuple(slice(0, n) for n in c.shape), precision)


def decode_region(c: Container | bytes, region: tuple,
                  precision: str = "exact") -> np.ndarray:
    """``decode(blob)[region]`` for a region of unit-step slices, from
    the tiles that the region touches alone."""
    if not isinstance(c, Container):
        c = Container(c)
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    region = tuple(slice(*s.indices(n)[:2]) for s, n in zip(region, c.shape))
    whole = tuple(slice(0, n) for n in c.shape)
    if c.flags & FLAG_HAS_NONFINITE and region != whole:
        # the non-finite mask is a whole-field section: decode it all
        return decode_region(c, whole, precision)[region]
    canon = (slice(0, 1),) * (3 - len(region)) + region
    lo = [s.start for s in canon]
    hi = [max(s.stop, s.start) for s in canon]
    out = np.zeros([b - a for a, b in zip(lo, hi)], c.dtype)
    t = c.tile_shape
    g = c.grid
    ranges = [range(a // t[d], -(-b // t[d])) for d, (a, b)
              in enumerate(zip(lo, hi))]
    for i in ranges[0]:
        for j in ranges[1]:
            for k in ranges[2]:
                tv = c.tile((i * g[1] + j) * g[2] + k, precision)
                o = (i * t[0], j * t[1], k * t[2])
                src, dst = [], []
                for d in range(3):
                    a = max(lo[d], o[d])
                    b = min(hi[d], o[d] + t[d])
                    src.append(slice(a - o[d], b - o[d]))
                    dst.append(slice(a - lo[d], b - lo[d]))
                out[tuple(dst)] = tv[tuple(src)]
    out = out.reshape([s.stop - s.start for s in region])
    if c.flags & FLAG_HAS_NONFINITE:
        out = _apply_nonfinite(c._extra(TAG_NONFINITE), out)
    return out
