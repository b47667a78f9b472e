"""Tiled, batched, device-resident plan/execute compression engine.

Public API:

    plan  = CompressionPlan(tile_shape=(16, 16, 64), batch_tiles=8)
    blobs = compress_many(fields, eb=1e-2, plan=plan, solver="auto")
    outs  = decompress_many(blobs)
    roi   = decompress_roi(blobs[0], (slice(0, 8), slice(4, 20)))

Single-field ``compress``/``decompress`` wrappers exist for convenience;
``core.lopc`` routes through them.  The execute half is the
device-resident :class:`~repro.engine.executor.Executor`: one tile
upload per compress group, a chain of resident stage programs
(quantize → flags → subbin solve with on-device halo exchange →
lossless pipeline) whose intermediates never leave the device, one
download of encoded streams.  ``solver`` picks the subbin schedule
(``jacobi``/``frontier``/``blockwise``/``auto``) — speed only, bytes
are schedule-independent.

Probes: ``device.TRACE_COUNTS`` / ``device.trace_count()`` expose the
jit-trace counter used to assert shape stability;
``executor.TRANSFER_COUNTS`` / ``executor.transfer_count()`` count
host↔device crossings (one upload + one download per compress group).
Both are views over registry counter families (``lopc_traces_total``,
``lopc_transfers_total``; see :mod:`repro.obs`).
"""
from .engine import (
    ADAPTIVE_EB_MODES,
    CompressStats,
    compress,
    compress_many,
    container_layout,
    decode_tiles_for_region,
    decode_tiles_many,
    decompress,
    decompress_many,
    decompress_roi,
    region_from_tiles,
)
from .executor import Executor
from .plan import CompressionPlan, TileLayout, tiles_for_region
from . import device, executor, halo

__all__ = [
    "ADAPTIVE_EB_MODES",
    "CompressionPlan",
    "TileLayout",
    "CompressStats",
    "Executor",
    "compress",
    "compress_many",
    "container_layout",
    "decode_tiles_for_region",
    "decode_tiles_many",
    "decompress",
    "decompress_many",
    "decompress_roi",
    "region_from_tiles",
    "tiles_for_region",
    "device",
    "executor",
    "halo",
]
