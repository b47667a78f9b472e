"""Distributed compression: sharded LOPC tile batches + gradient
compression with error feedback.

Field compression across a mesh
-------------------------------
The engine's resident tile batches are plain leading-axis arrays, so
sharding LOPC across devices is just placing that axis over a mesh
axis: ``compress_fields_sharded`` routes ``engine.compress_many``
through a ``put`` hook that lays every executor upload (tiles, eps,
the halo neighbour table) out with a NamedSharding.  The same
device-resident executor then runs unchanged: quantize/flags/solve/
encode stay sharded over tiles, and the halo build's face-plane gathers
are device-side collectives over the resident batch — no host
round-trips appear on the sharded path either.  Bytes are identical to
the single-device path — the engine's programs are
schedule-independent — which is what makes the sharded path safe to
enable anywhere.

Gradient compression (distributed-optimization trick, DESIGN.md §5):
int8 quantization of the gradient stream using the same guaranteed-bound
quantizer family as LOPC, plus an error-feedback accumulator so
compression noise does not bias convergence (Karimireddy et al.,
arXiv:1901.09847).

Two forms:
  * make_error_feedback_compressor: drop-in grad_transform for
    runtime.steps.make_train_step — quantize/dequantize every gradient
    leaf, carrying the residual in opt_state["ef"]. Models the bandwidth
    reduction of a compressed all-reduce (4x for f32 grads).
  * compressed_pod_psum: an explicit int8 all-reduce over the cross-pod
    mesh axis under shard_map — the DCI link is the slow/expensive hop
    on a multi-pod system, so that is where the 4x matters most.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .. import engine


# ----------------------------------------------------- sharded tile path

def make_tile_put(mesh, axis: str = "data"):
    """``put`` hook for the engine's executor: shard the tile-batch axis.

    Applied to every resident upload (haloed tiles, per-tile eps, halo
    tables).  Batches whose leading extent does not divide the mesh axis
    (and scalars/eps vectors) are replicated — correctness never depends
    on placement, only throughput does.  Resident capacities are
    multiples of 4 (executor.resident_capacity), so pick a plan whose
    tile counts land on multiples of the axis size to split every batch.
    """
    n = mesh.shape[axis]
    # ``jax.make_mesh`` gives Explicit axes, whose sharding-in-types rules
    # refuse the executor's pads and halo gathers on a sharded tile axis;
    # Auto axes leave placement to the compiler, which is all we ask.
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))

    def put(a):
        a = jnp.asarray(a)
        spec = P(axis) if (a.ndim >= 1 and a.shape[0] % n == 0) else P()
        return jax.device_put(a, NamedSharding(mesh, spec))

    # the executor traces its programs under this mesh (``jax.set_mesh``),
    # which is how the Mosaic kernels learn to run per device
    put.mesh = mesh
    return put


def compress_fields_sharded(fields, eb, mesh, axis: str = "data", **kw):
    """engine.compress_many with tile batches sharded across ``axis``.

    Produces byte-identical blobs to the unsharded engine (tested); use
    a plan whose ``batch_tiles`` is a multiple of the axis size so every
    batch actually splits.
    """
    return engine.compress_many(fields, eb, put=make_tile_put(mesh, axis), **kw)


def _quantize_leaf(g: jnp.ndarray):
    """Symmetric int8 quantization with per-leaf scale."""
    g32 = g.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_leaf(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_error_feedback_compressor():
    """grad_transform(grads, opt_state) -> (grads, opt_state).

    opt_state must contain an "ef" tree (init_error_feedback). Residual
    r = g_in - decode(encode(g_in + r_prev)) is carried forward."""

    def transform(grads, opt_state):
        ef = opt_state["ef"]

        def leaf(g, e):
            corrected = g.astype(jnp.float32) + e
            q, scale = _quantize_leaf(corrected)
            out = _dequantize_leaf(q, scale)
            return out.astype(g.dtype), corrected - out

        pairs = jax.tree.map(leaf, grads, ef)
        new_grads = jax.tree.map(lambda t: t[0], pairs,
                                 is_leaf=lambda t: isinstance(t, tuple))
        new_ef = jax.tree.map(lambda t: t[1], pairs,
                              is_leaf=lambda t: isinstance(t, tuple))
        return new_grads, {**opt_state, "ef": new_ef}

    return transform


def compressed_pod_psum(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """int8 all-reduce over `axis` (call inside shard_map): quantize,
    sum int32, dequantize with a max-combined scale. ~4x less DCI
    traffic than an f32 psum at <1% relative error per reduction."""
    q, scale = _quantize_leaf(x)
    scale_max = jax.lax.pmax(scale, axis)
    # requantize against the shared scale so the integer sum is exact
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale_max), -127, 127
                 ).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    return total.astype(jnp.float32) * scale_max
