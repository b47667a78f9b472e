"""Critical-point census on PL scalar fields (paper §II, Table III).

Classification on the Freudenthal link of each vertex, under Simulation
of Simplicity (all comparisons on (value, linear index)):

  lower link empty            -> local minimum
  upper link empty            -> local maximum
  1 lower CC and 1 upper CC   -> regular
  otherwise                   -> saddle

The "type" we compare is the *exact* signature (n_lower_cc, n_upper_cc),
which is stricter than min/max/saddle classes: it distinguishes 1- from
2-saddles and monkey saddles.  LOPC must reproduce signatures exactly
everywhere; lossy baselines will not.

Connected components of the lower/upper link are counted by min-label
propagation over the static link graph (K <= 14 vertices, diameter <= 4,
so a fixed number of sweeps converges; we run K for safety).  Everything
is vectorized over the full grid.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core import topology

CLASS_REGULAR = 0
CLASS_MIN = 1
CLASS_MAX = 2
CLASS_SADDLE = 3


def _neighbor_relation(values: jnp.ndarray):
    """(lower, upper, valid) masks of shape (K, *grid) under SoS."""
    ndim = values.ndim
    offs = topology.offsets(ndim)
    lowers, uppers, valids = [], [], []
    for k, off in enumerate(offs):
        nv = topology.shift(values, off, jnp.inf)
        # validity: a shifted +inf cell is out of grid. Track explicitly
        # (a field could contain inf-adjacent huge values; we require
        # finite fields so +inf fill is unambiguous).
        valid = topology.shift(jnp.ones_like(values, dtype=bool), off, False)
        lower = topology.sos_less(nv, values, k, ndim) & valid
        upper = valid & ~lower
        lowers.append(lower)
        uppers.append(upper)
        valids.append(valid)
    return jnp.stack(lowers), jnp.stack(uppers), jnp.stack(valids)


def _count_components(member: jnp.ndarray, adj: np.ndarray) -> jnp.ndarray:
    """#CCs of the link subgraph induced by ``member`` (K, *grid) -> (*grid)."""
    k = member.shape[0]
    big = jnp.int32(127)
    labels = jnp.where(member, jnp.arange(k, dtype=jnp.int32).reshape((k,) + (1,) * (member.ndim - 1)), big)
    adjm = jnp.asarray(adj)

    def sweep(labels, _):
        # label[i] <- min(label[i], min_{j adj i, member j} label[j])
        new = labels
        for i in range(k):
            nbr_labels = jnp.where(
                (adjm[i].reshape((k,) + (1,) * (labels.ndim - 1))) & member,
                labels,
                big,
            )
            m = jnp.min(nbr_labels, axis=0)
            new = new.at[i].set(jnp.where(member[i], jnp.minimum(new[i], m), big))
        return new, None

    labels, _ = jax.lax.scan(sweep, labels, None, length=k)
    roots = member & (labels == jnp.arange(k, dtype=jnp.int32).reshape((k,) + (1,) * (member.ndim - 1)))
    return jnp.sum(roots, axis=0).astype(jnp.int8)


@jax.jit
def critical_signature(values: jnp.ndarray):
    """(n_lower_cc, n_upper_cc) per vertex — the exact type signature."""
    adj = topology.link_adjacency(values.ndim)
    lower, upper, _ = _neighbor_relation(values)
    return _count_components(lower, adj), _count_components(upper, adj)


@jax.jit
def classify_critical_points(values: jnp.ndarray) -> jnp.ndarray:
    """int8 class per vertex: 0 regular / 1 min / 2 max / 3 saddle."""
    lo, up = critical_signature(values)
    cls = jnp.full(values.shape, CLASS_REGULAR, jnp.int8)
    cls = jnp.where((lo == 1) & (up == 1), CLASS_REGULAR, CLASS_SADDLE)
    cls = jnp.where(lo == 0, CLASS_MIN, cls)
    cls = jnp.where(up == 0, CLASS_MAX, cls)
    return cls.astype(jnp.int8)


def critical_point_errors(original: np.ndarray, reconstructed: np.ndarray):
    """Table III metrics: (false_positives, false_negatives, false_types).

    FP: critical in reconstruction, regular in original.
    FN: critical in original, regular in reconstruction.
    FT: critical in both but with a different exact signature.
    """
    o = jnp.asarray(original)
    r = jnp.asarray(reconstructed)
    lo_o, up_o = critical_signature(o)
    lo_r, up_r = critical_signature(r)
    crit_o = (lo_o != 1) | (up_o != 1)
    crit_r = (lo_r != 1) | (up_r != 1)
    fp = int(jnp.sum(crit_r & ~crit_o))
    fn = int(jnp.sum(crit_o & ~crit_r))
    ft = int(jnp.sum(crit_o & crit_r & ((lo_o != lo_r) | (up_o != up_r))))
    return fp, fn, ft


@jax.jit
def order_violation_counts(original, reconstructed) -> jnp.ndarray:
    """Per vertex, the neighbor pairs it owns whose SoS order differs.

    Each undirected pair is owned by one endpoint (the positive
    offsets), so the counts of any set of vertices add up without double
    counting — which lets a large field be checked slab by slab.
    """
    lower_o, _, valid = _neighbor_relation(original)
    lower_r, _, _ = _neighbor_relation(reconstructed)
    half = len(topology.offsets(original.ndim)) // 2
    viol = (lower_o != lower_r) & valid
    return jnp.sum(viol[:half], axis=0, dtype=jnp.int32)


def local_order_violations(original: np.ndarray, reconstructed: np.ndarray) -> int:
    """#neighbor pairs whose SoS order differs (0 for LOPC, by theorem)."""
    counts = order_violation_counts(jnp.asarray(original),
                                    jnp.asarray(reconstructed))
    return int(jnp.sum(counts))
