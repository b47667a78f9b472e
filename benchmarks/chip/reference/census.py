"""Plain numpy census of local order on the Freudenthal link.

LOPC's guarantee is that every pair of link neighbours keeps its order
under Simulation of Simplicity: values compare first, and a tie goes to
the larger linear index.  A vertex's lower-link mask (which of its 14
neighbours lie below it) is made of those pair relations, and its
critical-point signature (components of the lower and of the upper
link) is a function of that mask.  So counting the pairs whose relation
differs between two fields checks full local order and every critical
point at once.  Nothing here imports ``repro``; the whole field is
handled in a few vectorized passes.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np


@lru_cache(maxsize=None)
def offsets(ndim: int) -> np.ndarray:
    """Link offsets, the 2**ndim - 1 with components in {0, 1} first
    (neighbour's linear index larger), then their negations."""
    pos = [tuple((m >> (ndim - 1 - d)) & 1 for d in range(ndim))
           for m in range(1, 2**ndim)]
    pos.sort(key=lambda o: (sum(o), o))
    return np.array(pos + [tuple(-c for c in o) for o in pos], np.int64)


def _pair_views(x: np.ndarray, off):
    """(x[p], x[p + off]) over every p with both ends in the grid, for
    an offset with components in {0, 1}."""
    here = tuple(slice(0, n - o) for n, o in zip(x.shape, off))
    there = tuple(slice(o, n) for n, o in zip(x.shape, off))
    return x[here], x[there]


def order_flips(x: np.ndarray, y: np.ndarray) -> int:
    """Neighbour pairs whose order differs between ``x`` and ``y``.

    For a positive offset the neighbour has the larger index, so it is
    below the vertex only when its value is strictly smaller; every
    undirected pair is counted once, from its lower-index end."""
    if x.shape != y.shape:
        raise ValueError(f"shapes differ: {x.shape} vs {y.shape}")
    flips = 0
    for off in offsets(x.ndim)[: len(offsets(x.ndim)) // 2]:
        xa, xb = _pair_views(x, off)
        ya, yb = _pair_views(y, off)
        flips += int(np.count_nonzero((xb < xa) != (yb < ya)))
    return flips


def lower_link_masks(x: np.ndarray) -> np.ndarray:
    """uint16 per vertex: bit k set iff neighbour k exists and lies
    below the vertex under Simulation of Simplicity."""
    offs = offsets(x.ndim)
    half = len(offs) // 2
    masks = np.zeros(x.shape, np.uint16)
    for k, off in enumerate(offs[:half]):
        here = tuple(slice(0, n - o) for n, o in zip(x.shape, off))
        there = tuple(slice(o, n) for n, o in zip(x.shape, off))
        below = x[there] < x[here]          # neighbour +off is lower
        masks[here] |= below.astype(np.uint16) << np.uint16(k)
        # seen from the other end the pair reverses: p + off has
        # neighbour p at offset -off, which is lower unless p was below
        masks[there] |= (~below).astype(np.uint16) << np.uint16(k + half)
    return masks


@lru_cache(maxsize=None)
def _signature_table(ndim: int) -> tuple[np.ndarray, np.ndarray]:
    """(lower components, upper components) for every possible mask of
    a vertex whose whole link is in the grid."""
    offs = offsets(ndim)
    k = len(offs)
    adj = np.zeros((k, k), bool)
    for i in range(k):
        for j in range(k):
            d = offs[i] - offs[j]
            adj[i, j] = i != j and d.any() and (
                np.all((d == 0) | (d == 1)) or np.all((d == 0) | (d == -1)))

    def components(members: int) -> int:
        seen, n = 0, 0
        for s in range(k):
            if not members >> s & 1 or seen >> s & 1:
                continue
            n += 1
            stack = [s]
            seen |= 1 << s
            while stack:
                u = stack.pop()
                for v in np.flatnonzero(adj[u]):
                    if members >> v & 1 and not seen >> v & 1:
                        seen |= 1 << int(v)
                        stack.append(int(v))
        return n

    full = (1 << k) - 1
    lower = np.array([components(m) for m in range(1 << k)], np.int8)
    upper = np.array([components(full & ~m) for m in range(1 << k)], np.int8)
    return lower, upper


def signatures(x: np.ndarray, masks: np.ndarray | None = None):
    """(lower-link components, upper-link components) per vertex.

    Valid for vertices whose whole link lies in the grid (the interior);
    on the boundary the missing neighbours would count as upper."""
    lower, upper = _signature_table(x.ndim)
    m = lower_link_masks(x) if masks is None else masks
    return lower[m], upper[m]
