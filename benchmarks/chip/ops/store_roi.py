"""Region reads from a ``LopcStore`` archive through
``CompressionService.submit_store_roi``.

Set-up makes the archive's fields from the seed and writes them with
``submit_store_write`` (one step), warms every decode batch class a read
can touch, one read per class, and then empties the tile cache, so each
window starts from the same cold cache.  Each request reads a box of
``box`` times the field's extent per axis at a uniform position in a
uniformly chosen field; every seed reads the same population of boxes,
drawn once from a fixed stream, in its own order.

The check compares a sample of the window's regions bit for bit with
the plain reference decoder's region of the stored bytes, and holds
each to the bound and to local order inside the box against the input.
The control is the reference decoder with its bin anchors computed in
bfloat16.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from benchmarks.chip import checks
from benchmarks.chip.fields import make_fields
from benchmarks.chip.harness import Sample
from benchmarks.chip.reference import spec_decode

LIMITS = {"bits_differ": 0, "bound_ratio": 1.0, "order_flips": 0}
BOXES_SEED = 20101026   # fixed: the box population is the same for every seed
POPULATION = 8192


@dataclass
class State:
    svc: object
    store: object
    names: list[str]
    fields: list[np.ndarray]
    bounds: list[float]
    boxes: list[tuple[int, tuple[slice, ...]]]
    sample: Sample
    _stored: dict = field(default_factory=dict)

    def submit(self, f: int, region: tuple):
        return self.svc.submit_store_roi(self.store, self.names[f], region)

    def stored(self, f: int) -> spec_decode.Container:
        """Field ``f``'s container as the store holds it on disk."""
        if f not in self._stored:
            info = self.store.info(self.names[f])
            self._stored[f] = spec_decode.Container(
                (self.store.root / info["payload"]).read_bytes())
        return self._stored[f]


def _boxes(shape, frac, n_fields: int, rng) -> list:
    size = [max(1, round(f * n)) for f, n in zip(frac, shape)]
    base = np.random.default_rng(BOXES_SEED)
    fields = base.integers(n_fields, size=POPULATION)
    corners = np.stack([base.integers(n - s + 1, size=POPULATION)
                        for n, s in zip(shape, size)], axis=1)
    order = rng.permutation(POPULATION)
    return [(int(fields[k]), tuple(slice(int(c), int(c) + s)
                                   for c, s in zip(corners[k], size)))
            for k in order]


def _tile_block(c: spec_decode.Container, n: int) -> tuple:
    """A region of the field that touches exactly ``n`` tiles (fewer
    where the field has fewer): whole tile rows along the last axis
    first, then the middle, then the first."""
    t, g = c.tile_shape, c.grid
    n = min(n, int(np.prod(g)))
    k2 = min(n, g[2])
    k1 = min(-(-n // k2), g[1])
    k0 = -(-n // (k1 * k2))
    if k0 * k1 * k2 != n:        # n is not a block: settle for the block
        k0 = max(1, n // (k1 * k2))
    canon = tuple(slice(0, min(k * ti, ci))
                  for k, ti, ci in zip((k0, k1, k2), t, c.canonical()))
    return canon[3 - len(c.shape):]


def setup(env) -> State:
    from repro.engine import buckets
    from repro.store import LopcStore

    cfg, tr = env.cfg, env.traffic
    shape = tuple(cfg["shape"])
    fields = make_fields(cfg["generator"], shape, cfg["dtype"], env.seed,
                         int(cfg["fields"]))
    store = env.resources.enter_context(LopcStore.create(
        env.workdir / "archive", cache_bytes=int(cfg["tile_cache_bytes"])))
    names = [f"field{k}" for k in range(len(fields))]
    futs = [env.svc.submit_store_write(store, name, x, float(cfg["eb"]),
                                       cfg["mode"],
                                       bool(cfg["preserve_order"]))
            for name, x in zip(names, fields)]
    for fut in futs:
        fut.result()
    state = State(env.svc, store, names, fields,
                  [checks.bound(x, cfg["eb"], cfg["mode"]) for x in fields],
                  _boxes(shape, tr["box"], len(fields), env.rng("boxes")),
                  Sample(int(tr["check_requests"]), env.rng("sample")))
    # one cold read per decode batch class that a read can reach
    c = state.stored(0)
    floor = max(buckets.CAPACITY_FLOOR, env.svc.config.plan.batch_tiles)
    for n in buckets.capacity_classes(floor):
        store.cache.clear()
        state.submit(0, _tile_block(c, n)).result()
    store.cache.clear()
    return state


def issue(state: State, i: int):
    f, region = state.boxes[i % len(state.boxes)]
    return state.submit(f, region), 0


def finish(state: State, req, out: np.ndarray) -> int:
    state.sample.offer(req.index, out)
    return out.nbytes


def counters(state: State) -> dict:
    stats = state.store.cache.stats()
    return {"cache_hits": stats["hits"], "cache_misses": stats["misses"]}


def _numbers(state: State, outputs: dict) -> list:
    rows = []
    for i, out in sorted(outputs.items()):
        f, region = state.boxes[i % len(state.boxes)]
        want = spec_decode.decode_region(state.stored(f), region)
        x = state.fields[f][region]
        rows.append({"bits_differ": checks.bits_differ(out, want),
                     **checks.field_numbers(x, out, state.bounds[f])})
    return checks.worst(rows, LIMITS)


def check(state: State) -> list:
    return _numbers(state, state.sample.items)


def control(state: State) -> list:
    outputs = {}
    for i in state.sample.items:
        f, region = state.boxes[i % len(state.boxes)]
        outputs[i] = spec_decode.decode_region(state.stored(f), region,
                                               "bfloat16")
    return _numbers(state, outputs)
