"""Compress host fields through ``CompressionService.submit_compress``.

Set-up makes a pool of distinct fields, the same for every seed, in an
order drawn from the seed, and compresses each once, one at a time as
the window sends them: besides the programs every field shares, each
field compiles eager slices of data-dependent length, so only its own
compress warms them, and the window then compiles nothing.  The fields
are fixed because the solver's rounds depend on the data: fields drawn
from the seed changed a window's work by up to 14% from seed to seed,
where two runs of one seed agreed within 2%.  The check decodes a sample of the window's containers with
the plain reference decoder and holds each to the pointwise bound and
to full local order against its input.  The control puts a bfloat16
rounding of the input in the compressor's place: it keeps the bound
and breaks the order.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchmarks.chip import checks
from benchmarks.chip.fields import make_fields
from benchmarks.chip.harness import Sample
from benchmarks.chip.reference import spec_decode

LIMITS = {"undecodable": 0, "bound_ratio": 1.0, "order_flips": 0}
FIELDS_SEED = 20260301   # fixed: every seed compresses the same fields


@dataclass
class State:
    svc: object
    pool: list[np.ndarray]
    eb: float
    mode: str
    order: bool
    sample: Sample

    def submit(self, x):
        return self.svc.submit_compress(x, self.eb, self.mode, self.order)


def setup(env) -> State:
    cfg, tr = env.cfg, env.traffic
    fields = make_fields(cfg["generator"], cfg["shape"], cfg["dtype"],
                         FIELDS_SEED, int(tr["pool"]))
    pool = [fields[k] for k in env.rng("order").permutation(len(fields))]
    state = State(env.svc, pool, float(cfg["eb"]), cfg["mode"],
                  bool(cfg["preserve_order"]),
                  Sample(int(tr["check_requests"]), env.rng("sample")))
    for x in pool:
        state.submit(x).result()
    return state


def issue(state: State, i: int):
    x = state.pool[i % len(state.pool)]
    return state.submit(x), x.nbytes


def finish(state: State, req, blob: bytes) -> int:
    state.sample.offer(req.index, blob)
    return len(blob)


def _numbers(state: State, decoded) -> list:
    rows = []
    for i, blob in sorted(state.sample.items.items()):
        x = state.pool[i % len(state.pool)]
        limit = checks.bound(x, state.eb, state.mode)
        try:
            y = decoded(x, blob)
        except ValueError:
            rows.append({"undecodable": 1, "bound_ratio": 0.0,
                         "order_flips": 0})
            continue
        rows.append({"undecodable": 0, **checks.field_numbers(x, y, limit)})
    return checks.worst(rows, LIMITS)


def check(state: State) -> list:
    return _numbers(state, lambda x, blob: spec_decode.decode(blob))


def control(state: State) -> list:
    import ml_dtypes

    return _numbers(state, lambda x, blob: x.astype(ml_dtypes.bfloat16)
                    .astype(x.dtype))
