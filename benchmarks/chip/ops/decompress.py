"""Decode whole containers through ``CompressionService.submit_decompress``.

Set-up makes a pool of fields from the seed, compresses them through
the service and warms up with one decode: a field's tiles split into
full batches, so one decode warms every program of the window.  The
check compares a sample of the window's decoded fields bit for bit
with the plain reference decoder's output for the same container, and
holds each to the bound and to full local order against the input.
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


@dataclass
class State:
    svc: object
    pool: list[np.ndarray]
    blobs: list[bytes]
    eb: float
    mode: str
    sample: Sample
    _reference: dict = field(default_factory=dict)


def setup(env) -> State:
    cfg, tr = env.cfg, env.traffic
    pool = make_fields(cfg["generator"], cfg["shape"], cfg["dtype"],
                       env.seed, int(tr["pool"]))
    futs = [env.svc.submit_compress(x, float(cfg["eb"]), cfg["mode"],
                                    bool(cfg["preserve_order"]))
            for x in pool]
    blobs = [f.result() for f in futs]
    state = State(env.svc, pool, blobs, float(cfg["eb"]), cfg["mode"],
                  Sample(int(tr["check_requests"]), env.rng("sample")))
    env.svc.submit_decompress(blobs[0]).result()
    return state


def issue(state: State, i: int):
    blob = state.blobs[i % len(state.blobs)]
    return state.svc.submit_decompress(blob), len(blob)


def finish(state: State, req, out: np.ndarray) -> int:
    state.sample.offer(req.index, out)
    return out.nbytes


def _numbers(state: State, outputs: dict) -> list:
    rows = []
    for i, out in sorted(outputs.items()):
        j = i % len(state.blobs)
        if j not in state._reference:
            state._reference[j] = spec_decode.decode(state.blobs[j])
        x = state.pool[j]
        rows.append({"bits_differ": checks.bits_differ(out,
                                                       state._reference[j]),
                     **checks.field_numbers(
                         x, out, checks.bound(x, state.eb, state.mode))})
    return checks.worst(rows, LIMITS)


def check(state: State) -> list:
    return _numbers(state, state.sample.items)


def control(state: State) -> list:
    return _numbers(state, {
        i: spec_decode.decode(state.blobs[i % len(state.blobs)], "bfloat16")
        for i in state.sample.items})
