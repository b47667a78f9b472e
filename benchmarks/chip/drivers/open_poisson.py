"""Open loop: requests arrive on a Poisson schedule at ``rate_per_s``,
whether or not earlier ones have completed.

Every seed gets the same multiset of inter-arrival gaps, drawn once
from a fixed stream, in an order drawn from the seed, so seeds change
the order of the work and not its amount.  Each request is timed from
its due time, so a stall shows in the requests behind it.  The window
opens at the first due time; requests due in the first ``seconds`` are
issued and every one of them is waited for, up to ``WAIT_S`` past the
last due time.  The window closes at the first completion after
``seconds`` have passed.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.chip.harness import Request, Window, send

WAIT_S = 60.0
GAPS_SEED = 20100610   # fixed: the gap multiset is the same for every seed


def schedule(rate: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the window's opening) of the requests."""
    n = int(math.ceil(rate * seconds * 1.25)) + 16
    gaps = np.random.default_rng(GAPS_SEED).exponential(1.0 / rate, n)
    due = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    return due[due < seconds]


def run(op, state, env, seconds: float, profile) -> Window:
    due = schedule(float(env.traffic["rate_per_s"]), seconds,
                   env.rng("arrivals"))
    requests = [Request(i, 0.0) for i in range(len(due))]
    profile.start()
    t_open = time.perf_counter()
    for req, d in zip(requests, due):
        req.t_due = t_open + float(d)
        pause = req.t_due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        send(op, state, req)
    give_up = t_open + seconds + WAIT_S
    for req in requests:
        req.event.wait(max(0.0, give_up - time.perf_counter()))
    profile.stop()
    after = [r.t_done for r in requests
             if r.t_done is not None and r.t_done >= t_open + seconds]
    t_close = min(after) if after else t_open + seconds
    late_ms = np.array([(r.t_sent - r.t_due) * 1e3 for r in requests])
    notes = {"generator late ms (p50 p95 max)": " ".join(
        f"{v:.3f}" for v in (np.percentile(late_ms, 50),
                             np.percentile(late_ms, 95), late_ms.max()))
        if len(late_ms) else "no requests"}
    return Window(requests, t_open, t_close, notes)
