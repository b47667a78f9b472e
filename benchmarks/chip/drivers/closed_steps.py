"""Closed loop: one client submits a step of ``per_step`` requests
together and waits for all of them before it submits the next.

The window opens when the first request is issued and closes when the
first step completes after ``seconds`` have passed; a step completes
when its last request does.  Rates are the work of the completed steps
over that whole span.
"""
from __future__ import annotations

import time

from repro import obs

from benchmarks.chip.harness import Request, Window, send

WAIT_S = 300.0   # a request slower than this is lost


def run(op, state, env, seconds: float, profile) -> Window:
    per_step = int(env.traffic["per_step"])
    requests: list[Request] = []
    profile.start()
    t_open = time.perf_counter()
    deadline = t_open + seconds
    step = 0
    while True:
        with obs.span("bench.step", step=step):
            batch = [Request(len(requests) + k, time.perf_counter())
                     for k in range(per_step)]
            for req in batch:
                send(op, state, req)
            for req in batch:
                req.event.wait(WAIT_S)
        requests += batch
        done = [r.t_done for r in batch if r.t_done is not None]
        if len(done) < len(batch):
            t_close = time.perf_counter()   # a lost request ends the run
            break
        t_close = max(done)
        if t_close >= deadline:
            break
        step += 1
    profile.stop()
    return Window(requests, t_open, t_close)
