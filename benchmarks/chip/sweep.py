#!/usr/bin/env python3
"""Find the knee of an open-loop traffic mix on a configuration: the
highest offered rate at which the backlog does not grow.

    python3 benchmarks/chip/sweep.py --config isabel-archive \\
        --traffic roi-uniform --seed 5 --seconds 20 --rates 20,40,60,80

The mix and the configuration are files found by name, so a cell can
be swept before it is in ``BENCHMARK.json``.  One process sets it up
once and runs its driver at each rate in turn, from the same cold tile
cache.  For each rate it prints the completed rate, the latency
percentiles, and the median latency of the last third of the requests
over that of the first third: a backlog that grows makes that ratio
climb well above 1.  The cell's traffic file
fixes its rate at about 0.8 of the knee found here; the benchmark's own
runs never search for it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, default=20)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness

    harness.boot()
    import numpy as np

    from repro.service import CompressionService, ServiceConfig

    cfg = harness.load_json("configs", args.config)
    traffic = harness.load_json("traffic", args.traffic)
    op = harness.load_module("ops", traffic["op"])
    driver = harness.load_module("drivers", traffic["driver"])
    rows = []
    with tempfile.TemporaryDirectory(prefix="chipbench-") as work, \
            contextlib.ExitStack() as resources, \
            CompressionService(ServiceConfig()) as svc:
        env = harness.Env(cfg, dict(traffic), args.seed, svc, Path(work),
                          resources)
        state = op.setup(env)
        for rate in (float(r) for r in args.rates.split(",")):
            env.traffic["rate_per_s"] = rate
            if hasattr(state, "store"):
                state.store.cache.clear()
            w = driver.run(op, state, env, float(args.seconds),
                           harness.Profile(False))
            lat = np.array([(r.t_done - r.t_due) * 1e3 if r.ok else np.inf
                            for r in w.requests])
            third = max(1, len(lat) // 3)
            done = [r.t_done for r in w.requests if r.ok]
            span = (max(done) - w.t_open) if done else float("nan")
            row = {"offered_per_s": rate,
                   "completed_per_s": len(done) / span if done else 0.0,
                   "failed": int(sum(not r.ok for r in w.requests)),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "p99_ms": float(np.percentile(lat, 99)),
                   "growth": float(np.median(lat[-third:])
                                   / np.median(lat[:third])),
                   **w.notes}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
