#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 benchmarks/chip/run.py --workload isabel.compress --seed 7 \\
        --seconds 51 --trace 0

Reads the cell from ``BENCHMARK.json`` at the checkout's root and the
files it names under ``benchmarks/chip/`` (see ``harness.py``).  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace
1`` ``breakdown``, and last ``checks``: each compared number with its
limit, also printed as the last lines of standard error.  With no
accelerator, or fewer chips than the cell asks for, it exits 2 and
prints no result.  JAX's compilation cache is kept in ``.jax_cache`` at
the checkout's root.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness

    try:
        bench = harness.load_bench(ROOT)
        cell = harness.cell_of(bench, args.workload)
        peaks = harness.boot(cell["chips"])
    except (OSError, KeyError, ValueError, SystemExit) as e:
        print(f"run.py: cannot run {args.workload}: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), bench=bench, peaks=peaks,
                              t_start=T_START)
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
