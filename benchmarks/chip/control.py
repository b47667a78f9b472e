#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's numbers over
many seeds, and the control's, in one process.

    python3 benchmarks/chip/control.py --workload isabel.compress \\
        --seeds 11,12,13 --seconds 5

Each seed runs the cell as the benchmark does, at the cell's own size
and load, and then computes every compared number twice: for what the
timed path produced, and with the control in the program's place (the
plain reference one precision down: bfloat16 for these float32
deployments).  It prints one JSON line per seed, then the largest
reading of the program and the smallest of the control for each
number.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness

    peaks = harness.boot()
    bench = harness.load_bench(ROOT)
    program: dict[str, float] = {}
    control: dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               bench=bench, peaks=peaks, control=True)
        got = {k: v["value"] for k, v in res["checks"].items()}
        ctl = {k: v["value"] for k, v in res["control"].items()}
        for k, v in got.items():
            program[k] = max(program.get(k, v), v)
        for k, v in ctl.items():
            control[k] = min(control.get(k, v), v)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": all(
                              c["value"] <= c["limit"]
                              for c in res["control"].values()),
                          "program": got, "control": ctl,
                          "attempted": res["attempted"],
                          "seconds": round(time.time() - t0, 3)}),
              flush=True)
    print(json.dumps({"workload": args.workload, "program_max": program,
                      "control_min": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
