#!/usr/bin/env python3
"""Probe the program's tracing on the chip: one traced run of a cell,
read three ways.

    python3 benchmarks/chip/span_probe.py --workload isabel.compress \\
        --seed 7 --seconds 51 [--fresh-fields 2]

1. The run is ``run.py --workload ... --trace 1``, except that its line
   also holds the cell's end-to-end metrics, so traced and untraced
   runs compare: their difference is what tracing costs.
2. ``span_clock``: each program span placed on the profiler's clock the
   way the harness places it (one anchor beside the
   ``bench.traced_window`` annotation), against the host event of the
   same name that the span's own profiler annotation left.  The largest
   difference of starts and of ends, in microseconds, and how many
   spans matched (none where the program annotates no span).
3. With ``--fresh-fields K``, after the run: K fields drawn from the
   seed, not the cell's fixed fields, compressed one at a time through
   the service with tracing on; for each field, the spans that carry
   ``compiles`` tags, summed by name.

The last line of standard output is one JSON object.  Exits 2 off a TPU.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def span_clock(profile, spans, harness, trace_reduce) -> dict:
    """Anchored span starts and ends against their annotation events."""
    evs = list(trace_reduce.events(trace_reduce.load(profile.dir)))
    lo, hi = trace_reduce.annotation(evs, harness.Profile.ANNOTATION)
    offset = lo - profile.anchor_ns
    host = defaultdict(list)
    for plane, _line, name, a, b in evs:
        if not trace_reduce.DEVICE_PLANE.fullmatch(plane):
            host[name].append((a, b))
    starts, ends, worst = [], [], defaultdict(float)
    for s in spans:
        a = s.ts_us * 1000 + offset
        b = a + s.dur_us * 1000
        found = host.get(s.name)
        if not found or not lo <= a <= hi:
            continue
        ea, eb = min(found, key=lambda e: abs(e[0] - a))
        starts.append(abs(ea - a) / 1e3)
        ends.append(abs(eb - b) / 1e3)
        worst[s.name] = max(worst[s.name], starts[-1], ends[-1])
    if not starts:
        return {"matched": 0}
    return {"matched": len(starts), "max_start_us": max(starts),
            "max_end_us": max(ends),
            "median_start_us": statistics.median(starts),
            "worst_by_name_us": dict(sorted(worst.items(),
                                            key=lambda kv: -kv[1]))}


def fresh_compiles(cfg: dict, seed: int, count: int) -> list[dict]:
    """Compress ``count`` fields of the seed, one at a time; per field,
    ``{span name: [compiles, compile_ms]}`` over the spans tagged."""
    from repro import obs
    from repro.service import CompressionService, ServiceConfig

    from benchmarks.chip.fields import make_fields

    fields = make_fields(cfg["generator"], cfg["shape"], cfg["dtype"],
                         seed, count)
    out = []
    obs.enable(max_spans=1 << 16)
    try:
        with CompressionService(ServiceConfig()) as svc:
            for x in fields:
                obs.tracer().drain()
                t0 = time.perf_counter()
                svc.submit_compress(x, float(cfg["eb"]), cfg["mode"],
                                    bool(cfg["preserve_order"])).result()
                by_name: dict = defaultdict(lambda: [0, 0.0])
                for s in obs.tracer().drain():
                    if "compiles" in s.tags:
                        by_name[s.name][0] += s.tags["compiles"]
                        by_name[s.name][1] += s.tags["compile_ms"]
                out.append({"seconds": time.perf_counter() - t0,
                            "compiles": dict(by_name)})
    finally:
        obs.disable()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--fresh-fields", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness, trace_reduce

    try:
        bench = harness.load_bench(ROOT)
        cell = harness.cell_of(bench, args.workload)
        peaks = harness.boot(cell["chips"])
    except (OSError, KeyError, ValueError, SystemExit) as e:
        print(f"span_probe.py: cannot run {args.workload}: {e}",
              file=sys.stderr)
        return 2

    metrics_for, reduce_trace = harness.metrics_for, harness._reduce_trace
    clock: dict = {}

    def every_metric(bench_, cell_, trace):
        return metrics_for(bench_, cell_, False) + (
            metrics_for(bench_, cell_, True) if trace else [])

    def reduce_and_clock(profile, spans):
        clock.update(span_clock(profile, spans, harness, trace_reduce))
        return reduce_trace(profile, spans)

    harness.metrics_for = every_metric
    harness._reduce_trace = reduce_and_clock
    result = harness.run_cell(args.workload, args.seed, args.seconds, True,
                              bench=bench, peaks=peaks, t_start=T_START)
    result["span_clock"] = clock
    if args.fresh_fields:
        cfg = harness.load_json("configs", cell["config"])
        result["fresh_fields"] = fresh_compiles(cfg, args.seed,
                                                args.fresh_fields)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
