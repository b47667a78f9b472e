"""Benchmark harness: one module per paper table/figure (+ roofline).

Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME]
Emits ``name,us_per_call,derived`` CSV blocks per table.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="table3|table45|table67|fig3|fig4|table89|engine|"
                         "service|temporal|store|cluster|quality|roofline")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (  # noqa: WPS433
        cluster_bench,
        engine_bench,
        fig3_eb_sweep,
        fig4_binsplit,
        quality_bench,
        roofline,
        service_bench,
        store_bench,
        table3_preservation,
        table45_topo,
        table67_nontopo,
        table89_quality,
        temporal_bench,
    )
    from .common import load_inputs

    suites = {
        "table3": table3_preservation.run,
        "table45": table45_topo.run,
        "table67": table67_nontopo.run,
        "fig3": fig3_eb_sweep.run,
        "fig4": fig4_binsplit.run,
        "table89": table89_quality.run,
        "engine": engine_bench.run,
        "service": service_bench.run,
        "temporal": temporal_bench.run,
        "store": store_bench.run,
        "cluster": cluster_bench.run,
        "quality": quality_bench.run,
    }
    t0 = time.time()
    inputs = load_inputs()
    if args.only:
        if args.only == "roofline":
            roofline.run()
        else:
            suites[args.only](inputs)
    else:
        for name, fn in suites.items():
            print(f"== running {name} ==", file=sys.stderr, flush=True)
            fn(inputs)
        roofline.run()
    print(f"# total benchmark wall time: {time.time() - t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
