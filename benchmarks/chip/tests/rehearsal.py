"""What the CPU rehearsals share: the cells of ``BENCHMARK.json`` plus
the archive cell, which runs but is not admitted to the benchmark yet
(PERF.md, Open questions), so that its files stay rehearsed; and each
cell's configuration at a shape a CPU test run can hold."""
from __future__ import annotations

import json

from benchmarks.chip import harness

ARCHIVE_CELL = "isabel-archive.roi-uniform"


def _layer(name: str, unit: str, better: str, source: str,
           layer: str) -> dict:
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "roi_p95_ms",
            "workloads": [ARCHIVE_CELL]}


ARCHIVE = {
    "configs": [{"name": "isabel-archive", "source": "SDRBench Isabel read "
                 "as YCSB workload C", "file":
                 "benchmarks/chip/configs/isabel-archive.json",
                 "reduced": ["fields"], "why": "a LopcStore read by region"}],
    "workloads": [{"name": ARCHIVE_CELL, "config": "isabel-archive",
                   "traffic": "roi-uniform", "chips": 1,
                   "why": "open-loop Poisson region reads"}],
    "end_to_end": [{"name": "roi_p95_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": [ARCHIVE_CELL]}],
    "per_layer": [
        _layer("queue_ms.roi", "ms", "lower", "program_span",
               "service coalescer"),
        _layer("tile_hit_share.roi", "%", "higher", "program_counter",
               "store tile cache"),
        _layer("store_read_ms.roi", "ms", "lower", "program_span",
               "store read path"),
        _layer("idle_share.roi", "%", "lower", "device_trace", "device"),
    ],
}

PUBLISHED = harness.load_bench(harness.ROOT)


def _with_archive(bench: dict) -> dict:
    out = json.loads(json.dumps(bench))
    for key, entries in ARCHIVE.items():
        out[key] += entries
    return out


BENCH = _with_archive(PUBLISHED)
CELLS = [c["name"] for c in BENCH["workloads"]]


def tiny(cell: str) -> dict:
    """The cell's configuration at a shape a CPU test run can hold."""
    cfg = harness.load_json("configs", harness.cell_of(BENCH, cell)["config"])
    return dict(cfg, shape=[18, 36, 130], fields=2, tile_cache_bytes=1 << 20)
