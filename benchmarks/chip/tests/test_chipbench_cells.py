"""Every cell of ``BENCHMARK.json``, and the archive cell, rehearsed
on the CPU at a tiny shape through the same drivers, ops and readers;
the control fails where the program passes; the harness finds a new
cell's files by name; and the real command refuses to run off a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.rehearsal import BENCH, CELLS, PUBLISHED, tiny

ROOT = harness.ROOT
PEAKS = harness.peaks_for("TPU v5 lite")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_program_correct_control_not(cell):
    res = harness.run_cell(cell, 2**31 + 7, 1, False, bench=BENCH,
                           cfg=tiny(cell), peaks=PEAKS, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in harness.metrics_for(BENCH, cell, False)}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    failed = [k for k, c in res["control"].items() if c["value"] > c["limit"]]
    assert failed, res["control"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced_rehearsal_reads_every_span_metric(cell):
    res = harness.run_cell(cell, 5, 1, True, bench=BENCH, cfg=tiny(cell),
                           peaks=PEAKS)
    assert res["correct"], res["checks"]
    per_layer = harness.metrics_for(BENCH, cell, True)
    assert per_layer and all(cell in m["workloads"] for m in per_layer)
    # the CPU has no device trace: those readers return nothing
    want = {m["name"] for m in per_layer if m["source"] != "device_trace"}
    assert set(res["metrics"]) == want


@pytest.mark.parametrize("bench", [PUBLISHED, BENCH],
                         ids=["published", "with_archive"])
def test_contract_shape_of_benchmark_json(bench):
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    cells = {c["name"] for c in bench["workloads"]}
    configs = {c["config"] for c in bench["workloads"]}
    assert configs == {c["name"] for c in bench["configs"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for cell in bench["workloads"]:
        assert cell["chips"] == 1
        reported = {m["name"] for m in harness.metrics_for(bench, cell["name"],
                                                           False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_for(bench, cell["name"], True)
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert set(m["workloads"]) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells


def test_harness_finds_new_files_by_name(tmp_path, monkeypatch):
    """A later PR adds a configuration, a traffic mix and a metric as new
    files plus entries; nothing that exists is edited."""
    here = tmp_path / "chip"
    shutil.copytree(harness.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (here / "configs" / "newcfg.json").write_text(json.dumps(
        dict(harness.load_json("configs", "isabel"), shape=[8, 8, 8])))
    (here / "traffic" / "newmix.json").write_text(json.dumps(
        dict(harness.load_json("traffic", "compress"), per_step=2)))
    (here / "metrics" / "new_ms.compress.py").write_text(
        "def read(r):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "newcfg.newmix", "config": "newcfg",
                               "traffic": "newmix", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "new_ms.compress", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "engine host",
        "moves": "compress_MBps", "workloads": ["newcfg.newmix"]})
    bench["end_to_end"][0]["workloads"].append("newcfg.newmix")
    monkeypatch.setattr(harness, "HERE", here)
    cell = harness.cell_of(bench, "newcfg.newmix")
    assert harness.load_json("configs", cell["config"])["shape"] == [8, 8, 8]
    assert harness.load_json("traffic", cell["traffic"])["per_step"] == 2
    names = [m["name"] for m in harness.metrics_for(bench, cell["name"], True)]
    assert names == ["new_ms.compress"]
    assert harness.load_module("metrics", names[0]).read(None) == 42.0
    assert [m["name"] for m in harness.metrics_for(
        bench, cell["name"], False)] == ["compress_MBps", "setup_s"]


def test_window_writes_no_compile_to_the_persistent_cache():
    """Set-up caches every program; the window caches none, so each run
    pays the same compiles whatever ran in the checkout before."""
    import jax

    jax.config.update(harness.MIN_COMPILE_TIME, 0.0)
    try:
        with harness._window():
            assert getattr(jax.config, harness.MIN_COMPILE_TIME) == float(
                "inf")
        assert getattr(jax.config, harness.MIN_COMPILE_TIME) == 0.0
    finally:
        jax.config.update(harness.MIN_COMPILE_TIME, 1.0)


def test_unknown_device_kind_is_an_error():
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks_for("cpu")


def _command(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_to_run_off_a_tpu():
    out = _command(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "TPU" in out.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
