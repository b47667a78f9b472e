"""The CI quality gates, tested as code:

``benchmarks/check_regression.py`` must fail on an injected compression
-ratio drop or transfer-count increase and pass on clean/noisy-but-
in-tolerance output; ``benchmarks/check_determinism.py``'s manifest
comparison must catch hash drift.  The gates guard the repo, so the
gates themselves get unit tests — a gate that silently passes
everything is worse than no gate.
"""
from __future__ import annotations

import copy
import json

import pytest

from benchmarks.check_determinism import compare
from benchmarks.check_regression import (
    CLUSTER_HIT_RATE_TOL,
    QUALITY_PSNR_TOL,
    RATIO_TOL,
    SERVICE_LAT_HEADROOM,
    check,
    check_cluster,
    check_quality,
    check_service,
    extract_baseline,
    extract_cluster_baseline,
    extract_quality_baseline,
    extract_service_baseline,
    main,
)


def _bench():
    return {
        "eb": 0.01,
        "mode": "noa",
        "tile_shape": [16, 16, 64],
        "fields": {
            "miranda": {
                "engine": {"ratio": 11.125, "compress_mbps": 5.0},
                "transfers_per_compress": {
                    "h2d_tiles": 1.0, "h2d_aux": 2.0,
                    "d2h_aux": 1.0, "d2h_sections": 1.0,
                },
            },
            "isabel": {
                "engine": {"ratio": 5.039, "compress_mbps": 20.0},
                "transfers_per_compress": {
                    "h2d_tiles": 1.0, "h2d_aux": 2.0,
                    "d2h_aux": 1.0, "d2h_sections": 1.0,
                },
            },
        },
        "encode_paths": {
            "auto_min_elems": 1024 * 1024,
            "fields": {
                "miranda": {
                    "payload_bytes": 424135,
                    "fused": {"bytes_d2h_per_compress": 464084.0},
                },
                "isabel": {
                    "payload_bytes": 351141,
                    "fused": {"bytes_d2h_per_compress": 366548.0},
                },
            },
        },
    }


def test_clean_bench_passes():
    bench = _bench()
    assert check(extract_baseline(bench), bench) == []


def test_ratio_within_tolerance_passes():
    bench = _bench()
    baseline = extract_baseline(bench)
    bench["fields"]["miranda"]["engine"]["ratio"] *= 1 - RATIO_TOL / 2
    assert check(baseline, bench) == []


def test_injected_ratio_regression_fails():
    bench = _bench()
    baseline = extract_baseline(bench)
    bench["fields"]["miranda"]["engine"]["ratio"] *= 0.97  # 3% drop
    problems = check(baseline, bench)
    assert len(problems) == 1 and "miranda" in problems[0]
    assert "ratio" in problems[0]


def test_ratio_improvement_passes():
    bench = _bench()
    baseline = extract_baseline(bench)
    bench["fields"]["miranda"]["engine"]["ratio"] *= 1.5
    assert check(baseline, bench) == []


def test_transfer_count_increase_fails():
    bench = _bench()
    baseline = extract_baseline(bench)
    bench["fields"]["isabel"]["transfers_per_compress"]["h2d_tiles"] = 2.0
    problems = check(baseline, bench)
    assert len(problems) == 1 and "h2d_tiles" in problems[0]


def test_missing_field_fails():
    bench = _bench()
    baseline = extract_baseline(bench)
    del bench["fields"]["isabel"]
    assert any("missing" in p for p in check(baseline, bench))


def test_encode_d2h_growth_fails():
    # the compaction leak failure mode: fused downloads grow past the
    # committed per-field bytes
    bench = _bench()
    baseline = extract_baseline(bench)
    row = bench["encode_paths"]["fields"]["isabel"]
    row["fused"]["bytes_d2h_per_compress"] *= 1.03  # still under ceiling
    problems = check(baseline, bench)
    assert len(problems) == 1 and "isabel" in problems[0]
    assert "compaction" in problems[0]


def test_encode_d2h_payload_ceiling_fails():
    # committed bytes unchanged but the container shrank: the download
    # must still stay under the 1.1x-payload ceiling of the SAME run
    bench = _bench()
    baseline = extract_baseline(bench)
    row = bench["encode_paths"]["fields"]["miranda"]
    row["payload_bytes"] = int(
        row["fused"]["bytes_d2h_per_compress"] / 1.2)
    problems = check(baseline, bench)
    assert len(problems) == 1 and "miranda" in problems[0]
    assert "1.1x" in problems[0]


def test_encode_field_missing_from_bench_fails():
    bench = _bench()
    baseline = extract_baseline(bench)
    del bench["encode_paths"]["fields"]["isabel"]
    assert any("encode_paths" in p and "missing" in p
               for p in check(baseline, bench))


def test_config_drift_fails():
    bench = _bench()
    baseline = extract_baseline(bench)
    drifted = copy.deepcopy(bench)
    drifted["eb"] = 1e-4
    assert any("config drifted" in p for p in check(baseline, drifted))


def test_gate_cli_end_to_end(tmp_path):
    bench_p = tmp_path / "bench.json"
    base_p = tmp_path / "baseline.json"
    bench = _bench()
    bench_p.write_text(json.dumps(bench))
    # bootstrap the baseline from a clean run, then gate against it
    assert main(["--bench", str(bench_p), "--baseline", str(base_p),
                 "--update-baseline"]) == 0
    assert main(["--bench", str(bench_p), "--baseline", str(base_p)]) == 0
    bench["fields"]["miranda"]["engine"]["ratio"] *= 0.9
    bench_p.write_text(json.dumps(bench))
    assert main(["--bench", str(bench_p), "--baseline", str(base_p)]) == 1


def _service_bench():
    def point(clients, p50, p99, mbps):
        return {"clients": clients, "p50_ms": p50, "p99_ms": p99,
                "wall_mbps": mbps, "traces_added": 0}

    return {
        "eb": 0.01,
        "plan": {"tile_shape": [16, 16, 64], "batch_tiles": 8},
        "max_delay_ms": 5.0,
        "requests_per_client": 4,
        "load_points": [point(1, 30, 60, 1.2), point(4, 140, 210, 3.5),
                        point(8, 220, 400, 2.8), point(16, 490, 800, 2.8)],
    }


def test_service_clean_bench_passes():
    bench = _service_bench()
    assert check_service(extract_service_baseline(bench), bench) == []


def test_service_steady_state_retrace_fails():
    bench = _service_bench()
    baseline = extract_service_baseline(bench)
    bench["load_points"][3]["traces_added"] = 1
    problems = check_service(baseline, bench)
    assert len(problems) == 1 and "steady state" in problems[0]


def test_service_p99_collapse_fails():
    # the PR-5 failure mode: p99 blows past the committed multiple of
    # the reference pool's p99 under top load
    bench = _service_bench()
    baseline = extract_service_baseline(bench)
    bench["load_points"][3]["p99_ms"] = 19_000.0
    problems = check_service(baseline, bench)
    assert any("ceiling" in p for p in problems)


def test_service_p99_spread_headroom():
    bench = _service_bench()
    baseline = extract_service_baseline(bench)
    # within headroom: spread grows but stays under committed x headroom
    bench["load_points"][0]["p99_ms"] *= SERVICE_LAT_HEADROOM * 0.9
    assert check_service(baseline, bench) == []
    bench["load_points"][0]["p99_ms"] *= 1.3  # now beyond
    assert any("spread" in p for p in check_service(baseline, bench))


def test_service_throughput_floor_fails():
    bench = _service_bench()
    baseline = extract_service_baseline(bench)
    bench["load_points"][3]["wall_mbps"] = 0.4  # < 0.5 x single client
    assert any("throughput" in p for p in check_service(baseline, bench))


def test_service_missing_point_and_config_drift_fail():
    bench = _service_bench()
    baseline = extract_service_baseline(bench)
    drifted = copy.deepcopy(bench)
    drifted["max_delay_ms"] = 50.0
    assert any("config drifted" in p for p in check_service(baseline, drifted))
    short = copy.deepcopy(bench)
    short["load_points"] = short["load_points"][:2]
    assert any("missing" in p for p in check_service(baseline, short))


def test_service_gate_cli_end_to_end(tmp_path):
    bench_p = tmp_path / "bench.json"
    base_p = tmp_path / "baseline.json"
    bench = _service_bench()
    bench_p.write_text(json.dumps(bench))
    assert main(["--service", "--bench", str(bench_p),
                 "--baseline", str(base_p), "--update-baseline"]) == 0
    assert main(["--service", "--bench", str(bench_p),
                 "--baseline", str(base_p)]) == 0
    bench["load_points"][3]["traces_added"] = 3
    bench_p.write_text(json.dumps(bench))
    assert main(["--service", "--bench", str(bench_p),
                 "--baseline", str(base_p)]) == 1


def _cluster_bench():
    def point(replicas, mbps, hit):
        return {"n_replicas": replicas, "sweep_ms": 50.0, "read_mbps": mbps,
                "byte_identical": True, "cache_hit_rate": hit,
                "tiles_read": 384, "workers_reporting": replicas}

    return {
        "eb": 0.01,
        "tile_shape": [16, 16, 64],
        "cache_bytes": 2 << 20,
        "tiles_per_range": 4,
        "n_arrays": 6,
        "array_shape": [64, 64, 48],
        "shard_counts": {"1": point(1, 50.0, 0.0), "2": point(2, 60.0, 0.25),
                         "4": point(2, 130.0, 0.75),
                         "8": point(2, 100.0, 0.75)},
        "scaling": {"gated_counts": [1, 2, 4],
                    "mbps": {"1": 50.0, "2": 60.0, "4": 130.0, "8": 100.0},
                    "scale_4x": 2.6},
        "failover": {"shards": 4, "killed_shard": 2, "healthy_read_ms": 8.0,
                     "failover_read_ms": 9.0, "byte_identical": True,
                     "failover_reads": 15, "workers_reporting": 3},
    }


def test_cluster_clean_bench_passes():
    bench = _cluster_bench()
    assert check_cluster(extract_cluster_baseline(bench), bench) == []


def test_cluster_byte_identity_break_fails():
    bench = _cluster_bench()
    baseline = extract_cluster_baseline(bench)
    bench["shard_counts"]["4"]["byte_identical"] = False
    problems = check_cluster(baseline, bench)
    assert len(problems) == 1 and "byte-identical" in problems[0]


def test_cluster_scaling_floor_fails():
    bench = _cluster_bench()
    baseline = extract_cluster_baseline(bench)
    bench["scaling"]["scale_4x"] = 1.1  # below the committed 1.5x floor
    problems = check_cluster(baseline, bench)
    assert len(problems) == 1 and "scaling floor" in problems[0]


def test_cluster_monotonicity_break_fails():
    bench = _cluster_bench()
    baseline = extract_cluster_baseline(bench)
    bench["shard_counts"]["2"]["read_mbps"] = 30.0  # 50 -> 30 at 2 shards
    problems = check_cluster(baseline, bench)
    assert any("monotonically" in p for p in problems)


def test_cluster_hit_rate_regression_fails():
    bench = _cluster_bench()
    baseline = extract_cluster_baseline(bench)
    row = bench["shard_counts"]["4"]
    row["cache_hit_rate"] -= CLUSTER_HIT_RATE_TOL / 2  # within tolerance
    assert check_cluster(baseline, bench) == []
    row["cache_hit_rate"] = 0.3  # 0.75 committed — a real cache break
    problems = check_cluster(baseline, bench)
    assert len(problems) == 1 and "hit rate" in problems[0]


def test_cluster_failover_gates():
    bench = _cluster_bench()
    baseline = extract_cluster_baseline(bench)
    broken = copy.deepcopy(bench)
    broken["failover"]["byte_identical"] = False
    assert any("killed worker corrupts" in p
               for p in check_cluster(baseline, broken))
    never = copy.deepcopy(bench)
    never["failover"]["failover_reads"] = 0
    assert any("never actually failed over" in p
               for p in check_cluster(baseline, never))
    ghost = copy.deepcopy(bench)
    ghost["failover"]["workers_reporting"] = 4  # dead worker still counted
    assert any("dead worker" in p for p in check_cluster(baseline, ghost))


def test_cluster_config_drift_and_missing_point_fail():
    bench = _cluster_bench()
    baseline = extract_cluster_baseline(bench)
    drifted = copy.deepcopy(bench)
    drifted["cache_bytes"] = 64 << 20
    assert any("config drifted" in p for p in check_cluster(baseline, drifted))
    short = copy.deepcopy(bench)
    del short["shard_counts"]["2"]
    assert any("missing" in p for p in check_cluster(baseline, short))


def test_cluster_gate_cli_end_to_end(tmp_path):
    bench_p = tmp_path / "bench.json"
    base_p = tmp_path / "baseline.json"
    bench = _cluster_bench()
    bench_p.write_text(json.dumps(bench))
    assert main(["--cluster", "--bench", str(bench_p),
                 "--baseline", str(base_p), "--update-baseline"]) == 0
    assert main(["--cluster", "--bench", str(bench_p),
                 "--baseline", str(base_p)]) == 0
    bench["shard_counts"]["1"]["byte_identical"] = False
    bench_p.write_text(json.dumps(bench))
    assert main(["--cluster", "--bench", str(bench_p),
                 "--baseline", str(base_p)]) == 1


def _quality_bench():
    def runrec(ratio, psnr_, adaptive=False):
        rec = {"ratio": ratio, "psnr": psnr_, "ssim": 0.99,
               "order_violations": 0, "critical_signature_exact": True,
               "comp_mbps": 10.0, "decomp_mbps": 20.0}
        if adaptive:
            rec["ladder_hist"] = [2, 0, 1, 9]
        return rec

    def field(win):
        per_eb = {}
        for eb, base_ratio in (("0.01", 5.0), ("0.0001", 2.0)):
            per_eb[eb] = {"uniform": runrec(base_ratio, 80.0),
                          "adaptive": runrec(base_ratio * win, 78.0,
                                             adaptive=True),
                          "ratio_win": win}
        return {"per_eb": per_eb,
                "mean_ratio_uniform": 3.5,
                "mean_ratio_adaptive": 3.5 * win,
                "mean_ratio_win": win}

    # five winners and one hard case: the floor (5) is exactly met, so
    # the tests below can break it by flipping a single field
    wins = (("isabel", 1.16), ("tangaroa", 1.15), ("earthquake", 1.22),
            ("ionization", 1.23), ("miranda", 1.13), ("qmcpack", 0.98))
    return {"ebs": ["0.01", "0.0001"], "mode": "noa", "k_max": 3,
            "fields": {name: field(w) for name, w in wins},
            "winning_fields": 5}


def test_quality_clean_bench_passes():
    bench = _quality_bench()
    assert check_quality(extract_quality_baseline(bench), bench) == []


def test_quality_ratio_within_tolerance_passes():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    row = bench["fields"]["isabel"]
    row["mean_ratio_adaptive"] *= 1 - RATIO_TOL / 2
    row["mean_ratio_win"] *= 1 - RATIO_TOL / 2
    assert check_quality(baseline, bench) == []


def test_quality_injected_ratio_regression_fails():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    bench["fields"]["isabel"]["mean_ratio_adaptive"] *= 0.97  # 3% drop
    problems = check_quality(baseline, bench)
    assert len(problems) == 1 and "isabel" in problems[0]
    assert "mean ratio" in problems[0]


def test_quality_order_violation_is_absolute():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    bench["fields"]["miranda"]["per_eb"]["0.01"]["adaptive"][
        "order_violations"] = 3
    problems = check_quality(baseline, bench)
    assert len(problems) == 1 and "order preservation is broken" in problems[0]
    assert "miranda" in problems[0]


def test_quality_signature_break_is_absolute():
    # uniform mode is gated too: the solve guarantees topology at ANY
    # eps, so a uniform-mode break is just as much a codec bug
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    bench["fields"]["tangaroa"]["per_eb"]["0.0001"]["uniform"][
        "critical_signature_exact"] = False
    problems = check_quality(baseline, bench)
    assert len(problems) == 1 and "signature" in problems[0]
    assert "uniform" in problems[0]


def test_quality_psnr_floor():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    run = bench["fields"]["isabel"]["per_eb"]["0.01"]["adaptive"]
    run["psnr"] -= QUALITY_PSNR_TOL * 0.8  # within tolerance
    assert check_quality(baseline, bench) == []
    run["psnr"] -= 2.0  # now a real drop
    problems = check_quality(baseline, bench)
    assert len(problems) == 1 and "PSNR" in problems[0]


def test_quality_lost_winning_field_fails():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    row = bench["fields"]["earthquake"]
    row["mean_ratio_win"] = 0.999
    row["mean_ratio_adaptive"] = 3.5 * 0.999
    problems = check_quality(baseline, bench)
    assert any("winning field was lost" in p for p in problems)
    assert any("below the committed floor" in p for p in problems)


def test_quality_vacuous_baseline_fails():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    for f in baseline["fields"].values():
        f["wins"] = False
    assert any("vacuous" in p for p in check_quality(baseline, bench))


def test_quality_config_drift_and_missing_field_fail():
    bench = _quality_bench()
    baseline = extract_quality_baseline(bench)
    drifted = copy.deepcopy(bench)
    drifted["k_max"] = 2
    assert any("config drifted" in p
               for p in check_quality(baseline, drifted))
    short = copy.deepcopy(bench)
    del short["fields"]["qmcpack"]
    assert any("missing" in p for p in check_quality(baseline, short))


def test_quality_gate_cli_end_to_end(tmp_path):
    bench_p = tmp_path / "bench.json"
    base_p = tmp_path / "baseline.json"
    bench = _quality_bench()
    bench_p.write_text(json.dumps(bench))
    assert main(["--quality", "--bench", str(bench_p),
                 "--baseline", str(base_p), "--update-baseline"]) == 0
    assert main(["--quality", "--bench", str(bench_p),
                 "--baseline", str(base_p)]) == 0
    bench["fields"]["isabel"]["per_eb"]["0.01"]["adaptive"][
        "order_violations"] = 1
    bench_p.write_text(json.dumps(bench))
    assert main(["--quality", "--bench", str(bench_p),
                 "--baseline", str(base_p)]) == 1


@pytest.mark.parametrize("mutate,expect", [
    (lambda h: h, []),
    (lambda h: {**h, "a": "1" * 64},
     ["a: container hash"]),
    (lambda h: {k: v for k, v in h.items() if k != "a"},
     ["a: case missing"]),
])
def test_determinism_manifest_compare(mutate, expect):
    manifest = {"a": "0" * 64, "b": "f" * 64}
    problems = compare(manifest, mutate(dict(manifest)))
    assert len(problems) == len(expect)
    for p, want in zip(sorted(problems), sorted(expect)):
        assert p.startswith(want)


def test_determinism_new_case_flagged():
    manifest = {"a": "0" * 64}
    problems = compare(manifest, {"a": "0" * 64, "new": "1" * 64})
    assert problems == ["new: not in manifest (run --update-manifest)"]
