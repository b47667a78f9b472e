"""The trace reduction: interval arithmetic checked by hand, and a trace
recorded on the CPU in the test, whose annotated busy and idle times
are known from the sleeps that made them."""
from __future__ import annotations

import time

import pytest

from benchmarks.chip import trace_reduce as tr


def test_busy_gaps_and_top_ops_by_hand():
    ops = [("a", 10, 20), ("b", 15, 30), ("a", 40, 50), ("c", 55, 60),
           ("a", 95, 120)]
    spans = [("engine", 0, 100), ("serialize", 30, 42), ("stream", 50, 58)]
    lo, hi = 0, 100
    assert tr.merged([(a, b) for _, a, b in ops], lo, hi) == [
        (10, 30), (40, 50), (55, 60), (95, 100)]
    assert tr.busy([(a, b) for _, a, b in ops], lo, hi) == 20 + 10 + 5 + 5
    idle = tr.gaps([(a, b) for _, a, b in ops], lo, hi)
    assert idle == [(0, 10), (30, 40), (50, 55), (60, 95)]
    assert tr.top_ops(ops, lo, hi) == [["a", 25e-9], ["b", 15e-9],
                                       ["c", 5e-9]]
    # midpoints 5, 35, 52.5, 77.5: engine, serialize, stream, engine
    assert tr.label_gaps(idle, spans) == [["engine", 45e-9],
                                          ["serialize", 10e-9],
                                          ["stream", 5e-9]]
    assert tr.label_gaps([(200, 210)], spans) == [["no span", 10e-9]]


def test_summary_of_device_planes():
    evs = [("/device:TPU:0", "XLA Ops",
            "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)", 0, 40),
           ("/device:TPU:0", "XLA Ops", "%fusion.2", 60, 80),
           ("/device:TPU:0", "XLA Modules", "jit_m(1234)", 0, 45),
           ("/host:CPU", "python", "bench.traced_window", 0, 100)]
    s = tr.summarize(evs, tr.annotation(evs, "bench.traced_window"),
                     [("exec.stream_prep", 40, 60)])
    assert s["busy_s"] == 60e-9 and s["window_s"] == 100e-9
    assert s["device_ops"] == [["jit_m/%fusion.1", 40e-9],
                               ["%fusion.2", 20e-9]]
    assert s["idle_gaps"] == [["exec.stream_prep", 20e-9],
                              ["no span", 20e-9]]
    assert tr.summarize(evs[3:], (0, 100)) is None


def test_reduction_of_a_recorded_cpu_trace(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        with jax.profiler.TraceAnnotation("bench.op_a"):
            time.sleep(0.05)
        time.sleep(0.10)
        with jax.profiler.TraceAnnotation("bench.op_b"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    evs = list(tr.events(tr.load(str(tmp_path))))
    lo, hi = tr.annotation(evs, "bench.traced_window")
    ops = [(a, b) for _, _, name, a, b in evs
           if name.startswith("bench.op_")]
    assert len(ops) == 2
    busy = tr.busy(ops, lo, hi) / 1e9
    idle = sum(b - a for a, b in tr.gaps(ops, lo, hi)) / 1e9
    assert busy == pytest.approx(0.10, abs=0.02)
    assert idle == pytest.approx(0.10, abs=0.03)
    assert busy + idle == pytest.approx((hi - lo) / 1e9)
    # the CPU has no device plane: nothing to reduce
    assert tr.summarize(evs, (lo, hi)) is None
