"""Stage-level tracing: where the device waits, and why it costs.

1. Leaf host spans: a traced service compress and decode emit
   ``engine.admit``/``engine.tile``/``exec.pack``/``engine.serialize``
   and ``engine.parse``/``engine.assemble`` once per group (or chunk),
   under the service group that ran them, never once per tile.
2. Halo rounds: the ``halo_rounds`` tag of a compress group is the
   summed ``1 + max(last_round)`` of its device chunks, consistent with
   the ``last_round`` that ``CompressStats.n_sweeps`` is built from.
3. Spans on the profiler's timeline: a ``jax.profiler`` capture holds
   host events named after the spans, inside the capture.
4. Compile tags: a fresh shape puts ``compiles``/``compile_ms`` on the
   innermost span open on the compiling thread.
5. Off means off: no span, no compile listener, no annotation.
6. The jit-trace counter lives in the registry (``lopc_traces_total``).
"""
from __future__ import annotations

import argparse
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro.engine import device
from repro.engine import executor as engine_executor
from repro.engine.plan import CompressionPlan
from repro.obs import trace as obs_trace
from repro.service import CompressionService, ServiceConfig

from conftest import make_field

PLAN = CompressionPlan(tile_shape=(8, 8, 8), batch_tiles=4)
CFG = ServiceConfig(plan=PLAN, max_delay_ms=20.0)

COMPRESS_LEAVES = {"engine.admit": "service.group",
                   "engine.tile": "engine.compress_group",
                   "exec.pack": "engine.compress_group",
                   "engine.serialize": "engine.compress_group"}
DECODE_LEAVES = {"engine.parse": "service.group",
                 "engine.assemble": "service.group"}


@pytest.fixture
def traced():
    obs.tracer().drain()
    obs.enable()
    try:
        yield obs.tracer()
    finally:
        obs.disable()


def _ancestors(span, by_id) -> list[str]:
    out = []
    while span.parent_id in by_id:
        span = by_id[span.parent_id]
        out.append(span.name)
    return out


def test_service_leaf_spans_once_per_group_under_the_group(rng, traced):
    x = make_field(rng, (20, 18, 30)).astype(np.float32)
    with CompressionService(CFG) as svc:
        blob = svc.submit_compress(x, 1e-2).result(timeout=300)
        y = svc.submit_decompress(blob).result(timeout=300)
    assert y.shape == x.shape
    spans = traced.drain()
    by_id = {s.span_id: s for s in spans}
    (group,) = [s for s in spans if s.name == "engine.compress_group"]
    assert group.tags["n_tiles"] > 1   # so one span per tile would show
    for name, parent in COMPRESS_LEAVES.items():
        (leaf,) = [s for s in spans if s.name == name]
        assert by_id[leaf.parent_id].name == parent, name
        assert "service.group" in _ancestors(leaf, by_id), name
        assert leaf.trace_id == group.trace_id
    decode_trace = next(s for s in spans
                        if s.name == "engine.decode_group").trace_id
    parses = [s for s in spans if s.name == "engine.parse"]
    # one for the containers, one for the decode group's work list
    assert len(parses) == 2
    (assemble,) = [s for s in spans if s.name == "engine.assemble"]
    for leaf in parses + [assemble]:
        assert by_id[leaf.parent_id].name == DECODE_LEAVES[leaf.name]
        assert leaf.trace_id == decode_trace
    (decode_group,) = [s for s in spans if s.name == "engine.decode_group"]
    assert decode_group.tags["n_tiles"] > 1
    doc = obs.write_trace(os.devnull, spans)
    assert obs.validate_trace(doc, {"nest_under": {"exec.": "engine."}}) \
        == []


def test_halo_rounds_sum_one_plus_last_round_per_chunk(rng, traced,
                                                       monkeypatch):
    """Two 100-tile requests in one group exceed the 128-tile packing
    cap of an 8-tile floor, so they run as two device chunks."""
    fields = [make_field(rng, (40, 40, 32)).astype(np.float32)
              for _ in range(2)]
    seen = []
    run = engine_executor.Executor._compress_tiles

    def keep(self, *a, **kw):
        seen.append(run(self, *a, **kw))
        return seen[-1]

    monkeypatch.setattr(engine_executor.Executor, "_compress_tiles", keep)
    blobs, stats = engine.compress_many(fields, 1e-2, plan=PLAN,
                                        return_stats=True)
    (gs,) = seen
    assert gs.chunk_tiles == (100, 100)
    (group,) = [s for s in traced.drain()
                if s.name == "engine.compress_group"]
    per_chunk = [gs.last_round[:100], gs.last_round[100:]]
    assert group.tags["halo_rounds"] == sum(1 + int(r.max())
                                            for r in per_chunk)
    assert group.tags["halo_rounds"] > 2   # the halo exchange did work
    assert group.tags["local_sweeps"] == int(gs.local_sweeps.max())
    for st, rounds, local in zip(stats, per_chunk,
                                 [gs.local_sweeps[:100],
                                  gs.local_sweeps[100:]]):
        assert st.n_sweeps == int(local.max()) + max(0, int(rounds.max())
                                                     - 1)
    assert blobs == engine.compress_many(fields, 1e-2, plan=PLAN)


def _host_events(profile_dir) -> list[tuple[str, int, int]]:
    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if not plane.name.startswith("/device")
            for line in plane.lines for e in line.events]


def test_profiler_capture_holds_span_events(rng, traced, tmp_path):
    x = make_field(rng, (16, 20, 24)).astype(np.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test.capture"):
            detached = obs.start_span("test.detached")
            (blob,) = engine.compress_many([x], 1e-2, plan=PLAN)
            (y,) = engine.decompress_many([blob], plan=PLAN)
            obs.finish_span(detached)
    finally:
        jax.profiler.stop_trace()
    assert y.shape == x.shape
    events = _host_events(tmp_path)
    (outer,) = [e for e in events if e[0] == "test.capture"]
    for name in ("engine.serialize", "engine.parse", "engine.assemble",
                 "exec.solve"):
        inside = [e for e in events if e[0] == name
                  and outer[1] <= e[1] <= e[2] <= outer[2]]
        assert inside, name
    # detached spans are recorded but stay off the profiler's timeline
    assert "test.detached" in {sp.name for sp in traced.snapshot()}
    assert "test.detached" not in {e[0] for e in events}


def test_fresh_shape_tags_the_span_that_compiled(traced):
    n = 1237   # a length nothing else in the suite compiles for
    step = jax.jit(lambda v: v * 3 + 1)
    with obs.span("test.outer"):
        with obs.span("test.inner"):
            step(jnp.arange(n, dtype=jnp.float32)).block_until_ready()
        with obs.span("test.warm"):
            step(jnp.arange(n, dtype=jnp.float32)).block_until_ready()
    spans = {s.name: s for s in traced.drain()}
    assert spans["test.inner"].tags["compiles"] >= 1
    assert spans["test.inner"].tags["compile_ms"] > 0
    assert "compiles" not in spans["test.warm"].tags
    assert "compiles" not in spans["test.outer"].tags


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; counts entries."""

    entered: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Annotations.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_tracing_off_records_nothing_registers_nothing(rng, monkeypatch):
    from jax._src import monitoring

    assert not obs.enabled()
    registered = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    monkeypatch.setattr(obs_trace, "_TRACE_ANNOTATION", _Annotations)
    _Annotations.entered = []
    x = make_field(rng, (12, 10, 20)).astype(np.float32)
    with CompressionService(CFG) as svc:
        blob = svc.submit_compress(x, 1e-2).result(timeout=300)
        svc.submit_decompress(blob).result(timeout=300)
    assert obs.tracer().snapshot() == []
    assert registered == [] and not obs_trace._COMPILE_LISTENER
    assert obs_trace._on_compile not in \
        monitoring.get_event_duration_listeners()
    assert _Annotations.entered == []
    # the control: tracing on registers the listener once and annotates
    monkeypatch.undo()
    monkeypatch.setattr(obs_trace, "_TRACE_ANNOTATION", _Annotations)
    obs.enable()
    try:
        obs.enable()
        listeners = monitoring.get_event_duration_listeners()
        assert listeners.count(obs_trace._on_compile) == 1
        with obs.span("test.on"):
            pass
        assert _Annotations.entered == ["test.on"]
    finally:
        obs.disable()
        obs.tracer().drain()
    assert obs_trace._on_compile not in \
        monitoring.get_event_duration_listeners()


def test_trace_counts_live_in_the_registry(rng, tmp_path):
    from repro.launch import serve

    assert isinstance(device.TRACE_COUNTS, obs.CounterView)
    x = make_field(rng, (10, 12, 14)).astype(np.float32)
    engine.compress(x, 1e-2, plan=CompressionPlan(tile_shape=(4, 4, 8),
                                                  batch_tiles=4))
    counts = dict(device.TRACE_COUNTS)
    assert counts and device.trace_count() == sum(counts.values())
    dump = tmp_path / "metrics.txt"
    serve._obs_report(argparse.Namespace(trace_out=None,
                                         metrics_dump=str(dump)),
                      "compress-service")
    text = dump.read_text()
    assert "# TYPE lopc_traces_total counter" in text
    for program, n in counts.items():
        assert f'lopc_traces_total{{program="{program}"}} {n}' in text
