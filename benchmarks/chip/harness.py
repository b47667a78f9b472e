"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything particular to a cell lives in files found by name:

    configs/<config>.json   the deployment (shape, dtype, bound, guarantees)
    traffic/<traffic>.json  the traffic parameters; names a driver and an op
    drivers/<driver>.py     closed-loop steps or open-loop arrivals
    ops/<op>.py             set-up, one request, the check against the
                            plain reference, and the control
    metrics/<metric>.py     one reader per metric, end-to-end or per-layer

An op module provides ``setup(env) -> state``, ``issue(state, i) ->
(future, in_bytes)``, ``finish(state, request, result) -> out_bytes``,
``check(state) -> [(name, value, limit)]``, ``control(state) -> [(name,
value, limit)]`` and, where a metric reads the program's counters,
``counters(state) -> dict`` of running totals.  A driver provides
``run(op, state, env, seconds, profile) -> Window``.  A metric
reader provides ``read(reading) -> float | None``; ``None`` leaves the
metric out of the line.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


MIN_COMPILE_TIME = "jax_persistent_cache_min_compile_time_secs"


def boot(chips: int = 1) -> dict:
    """Start a process that measures on the chip: JAX's compilation
    cache at a fixed path in the checkout (the path is part of the
    cache's key), the program importable, and at least ``chips`` TPU
    chips of a kind with published peaks -> those peaks.  Anything less
    raises ``SystemExit`` with the reason, before a result is printed."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import jax

        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no accelerator: {e}") from None
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    try:
        peaks = peaks_for(devices[0].device_kind)
        from repro.compile_cache import enable_compile_cache
    except (KeyError, ImportError) as e:
        raise SystemExit(str(e)) from None
    # cache every program of set-up, however quickly it compiles: later
    # runs of a cell then compile nothing there (see ``_window``)
    jax.config.update(MIN_COMPILE_TIME, 0.0)
    enable_compile_cache()
    return peaks


# --------------------------------------------------------------- lookup

def load_bench(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_json(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    key = f"_chipbench_{kind}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def peaks_for(kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` ``kind``; a kind
    that ``peaks.json`` does not list is an error, never a default."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       "peaks.json")
    return peaks[kind]


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced.  A metric without ``workloads`` applies to
    every cell (end-to-end) or to every cell that reports the metric it
    moves (per-layer)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m["workloads"] or ("workloads" not in m
                                          and m["moves"] in reported)]


# ---------------------------------------------------------- run records

@dataclass
class Request:
    """One request of the window, on the ``time.perf_counter`` clock."""

    index: int
    t_due: float
    t_sent: float = 0.0
    t_done: float | None = None
    error: str | None = None
    in_bytes: int = 0
    out_bytes: int = 0

    @property
    def ok(self) -> bool:
        return self.t_done is not None and self.error is None

    def __post_init__(self):
        self.event = threading.Event()


def send(op, state, req: Request) -> None:
    """Issue ``req`` through the op; its completion is recorded on the
    thread that completes it.  A refusal at submit completes it at once."""
    req.t_sent = time.perf_counter()
    try:
        fut, req.in_bytes = op.issue(state, req.index)
    except Exception as e:  # noqa: BLE001 - a refused request is a failure
        req.error = f"{type(e).__name__}: {e}"
        req.t_done = time.perf_counter()
        req.event.set()
        return
    fut.add_done_callback(lambda f: _completed(op, state, req, f))


def _completed(op, state, req: Request, fut) -> None:
    t = time.perf_counter()
    try:
        req.out_bytes = op.finish(state, req, fut.result())
    except Exception as e:  # noqa: BLE001 - recorded, judged by the check
        req.error = f"{type(e).__name__}: {e}"
    req.t_done = t
    req.event.set()


@dataclass
class Window:
    requests: list[Request]
    t_open: float
    t_close: float
    notes: dict = field(default_factory=dict)   # printed before the result


class Sample:
    """A uniform sample of ``k`` completed requests with their answers,
    drawn from the seed (reservoir sampling)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng = k, rng
        self.items: dict[int, object] = {}
        self._seen = 0
        self._lock = threading.Lock()

    def offer(self, index: int, answer) -> None:
        with self._lock:
            self._seen += 1
            if len(self.items) < self.k:
                self.items[index] = answer
                return
            j = int(self.rng.integers(self._seen))
            if j < self.k:
                del self.items[sorted(self.items)[j]]
                self.items[index] = answer


@dataclass
class Env:
    """What an op's set-up gets."""

    cfg: dict
    traffic: dict
    seed: int
    svc: object
    workdir: Path
    resources: contextlib.ExitStack   # closed after the check

    def rng(self, purpose: str) -> np.random.Generator:
        """A generator of its own for each purpose, from the seed."""
        return np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, zlib.crc32(
                purpose.encode())])


@dataclass
class Reading:
    """What a metric reader reads."""

    window: Window
    setup_s: float
    counters: dict
    spans: list = field(default_factory=list)
    trace: dict | None = None
    traced_bytes: int = 0
    peaks: dict | None = None

    @property
    def window_s(self) -> float:
        return self.window.t_close - self.window.t_open

    def spans_named(self, name: str, **tags) -> list:
        return [s for s in self.spans if s.name == name
                and all(s.tags.get(k) == v for k, v in tags.items())]

    def descendants(self, span, names: tuple[str, ...]) -> list:
        """Spans called one of ``names`` anywhere under ``span``."""
        if not hasattr(self, "_kids"):
            self._kids: dict = {}
            for s in self.spans:
                self._kids.setdefault(s.parent_id, []).append(s)
        out, stack = [], [span]
        while stack:
            for kid in self._kids.get(stack.pop().span_id, []):
                if kid.name in names:
                    out.append(kid)
                stack.append(kid)
        return out

    def completed(self) -> list[Request]:
        """Requests completed without error by the window's close."""
        return [r for r in self.window.requests
                if r.ok and r.t_done <= self.window.t_close]


class CompileClock:
    """Counts the backend compiles JAX reports while it is open."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        self._jax = jax

        def listen(event, duration, **_):
            if "backend_compile" in event:
                self.count += 1
                self.seconds += duration

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def lap(self) -> tuple[int, float]:
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._listen)


@contextlib.contextmanager
def _window():
    """Nothing compiled inside the window is written to the persistent
    cache.  The program compiles eager slices of data-dependent length
    for every new field it compresses; cached, a later run of the same
    seed would skip them and read faster than the first.  So every run
    pays the same compiles, whatever ran in the checkout before."""
    import jax

    before = getattr(jax.config, MIN_COMPILE_TIME)
    jax.config.update(MIN_COMPILE_TIME, float("inf"))
    try:
        yield
    finally:
        jax.config.update(MIN_COMPILE_TIME, before)


class Profile:
    """The profiled part of a traced run's window (no-op untraced)."""

    ANNOTATION = "bench.traced_window"

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") if on else None
        self.span: tuple[float, float] | None = None
        self.anchor_ns = 0      # time.time_ns() at the annotation's start
        self._ann = None

    def start(self) -> None:
        if not self.on:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(self.ANNOTATION)
        self.anchor_ns = time.time_ns()
        self._t0 = time.perf_counter()
        self._ann.__enter__()

    def stop(self) -> None:
        if not self.on or self._ann is None:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self.span = (self._t0, time.perf_counter())
        self._ann = None
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self._ann is not None


# ------------------------------------------------------------- a run

def _device_info(jax, n: int) -> dict:
    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def _reduce_trace(profile: Profile, spans: list) -> dict | None:
    from . import trace_reduce

    evs = list(trace_reduce.events(trace_reduce.load(profile.dir)))
    lo, hi = trace_reduce.annotation(evs, Profile.ANNOTATION)
    offset = lo - profile.anchor_ns
    host = [(s.name, s.ts_us * 1000 + offset,
             s.ts_us * 1000 + offset + s.dur_us * 1000) for s in spans]
    return trace_reduce.summarize(evs, (lo, hi), host)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, cfg: dict | None = None,
             peaks: dict | None = None, t_start: float | None = None,
             control: bool = False) -> dict:
    """Run ``workload`` once -> the result line as a dict.

    ``cfg`` replaces the cell's configuration file (the CPU rehearsal
    runs the cells at a tiny shape); ``control`` adds the control's
    numbers under ``control`` (readings only, never in a benchmark run).
    """
    import jax

    from repro import obs
    from repro.service import CompressionService, ServiceConfig

    t_start = time.time() if t_start is None else t_start
    bench = bench or load_bench()
    cell = cell_of(bench, workload)
    cfg = cfg or load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    op = load_module("ops", traffic["op"])
    driver = load_module("drivers", traffic["driver"])
    clock = CompileClock()
    profile = Profile(trace)
    counters = getattr(op, "counters", lambda state: {})
    wall0, perf0 = time.time(), time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="chipbench-") as work, \
                contextlib.ExitStack() as resources:
            with CompressionService(ServiceConfig()) as svc:
                env = Env(cfg, traffic, int(seed), svc, Path(work), resources)
                state = op.setup(env)
                n_setup, s_setup = clock.lap()
                counters0 = counters(state)
                if trace:
                    obs.enable(max_spans=1 << 20)
                    obs.tracer().drain()
                with _window():
                    window = driver.run(op, state, env, float(seconds),
                                        profile)
                spans = obs.tracer().drain() if trace else []
                obs.disable()
                n_win, s_win = clock.lap()
                deltas = {k: v - counters0.get(k, 0)
                          for k, v in counters(state).items()}
                device = _device_info(jax, cell["chips"])
            setup_s = (wall0 - t_start) + (window.t_open - perf0)
            summary = _reduce_trace(profile, spans) if trace else None
            reading = Reading(window, setup_s, deltas, spans, summary,
                              peaks=peaks)
            if profile.span is not None:
                lo, hi = profile.span
                reading.traced_bytes = sum(
                    r.in_bytes + r.out_bytes for r in window.requests
                    if r.ok and lo <= r.t_done <= hi)
            metrics = {}
            for m in metrics_for(bench, workload, trace):
                value = load_module("metrics", m["name"]).read(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            # the reference runs once the window has closed, the device
            # peak has been read and the service has stopped
            checks = _checks(window, op.check, state)
            control_checks = (_checks(window, op.control, state)
                              if control else None)
    finally:
        clock.close()
        obs.disable()
        if profile.dir:
            shutil.rmtree(profile.dir, ignore_errors=True)

    log(f"set-up: {setup_s:.3f} s, {n_setup} compiles in {s_setup:.3f} s")
    log(f"window: {window.t_close - window.t_open:.3f} s, "
        f"{len(window.requests)} requests, {n_win} compiles in "
        f"{s_win:.3f} s inside it")
    for k, v in window.notes.items():
        log(f"{k}: {v}")
    attempted = len(window.requests)
    failed = sum(1 for r in window.requests if not r.ok)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if control_checks is not None:
        result["control"] = control_checks
    result["checks"] = checks
    return result


def _checks(window: Window, check, state) -> dict:
    """The compared numbers, each with its limit; a request that never
    completed, or failed with anything but the service's refusal under
    load, is ``lost``.  A check that raises reads beyond its limit."""
    lost = sum(1 for r in window.requests
               if r.t_done is None or (r.error is not None
                                       and "ServiceOverloaded" not in r.error))
    out = {"lost": {"value": lost, "limit": 0}}
    try:
        for name, value, limit in check(state):
            out[name] = {"value": value, "limit": limit}
    except Exception as e:  # noqa: BLE001 - a broken answer is a failed check
        import traceback

        log("check raised:\n" + traceback.format_exc())
        out["check_raised"] = {"value": 1, "limit": 0,
                               "error": f"{type(e).__name__}: {e}"}
    return out
