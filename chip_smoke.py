#!/usr/bin/env python3
"""Bring-up smoke of the LOPC served path on a TPU.

    python chip_smoke.py                 # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips    # the mesh-sharded path on 4 chips

One chip, one process (no child touches JAX):

(a) served snapshot at published shape: a ``CompressionService`` with
    solver, encode and decode path all ``auto`` compresses a
    Hurricane-Isabel-shaped field (SDRBench Isabel, 100x500x500 f32,
    generated from a seed), decompresses it and reads one ROI; then the
    same field as a plain request (preserve_order=False, 16-bit bins:
    the fused quantize-and-encode kernel), whose bytes must equal a
    compress on the host CPU;
(b) chain and store: a few-frame temporal chain, then a ``LopcStore``
    write and ``read_roi``, all through the service;
(c) cross-backend bytes: the determinism cases against the committed
    CPU manifest, float64 cases expecting the typed refusal;
(d) set-up time: cold compile seconds and phase wall times of this one
    run (not a benchmark).

Every decode is checked on the host CPU, never by the chip itself: the
pointwise bound, zero local-order violations, exact critical
signatures, host decode == chip decode bit for bit, ROI == the slice of
the full decode (the plain request preserves no order: there the bound
and host == chip only).  ``--four-chips`` compresses the Isabel field
through ``compress_fields_sharded`` over a 4-chip mesh and compares its
bytes with the one-chip engine's, and nothing else.

The last line of standard output is ``{"ok": true, "device": ...}``; it
is printed only when every phase passed on a TPU.  Off a TPU the script
exits non-zero before any phase (``--rehearse`` runs the phases there
at a given ``--shape``, kernels interpreted, and still exits non-zero).
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

EB = 1e-2                      # NOA bound, the paper's headline setting
ISABEL_SHAPE = (100, 500, 500)
CHAIN_SHAPE = (48, 128, 128)
CHAIN_FRAMES = 4
STORE_SHAPE = (32, 64, 128)    # 16 tiles: one small decode batch
SEED = 2026
SLAB = 8                       # host topology checks run slab by slab

# Determinism cases whose chip bytes may differ from the CPU manifest,
# each with the stage and op found to differ.  Empty: every float32
# case must match, and every float64 case must be refused.
KNOWN_MISMATCHES: dict[str, str] = {}


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums the backend compile seconds JAX reports."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0

        def listen(event, duration, **_):
            if "backend_compile" in event:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


# ----------------------------------------------------------- host checks

def host_topology(x, y, cpu):
    """(order violations, signature mismatches, critical points of x)
    on the host CPU, slab by slab along axis 0 with a one-plane halo
    (every check is local to a vertex's Freudenthal link)."""
    import jax
    import numpy as np

    from repro.tda.critpoints import critical_signature, order_violation_counts

    viol = bad = crit = 0
    n0 = x.shape[0]
    for a in range(0, n0, SLAB):
        b = min(a + SLAB, n0)
        lo, hi = max(a - 1, 0), min(b + 1, n0)
        own = slice(a - lo, b - lo)
        xs = jax.device_put(x[lo:hi], cpu)
        ys = jax.device_put(y[lo:hi], cpu)
        viol += int(np.asarray(order_violation_counts(xs, ys))[own].sum())
        lo_x, up_x = (np.asarray(s)[own] for s in critical_signature(xs))
        lo_y, up_y = (np.asarray(s)[own] for s in critical_signature(ys))
        bad += int(((lo_x != lo_y) | (up_x != up_y)).sum())
        crit += int(((lo_x != 1) | (up_x != 1)).sum())
    return viol, bad, crit


def verify(label, x, y_chip, y_host, cpu, topology=True):
    """The host-side checks of one decoded field (``topology``: local
    order and critical signatures too)."""
    import numpy as np

    check(y_chip.shape == x.shape and y_chip.dtype == x.dtype,
          f"{label}: decode is {y_chip.shape} {y_chip.dtype}, "
          f"want {x.shape} {x.dtype}")
    check(y_host.tobytes() == y_chip.tobytes(),
          f"{label}: host decode differs from chip decode")
    bound = EB * (float(x.max()) - float(x.min()))
    err = float(np.abs(x.astype(np.float64) - y_chip.astype(np.float64)).max())
    check(err <= bound, f"{label}: max error {err:.6e} > bound {bound:.6e}")
    if not topology:
        log(f"  {label}: max err {err:.6e} <= {bound:.6e}, host == chip")
        return
    viol, bad, crit = host_topology(x, y_chip, cpu)
    check(viol == 0, f"{label}: {viol} local-order violations")
    check(bad == 0, f"{label}: {bad} critical signatures differ")
    log(f"  {label}: max err {err:.6e} <= {bound:.6e}, 0 order violations, "
        f"{crit} critical points with exact signatures, host == chip")


# ---------------------------------------------------------------- phases

def phase_snapshot(svc, shape, cpu):
    import jax
    import numpy as np

    from repro import engine
    from repro.data.fields import make_scientific_field

    x = make_scientific_field("turbulence", shape, np.float32, seed=SEED)
    t0 = time.perf_counter()
    blob = svc.submit_compress(x, EB).result()
    t_c = time.perf_counter() - t0
    y = svc.decompress(blob)
    region = tuple(slice(n // 4, n // 4 + max(1, n // 3)) for n in shape)
    roi = svc.decompress_roi(blob, region)
    t_all = time.perf_counter() - t0
    with jax.default_device(cpu):
        y_host = engine.decompress(blob, decode_path="staged")
    verify("snapshot", x, y, y_host, cpu)
    check(roi.tobytes() == np.ascontiguousarray(y[region]).tobytes(),
          "snapshot: ROI differs from the slice of the full decode")
    log(f"  snapshot: {x.nbytes / 1e6:.1f} MB -> {len(blob) / 1e6:.3f} MB "
        f"(ratio {x.nbytes / len(blob):.2f}), ROI {roi.shape} == slice")
    return x, {"compress_s": t_c, "compress_decode_roi_s": t_all}


def phase_plain(svc, x, cpu):
    """The field as a plain (preserve_order=False) request: bins fit 16
    bits, so the chip quantizes inside the fused encode kernel."""
    import jax

    from repro import engine

    blob = svc.submit_compress(x, EB, preserve_order=False).result()
    y = svc.decompress(blob)
    with jax.default_device(cpu):
        want = engine.compress(x, EB, preserve_order=False,
                               solver="jacobi", encode_path="staged")
        y_host = engine.decompress(blob, decode_path="staged")
    check(blob == want, "plain: chip bytes differ from the host CPU's")
    verify("plain", x, y, y_host, cpu, topology=False)
    log(f"  plain: {len(blob)} bytes == the host CPU compress")


def phase_chain_store(svc, cpu, chain_shape, store_shape):
    import jax
    import numpy as np

    from repro import temporal
    from repro.data.fields import make_field_sequence, make_scientific_field
    from repro.store import LopcStore

    frames = make_field_sequence("advect", "turbulence", chain_shape,
                                 CHAIN_FRAMES, np.float32, seed=SEED)
    blob = svc.submit_compress_chain(frames, EB).result()
    dec = svc.decompress_chain(blob)
    with jax.default_device(cpu):
        dec_host = temporal.decompress_chain(blob)
    for t, f in enumerate(frames):
        verify(f"chain frame {t}", f, dec[t], dec_host[t], cpu)

    x = make_scientific_field("gaussians", store_shape, np.float32,
                              seed=SEED)
    full = tuple(slice(0, n) for n in store_shape)
    region = tuple(slice(n // 3, n // 3 + n // 2) for n in store_shape)
    with tempfile.TemporaryDirectory() as root:
        with LopcStore.create(root, plan=svc.config.plan) as store:
            svc.submit_store_write(store, "field", x, EB).result()
            y = svc.store_roi(store, "field", full)
            roi = svc.store_roi(store, "field", region)
        with jax.default_device(cpu), LopcStore.open(root) as fresh:
            y_host = fresh.read("field")
    verify("store", x, y, y_host, cpu)
    check(roi.tobytes() == np.ascontiguousarray(y[region]).tobytes(),
          "store: read_roi differs from the slice of the full read")
    log(f"  store: read_roi {roi.shape} == slice of the full read")


def phase_manifest(allow_cpu: bool):
    """Determinism cases on this backend vs the committed CPU manifest.
    -> (matched, total) over the cases that ran."""
    import hashlib

    from benchmarks.check_determinism import MANIFEST_PATH, cases
    from repro.core.quantize import BackendUnsupported

    manifest = json.loads(MANIFEST_PATH.read_text())
    tally: dict[tuple[str, str], list[int]] = {}
    failures = []
    seen = set()
    for case, dtype, run in cases():
        seen.add(case)
        family = case.split("/")[0] \
            if case.startswith(("chain", "adaptive")) else "snapshot"
        row = tally.setdefault((family, dtype), [0, 0, 0])  # match/total/refused
        row[1] += 1
        try:
            blob, problems = run()
        except BackendUnsupported as e:
            if dtype == "float64":
                row[2] += 1
                continue
            raise
        if dtype == "float64" and not allow_cpu:
            failures.append(f"{case}: float64 ran on the chip instead of "
                            "being refused")
        failures += problems
        got = hashlib.sha256(blob).hexdigest()
        if got == manifest[case]:
            row[0] += 1
        elif case in KNOWN_MISMATCHES:
            log(f"  {case}: differs, known: {KNOWN_MISMATCHES[case]}")
        else:
            failures.append(f"{case}: bytes differ from the CPU manifest")
    check(seen == set(manifest), "determinism cases != manifest cases")
    for (family, dtype), (m, n, refused) in sorted(tally.items()):
        log(f"  manifest {family:<14} {dtype}: matched {m}/{n}"
            + (f", {refused} refused (typed float64 error)" if refused else ""))
    check(not failures, "; ".join(failures))
    matched = sum(r[0] for r in tally.values())
    ran = sum(r[1] - r[2] for r in tally.values())
    log(f"  manifest: matched {matched}/{ran} cases that ran, "
        f"{len(manifest) - ran} float64 cases refused")
    return matched, len(manifest)


def run_one_chip(args, jax, cpu, clock) -> None:
    from repro.engine import device
    from repro.service import CompressionService, ServiceConfig

    walls = {}
    on_tpu = not args.rehearse
    if on_tpu:
        got = device.resolve_solver("auto")
        check(got == ("blockwise", False),
              f"resolve_solver('auto') is {got}, want ('blockwise', False)")
        cfg = ServiceConfig()
    else:
        # the same kernels, interpreted: what auto picks on the chip
        cfg = ServiceConfig(solver="blockwise", encode_path="fused",
                            decode_path="fused")
    before = dict(device.TRACE_COUNTS)
    t0 = time.perf_counter()
    with CompressionService(cfg) as svc:
        log("(a) served snapshot")
        x, snap_walls = phase_snapshot(svc, args.shape, cpu)
        walls.update(snap_walls)
        phase_plain(svc, x, cpu)
        walls["a_s"] = time.perf_counter() - t0
        programs = ("resident_solve", "fused_encode", "fused_encode_values",
                    "fused_decode")
        for program in programs:
            check(device.TRACE_COUNTS[program] > before.get(program, 0),
                  f"program {program} did not run in phase (a)")
        log("  ran: resident_solve (blockwise), " + ", ".join(programs[1:]))
        log("(b) chain and store")
        t1 = time.perf_counter()
        small = args.shape != ISABEL_SHAPE
        phase_chain_store(svc, cpu, args.shape if small else CHAIN_SHAPE,
                          args.shape if small else STORE_SHAPE)
        walls["b_s"] = time.perf_counter() - t1
    log("(c) cross-backend bytes")
    t2 = time.perf_counter()
    phase_manifest(allow_cpu=args.rehearse)
    walls["c_s"] = time.perf_counter() - t2
    log(f"(d) set-up, one cold run (not a benchmark): {clock.count} "
        f"compiles took {clock.seconds:.1f}s; phase walls "
        + ", ".join(f"{k} {v:.1f}s" for k, v in walls.items()))


def run_four_chips(args, jax) -> None:
    import numpy as np

    from repro import engine
    from repro.data.fields import make_scientific_field
    from repro.distributed.compression import compress_fields_sharded

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
          f"{len(jax.devices())}")
    # rehearsals interpret the kernels auto picks on the chip
    kw = dict(solver="blockwise", encode_path="fused") if args.rehearse \
        else {}
    x = make_scientific_field("turbulence", args.shape, np.float32, seed=SEED)
    t0 = time.perf_counter()
    one = engine.compress_many([x], EB, **kw)[0]
    t1 = time.perf_counter()
    mesh = jax.make_mesh((4,), ("data",))
    sharded = compress_fields_sharded([x], EB, mesh, **kw)[0]
    t2 = time.perf_counter()
    check(sharded == one, "sharded bytes differ from the one-chip engine's")
    log(f"four chips: compress_fields_sharded == one-chip engine, "
        f"{len(one)} bytes (one cold run: one chip {t1 - t0:.1f}s, "
        f"4-chip mesh {t2 - t1:.1f}s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded path on 4 chips")
    ap.add_argument("--shape", default=",".join(map(str, ISABEL_SHAPE)),
                    help="snapshot field shape (default: Isabel's; "
                         "another only with --rehearse)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases off a TPU, kernels interpreted; "
                         "never reports ok")
    args = ap.parse_args(argv)
    args.shape = tuple(int(n) for n in args.shape.split(","))
    if args.shape != ISABEL_SHAPE and not args.rehearse:
        ap.error("--shape other than Isabel's needs --rehearse: the chip "
                 "run's verdict holds only at the published shape")

    try:
        import jax

        import repro  # noqa: F401
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    cpu = jax.devices("cpu")[0]
    clock = CompileClock(jax)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")
    try:
        if args.four_chips:
            run_four_chips(args, jax)
        else:
            run_one_chip(args, jax, cpu, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if args.rehearse:
        print("chip_smoke: rehearsal passed; not a chip run, no result",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
