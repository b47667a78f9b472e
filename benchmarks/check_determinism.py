"""CI determinism gate: the paper's bit-for-bit claim as a standing check.

Compresses every generator field (both dtypes, mixed ranks) with each
subbin solver schedule and verifies, by SHA-256 of the emitted v2
containers, that

  * all schedules (``jacobi`` and the Pallas ``blockwise`` kernel, which
    runs in interpret mode off-TPU) emit byte-identical containers —
    the schedule-independence of the least fixed point (paper §IV-E);
  * the bytes match the committed manifest
    (``benchmarks/baselines/determinism_hashes.json``) — so a numerics
    drift anywhere in quantize/solve/encode (new jax version, new
    platform, accidental float reassociation) fails CI instead of
    silently changing archived containers;
  * every container round-trips within its error bound.

Inputs are synthesized deterministically (crc32-seeded generators), so
the hashes are machine-independent by construction — exactly the
reproducibility the paper claims for CPU vs GPU runs.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.check_determinism
  PYTHONPATH=src python -m benchmarks.check_determinism --update-manifest
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
from pathlib import Path

import numpy as np

MANIFEST_PATH = (
    Path(__file__).resolve().parent / "baselines" / "determinism_hashes.json"
)

SOLVERS = ("jacobi", "blockwise")
EB = 1e-2
SHAPES = ((13, 11, 9), (40, 28), (500,))
DTYPES = ("float32", "float64")

# Temporal chain cases: every evolution x two bases, both dtypes, a
# mid-chain keyframe (interval 2 over 5 frames) so both frame kinds and
# the residual-run replay are pinned.
CHAIN_SHAPE = (13, 11, 9)
CHAIN_FRAMES = 5
CHAIN_INTERVAL = 2
CHAIN_BASES = ("gaussians", "turbulence")

# Topology-adaptive eb cases: same cross-solver byte-equality contract
# with the per-tile ladder active (FLAG_ADAPTIVE_EB containers). The
# round-trip bound loosens to the ladder's rung-0 bound, eb * 2**k_max.
ADAPTIVE_SHAPE = (17, 14, 12)
ADAPTIVE_CHAIN_EVOS = ("advect", "diffuse")


def _round_trip(case, fields, decoded, bound_eb):
    """Problems of a case whose fields decode to ``decoded``."""
    problems = []
    for t, (f, y) in enumerate(zip(fields, decoded)):
        bound = bound_eb * (float(f.max()) - float(f.min()))
        err = float(np.abs(f.astype(np.float64)
                           - y.astype(np.float64)).max())
        if err > bound:
            where = f"frame {t} " if len(fields) > 1 else ""
            problems.append(f"{case}: {where}round-trip error {err:.3e} "
                            f"exceeds bound {bound:.3e}")
    return problems


def _solver_problems(case, blobs):
    ref = blobs[SOLVERS[0]]
    return [f"{case}: solver {s} bytes differ from {SOLVERS[0]} "
            "(schedule independence broken)"
            for s, b in blobs.items() if b != ref]


def cases():
    """Yield ``(case, dtype, run)`` for every manifest case, in manifest
    order; ``run()`` compresses with every solver and returns
    ``(container bytes, [problems])``."""
    from repro import engine, temporal
    from repro.core.bitstream import EB_LADDER_K_MAX
    from repro.data.fields import (
        FIELD_GENERATORS,
        SEQUENCE_EVOLUTIONS,
        make_field_sequence,
        make_scientific_field,
    )

    loose = 2.0 ** EB_LADDER_K_MAX

    def snapshot(case, x, bound_eb, **kw):
        def run():
            blobs = {s: engine.compress(x, EB, solver=s, **kw)
                     for s in SOLVERS}
            ref = blobs[SOLVERS[0]]
            return ref, (_solver_problems(case, blobs) + _round_trip(
                case, [x], [engine.decompress(ref)], bound_eb))
        return run

    def chain(case, frames, bound_eb, **kw):
        def run():
            blobs = {s: temporal.compress_chain(
                frames, EB, solver=s, keyframe_interval=CHAIN_INTERVAL, **kw)
                for s in SOLVERS}
            ref = blobs[SOLVERS[0]]
            return ref, (_solver_problems(case, blobs) + _round_trip(
                case, frames, temporal.decompress_chain(ref), bound_eb))
        return run

    for name in sorted(FIELD_GENERATORS):
        for shape in SHAPES:
            for dtype in DTYPES:
                x = make_scientific_field(name, shape, np.dtype(dtype), seed=5)
                case = f"{name}/{'x'.join(map(str, shape))}/{dtype}"
                yield case, dtype, snapshot(case, x, EB)
    for evo in sorted(SEQUENCE_EVOLUTIONS):
        for base in CHAIN_BASES:
            for dtype in DTYPES:
                frames = make_field_sequence(evo, base, CHAIN_SHAPE,
                                             CHAIN_FRAMES, np.dtype(dtype),
                                             seed=5)
                case = f"chain/{evo}/{base}/{dtype}"
                yield case, dtype, chain(case, frames, EB)
    for name in sorted(FIELD_GENERATORS):
        for dtype in DTYPES:
            x = make_scientific_field(name, ADAPTIVE_SHAPE,
                                      np.dtype(dtype), seed=5)
            case = f"adaptive/{name}/{dtype}"
            yield case, dtype, snapshot(case, x, EB * loose,
                                        adaptive_eb="tda")
    for evo in sorted(ADAPTIVE_CHAIN_EVOS):
        for dtype in DTYPES:
            frames = make_field_sequence(evo, "gaussians", ADAPTIVE_SHAPE,
                                         CHAIN_FRAMES, np.dtype(dtype),
                                         seed=5)
            case = f"chain-adaptive/{evo}/{dtype}"
            yield case, dtype, chain(case, frames, EB * loose,
                                     adaptive_eb="tda")


def compute_hashes() -> tuple[dict, list[str]]:
    """-> ({case: sha256}, [cross-solver and round-trip violations])."""
    hashes = {}
    problems = []
    for case, _, run in cases():
        blob, found = run()
        hashes[case] = hashlib.sha256(blob).hexdigest()
        problems += found
    return hashes, problems


def compare(manifest: dict, hashes: dict) -> list[str]:
    problems = []
    for case, want in manifest.items():
        got = hashes.get(case)
        if got is None:
            problems.append(f"{case}: case missing from this run")
        elif got != want:
            problems.append(
                f"{case}: container hash {got[:16]}... != manifest "
                f"{want[:16]}... (bit-for-bit determinism broken)"
            )
    for case in hashes:
        if case not in manifest:
            problems.append(f"{case}: not in manifest (run --update-manifest)")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--manifest", type=Path, default=MANIFEST_PATH)
    ap.add_argument("--update-manifest", action="store_true",
                    help="rewrite the committed hash manifest from this run")
    args = ap.parse_args(argv)

    hashes, problems = compute_hashes()
    if args.update_manifest:
        if problems:  # never pin bytes that already violate the contract
            print("refusing to update manifest; violations:")
            for p in problems:
                print(f"  - {p}")
            return 1
        args.manifest.parent.mkdir(parents=True, exist_ok=True)
        args.manifest.write_text(json.dumps(hashes, indent=1) + "\n")
        print(f"manifest updated: {len(hashes)} cases -> {args.manifest}")
        return 0

    manifest = json.loads(args.manifest.read_text())
    problems += compare(manifest, hashes)
    if problems:
        print(f"determinism gate FAILED ({len(problems)} problem(s)):")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"determinism gate passed: {len(hashes)} cases, "
          f"{len(SOLVERS)} solvers byte-identical, manifest matched")
    return 0


if __name__ == "__main__":
    sys.exit(main())
