#!/usr/bin/env python3
"""Which 64-bit operations a TPU computes exactly: each op runs on the
chip and on the host CPU over the same 2**20 random operands, and the
count of results whose bytes differ is printed, or the compiler's error
where the op does not lower.

    python benchmarks/probe_f64_tpu.py             # on a TPU host
    JAX_PLATFORMS=cpu python benchmarks/probe_f64_tpu.py --rehearse

This is the evidence behind ``quantize.check_backend``: float64 fields
are refused on a TPU because its f64 arithmetic is emulated inexactly
and the f64 -> s64 bitcast of the decoder does not lower, while the f32
path's operations (the integer anchor ``f32_base_ordered``, f32
quotients, casts and compares) agree bit for bit.  With ``--rehearse``
the "chip" is the CPU itself, so every count is 0.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

N = 1 << 20
EPS = 1e-2 * (1 - 2.0**-20)     # the effective bound of eb=1e-2 on [0, 1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run off a TPU, comparing the CPU with itself")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import repro  # noqa: F401  (enables x64)
    from repro.core.quantize import eps_operand, f32_base_ordered

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"probe: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 2
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(1)
    a = rng.standard_normal(N) * 10.0 ** rng.integers(-6, 6, N)
    b = rng.standard_normal(N) * 10.0 ** rng.integers(-6, 6, N)
    x32 = (rng.standard_normal(N) * 100).astype(np.float32)
    bins = rng.integers(-2**30, 2**30, N).astype(np.int32)
    e = eps_operand(np.full(N, EPS))

    def compare(name, fn, *operands):
        try:
            got = np.asarray(jax.jit(fn)(*operands))
            with jax.default_device(cpu):
                want = np.asarray(jax.jit(fn)(
                    *jax.device_put(operands, cpu)))
        except Exception as exc:  # the op does not lower: report it
            print(f"{name}: ERROR {str(exc).splitlines()[0][:200]}")
            return
        differ = (got.view(np.uint8).reshape(N, -1)
                  != want.view(np.uint8).reshape(N, -1)).any(axis=1)
        print(f"{name}: {int(differ.sum())} of {N} differ", flush=True)

    print(f"device: {dev.platform} {dev.device_kind}")
    compare("f64 mul", lambda p, q: p * q, a, b)
    compare("f64 div", lambda p, q: p / q, a, b)
    compare("f64 add", lambda p, q: p + q, a, b)
    compare("f64 round(div)", lambda p, q: jnp.round(p / q), a, b)
    compare("f64 compare", lambda p, q: p < q, a, b)
    compare("f64 -> f32", lambda p: p.astype(jnp.float32), a)
    compare("f32 -> f64", lambda p: p.astype(jnp.float64), x32)
    compare("i64 mul-add", lambda p: p.astype(jnp.int64) * 3_000_000_007
            + 12345, bins)
    compare("f64 -> s64 bitcast",
            lambda p: lax.bitcast_convert_type(p, jnp.int64), a)
    compare("f32 first guess via f64", lambda p: jnp.round(
        p.astype(jnp.float64) / EPS).astype(jnp.int32), x32)
    compare("f32 first guess via f32", lambda p: jnp.round(
        p / jnp.float32(EPS)).astype(jnp.int32), x32)
    compare("f32 anchor (f32_base_ordered)", f32_base_ordered, bins, e)
    if dev.platform != "tpu":
        print("probe: rehearsal, the CPU against itself", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
