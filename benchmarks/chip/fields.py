"""Seeded stand-in fields, made on the device.

A ``jax.numpy`` copy of the ``turbulence`` generator of
``repro/data/fields.py``: complex Gaussian noise shaped to a k^-5/3
energy spectrum, transformed back and scaled to a peak of 1.  One
compiled program makes one field; the benchmark fetches each field to
host memory, because users hand the service host arrays.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int, index: int):
    # seeds may exceed 32 bits: fold the high word in
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, index)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _turbulence(key, shape, dtype):
    re, im = jax.random.normal(key, (2,) + shape, jnp.float32)
    ks = jnp.meshgrid(*[jnp.fft.fftfreq(n).astype(jnp.float32) * n
                        for n in shape], indexing="ij")
    k2 = sum(k * k for k in ks)
    k2 = k2.at[(0,) * len(shape)].set(1.0)
    spec = (re + 1j * im) * (k2 ** (-11.0 / 12.0))
    x = jnp.real(jnp.fft.ifftn(spec))
    return (x / jnp.abs(x).max()).astype(dtype)


GENERATORS = {"turbulence": _turbulence}


def make_fields(generator: str, shape, dtype, seed: int,
                count: int) -> list[np.ndarray]:
    """``count`` distinct host fields, deterministic in the arguments."""
    gen = GENERATORS[generator]
    shape = tuple(int(n) for n in shape)
    out = []
    for i in range(count):
        out.append(np.asarray(jax.device_get(
            gen(_key(seed, i), shape, np.dtype(dtype).name))))
    return out
