"""The main-path programs compile for a TPU v5e at real widths.

Nothing runs: each program is lowered and compiled for a *described*
``v5e:2x2`` topology, which raises whatever the chip's compiler would
refuse (block shapes off the (8, 128) tiling, 64-bit vector types,
kernels that cannot be partitioned).  The topology is described inside
a fixture, never at import, so every pytest worker collects the same
tests and only the one running this file loads the TPU compiler.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import Eps
from repro.engine import device, halo
from repro.kernels import fused_decode, fused_encode, subbin_sweep

TILE = (16, 16, 64)
HALO = tuple(t + 2 for t in TILE)
ELEMS = int(np.prod(TILE))
BATCH = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    return make


def _eps(spec, n, **kw):
    return Eps(spec((n,), jnp.float64, **kw),
               *(spec((n,), jnp.int32, **kw) for _ in range(3)))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("state", ["int32", "uint32"])
def test_blockwise_solve_compiles(spec, state):
    compiled = _compile(
        lambda s, f: subbin_sweep.solve_tiles_blockwise(s, f, interpret=False),
        spec((BATCH,) + HALO, state), spec((BATCH,) + TILE, jnp.uint32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("word,chunk", [("int16", 8192), ("int32", 4096)])
@pytest.mark.parametrize("transform", ["delta", "zigzag", "raw"])
def test_fused_encode_ints_compiles(spec, word, chunk, transform):
    _compile(lambda x: fused_encode.encode_ints_fused(
        x, chunk, transform, interpret=False), spec((BATCH, ELEMS), word))


@pytest.mark.parametrize("batch", [BATCH, 2048])
def test_fused_encode_values_compiles(spec, batch):
    _compile(lambda x, e: fused_encode.encode_values_fused(
        x, e, 8192, jnp.float32, jnp.dtype("int16"), interpret=False),
        spec((batch, ELEMS), jnp.float32), _eps(spec, batch))


@pytest.mark.parametrize("bins_word,subs_word", [(2, 2), (4, 4), (2, 4)])
def test_fused_decode_compiles(spec, bins_word, subs_word):
    def streams(word):
        chunk = {2: 8192, 4: 4096}[word]
        rows = BATCH * -(-ELEMS // chunk)
        return (spec((rows, chunk // (8 * word)), f"uint{8 * word}"),
                spec((rows, chunk), f"uint{8 * word}"))

    _compile(lambda a, b, c, d, e: fused_decode.decode_tiles_fused(
        a, b, c, d, e, tile_elems=ELEMS, dtype=jnp.float32, interpret=False),
        *streams(bins_word), *streams(subs_word), _eps(spec, BATCH))


def _resident_compress(x, e, n, r, encode_fused):
    return device.resident_compress(
        x, e, n, r, jnp.dtype("float32"), True, "blockwise", False,
        ELEMS + 2, jnp.dtype("int16"), 8192, encode_fused=encode_fused)


def _compress_args(make, eps):
    return (make((BATCH,) + HALO, jnp.float32), eps,
            make((BATCH, halo.N_COLS), jnp.int32))


def test_staged_resident_compress_compiles(spec):
    _compile(lambda x, e, n, r: _resident_compress(x, e, n, r, False),
             *_compress_args(spec, _eps(spec, BATCH)), spec((), jnp.int64))


def test_dequantize_f32_compiles(spec):
    _compile(lambda b, s, e: device.dequantize_tiles(b, s, e, jnp.float32),
             spec((BATCH, ELEMS), jnp.int32), spec((BATCH, ELEMS), jnp.int32),
             _eps(spec, BATCH))


def test_sharded_compress_compiles_on_four_chips(topo, spec):
    """The mesh-sharded compress (``compress_fields_sharded``): under the
    mesh the Mosaic kernels run per device through ``shard_map``."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",),
                axis_types=(AxisType.Auto,))
    tiles = NamedSharding(mesh, P("data"))
    with jax.set_mesh(mesh):
        compiled = _compile(
            lambda x, e, n, r: _resident_compress(x, e, n, r, True),
            *_compress_args(partial(spec, sharding=tiles),
                            _eps(spec, BATCH, sharding=tiles)),
            spec((), jnp.int64, sharding=NamedSharding(mesh, P())))
    assert "tpu_custom_call" in compiled.as_text()
