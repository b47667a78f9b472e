"""What running on a TPU changes, steered from inside the tests.

The backend is never switched for real here: each test patches the one
predicate the code under test consults, so the rest of the process
keeps its CPU backend (and its interpreted kernels).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import compile_cache, engine, temporal
from repro.cluster import router as cluster_router
from repro.core import quantize

ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------------------- compile cache

@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_leaves_env_dir_alone(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path,
                                            cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)   # the path must not follow the cwd
    want = str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


# ----------------------------------------------------- one process per chip

def test_process_cluster_refuses_on_tpu(monkeypatch, tmp_path):
    from repro.cluster import ProcessCluster

    def no_spawn(*a, **kw):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(cluster_router, "one_process_per_chip", lambda: True)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    with pytest.raises(RuntimeError, match="one process at a time"):
        ProcessCluster(tmp_path, 2)


def test_serve_cluster_runs_in_process_on_tpu(monkeypatch, tmp_path,
                                              capsys):
    from repro.launch import serve

    def no_spawn(*a, **kw):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(cluster_router, "one_process_per_chip", lambda: True)
    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    args = argparse.Namespace(
        tile="8,8,16", batch_tiles=8, store_dir=str(tmp_path), cluster=2,
        clients=2, requests_per_client=2, eb=1e-2, trace_out=None,
        flight_dir=None, adaptive_eb="off")
    serve.serve_cluster(args)
    out = capsys.readouterr().out
    assert "2 shard workers (in-process workers)" in out
    assert "byte-identical to a single-process store" in out


# ------------------------------------------------------- float64 on a TPU

def test_float64_is_refused_on_tpu(monkeypatch, rng):
    x = rng.standard_normal((12, 10, 9))
    blob = engine.compress(x, 1e-2)
    chain = temporal.compress_chain([x, x * 0.9], 1e-2)
    monkeypatch.setattr(quantize.jax, "default_backend", lambda: "tpu")
    with pytest.raises(quantize.BackendUnsupported, match="stage quantize"):
        engine.compress(x, 1e-2)
    with pytest.raises(quantize.BackendUnsupported, match="stage quantize"):
        temporal.compress_chain([x, x], 1e-2)
    with pytest.raises(quantize.BackendUnsupported, match="stage dequantize"):
        engine.decompress(blob)
    with pytest.raises(quantize.BackendUnsupported, match="stage dequantize"):
        temporal.decompress_chain(chain)


def test_float32_is_not_refused_on_tpu(monkeypatch):
    monkeypatch.setattr(quantize.jax, "default_backend", lambda: "tpu")
    quantize.check_backend(np.float32, "compress")
    quantize.check_backend(np.float32, "decompress")


# ------------------------------------------------------------ chip_smoke

def test_chip_smoke_refuses_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_takes_a_small_shape_only_in_rehearsal():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                          "--shape", "8,8,16"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert "--rehearse" in out.stderr
    assert '"ok"' not in out.stdout
