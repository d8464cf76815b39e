"""What the program picks from the platform it runs on: the aggregation
backend, the hardware spec, and the compile-cache directory.  The platform
is steered inside each test; nothing here needs a chip."""
import types

import jax
import pytest

from repro.hw import TPU_V5E, device_spec
from repro.kernels.ops import resolve_backend
from repro.launch.compile_cache import (CACHE_ENV, DEFAULT_CACHE_DIR,
                                        enable_compile_cache)
from repro.models.gnn import GNNConfig


def test_default_backend_is_xla_on_cpu():
    assert jax.default_backend() == "cpu"
    assert resolve_backend() == "xla"
    assert GNNConfig().backend == "xla"


def test_default_backend_is_pallas_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend() == "pallas"
    assert GNNConfig().backend == "pallas"
    # asked-for backends are kept: the interpreter only runs when named
    assert resolve_backend("pallas_interpret") == "pallas_interpret"
    assert GNNConfig(backend="xla").backend == "xla"


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")


def _fake_tpu(monkeypatch, kind: str):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [types.SimpleNamespace(device_kind=kind)])


def test_device_spec_keyed_by_device_kind(monkeypatch):
    assert device_spec() is TPU_V5E             # off a TPU: the target
    _fake_tpu(monkeypatch, "TPU v5 lite")
    assert device_spec() is TPU_V5E


def test_device_spec_unknown_tpu_kind_raises(monkeypatch):
    _fake_tpu(monkeypatch, "TPU v99")
    with pytest.raises(ValueError, match="no hardware spec"):
        device_spec()


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before    # set nothing


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)  # stable
        assert (DEFAULT_CACHE_DIR.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
