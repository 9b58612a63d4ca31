"""repro.device: the one place that decides interpret mode, the compile
cache directory and which device a replica lives on."""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import device
from repro.kernels import HybridKernelDispatcher
from repro.kernels.compiled import CompiledDispatcher

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test that sets it, before any
    compile could write there."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_cache_honours_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_cache_defaults_to_fixed_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    first = device.enable_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert device.enable_compile_cache() == first  # no pid, time, tmp name
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", False)])
def test_interpret_follows_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert device.resolve_interpret() is want
    assert device.resolve_interpret(True) is True   # explicit wins
    assert device.resolve_interpret(False) is False


def test_dispatchers_do_not_interpret_off_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    disp = HybridKernelDispatcher.virtual("ultra-125h", execute=True)
    assert disp.interpret is False
    assert CompiledDispatcher(disp).interpret is False


def test_committed_device():
    assert device.committed_device({"w": jnp.ones(3)}) is None
    dev = jax.devices()[0]
    tree = {"w": jax.device_put(jnp.ones(3), dev), "n": 3}
    assert device.committed_device(tree) == dev
