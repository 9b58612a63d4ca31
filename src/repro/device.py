"""Where the program runs: the Pallas interpret decision, the persistent
compile cache, and the device line every chip-facing entry point prints.

Kept in one module so that no caller decides these for itself:

* :func:`resolve_interpret` — Pallas kernels run in interpret mode only on
  the CPU backend; on any accelerator they lower to Mosaic.
* :func:`enable_compile_cache` — honours ``JAX_COMPILATION_CACHE_DIR`` when
  it is set and otherwise uses one fixed directory inside the checkout.
* :func:`device_info` — platform, device kind and count as JAX reports them.
* :func:`committed_device` — the device a replica's arrays are committed to,
  so that its state is made on the same one.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["resolve_interpret", "enable_compile_cache", "device_info",
           "committed_device", "CACHE_ENV", "DEFAULT_CACHE_DIR"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — fixed, so a second run finds what the first wrote
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The Pallas ``interpret`` flag: an explicit bool wins, ``None`` means
    "interpret exactly when the default backend is the CPU"."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and nothing
    is set here; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    DEFAULT_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend's devices."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def committed_device(tree) -> Optional[jax.Device]:
    """The one device that the committed arrays of ``tree`` live on, or
    ``None`` when none is committed (JAX then uses its default device).
    Raises when committed arrays span several devices."""
    devs = set()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and leaf.committed:
            devs |= leaf.devices()
    if len(devs) > 1:
        raise ValueError(f"arrays are committed to several devices: {devs}")
    return devs.pop() if devs else None
