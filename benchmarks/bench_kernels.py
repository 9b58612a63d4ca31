"""Pallas kernel micro-bench.  On the CPU backend the kernels run in
interpret mode (correctness and dispatch cost, not speed); on a TPU they
lower to Mosaic.  Reports host-clock us/call and max error vs the pure-jnp
oracle, plus the kernel's arithmetic volume; row names carry the mode."""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.device import resolve_interpret
from repro.kernels import int8_gemm, q4_matmul, ref
from repro.quant import quantize_q4_0

from .common import fmt


def _time(fn, *args, iters=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jnp.asarray(out).block_until_ready()
    return (time.perf_counter() - t0) / iters, out


def run() -> list[tuple]:
    rng = np.random.default_rng(0)
    rows = []
    mode = "interp" if resolve_interpret() else "mosaic"

    m, n, k = 8, 512, 1024
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    qw = quantize_q4_0(jnp.asarray(rng.normal(size=(n, k)).astype(np.float32)))
    t, out = _time(q4_matmul, x, qw)
    err = float(jnp.max(jnp.abs(out - ref.q4_matmul_ref(x, qw))))
    rows.append((f"kernel_q4_matmul_{mode}", fmt(t),
                 f"flops={2 * m * n * k}|max_err={err:.2e}"))

    a = jnp.asarray(rng.integers(0, 256, size=(128, 512)), dtype=jnp.uint8)
    w = jnp.asarray(rng.integers(-127, 128, size=(256, 512)), dtype=jnp.int8)
    t, out = _time(int8_gemm, a, w)
    exact = bool((out == ref.int8_gemm_ref(a, w)).all())
    rows.append((f"kernel_int8_gemm_{mode}", fmt(t),
                 f"flops={2 * 128 * 256 * 512}|exact={exact}"))
    return rows
