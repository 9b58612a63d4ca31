"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * shape hygiene — pad M/N/K up to block multiples, slice the result back;
  * config selection — candidate block shapes are chosen by the dynamic
    :class:`repro.core.tuner.KernelTuner` (the paper's per-ISA performance
    table, re-keyed by (kernel, shape-class)), falling back to defaults when
    no tuner is supplied;
  * backend selection — ``interpret=None`` (the default) resolves through
    :func:`repro.device.resolve_interpret`: the kernel body is interpreted
    on the CPU backend only and lowers to Mosaic on the chip.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.tuner import KernelTuner, shape_class
from repro.quant.q4 import GROUP, QuantizedLinear
from repro.quant.int8 import QuantizedActivation, QuantizedWeightI8, u8s8_matmul_decompose

from . import int8_gemm as _i8
from . import q4_matmul as _q4
from . import ref as _ref

__all__ = ["int8_gemm", "int8_linear", "q4_matmul", "TunedMatmul"]


def _pad_to(x: jax.Array, rows: int, cols: int, value=0) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)), constant_values=value)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def int8_gemm(
    a_u8: jax.Array,
    w_s8: jax.Array,
    *,
    blocks: tuple[int, int, int] = _i8.DEFAULT_BLOCKS,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """u8 (M,K) x s8 (N,K) -> s32 (M,N), padding to block multiples.

    Zero-padding is exact for the s32 accumulation (0*w == 0).
    """
    m, k = a_u8.shape
    n = w_s8.shape[0]
    bm, bn, bk = blocks
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    a_p = _pad_to(a_u8, mp, kp)
    w_p = _pad_to(w_s8, np_, kp)
    out = _i8.int8_gemm_pallas(a_p, w_p, blocks=blocks, interpret=interpret)
    return out[:m, :n]


def int8_linear(
    a: QuantizedActivation,
    w: QuantizedWeightI8,
    *,
    blocks: tuple[int, int, int] = _i8.DEFAULT_BLOCKS,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Full quantized linear (u8s8 -> s32 -> dequant f32)."""
    acc = int8_gemm(a.q, w.q, blocks=blocks, interpret=interpret)
    return u8s8_matmul_decompose(a, w, acc)


def q4_matmul(
    x: jax.Array,
    qw: QuantizedLinear,
    *,
    blocks: tuple[int, int, int] = _q4.DEFAULT_BLOCKS,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """f32/bf16 (M,K) x Q4_0 (N,K) -> (M,N), padding M/N to block multiples.

    K padding would shift group boundaries, so K must already be a multiple
    of ``blocks[2]`` (all assigned configs satisfy this; the ops layer picks
    a compatible bk otherwise).
    """
    m, k = x.shape
    n = qw.packed.shape[0]
    bm, bn, bk = blocks
    if k % bk:
        # choose the largest group-multiple bk that divides K
        bk = GROUP
        for cand in (1024, 512, 256, 128, 64, 32):
            if k % cand == 0:
                bk = cand
                break
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    x_p = _pad_to(x, mp, k)
    packed_p = _pad_to(qw.packed, np_, k // 2)
    scales_p = _pad_to(qw.scales, np_, k // GROUP)
    out = _q4.q4_matmul_pallas(
        x_p, QuantizedLinear(packed_p, scales_p), blocks=(bm, bn, bk),
        interpret=interpret,
    )
    return out[:m, :n]


class TunedMatmul:
    """Dispatch wrapper that lets a :class:`KernelTuner` pick block configs
    online — per-(kernel, shape-class) EMA argmin, the paper's table re-keyed.
    """

    def __init__(self, tuner: Optional[KernelTuner] = None,
                 interpret: Optional[bool] = None):
        self.tuner = tuner or KernelTuner()
        self.interpret = interpret

    def q4(self, x: jax.Array, qw: QuantizedLinear) -> jax.Array:
        key = ("q4_matmul", shape_class(x.shape[0], qw.out_features, x.shape[1]))
        cfg = self.tuner.select(key, _q4.CANDIDATE_BLOCKS)
        t0 = time.perf_counter()
        out = q4_matmul(x, qw, blocks=cfg, interpret=self.interpret)
        out.block_until_ready()
        self.tuner.report(key, cfg, time.perf_counter() - t0)
        return out

    def int8(self, a: QuantizedActivation, w: QuantizedWeightI8) -> jax.Array:
        key = ("int8_gemm", shape_class(a.q.shape[0], w.q.shape[0], a.q.shape[1]))
        cfg = self.tuner.select(key, _i8.CANDIDATE_BLOCKS)
        t0 = time.perf_counter()
        out = int8_linear(a, w, blocks=cfg, interpret=self.interpret)
        out.block_until_ready()
        self.tuner.report(key, cfg, time.perf_counter() - t0)
        return out
