"""Pallas TPU kernel: u8 x s8 -> s32 GEMM (the paper's INT8 GEMM hot-spot).

The paper's AVX-VNNI micro-kernel (``vpdpbusd``: u8 activations x s8 weights
accumulated in s32) maps onto the TPU MXU's int8 systolic path.  TPU-native
rethink (not a port): instead of per-core row ranges, the work decomposition
is a (M/bm, N/bn) parallel grid with an arbitrary (sequential) K reduction,
accumulated in a VMEM scratch tile; tile shapes are MXU-aligned multiples of
(32, 128) for int8 operands.

Block shapes are parameters so the dynamic tuner (repro.core.tuner) can pick
among candidates — the TPU analogue of the paper's per-ISA ratio tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import resolve_interpret

__all__ = ["int8_gemm_pallas", "DEFAULT_BLOCKS", "CANDIDATE_BLOCKS"]

# (bm, bn, bk) candidates, MXU-aligned. VMEM use per step:
#   a: bm*bk + w: bn*bk bytes (int8) + acc: bm*bn*4 bytes.
DEFAULT_BLOCKS = (128, 128, 256)
CANDIDATE_BLOCKS = (
    (128, 128, 256),
    (256, 128, 128),
    (128, 256, 128),
    (64, 128, 512),
    (256, 256, 256),
)


def _kernel(a_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU int8 path: s8 x s8 operands, s32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def int8_gemm_pallas(
    a_u8: jax.Array,
    w_s8: jax.Array,
    *,
    blocks: tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool | None = None,
) -> jax.Array:
    """``a_u8`` (M, K) u8 x ``w_s8`` (N, K) s8 -> (M, N) s32.

    M, N, K must be divisible by the block shape (the ops.py wrapper pads).
    The chip's MXU multiplies signed int8 (a u8 operand is read as s8), so
    ``a`` enters the kernel shifted into s8 range, ``a - 128`` (its top
    bit flipped), and the shift comes back as ``128 * sum_k w[n, k]``:
    exact, since every term stays far inside s32.
    """
    m, k = a_u8.shape
    n, k2 = w_s8.shape
    if k != k2:
        raise ValueError(f"K mismatch: {k} vs {k2}")
    bm, bn, bk = blocks
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by blocks {blocks}")
    a_s8 = jax.lax.bitcast_convert_type(a_u8 ^ jnp.uint8(0x80), jnp.int8)
    shift = 128 * jnp.sum(w_s8, axis=1, dtype=jnp.int32)
    out = pl.pallas_call(
        _kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=resolve_interpret(interpret),
    )(a_s8, w_s8)
    return out + shift[None, :]
