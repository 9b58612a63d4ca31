"""Pallas TPU kernel: fused Q4_0 dequant + matmul (the paper's INT4 GEMV).

The paper's decode hot-spot is "Fp32-Int4-Fp32" GEMV: weights stay packed in
memory (0.5625 bytes/element) and are dequantized group-wise on the fly.
This is memory-bandwidth bound, so the TPU kernel's objective is to stream
the *packed* bytes HBM->VMEM (the f32 dequantized form exists only in
VMEM/VREGs) — the same reason Neural Speed fuses dequant into the VNNI
micro-kernel instead of materializing f32 weights.

Layout note: the storage layout is llama.cpp's, bit for bit (byte ``j`` of
a 32-group holds element ``j`` in its low nibble and ``j+16`` in its high
nibble; scales are f16).  The kernel never reshapes a tile:

* the activation is split *outside* the kernel into ``x_lo`` / ``x_hi``
  (M, K/2), the columns that meet the low and the high nibble of each
  packed byte column, so both nibble planes contract as plain 2-D dots;
* packed bytes are widened u8 -> i32 (the chip has no u8 -> f32 cast) and
  the nibbles are split with integer mask/shift;
* scales travel as their raw f16 bits (a free ``int16`` view — the chip's
  vector unit has no f16) and are rebuilt as f32 in the kernel, then
  spread across the 16 byte columns of their group by a 0/1 matmul on the
  MXU (exact, see :func:`_expand_scales`), once per grid step, into a VMEM
  scratch.

Each grid step owns one (bm, bn) output tile and the whole K extent of its
weight rows (the scale block then spans the full ``K/32`` columns, which
the chip's tiling accepts for any K); the K reduction is a loop over
``bk``-wide chunks.  On the chip ``bk`` must be a multiple of 256, so
that every chunk starts on a 128-lane boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import resolve_interpret
from repro.quant.q4 import GROUP, QuantizedLinear

__all__ = ["q4_matmul_pallas", "q4_matmul_pallas_db", "DEFAULT_BLOCKS",
           "CANDIDATE_BLOCKS", "f16_bits_to_f32"]

# (bm, bn, bk): bk is the K chunk of one dequant step, a multiple of GROUP.
DEFAULT_BLOCKS = (8, 256, 512)
CANDIDATE_BLOCKS = (
    (8, 256, 512),
    (8, 512, 256),
    (8, 128, 1024),
    (128, 128, 512),
    (256, 256, 256),
)

_HALF = GROUP // 2
_LANES = 128
# the plain kernel keeps a whole row block of packed weights double-buffered
# plus its expanded scales in VMEM; above the default scoped limit
_VMEM_LIMIT = 96 * 1024 * 1024


def f16_bits_to_f32(bits: jax.Array) -> jax.Array:
    """Exact f16 -> f32 from the raw bits (int32 holding an f16 pattern in
    its low 16 bits), with integer ops only."""
    b = bits & 0xFFFF
    sign = b >> 15
    exp = (b >> 10) & 0x1F
    man = b & 0x3FF
    exp32 = jnp.where(exp == 0x1F, 0xFF, exp + (127 - 15))
    normal = jax.lax.bitcast_convert_type(
        (sign << 31) | (exp32 << 23) | (man << 13), jnp.float32)
    sub = man.astype(jnp.float32) * (2.0 ** -24)  # zero and subnormals
    sub = jnp.where(sign == 1, -sub, sub)
    return jnp.where(exp == 0, sub, normal)


def _dot_nt(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x (bm, c) @ w (bn, c).T`` in f32."""
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _chunk(acc, x_lo, x_hi, packed, scales):
    """Accumulate one K chunk: ``packed`` (bn, c/2) u8, ``scales`` (bn, c/2)
    f32 (already repeated per byte column), ``x_lo``/``x_hi`` (bm, c/2)
    f32.  Low plane first, then high plane — the order both kernels
    share."""
    p = packed.astype(jnp.int32)
    w_lo = ((p & 0x0F) - 8).astype(jnp.float32) * scales
    w_hi = ((p >> 4) - 8).astype(jnp.float32) * scales
    acc = acc + _dot_nt(x_lo, w_lo)
    return acc + _dot_nt(x_hi, w_hi)


def _spread(bits: jax.Array) -> jax.Array:
    """(bn, g) f16 scale bits -> (bn, 16 g) f32, scale ``i`` repeated over
    columns ``[16 i, 16 i + 16)``.  The repeat is a matmul with a 0/1
    matrix: each scale is split into two bf16 parts that sum to it exactly
    (an f16 significand has 11 bits, a bf16 one 8), and every output
    element is one part times 1, so the MXU reproduces the scale bit for
    bit.  A lane repeat costs the chip's compiler several seconds per
    kernel; this compiles in well under one."""
    s = f16_bits_to_f32(bits.astype(jnp.int32))
    hi = s.astype(jnp.bfloat16)
    lo = (s - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    g = bits.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (g, g * _HALF), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (g, g * _HALF), 1)
    ones = (cols // _HALF == rows).astype(jnp.bfloat16)

    def spread(part):
        return jax.lax.dot_general(part, ones, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    return spread(hi) + spread(lo)


def _expand_scales(s_ref, e_ref):
    """Rebuild the f32 scales of this grid step's weight rows from their
    f16 bits (bn, G) and repeat each across its group's 16 byte columns
    into the VMEM scratch ``e_ref`` (bn, 16 G).  Whole 128-group windows
    run in a loop (lane offsets the chip can prove aligned); the tail, if
    K/32 is not a multiple of 128, is one static slice."""
    full, tail = divmod(s_ref.shape[1], _LANES)
    width = _LANES * _HALF

    def window(w, carry):
        s = s_ref[:, pl.ds(pl.multiple_of(w * _LANES, _LANES), _LANES)]
        e_ref[:, pl.ds(pl.multiple_of(w * width, width), width)] = _spread(s)
        return carry

    if full:  # the body is traced even for an empty loop
        jax.lax.fori_loop(0, full, window, 0)
    if tail:
        e_ref[:, full * width:] = _spread(s_ref[:, full * _LANES:])


def _cols(c, half_bk: int):
    """Lane window of K chunk ``c`` in the (., K/2) operands."""
    return pl.ds(pl.multiple_of(c * half_bk, half_bk), half_bk)


def _kernel(xl_ref, xh_ref, p_ref, s_ref, o_ref, e_ref, *, bk: int):
    half_bk = bk // 2
    _expand_scales(s_ref, e_ref)

    def body(c, acc):
        cols = _cols(c, half_bk)
        return _chunk(acc, xl_ref[:, cols], xh_ref[:, cols], p_ref[:, cols],
                      e_ref[:, cols])

    o_ref[...] = jax.lax.fori_loop(0, xl_ref.shape[1] // half_bk, body,
                                   jnp.zeros(o_ref.shape, jnp.float32))


def _db_kernel(xl_ref, xh_ref, p_hbm, s_ref, o_ref, e_ref, p_buf, sem, *,
               bk: int):
    """Double-buffered variant of :func:`_kernel`: the packed weight rows
    stay in HBM and stream through a two-slot VMEM scratch with async
    copies — chunk ``c+1``'s DMA is issued *before* chunk ``c``'s dot
    products run, so the stream overlaps compute (the decode GEMV is
    bandwidth-bound, so hiding the fetch behind the dot is the whole win).
    Same chunk order and arithmetic as the plain kernel, so outputs are
    bit-identical."""
    j = pl.program_id(1)
    _, bn, half_bk = p_buf.shape
    nk = xl_ref.shape[1] // half_bk

    def dma(c, slot):
        return pltpu.make_async_copy(
            p_hbm.at[pl.ds(j * bn, bn), _cols(c, half_bk)],
            p_buf.at[slot], sem.at[slot])

    dma(0, 0).start()
    _expand_scales(s_ref, e_ref)  # overlaps the first weight chunk's DMA

    def body(c, acc):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < nk)
        def _prefetch():  # next chunk into the other slot
            dma(c + 1, 1 - slot).start()

        dma(c, slot).wait()
        cols = _cols(c, half_bk)
        return _chunk(acc, xl_ref[:, cols], xh_ref[:, cols], p_buf[slot],
                      e_ref[:, cols])

    o_ref[...] = jax.lax.fori_loop(0, nk, body,
                                   jnp.zeros(o_ref.shape, jnp.float32))


def _prepare(x: jax.Array, qw: QuantizedLinear, blocks):
    """Validate shapes; split ``x`` into its nibble-plane halves (f32) and
    view the f16 scales as their raw bits."""
    m, k = x.shape
    n = qw.packed.shape[0]
    if qw.packed.shape[1] * 2 != k:
        raise ValueError("K mismatch between x and packed weights")
    bm, bn, bk = blocks
    if bk % GROUP:
        raise ValueError(f"bk={bk} must be a multiple of {GROUP}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by blocks {blocks}")
    xg = x.astype(jnp.float32).reshape(m, k // GROUP, 2, _HALF)
    x_lo = xg[:, :, 0, :].reshape(m, k // 2)
    x_hi = xg[:, :, 1, :].reshape(m, k // 2)
    bits = jax.lax.bitcast_convert_type(qw.scales, jnp.int16)
    return (m, n, k), x_lo, x_hi, bits


def _row_specs(bm: int, bn: int, k: int):
    """BlockSpecs shared by both kernels: activation halves and scales
    cover the full K extent of their rows; the output is one tile."""
    x_spec = pl.BlockSpec((bm, k // 2), lambda i, j: (i, 0))
    s_spec = pl.BlockSpec((bn, k // GROUP), lambda i, j: (j, 0))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return x_spec, s_spec, o_spec


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def q4_matmul_pallas_db(
    x: jax.Array,
    qw: QuantizedLinear,
    *,
    blocks: tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool | None = None,
) -> jax.Array:
    """Double-buffered ``x (M, K) x Q4_0 (N, K) -> (M, N)``: one grid over
    (M, N) tiles with the K stream hand-pipelined inside the kernel (two
    VMEM slots, DMA-prefetch of chunk ``c+1`` overlapping chunk ``c``'s
    compute).  Bit-identical to :func:`q4_matmul_pallas` at equal ``bk``."""
    (m, n, k), x_lo, x_hi, bits = _prepare(x, qw, blocks)
    bm, bn, bk = blocks
    x_spec, s_spec, o_spec = _row_specs(bm, bn, k)
    out = pl.pallas_call(
        functools.partial(_db_kernel, bk=bk),
        grid=(m // bm, n // bn),
        in_specs=[x_spec, x_spec,
                  pl.BlockSpec(memory_space=pl.ANY),  # packed stays in HBM
                  s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, k // 2), jnp.float32),    # expanded scales
            pltpu.VMEM((2, bn, bk // 2), jnp.uint8),  # two packed slots
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(x_lo, x_hi, qw.packed, bits)
    return out.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def q4_matmul_pallas(
    x: jax.Array,
    qw: QuantizedLinear,
    *,
    blocks: tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool | None = None,
) -> jax.Array:
    """``x`` (M, K) f32/bf16 x Q4_0 (N, K) -> (M, N) in x.dtype."""
    (m, n, k), x_lo, x_hi, bits = _prepare(x, qw, blocks)
    bm, bn, bk = blocks
    x_spec, s_spec, o_spec = _row_specs(bm, bn, k)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=(m // bm, n // bn),
        in_specs=[x_spec, x_spec,
                  pl.BlockSpec((bn, k // 2), lambda i, j: (j, 0)),
                  s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, k // 2), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(x_lo, x_hi, qw.packed, bits)
    return out.astype(x.dtype)
