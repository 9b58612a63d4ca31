"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Shapes are kept modest because interpret mode executes the kernel body in
Python on CPU; divisible and non-divisible (padded) shapes are both swept.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import int8_gemm, int8_linear, q4_matmul, TunedMatmul
from repro.kernels import ref
from repro.quant import (
    quantize_q4_0,
    dequantize_q4_0,
    quantize_u8_dynamic,
    quantize_s8_symmetric,
    dequantize_u8,
    dequantize_s8,
)

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------ Q4_0 ---
def test_q4_roundtrip_exact_codes():
    """Quantize->dequantize->quantize is idempotent (codes are stable)."""
    w = RNG.normal(size=(8, 64)).astype(np.float32)
    qw = quantize_q4_0(jnp.asarray(w))
    w2 = dequantize_q4_0(qw)
    qw2 = quantize_q4_0(w2)
    np.testing.assert_array_equal(np.asarray(qw.packed), np.asarray(qw2.packed))


def test_f16_bits_to_f32_every_pattern():
    """The kernel rebuilds f16 scales from their raw bits with integer ops:
    exact for every normal, subnormal, zero and infinity; NaN stays NaN."""
    from repro.kernels.q4_matmul import f16_bits_to_f32

    bits = np.arange(1 << 16, dtype=np.uint16)
    want = bits.view(np.float16).astype(np.float32)
    got = np.asarray(f16_bits_to_f32(jnp.asarray(bits.astype(np.int32))))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan], want[~nan])
    np.testing.assert_array_equal(np.signbit(got[~nan]), np.signbit(want[~nan]))


def test_q4_quant_error_bounded():
    w = RNG.normal(size=(16, 128)).astype(np.float32)
    qw = quantize_q4_0(jnp.asarray(w))
    w2 = np.asarray(dequantize_q4_0(qw))
    # Q4_0 codes span [-8, 7]*d: interior error <= |d|/2 but the side the
    # code range doesn't reach (asymmetry) can err up to one full step |d|
    # (plus fp16 scale rounding).
    group_max = np.abs(w.reshape(16, -1, 32)).max(-1)
    bound = (group_max / 8).repeat(32, -1).reshape(16, 128) + 1e-6
    assert np.all(np.abs(w - w2) <= bound * 1.01)


@pytest.mark.parametrize("m,n,k", [
    (8, 256, 512),      # exactly one block
    (16, 512, 1024),    # multi-block in every dim
    (8, 256, 1536),     # 3 k-steps
    (1, 100, 512),      # GEMV with N padding
    (5, 256, 512),      # M padding
    (9, 300, 512),      # M and N padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_q4_matmul_matches_ref(m, n, k, dtype):
    x = jnp.asarray(RNG.normal(size=(m, k)), dtype=dtype)
    w = jnp.asarray(RNG.normal(size=(n, k)).astype(np.float32))
    qw = quantize_q4_0(w)
    got = q4_matmul(x, qw, interpret=True)
    want = ref.q4_matmul_ref(x, qw)
    assert got.shape == (m, n) and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
        rtol=tol, atol=tol * k,
    )


@pytest.mark.parametrize("blocks", [(8, 256, 512), (8, 128, 1024), (128, 128, 512)])
def test_q4_matmul_block_sweep(blocks):
    m, n, k = 16, 512, 1024
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    qw = quantize_q4_0(jnp.asarray(RNG.normal(size=(n, k)).astype(np.float32)))
    got = q4_matmul(x, qw, blocks=blocks, interpret=True)
    want = ref.q4_matmul_ref(x, qw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-2)


# ------------------------------------------------------------------ INT8 ---
@pytest.mark.parametrize("m,n,k", [
    (128, 128, 256),     # one block
    (256, 256, 512),     # multi-block
    (100, 120, 200),     # all dims padded
    (1, 128, 256),       # GEMV row
])
def test_int8_gemm_exact(m, n, k):
    a = jnp.asarray(RNG.integers(0, 256, size=(m, k)), dtype=jnp.uint8)
    w = jnp.asarray(RNG.integers(-127, 128, size=(n, k)), dtype=jnp.int8)
    got = int8_gemm(a, w, interpret=True)
    want = ref.int8_gemm_ref(a, w)
    # integer accumulation must be bit-exact
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("blocks", [(128, 128, 256), (64, 128, 512)])
def test_int8_gemm_block_sweep(blocks):
    a = jnp.asarray(RNG.integers(0, 256, size=(64, 512)), dtype=jnp.uint8)
    w = jnp.asarray(RNG.integers(-127, 128, size=(128, 512)), dtype=jnp.int8)
    got = int8_gemm(a, w, blocks=blocks, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.int8_gemm_ref(a, w)))


def test_int8_linear_dequant_close_to_f32():
    """Quantized linear approximates the float matmul (paper's GEMM path)."""
    x = RNG.normal(size=(32, 256)).astype(np.float32)
    w = RNG.normal(size=(64, 256)).astype(np.float32)
    qa = quantize_u8_dynamic(jnp.asarray(x))
    qw = quantize_s8_symmetric(jnp.asarray(w))
    got = int8_linear(qa, qw, interpret=True)
    want = np.asarray(dequantize_u8(qa)) @ np.asarray(dequantize_s8(qw)).T
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-3)
    # and the quantized result is close to the unquantized one
    full = x @ w.T
    err = np.abs(np.asarray(got) - full).max() / np.abs(full).max()
    assert err < 0.05


# ----------------------------------------------------------------- tuner ---
def test_tuned_matmul_dispatch():
    tm = TunedMatmul(interpret=True)
    x = jnp.asarray(RNG.normal(size=(8, 512)).astype(np.float32))
    qw = quantize_q4_0(jnp.asarray(RNG.normal(size=(256, 512)).astype(np.float32)))
    for _ in range(3):
        out = tm.q4(x, qw)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.q4_matmul_ref(x, qw)),
        rtol=2e-5, atol=1e-2,
    )
