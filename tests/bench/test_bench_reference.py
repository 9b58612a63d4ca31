"""The benchmark's plain reference against the engine's own model forward,
in float32 at a tiny size: full RoPE, and ChatGLM's half-head RoPE with
QKV bias."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import tiny_shape

from bench.model import make_weights, program_config, program_params
from bench.references import decoder


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@pytest.mark.parametrize("kw", [
    dict(rope_fraction=1.0, qkv_bias=False, n_kv_heads=2),
    dict(rope_fraction=0.5, qkv_bias=True, n_kv_heads=1),
    dict(rope_fraction=0.5, qkv_bias=True, n_kv_heads=4, rope_theta=1e7),
    dict(rope_fraction=1.0, qkv_bias=False, n_kv_heads=2, rope_theta=1e7,
         tied=True),
], ids=["llama", "chatglm", "mha-theta", "granite-tied"])
def test_reference_matches_engine_forward(kw):
    from repro.models import forward

    shape = tiny_shape(**kw)
    w = _f32(make_weights(shape, 2 ** 32 + 3))
    cfg = dataclasses.replace(program_config(shape), dtype="float32")
    tokens = np.random.default_rng(0).integers(0, shape.vocab, 40,
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = forward(cfg, program_params(w), jnp.asarray(tokens)[None]
                       ).logits[0]
    got = decoder.served_logits(shape, w, tokens, 0, 40, 48)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * scale, rtol=0)


def test_rows_and_padding():
    """Rows are positions first..first+rows-1, whatever the padding."""
    shape = tiny_shape()
    w = make_weights(shape, 7)
    tokens = np.arange(30, dtype=np.int32) % shape.vocab
    whole = decoder.served_logits(shape, w, tokens, 0, 30, 32)
    part = decoder.served_logits(shape, w, tokens[:25], 10, 15, 64)
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole[10:25]),
                               atol=1e-5, rtol=1e-5)


def test_lower_precision_differs():
    shape = tiny_shape()
    w = make_weights(shape, 7)
    tokens = np.arange(30, dtype=np.int32)
    ref = np.asarray(decoder.served_logits(shape, w, tokens, 0, 30, 32))
    for q in ("int8", "fp8"):
        low = np.asarray(decoder.served_logits(shape, w, tokens, 0, 30, 32,
                                               quant=q))
        err = np.max(np.abs(low - ref)) / np.max(np.abs(ref))
        assert 1e-4 < err < 0.5, (q, err)
    with pytest.raises(ValueError):
        decoder.served_logits(shape, w, tokens, 0, 30, 32, quant="int4")


def test_weights_follow_the_seed():
    shape = tiny_shape()
    a, b = make_weights(shape, 2 ** 40 + 1), make_weights(shape, 2 ** 40 + 1)
    c = make_weights(shape, 1)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool(jnp.array_equal(x, y)), a, b)))
    assert not jnp.array_equal(a["embed"], c["embed"])
    assert a["embed"].dtype == jnp.bfloat16
    assert a["layers"]["attn_norm"].dtype == jnp.float32
