"""Plain float32 reference of a dense pre-norm decoder, from the published
description of Llama-style models (granite-8b) and ChatGLM:

  x = embed[tokens]
  per layer:  h = rmsnorm(x) * g1
              q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)
              rotary on the first ``rot_dim`` dims of every head of q and k,
                  pairs (2i, 2i + 1), angle pos / theta^(2i / rot_dim)
              grouped-query causal softmax attention, scale 1/sqrt(head_dim)
              x = x + attn Wo
              h = rmsnorm(x) * g2
              x = x + (silu(h Wgate) * (h Wup)) Wdown
  logits = (rmsnorm(x) * g) Whead      (Whead = embed^T where tied)

No cache, no batching and no kernels: the whole sequence at once, one layer
per call, every matmul at ``Precision.HIGHEST``.  It imports nothing of the
engine under test.

``quant`` computes the linear layers in a lower precision instead (the
benchmark's control): ``"int8"`` quantizes weights per output column and
activations per row to int8 and multiplies in int32; ``"fp8"`` rounds both
to float8 e4m3 with the same scaling.  Attention stays in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block

__all__ = ["served_logits", "QUANTS"]

QUANTS = (None, "int8", "fp8")


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _quantize(a, axis, quant):
    """``a`` scaled so that its largest magnitude along ``axis`` hits the
    format's largest value, rounded to the format; returns (q, scale)."""
    top = 127.0 if quant == "int8" else 448.0
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if quant == "int8":
        q = jnp.clip(jnp.rint(a / scale), -127, 127).astype(jnp.int8)
    else:
        q = (a / scale).astype(jnp.float8_e4m3fn)
    return q, scale


def _linear(x, w, quant):
    if quant is None:
        return jnp.dot(x, w, precision=HIGHEST)
    xq, xs = _quantize(x, -1, quant)
    wq, ws = _quantize(w, 0, quant)
    if quant == "int8":
        acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32)
    else:
        y = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32),
                    precision=HIGHEST)
    return y * xs * ws


def _rope(x, pos, rot, theta):
    """x (T, H, hd); rotate dims [0, rot) in interleaved pairs."""
    if rot == 0:
        return x
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (T, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    rotated = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate(
        [rotated.reshape(*x.shape[:-1], rot), x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(shape, x, layers, index, quant):
    lw = {k: jax.lax.dynamic_index_in_dim(v, index, keepdims=False)
          .astype(jnp.float32) for k, v in layers.items()}
    t = x.shape[0]
    hq, hkv, hd = shape.n_heads, shape.n_kv_heads, shape.head_dim
    pos = jnp.arange(t)

    h = _rmsnorm(x, lw["attn_norm"], shape.eps)
    q, k, v = (_linear(h, lw[n], quant) for n in ("wq", "wk", "wv"))
    if shape.qkv_bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = _rope(q.reshape(t, hq, hd), pos, shape.rot_dim, shape.rope_theta)
    k = _rope(k.reshape(t, hkv, hd), pos, shape.rot_dim, shape.rope_theta)
    v = v.reshape(t, hkv, hd)
    q = q.reshape(t, hkv, hq // hkv, hd)

    outs = []
    for start in range(0, t, Q_BLOCK):       # causal: keys up to the block end
        end = min(start + Q_BLOCK, t)
        s = jnp.einsum("qhgd,khd->hgqk", q[start:end], k[:end],
                       precision=HIGHEST) * hd ** -0.5
        allowed = pos[:end][None, :] <= pos[start:end][:, None]
        s = jnp.where(allowed, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", p, v[:end],
                               precision=HIGHEST))
    attn = jnp.concatenate(outs, 0).reshape(t, hq * hd)
    x = x + _linear(attn, lw["wo"], quant)

    h = _rmsnorm(x, lw["mlp_norm"], shape.eps)
    mlp = (jax.nn.silu(_linear(h, lw["w_gate"], quant))
           * _linear(h, lw["w_up"], quant))
    return x + _linear(mlp, lw["w_down"], quant)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _head(shape, x, final_norm, head, first, rows, quant):
    x = jax.lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    h = _rmsnorm(x, final_norm, shape.eps)
    head = head.astype(jnp.float32)
    return _linear(h, head.T if shape.tied else head, quant)


def served_logits(shape, w, tokens, first: int, rows: int, length: int,
                  quant=None):
    """Logits ``(rows, vocab)`` at positions ``first .. first + rows - 1``
    of ``tokens`` (at most ``length`` ids, padded to ``length`` so that
    every call has one shape; causal attention keeps the padding out of
    the rows asked for)."""
    if quant not in QUANTS:
        raise ValueError(f"unknown precision {quant!r}")
    n = len(tokens)
    if n > length or first + rows > length:
        raise ValueError(f"{n} tokens, rows {first}+{rows} exceed {length}")
    ids = jnp.zeros((length,), jnp.int32).at[:n].set(jnp.asarray(tokens))
    with jax.default_matmul_precision("highest"):
        x = _embed(w["embed"], ids)
        for i in range(shape.n_layers):
            x = _layer(shape, x, w["layers"], jnp.int32(i), quant)
        head = w["embed"] if shape.tied else w["lm_head"]
        return _head(shape, x, w["final_norm"], head,
                     jnp.int32(first), rows, quant)
