"""GQA attention with rotary embeddings, KV cache, and query-chunking.

Memory discipline: scores are never materialized for more than one query
chunk at a time (``cfg.attn_chunk``) — a pure-JAX flash-attention analogue
(the online-softmax Pallas kernel is a hillclimb candidate, see §Perf).
GQA is computed in grouped form (no KV head repetition is materialized).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import _dense, apply_rope

NEG_INF = -1e30


class KVCache(NamedTuple):
    """One period position's cache, every field stacked over the position's
    L layers (the trunk's repeats); ``attn_fwd`` reads and writes one layer
    of the stack."""
    k: jax.Array    # (L, B, Hkv, S_max, hd)
    v: jax.Array    # (L, B, Hkv, S_max, hd)
    idx: jax.Array  # (L,) int32 — number of valid positions; or (L, B) int32
                    # for slot-batched serving where every row advances
                    # independently (continuous batching)


def init_attn(cfg: ModelConfig, key) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    dt = cfg.cdtype
    p = {
        "wq": _dense(k1, d, cfg.n_heads * hd, dt),
        "wk": _dense(k2, d, cfg.n_kv_heads * hd, dt),
        "wv": _dense(k3, d, cfg.n_kv_heads * hd, dt),
        "wo": _dense(k4, cfg.n_heads * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
    return p


def _sdpa_grouped(q, k, v, q_pos, kv_pos, kv_len) -> jax.Array:
    """Grouped scaled-dot-product attention on one query chunk.

    q: (B, Hkv, G, Sq, hd);  k, v: (B, Hkv, Skv, hd)
    q_pos: (B, Sq) global query positions; kv_pos: (Skv,);
    kv_len: () number of valid kv entries (cache may be partially filled),
    or (B,) when each row's cache fill differs (slot-batched decode).
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bhgqd,bhsd->bhgqs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    kv_len = jnp.asarray(kv_len)
    if kv_len.ndim == 1:
        kv_len = kv_len[:, None, None]  # (B, 1, 1) against (B, Sq, Skv)
    allowed = (kv_pos[None, :] <= q_pos[..., None]) & (kv_pos < kv_len)
    scores = jnp.where(allowed[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqs,bhsd->bhgqd", probs, v.astype(jnp.float32))
    return out.astype(v.dtype)


def _append(buf: jax.Array, new: jax.Array, layer, idx) -> jax.Array:
    """Write ``new`` (B, Hkv, s, hd) into the layer stack ``buf``
    (L, B, Hkv, S_max, hd) at ``(layer, b, h, idx[b] + t, :)`` as one
    scatter of ``hd``-wide rows, so a donated stack is updated in place.
    ``idx`` is () or (B,).  A position at or past ``S_max`` is dropped,
    never clamped: a freed slot's drifting index writes nothing, and no
    row writes into another.  (Rows of ``hd``, not (s, hd) blocks: the
    TPU compiler emits the row scatter as one fused kernel, but expands a
    block scatter into a loop over blocks; the sorted and unique hints
    make the 2048-row prefill write about twice as fast on a v5e.)"""
    b, h, s = new.shape[:3]
    starts = jnp.stack(jnp.broadcast_arrays(
        jnp.asarray(layer, jnp.int32),
        jnp.arange(b)[:, None, None],
        jnp.arange(h)[None, :, None],
        (jnp.broadcast_to(idx, (b,)).astype(jnp.int32)[:, None, None]
         + jnp.arange(s))), axis=-1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(3,), inserted_window_dims=(0, 1, 2, 3),
        scatter_dims_to_operand_dims=(0, 1, 2, 3))
    return jax.lax.scatter(
        buf, starts, new.astype(buf.dtype), dims, indices_are_sorted=True,
        unique_indices=True, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def attn_fwd(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[KVCache] = None,
    proj: Optional[callable] = None,
    layer=0,
) -> tuple[jax.Array, Optional[KVCache]]:
    """x: (B, S, d); positions: (B, S) global positions of these tokens.

    Without cache: plain causal self-attention (training).
    With cache — the stack of every layer of this period position, each
    field with a leading (L,) axis — appends this chunk's K/V to layer
    ``layer`` at its ``idx`` (prefill writes a block, decode writes one
    token), attends over everything valid in that layer, and returns the
    updated stack.  ``proj(name, x, w)`` overrides each projection matmul
    (balanced hybrid dispatch of the trunk); default is the in-graph
    ``x @ w``.  A ``proj`` carrying a ``qkv`` attribute fuses the three
    input projections into one call (one jit-bridge round trip per layer
    instead of three).
    """
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = hq // hkv

    mm = proj or (lambda name, x, w: x @ w)
    fused_qkv = getattr(mm, "qkv", None)
    if fused_qkv is not None:
        q, k, v = fused_qkv(x, p["wq"], p["wk"], p["wv"])
    else:
        q = mm("wq", x, p["wq"])
        k = mm("wk", x, p["wk"])
        v = mm("wv", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)

    q = apply_rope(q, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    k = apply_rope(k, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)

    if cache is not None:
        idx = cache.idx[layer]
        new_cache = KVCache(
            k=_append(cache.k, k, layer, idx),
            v=_append(cache.v, v, layer, idx),
            idx=cache.idx.at[layer].add(s))
        k_all, v_all = new_cache.k[layer], new_cache.v[layer]
        kv_pos = jnp.arange(k_all.shape[2])
        kv_len = idx + s
    else:
        k_all, v_all = k, v
        new_cache = None
        kv_pos = jnp.arange(s)
        kv_len = jnp.asarray(s)

    qg = q.reshape(b, hkv, g, s, hd)
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None, :], (b, s))

    chunk = cfg.attn_chunk
    if s <= chunk or s % chunk:
        out = _sdpa_grouped(qg, k_all, v_all, positions, kv_pos, kv_len)
    else:
        n_chunks = s // chunk
        qc = qg.reshape(b, hkv, g, n_chunks, chunk, hd).transpose(3, 0, 1, 2, 4, 5)
        pc = positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

        def body(carry, inp):
            qi, pi = inp
            return carry, _sdpa_grouped(qi, k_all, v_all, pi, kv_pos, kv_len)

        _, outs = jax.lax.scan(body, None, (qc, pc))
        out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, hkv, g, s, hd)

    out = out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return mm("wo", out, p["wo"]).astype(x.dtype), new_cache
