"""The trunk carries its stacked decoding state through the layer scan and
appends each layer's new K/V rows in place.  These tests hold that path to
a reference that loops over layers in Python with one unstacked cache per
layer and writes each row's positions one by one: logits and every cache
row agree, for slot-batched decode with per-row indices (a freed row's
index drifted past the buffer), for the scalar-index path, and for a
two-lane prefill."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import forward, init_params, init_slot_state, init_state
from repro.models.layers import (apply_rope, embed_fwd, logits_fwd, mlp_fwd,
                                 norm_fwd)
from repro.serving.engine import ServeEngine, step_programs

CFG = ModelConfig(name="t", family="dense", n_layers=3, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32")
PARAMS = init_params(CFG, jax.random.key(0))
MAX_SEQ = 16
TOL = dict(rtol=2e-5, atol=2e-5)


def _reference(tokens, caches, idx, positions):
    """One step of the trunk, layer by layer.  ``caches`` is a list over
    layers of (k, v) numpy arrays (B, Hkv, S, hd), updated in place;
    ``idx`` (B,) is each row's fill before the step; ``positions`` (B, s).
    Returns the last position's logits (B, V)."""
    cfg, p_all = CFG, PARAMS
    b, s = tokens.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    positions = jnp.asarray(positions)
    x = embed_fwd(cfg, p_all["embed"], jnp.asarray(tokens))
    kv_pos = np.arange(MAX_SEQ)
    for layer, (kc, vc) in enumerate(caches):
        p = jax.tree.map(lambda a: a[layer], p_all["period"][0])
        a = p["mixer"]
        h = norm_fwd(cfg, p["norm1"], x)

        def heads(w, n):
            return (h @ w).reshape(b, s, n, hd).transpose(0, 2, 1, 3)

        q = apply_rope(heads(a["wq"], hq), positions, theta=cfg.rope_theta)
        k = np.asarray(apply_rope(heads(a["wk"], hkv), positions,
                                  theta=cfg.rope_theta))
        v = np.asarray(heads(a["wv"], hkv))
        for row in range(b):
            for t in range(s):
                at = idx[row] + t
                if at < MAX_SEQ:  # past the buffer: dropped
                    kc[row, :, at] = k[row, :, t]
                    vc[row, :, at] = v[row, :, t]
        qg = q.reshape(b, hkv, hq // hkv, s, hd).astype(jnp.float32)
        scores = jnp.einsum("bhgqd,bhsd->bhgqs", qg, jnp.asarray(kc)) * hd ** -0.5
        allowed = ((kv_pos[None, None, :] <= np.asarray(positions)[:, :, None])
                   & (kv_pos[None, None, :] < (idx + s)[:, None, None]))
        scores = jnp.where(allowed[:, None, None], scores, -1e30)
        out = jnp.einsum("bhgqs,bhsd->bhgqd", jax.nn.softmax(scores, -1),
                         jnp.asarray(vc))
        out = out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = x + out @ a["wo"]
        x = x + mlp_fwd(cfg, p["ffn"], norm_fwd(cfg, p["norm2"], x))
    x = norm_fwd(cfg, p_all["final_norm"], x[:, -1:])
    return np.asarray(logits_fwd(cfg, p_all["embed"], x))[:, -1]


def _filled(state, rng, idx):
    """``state`` with random cache contents and fill ``idx`` per layer
    ((B,) rows or a scalar)."""
    kv = state[0]
    return [kv._replace(
        k=jnp.asarray(rng.standard_normal(kv.k.shape), kv.k.dtype),
        v=jnp.asarray(rng.standard_normal(kv.v.shape), kv.v.dtype),
        idx=jnp.broadcast_to(jnp.asarray(idx, jnp.int32), kv.idx.shape))]


def _unstack(state):
    """Per-layer (k, v) numpy copies of a stacked state."""
    kv = state[0]
    k, v = np.array(kv.k), np.array(kv.v)
    return [(k[i], v[i]) for i in range(k.shape[0])]


def _assert_state(state, ref_caches, idx):
    kv = state[0]
    for layer, (kc, vc) in enumerate(ref_caches):
        np.testing.assert_allclose(np.asarray(kv.k[layer]), kc, **TOL)
        np.testing.assert_allclose(np.asarray(kv.v[layer]), vc, **TOL)
    np.testing.assert_array_equal(
        np.asarray(kv.idx), np.broadcast_to(idx, kv.idx.shape))


def test_slot_decode_matches_per_layer_reference():
    """Four slots: two mid-sequence, one fresh, one freed whose index has
    drifted past ``max_seq - 1``; five decode steps of the engine's
    donated decode program."""
    rng = np.random.default_rng(1)
    freed = 3
    idx = np.array([5, 11, 0, MAX_SEQ + 2], np.int32)
    pos = np.where(np.arange(4) == freed, 0, idx).astype(np.int32)
    state = _filled(init_slot_state(CFG, 4, MAX_SEQ), rng, idx)
    before = _unstack(state)
    ref = _unstack(state)
    _, _, decode = step_programs(CFG)
    for _ in range(5):
        tok = rng.integers(0, CFG.vocab_size, (4, 1)).astype(np.int32)
        want = _reference(tok, ref, idx, pos[:, None])
        logits, state = decode(PARAMS, jnp.asarray(tok), state,
                               jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits), want, **TOL)
        idx = idx + 1
        pos = np.where(np.arange(4) == freed, 0, pos + 1).astype(np.int32)
        _assert_state(state, ref, idx)
    # the freed row wrote nothing; live rows changed only at their new
    # positions (5 steps from their starting fill)
    start = np.array([5, 11, 0])
    for (k0, v0), k1, v1 in zip(before, state[0].k, state[0].v):
        k1, v1 = np.asarray(k1), np.asarray(v1)
        np.testing.assert_array_equal(k1[freed], k0[freed])
        np.testing.assert_array_equal(v1[freed], v0[freed])
        for row, s0 in enumerate(start):
            keep = np.ones(MAX_SEQ, bool)
            keep[s0:s0 + 5] = False
            np.testing.assert_array_equal(k1[row][:, keep], k0[row][:, keep])
            np.testing.assert_array_equal(v1[row][:, keep], v0[row][:, keep])


def test_scalar_index_prefill_then_decode_matches_reference():
    """The legacy engine's state: one index for every row.  A 5-token
    prefill from position 0, then three decode steps."""
    rng = np.random.default_rng(2)
    eng = ServeEngine(CFG, PARAMS, batch_size=2, max_seq=MAX_SEQ)
    state = _filled(eng.fresh_state(), rng, 0)
    ref = _unstack(state)
    prompt = rng.integers(0, CFG.vocab_size, (2, 5)).astype(np.int32)
    idx = np.zeros(2, np.int32)
    want = _reference(prompt, ref, idx, np.arange(5)[None, :].repeat(2, 0))
    logits, state = eng._prefill(PARAMS, jnp.asarray(prompt), state)
    np.testing.assert_allclose(np.asarray(logits), want, **TOL)
    idx = idx + 5
    _assert_state(state, ref, 5)
    for step in range(3):
        tok = rng.integers(0, CFG.vocab_size, (2, 1)).astype(np.int32)
        want = _reference(tok, ref, idx, idx[:, None])
        logits, state = eng._decode(PARAMS, jnp.asarray(tok), state,
                                    jnp.asarray(5 + step, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits), want, **TOL)
        idx = idx + 1
        _assert_state(state, ref, idx[0])


def test_two_lane_prefill_matches_reference():
    """Two batch-1 partial states prefilled in one call: a fresh lane from
    position 0 and a lane already holding 6 positions."""
    rng = np.random.default_rng(3)
    starts = np.array([0, 6], np.int32)
    lanes = [_filled(init_state(CFG, 1, MAX_SEQ), rng, int(s0))
             for s0 in starts]
    refs = [_unstack(st) for st in lanes]
    tokens = rng.integers(0, CFG.vocab_size, (2, 4)).astype(np.int32)
    _, prefill_lanes, _ = step_programs(CFG)
    logits, rows = prefill_lanes(PARAMS, jnp.asarray(tokens), lanes,
                                 jnp.asarray(starts))
    for i, s0 in enumerate(starts):
        want = _reference(tokens[i:i + 1], refs[i], starts[i:i + 1],
                          s0 + np.arange(4)[None, :])
        np.testing.assert_allclose(np.asarray(logits[i:i + 1]), want, **TOL)
        _assert_state(rows[i], refs[i], s0 + 4)


@pytest.mark.parametrize("fill", [MAX_SEQ - 2, MAX_SEQ + 3])
def test_writes_past_the_buffer_are_dropped(fill):
    """A 4-token chunk at the buffer's edge writes only the positions that
    exist, and a row past the edge writes none; the other row's chunk
    lands at its own positions and nowhere else."""
    rng = np.random.default_rng(4)
    state = _filled(init_slot_state(CFG, 2, MAX_SEQ), rng, [fill, 3])
    before = _unstack(state)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 4)), jnp.int32)
    pos = jnp.asarray([0, 3], jnp.int32)
    out = jax.jit(lambda s: forward(CFG, PARAMS, tokens, state=s,
                                    pos_offset=pos).state)(state)
    edge = min(fill, MAX_SEQ)
    for (k0, _), k1 in zip(before, out[0].k):
        k1 = np.asarray(k1)
        np.testing.assert_array_equal(k1[0][:, :edge], k0[0][:, :edge])
        assert not np.any(k1[0][:, edge:] == k0[0][:, edge:])
        np.testing.assert_array_equal(k1[1][:, :3], k0[1][:, :3])
        assert not np.any(k1[1][:, 3:7] == k0[1][:, 3:7])
        np.testing.assert_array_equal(k1[1][:, 7:], k0[1][:, 7:])
