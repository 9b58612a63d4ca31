"""Mixture-of-Experts layer with capacity-bounded sort-based dispatch.

Dispatch is the sort/scatter formulation (no (T, E, C) one-hot einsum — that
tensor is ~5e12 elements for llama4-maverick at train_4k): token->expert
assignments are sorted by expert id, positions within each expert segment
become buffer offsets, and overflow beyond the expert's capacity is dropped.
Expert compute is a static (E, C, d) x (E, d, f) einsum, shardable with E on
the 'model' axis (expert parallelism); GSPMD inserts the dispatch/combine
collectives.

Paper integration (first-class): expert load imbalance is the MoE
incarnation of the paper's hybrid-core imbalance.  Two Eq.-3 mechanisms:

* :class:`repro.runtime.ExpertCapacityPlanner` retunes the static
  capacity between recompiles from the load EMA (slow loop);
* :func:`balanced_expert_assignment` (here) computes an LPT expert->shard
  permutation from the load EMA so each EP shard carries equal expected
  load (fast loop, a pure weight/router-column permutation).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from .layers import _dense


def default_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor)
    return max(8, (c + 7) // 8 * 8)  # MXU-friendly multiple of 8


def init_moe(cfg: ModelConfig, key) -> dict:
    m = cfg.moe
    dff = m.d_ff or cfg.d_ff
    d, e = cfg.d_model, m.n_experts
    dt = cfg.cdtype
    ks = jax.random.split(key, 7)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, dff)) * d ** -0.5).astype(dt),
        "wg": (jax.random.normal(ks[2], (e, d, dff)) * d ** -0.5).astype(dt),
        "wo": (jax.random.normal(ks[3], (e, dff, d)) * dff ** -0.5).astype(dt),
    }
    if m.shared_expert:
        p["swi"] = _dense(ks[4], d, dff, dt)
        p["swg"] = _dense(ks[5], d, dff, dt)
        p["swo"] = _dense(ks[6], dff, d, dt)
    return p


def _dispatch(cfg: ModelConfig, xf: jax.Array, probs: jax.Array, c: int):
    """Sort-based dispatch of ``xf`` (T, d) into an (E, C, d) buffer.

    Returns (buf, dest, st, swk, counts) — all index arrays are local to
    this token shard (the combine must use the same shard).
    """
    m = cfg.moe
    t, d = xf.shape
    e, k = m.n_experts, m.top_k
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                       # (T*k,)
    flat_w = top_p.reshape(-1)
    tok_of = jnp.arange(t * k, dtype=jnp.int32) // k

    order = jnp.argsort(flat_e, stable=True)
    se, sw, st = flat_e[order], flat_w[order], tok_of[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    seg_start = jnp.cumsum(counts) - counts          # (E,)
    seg_pos = jnp.arange(t * k, dtype=jnp.int32) - seg_start[se]
    keep = seg_pos < c
    dest = jnp.where(keep, se * c + seg_pos, e * c - 1)

    gathered = xf[st] * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((e * c, d), xf.dtype).at[dest].add(gathered)
    return buf.reshape(e, c, d), dest, st, (sw * keep).astype(xf.dtype), counts


def _combine(out_buf: jax.Array, dest, st, swk, t: int, dtype) -> jax.Array:
    e, c, d = out_buf.shape
    contrib = out_buf.reshape(e * c, d)[dest] * swk[:, None].astype(out_buf.dtype)
    return jnp.zeros((t, d), dtype).at[st].add(contrib.astype(dtype))


def _expert_ffn(p: dict, buf: jax.Array) -> jax.Array:
    """Expert SwiGLU on the (E, C, d) buffer.  With E sharded on 'model'
    and C sharded on the data axes this is a pure block-local einsum."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["wi"]
    )
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def moe_fwd(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    capacity: Optional[int] = None,
) -> tuple[jax.Array, dict]:
    """x: (B, S, d) -> (y, aux) with aux = {lb_loss, load, dropped}.

    Distribution: when an activation-sharding mesh is installed and the
    token count divides the data axes, dispatch/combine run *per data
    shard* under shard_map (local argsort/scatter — no global token
    gather; measured ~100x wire reduction on llama4 train vs the naive
    GSPMD lowering of a global sort).  Expert compute stays a GSPMD einsum
    with E on 'model' and C on the data axes (block-local).
    """
    from repro.sharding.specs import current_mesh, data_axes
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32)) @ p["router"]  # (T, E) f32
    probs = jax.nn.softmax(logits, axis=-1)

    mesh = current_mesh()
    import math as _math
    dp = data_axes(mesh) if mesh is not None else ()
    dp_size = _math.prod(mesh.shape[a] for a in dp) if mesh is not None else 1
    local_path = mesh is not None and dp_size > 1 and t % dp_size == 0 \
        and (t // dp_size) >= 1

    tp_size = mesh.shape.get("model", 1) if mesh is not None else 1
    # EP all-to-all moves token buffers but requires the (FSDP-sharded)
    # expert weights gathered per layer — worth it only when the token
    # volume is large (train/prefill).  Decode (a handful of tokens) must
    # keep weights stationary: the GSPMD einsum path reshard's the tiny
    # buffer instead.
    tokens_per_expert = (t // dp_size) * k / e if dp_size else t * k / e
    ep_path = (local_path and tp_size > 1 and e % tp_size == 0
               and tokens_per_expert >= 8)

    if ep_path:
        # Full expert parallelism: dispatch locally per data shard, exchange
        # expert chunks with all-to-all over 'model', run the e/tp local
        # experts, reverse the exchange, combine locally.  Wire per trip =
        # 2 x buffer bytes (fwd) [+ same bwd] — no buffer-sized gathers.
        t_l = t // dp_size
        c = capacity if capacity is not None else default_capacity(cfg, t_l)
        c = max(8, min(c, t_l * k))

        def moe_local(xf_l, probs_l, wg_l, wi_l, wo_l):
            buf, dest, st, swk, counts = _dispatch(cfg, xf_l, probs_l, c)
            # (E, c, d) -> (E/tp, c*tp, d): expert chunks to their owners
            bufx = jax.lax.all_to_all(buf, "model", split_axis=0,
                                      concat_axis=1, tiled=True)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufx, wg_l)) * \
                jnp.einsum("ecd,edf->ecf", bufx, wi_l)
            outx = jnp.einsum("ecf,efd->ecd", h, wo_l)
            out = jax.lax.all_to_all(outx, "model", split_axis=1,
                                     concat_axis=0, tiled=True)
            y_l = _combine(out, dest, st, swk, t_l, x.dtype)
            return y_l, counts[None, :]

        y, counts_g = jax.shard_map(
            moe_local,
            mesh=mesh,
            in_specs=(P(dp, None), P(dp, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(dp, None), P(dp, None)),
            check_vma=False,
        )(xf, probs, p["wg"], p["wi"], p["wo"])
        counts = counts_g.sum(0)
        dropped = 1.0 - jnp.minimum(counts, c).sum() / jnp.maximum(
            counts.sum(), 1).astype(jnp.float32)
    elif local_path:
        t_l = t // dp_size
        c = capacity if capacity is not None else default_capacity(cfg, t_l)
        c = max(8, min(c, t_l * k))

        def dispatch_local(xf_l, probs_l):
            buf, dest, st, swk, counts = _dispatch(cfg, xf_l, probs_l, c)
            return buf, dest, st, swk, counts[None, :]

        buf, dest, st, swk, counts_g = jax.shard_map(
            dispatch_local,
            mesh=mesh,
            in_specs=(P(dp, None), P(dp, None)),
            out_specs=(P(None, dp, None), P(dp), P(dp), P(dp), P(dp, None)),
        )(xf, probs)

        out_buf = _expert_ffn(p, buf)

        def combine_local(out_buf_l, dest_l, st_l, swk_l):
            return _combine(out_buf_l, dest_l, st_l, swk_l, t_l, x.dtype)

        y = jax.shard_map(
            combine_local,
            mesh=mesh,
            in_specs=(P(None, dp, None), P(dp), P(dp), P(dp)),
            out_specs=P(dp, None),
        )(out_buf, dest, st, swk)
        counts = counts_g.sum(0)
        dropped = 1.0 - jnp.minimum(counts, c).sum() / jnp.maximum(
            counts.sum(), 1).astype(jnp.float32)
    else:
        c = capacity if capacity is not None else default_capacity(cfg, t)
        buf, dest, st, swk, counts = _dispatch(cfg, xf, probs, c)
        if mesh is not None:
            from repro.sharding.specs import constrain
            # move the (small) buffer to the experts, not the other way
            buf = constrain(buf, ("tp", None, None))
        out_buf = _expert_ffn(p, buf)
        y = _combine(out_buf, dest, st, swk, t, x.dtype)
        dropped = 1.0 - jnp.minimum(counts, c).sum() / jnp.maximum(
            counts.sum(), 1).astype(jnp.float32)

    if m.shared_expert:
        sh = jax.nn.silu(xf @ p["swg"]) * (xf @ p["swi"])
        y = y + (sh @ p["swo"]).astype(x.dtype)

    # Switch-style load-balance loss + telemetry for the capacity planner.
    frac = counts.astype(jnp.float32) / jnp.maximum(counts.sum(), 1)
    mean_prob = probs.mean(axis=0)
    aux = {
        "lb_loss": e * jnp.sum(frac * mean_prob),
        "load": counts.astype(jnp.float32),
        "dropped": dropped,
    }
    return y.reshape(b, s, d), aux


# ------------------------------------------------------- expert placement --
def balanced_expert_assignment(load: np.ndarray, n_shards: int) -> np.ndarray:
    """LPT (longest-processing-time) expert->shard placement.

    Returns a permutation ``perm`` of expert ids such that slicing
    ``perm`` into ``n_shards`` contiguous blocks yields near-equal summed
    load per block — Eq. 3 applied to EP shards, realized as placement
    because per-shard *capacity* must stay static for XLA.
    """
    load = np.asarray(load, dtype=np.float64)
    e = len(load)
    if e % n_shards:
        raise ValueError(f"{e} experts not divisible by {n_shards} shards")
    per = e // n_shards
    shard_load = np.zeros(n_shards)
    shard_members: list[list[int]] = [[] for _ in range(n_shards)]
    for idx in np.argsort(-load):
        open_shards = [s for s in range(n_shards) if len(shard_members[s]) < per]
        s = min(open_shards, key=lambda s: shard_load[s])
        shard_members[s].append(int(idx))
        shard_load[s] += load[idx]
    return np.concatenate([np.array(ms, dtype=np.int64) for ms in shard_members])


def apply_expert_permutation(p: dict, perm: np.ndarray) -> dict:
    """Permute expert-stacked params (and router columns) so that logical
    expert ``perm[i]`` lives at position ``i``.  Forward output is invariant.
    """
    perm = jnp.asarray(perm)
    q = dict(p)
    q["router"] = p["router"][:, perm]
    for name in ("wi", "wg", "wo"):
        q[name] = p[name][perm]
    return q
