"""Model trunk: composes mixers (attn/mamba/mlstm/slstm) + FFNs (dense/moe)
into the per-architecture layer plan, scanning over repeated periods.

Compile-time discipline: layers are grouped into the smallest repeating
(mixer, ffn) *period* (see ``ModelConfig.period``); parameters of each
period position are stacked over repeats and the trunk is a single
``lax.scan`` whose body applies one period.  A 72-layer jamba therefore
lowers as one 8-layer body — HLO size and compile time stay bounded across
the whole zoo.

States (KV caches / SSM / xLSTM states) follow the same stacking and ride
in the scan's carry: each layer updates its repeat of the stack in place.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.specs import constrain, constrain_tree
from . import attention as A
from . import moe as M
from . import ssm as S
from . import xlstm as X
from .layers import (
    _norm_init,
    embed_fwd,
    init_embedding,
    init_mlp,
    logits_fwd,
    mlp_fwd,
    norm_fwd,
)

MIXER_INIT = {
    "attn": A.init_attn,
    "mamba": S.init_mamba,
    "mlstm": X.init_mlstm,
    "slstm": X.init_slstm,
}

# mixers whose decoding state is a small recurrent state, not a KV cache
RECURRENT_FWD = {
    "mamba": S.mamba_fwd,
    "mlstm": X.mlstm_fwd,
    "slstm": X.slstm_fwd,
}


def _init_layer(cfg: ModelConfig, key, mixer: str, ffn: str) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict[str, Any] = {
        "norm1": _norm_init(cfg, k1),
        "mixer": MIXER_INIT[mixer](cfg, k2),
    }
    if ffn != "none":
        p["norm2"] = _norm_init(cfg, k3)
        p["ffn"] = M.init_moe(cfg, k4) if ffn == "moe" else init_mlp(cfg, k4)
    return p


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_stack(cfg: ModelConfig, keys, mixer: str, ffn: str) -> dict:
    """One period position's parameters for every repeat (one key each),
    stacked on a leading axis.  Built as one program, so the stack is
    written in place: stacking per-repeat trees would hold the trunk twice,
    which at published widths does not fit one chip."""
    return jax.vmap(lambda k: _init_layer(cfg, k, mixer, ffn))(keys)


def init_params(cfg: ModelConfig, key) -> dict:
    """Returns {"embed": ..., "period": [stacked per-position params],
    "final_norm": ...}."""
    period = cfg.period()
    n_rep = cfg.n_periods
    keys = jax.random.split(key, n_rep * len(period) + 2)
    # repeat i of position j draws from keys[i * len(period) + j]
    stacked = [_init_stack(cfg, keys[j:n_rep * len(period):len(period)],
                           mixer, ffn)
               for j, (mixer, ffn) in enumerate(period)]
    return {
        "embed": init_embedding(cfg, keys[-2]),
        "period": stacked,
        "final_norm": _norm_init(cfg, keys[-1]),
    }


def abstract_params(cfg: ModelConfig, key=None) -> dict:
    """ShapeDtypeStruct pytree (no allocation) — dry-run weights."""
    k = jax.random.key(0) if key is None else key
    return jax.eval_shape(lambda: init_params(cfg, k))


# --------------------------------------------------------------- states ---
def init_state(cfg: ModelConfig, batch: int, max_seq: int):
    """Per-period-position stacked decoding state."""
    period = cfg.period()
    n_rep = cfg.n_periods
    out = []
    for mixer, _ in period:
        if mixer == "attn":
            one = lambda: A.KVCache(
                k=jnp.zeros((batch, cfg.n_kv_heads, max_seq, cfg.hd), cfg.cdtype),
                v=jnp.zeros((batch, cfg.n_kv_heads, max_seq, cfg.hd), cfg.cdtype),
                idx=jnp.zeros((), jnp.int32),
            )
        elif mixer == "mamba":
            one = lambda: S.init_mamba_state(cfg, batch)
        elif mixer == "mlstm":
            one = lambda: X.init_mlstm_state(cfg, batch)
        elif mixer == "slstm":
            one = lambda: X.init_slstm_state(cfg, batch)
        else:
            raise ValueError(mixer)
        reps = [one() for _ in range(n_rep)]
        out.append(jax.tree.map(lambda *xs: jnp.stack(xs), *reps))
    return out


def abstract_state(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.eval_shape(lambda: init_state(cfg, batch, max_seq))


def init_slot_state(cfg: ModelConfig, n_slots: int, max_seq: int):
    """Like :func:`init_state` but with per-row KV-cache indices: each of the
    ``n_slots`` batch rows advances through its cache independently, which is
    what a continuous-batching decode batch needs (rows are unrelated
    requests at different positions)."""
    state = init_state(cfg, n_slots, max_seq)

    def widen(leaf):
        if not isinstance(leaf, A.KVCache):
            return leaf  # SSM/xLSTM states already carry a batch axis
        # stacked over period repeats: k (n_rep, B, ...), idx (n_rep,)
        return A.KVCache(
            k=leaf.k, v=leaf.v,
            idx=jnp.zeros((leaf.k.shape[0], n_slots), jnp.int32))

    return jax.tree.map(widen, state,
                        is_leaf=lambda x: isinstance(x, A.KVCache))


# -------------------------------------------------------------- forward ---
class ForwardOut(NamedTuple):
    logits: jax.Array
    state: Any
    aux: dict


def _apply_layer(cfg, mixer, ffn, p, x, positions, state, layer, capacity,
                 proj_attn=None, proj_ffn=None):
    """One layer.  ``state`` is this period position's state stacked over
    its repeats (or None); the layer reads repeat ``layer`` of it and
    returns the stack with that repeat updated: attention appends its new
    K/V rows in place, the small SSM/xLSTM states replace their entry."""
    h = norm_fwd(cfg, p["norm1"], x)
    if mixer == "attn":
        mix, state = A.attn_fwd(cfg, p["mixer"], h, positions, state,
                                proj=proj_attn, layer=layer)
    elif mixer in RECURRENT_FWD:
        st = None if state is None else jax.tree.map(lambda a: a[layer], state)
        mix, st = RECURRENT_FWD[mixer](cfg, p["mixer"], h, st)
        if state is not None:
            state = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), layer, 0), state, st)
    else:
        raise ValueError(mixer)
    x = x + mix
    aux = None
    if ffn != "none":
        h2 = norm_fwd(cfg, p["norm2"], x)
        if ffn == "moe":
            y, aux = M.moe_fwd(cfg, p["ffn"], h2, capacity)
        else:
            y = mlp_fwd(cfg, p["ffn"], h2, proj=proj_ffn)
        x = x + y
    return x, state, aux


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: Optional[jax.Array] = None,
    *,
    embeds: Optional[jax.Array] = None,
    prefix_embeds: Optional[jax.Array] = None,
    state: Optional[list] = None,
    pos_offset: jax.Array | int = 0,
    capacity: Optional[int] = None,
    logits_mode: str = "all",
    apply_head: bool = True,
    remat: bool = False,
    trunk=None,
    trunk_isa: str = "membw",
    trunk_offsets=None,
) -> ForwardOut:
    """Trunk forward.

    tokens: (B, S) int32 — or ``embeds`` (B, S, d) for embed-input archs
    (musicgen stub).  ``prefix_embeds`` (B, P, d) is prepended (internvl2
    stub).  ``state`` enables prefill/decode (returned updated).
    ``apply_head=False`` skips the LM-head matmul and returns the final-
    normed hidden states in the ``logits`` slot — for callers that run the
    head outside the jitted trunk (balanced hybrid kernel dispatch).

    ``trunk`` (a :class:`~repro.models.balanced.BalancedTrunk`) reroutes
    every supported projection through balanced per-core shard dispatch
    under the ``trunk_isa`` execution ISA (the caller's phase: "membw"
    decode / "avx_vnni" prefill).  The period loop is then unrolled in
    Python instead of ``lax.scan`` — each (position, repeat) needs its own
    host-side weight bank, whether the callbacks are traced into a jitted
    step or executed eagerly.  ``trunk_offsets`` (compiled trunks only) is
    the device offset snapshot forwarded to every projection.
    """
    if embeds is not None:
        x = embeds.astype(cfg.cdtype)
    else:
        x = embed_fwd(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x = constrain(x, ("dp", None, None))

    b, s, _ = x.shape
    offset = jnp.asarray(pos_offset)
    if offset.ndim == 1:  # per-row offsets (slot-batched serving)
        positions = offset[:, None] + jnp.arange(s)[None, :]
    else:
        positions = offset + jnp.arange(s)[None, :]
    positions = jnp.broadcast_to(positions, (b, s))

    period = cfg.period()
    have_state = state is not None
    moe_cfg = cfg.moe

    if trunk is not None:
        # Balanced-trunk path: unrolled Python loop over period repeats so
        # each (position, repeat) projection reaches its own host-side
        # balanced layer (static at trace time — the io_callback bridge
        # closes over the concrete weight bank).
        lb = jnp.zeros((), jnp.float32)
        dropped = jnp.zeros((), jnp.float32)
        st = list(state) if have_state else None
        for r in range(cfg.n_periods):
            for j, (mixer, ffn) in enumerate(period):
                p_j = jax.tree.map(lambda a, r=r: a[r], params["period"][j])
                x, st_j, aux = _apply_layer(
                    cfg, mixer, ffn, p_j, x, positions,
                    st[j] if have_state else None, r, capacity,
                    proj_attn=trunk.projector(j, r, "attn", trunk_isa,
                                              offsets=trunk_offsets),
                    proj_ffn=trunk.projector(j, r, "ffn", trunk_isa,
                                             offsets=trunk_offsets),
                )
                x = constrain(x, ("dp", None, None))
                if have_state:
                    st[j] = st_j
                if aux is not None:
                    lb = lb + aux["lb_loss"]
                    dropped = dropped + aux["dropped"]
    else:
        # The state rides in the scan's carry: each layer reads its repeat by
        # index and writes back only what it changed, so a donated state is
        # updated in place.
        def period_body(carry, xs):
            x, lb, dropped, st = carry
            p_stack, r = xs
            for j, (mixer, ffn) in enumerate(period):
                x, st_j, aux = _apply_layer(
                    cfg, mixer, ffn, p_stack[j], x, positions,
                    st[j] if have_state else None, r, capacity)
                # anchor sharding propagation inside the while body (GSPMD
                # does not reliably propagate through scan+remat)
                x = constrain(x, ("dp", None, None))
                if have_state:
                    st[j] = st_j
                if aux is not None:
                    lb = lb + aux["lb_loss"]
                    dropped = dropped + aux["dropped"]
            return (x, lb, dropped, st), None

        carry0 = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                  list(state) if have_state else None)
        xs = (params["period"], jnp.arange(cfg.n_periods))
        body = jax.checkpoint(period_body) if remat else period_body
        (x, lb, dropped, st), _ = jax.lax.scan(body, carry0, xs)

    if logits_mode == "last":
        # Serving prefill: only the last position's logits are consumed;
        # slicing *before* the (d x vocab) matmul avoids materializing a
        # (B, S, V) tensor (53 GB/device for llama4 at prefill_32k).
        x = x[:, -1:, :]
    x = norm_fwd(cfg, params["final_norm"], x)
    if apply_head:
        logits = logits_fwd(cfg, params["embed"], x)
        logits = constrain(logits, ("dp", None, "tp"))
    else:
        logits = x.astype(jnp.float32)
    n_moe = max(1, sum(1 for _, f in cfg.layer_plan() if f == "moe"))
    aux = {"lb_loss": lb / n_moe, "dropped": dropped / n_moe}
    return ForwardOut(logits=logits, state=st, aux=aux)


def balanced_lm_head(cfg: ModelConfig, params: dict, dispatcher):
    """Bind the model's LM head to a hybrid kernel dispatcher: the (vocab,
    d_model) head matrix is Q4_0-quantized and every call runs as balanced
    per-core Pallas shards (see
    :class:`~repro.models.layers.BalancedQuantLinear`).  Use with
    ``forward(..., apply_head=False)``: the decode-step Fp32-Int4-Fp32 GEMV
    — the paper's hot path — then executes through the ratio-table loop
    instead of inside the jitted trunk."""
    from .layers import BalancedQuantLinear

    w = (params["embed"]["tok"] if cfg.tie_embeddings
         else params["embed"]["out"].T)  # (vocab, d_model) = (N, K)
    return BalancedQuantLinear.from_dense(w, dispatcher)


def loss_fn(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    *,
    lb_coef: float = 0.01,
    capacity: Optional[int] = None,
    remat: bool = False,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy (+ MoE load-balance loss).

    batch: {"tokens": (B,S), "labels": (B,S) with -100 = ignore} and
    optionally "embeds"/"prefix_embeds" for stub-frontend archs.
    """
    out = forward(
        cfg,
        params,
        batch.get("tokens"),
        embeds=batch.get("embeds"),
        prefix_embeds=batch.get("prefix_embeds"),
        capacity=capacity,
        remat=remat,
    )
    labels = batch["labels"]
    logits = out.logits
    if logits.shape[1] != labels.shape[1]:  # prefix positions carry no loss
        logits = logits[:, logits.shape[1] - labels.shape[1]:, :]
    valid = labels != -100
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(valid.sum(), 1)
    ce = jnp.where(valid, nll, 0.0).sum() / denom
    total = ce + lb_coef * out.aux["lb_loss"]
    metrics = {"loss": total, "ce": ce, "lb": out.aux["lb_loss"],
               "dropped": out.aux["dropped"]}
    return total, metrics
