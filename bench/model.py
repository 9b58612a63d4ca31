"""A configuration's sizes, its seeded weights, and how both are handed to
the engine under test.

``Shape`` reads a configuration file (``bench/configs/<name>.json``): its
``config`` is the published ``config.json`` as run, and its ``shape`` maps
each size the benchmark needs to a key of that config (or gives it as a
constant, with the reason under ``assumed``).

The weights are made here, on the device, in one jitted call from the
seed, in the types they are served in.  Their layout is the benchmark's
own (published names, stacked over layers); :func:`program_params` views
them in the engine's parameter tree without copying.
"""

from __future__ import annotations

import functools
import json
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Shape", "load_config", "make_weights", "weights_seed",
           "program_config", "program_params"]


@dataclass(frozen=True)
class Shape:
    """The sizes of a dense decoder, as the benchmark uses them."""

    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rope_fraction: float
    qkv_bias: bool
    eps: float
    tied: bool = False           # the head is the embedding, transposed

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def rot_dim(self) -> int:
        """Rotated dims per head (even)."""
        rot = int(self.head_dim * self.rope_fraction)
        return rot - rot % 2


def load_config(root: Path, name: str) -> tuple:
    """``(Shape, the configuration file as a dict)``."""
    conf = json.loads((root / "configs" / f"{name}.json").read_text())
    src = conf["config"]

    def get(key):
        spec = conf["shape"].get(key)
        return src[spec] if isinstance(spec, str) else spec

    d, h = int(get("d_model")), int(get("n_heads"))
    hd = get("head_dim")
    shape = Shape(
        name=name, d_model=d, n_layers=int(get("n_layers")), n_heads=h,
        n_kv_heads=int(get("n_kv_heads")),
        head_dim=int(hd) if hd is not None else d // h,
        d_ff=int(get("d_ff")), vocab=int(get("vocab")),
        rope_theta=float(get("rope_theta")),
        rope_fraction=float(get("rope_fraction")),
        qkv_bias=bool(get("qkv_bias")), eps=float(get("eps")),
        tied=bool(get("tied")))
    return shape, conf


def weights_seed(seed: int) -> int:
    """A 32-bit word that depends on every bit of ``seed`` (which may be
    wider than 32 bits)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def _normal(key, shape, scale, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


@functools.partial(jax.jit, static_argnums=0)
def _make(shape: Shape, key) -> dict:
    L, d, f = shape.n_layers, shape.d_model, shape.d_ff
    qd, kvd = shape.q_dim, shape.kv_dim
    ks = iter(jax.random.split(key, 16))
    layers = {
        "attn_norm": 1.0 + _normal(next(ks), (L, d), 0.1, jnp.float32),
        "wq": _normal(next(ks), (L, d, qd), d ** -0.5),
        "wk": _normal(next(ks), (L, d, kvd), d ** -0.5),
        "wv": _normal(next(ks), (L, d, kvd), d ** -0.5),
        "wo": _normal(next(ks), (L, qd, d), qd ** -0.5),
        "mlp_norm": 1.0 + _normal(next(ks), (L, d), 0.1, jnp.float32),
        "w_gate": _normal(next(ks), (L, d, f), d ** -0.5),
        "w_up": _normal(next(ks), (L, d, f), d ** -0.5),
        "w_down": _normal(next(ks), (L, f, d), f ** -0.5),
    }
    if shape.qkv_bias:
        layers["bq"] = _normal(next(ks), (L, qd), 0.1)
        layers["bk"] = _normal(next(ks), (L, kvd), 0.1)
        layers["bv"] = _normal(next(ks), (L, kvd), 0.1)
    w = {
        "embed": _normal(next(ks), (shape.vocab, d), d ** -0.5),
        "layers": layers,
        "final_norm": 1.0 + _normal(next(ks), (d,), 0.1, jnp.float32),
    }
    if not shape.tied:
        w["lm_head"] = _normal(next(ks), (d, shape.vocab), d ** -0.5)
    return w


def make_weights(shape: Shape, seed: int) -> dict:
    """The seeded weights, made on the default device in one program."""
    return _make(shape, jax.random.key(weights_seed(seed), impl="rbg"))


def program_config(shape: Shape):
    """The engine's ``ModelConfig`` for ``shape`` (bf16, dense, SwiGLU)."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=shape.name, family="dense", n_layers=shape.n_layers,
        d_model=shape.d_model, n_heads=shape.n_heads,
        n_kv_heads=shape.n_kv_heads, d_ff=shape.d_ff, vocab_size=shape.vocab,
        head_dim=shape.head_dim, rope_theta=shape.rope_theta,
        rope_fraction=shape.rope_fraction, qkv_bias=shape.qkv_bias,
        tie_embeddings=shape.tied, dtype="bfloat16")


def program_params(w: dict) -> dict:
    """The engine's parameter tree over the same arrays (one scanned
    period of one layer, stacked over ``n_layers``)."""
    lw = w["layers"]
    mixer = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
    mixer.update({k: lw[k] for k in ("bq", "bk", "bv") if k in lw})
    embed = {"tok": w["embed"]}
    if "lm_head" in w:
        embed["out"] = w["lm_head"]
    return {
        "embed": embed,
        "period": [{
            "norm1": {"w": lw["attn_norm"]},
            "mixer": mixer,
            "norm2": {"w": lw["mlp_norm"]},
            "ffn": {"wg": lw["w_gate"], "wi": lw["w_up"],
                    "wo": lw["w_down"]},
        }],
        "final_norm": {"w": w["final_norm"]},
    }
