"""Operations and bytes that a step needs, from the shapes alone.

"Needed" means what the model's arithmetic asks for, not what a program
happens to do: causal attention over the valid positions only, the LM
head only where logits are used, and for decode only the rows that hold
a request.  Bytes are those a step must move through HBM at least once:
every weight, the K/V of the valid positions and the new K/V rows.
"""

from __future__ import annotations

from typing import Sequence

__all__ = ["linear_params", "weight_bytes", "prefill_flops",
           "decode_flops", "decode_bytes", "kv_bytes_per_token"]

BF16 = 2
F32 = 4


def linear_params(shape) -> int:
    """Weights of one layer's projections (q, k, v, o, gate, up, down)."""
    d, f = shape.d_model, shape.d_ff
    return d * (shape.q_dim + 2 * shape.kv_dim) + shape.q_dim * d + 3 * d * f


def weight_bytes(shape) -> int:
    """Every weight a forward step reads: the trunk's projections and the
    LM head in bf16, QKV biases in bf16, norm weights in f32 (the
    embedding is gathered a row per token and left out)."""
    L, d = shape.n_layers, shape.d_model
    trunk = L * linear_params(shape) * BF16
    bias = L * (shape.q_dim + 2 * shape.kv_dim) * BF16 if shape.qkv_bias else 0
    norms = (2 * L + 1) * d * F32
    return trunk + bias + norms + d * shape.vocab * BF16


def kv_bytes_per_token(shape) -> int:
    """K and V of one position in every layer, bf16."""
    return shape.n_layers * 2 * shape.kv_dim * BF16


def _attn_flops(shape, n_pairs: float) -> float:
    """QK^T and PV over ``n_pairs`` (query, key) pairs in every layer."""
    return shape.n_layers * 2 * 2 * shape.q_dim * n_pairs


def prefill_flops(shape, length: int, start: int) -> float:
    """One prompt piece of ``length`` tokens after ``start`` cached ones;
    logits at its last position only."""
    pairs = length * start + length * (length + 1) / 2
    return (2 * shape.n_layers * linear_params(shape) * length
            + _attn_flops(shape, pairs) + 2 * shape.d_model * shape.vocab)


def decode_flops(shape, kv_lens: Sequence[int]) -> float:
    """One decode step; ``kv_lens`` has, for each row holding a request,
    the positions its new token attends to (itself included)."""
    per_row = (2 * shape.n_layers * linear_params(shape)
               + 2 * shape.d_model * shape.vocab)
    return len(kv_lens) * per_row + _attn_flops(shape, sum(kv_lens))


def decode_bytes(shape, kv_lens: Sequence[int]) -> float:
    """Weights once, plus every valid K/V position of every row holding a
    request (the new rows included)."""
    return weight_bytes(shape) + kv_bytes_per_token(shape) * sum(kv_lens)
