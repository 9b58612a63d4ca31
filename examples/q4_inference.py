"""Q4_0 weight-only inference through the Pallas kernels — the paper's
actual compute path (fused dequant-matmul), validated against the float
model, with the KernelTuner picking block configs online.

  PYTHONPATH=src python examples/q4_inference.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.core import KernelTuner, shape_class
from repro.kernels import TunedMatmul, q4_matmul, ref
from repro.models import forward, init_params
from repro.quant import quantize_q4_0, dequantize_q4_0, BYTES_PER_ELEM


def quantize_params(params):
    """Quantize every >=2D matmul weight of the trunk to Q4_0."""
    count = [0]

    def q(path, leaf):
        if leaf.ndim == 2 and min(leaf.shape) >= 32 and leaf.shape[0] % 32 == 0:
            count[0] += 1
            # store as (out, in) for y = x @ W: quantize W^T rows
            return quantize_q4_0(jnp.asarray(leaf).T)
        if leaf.ndim == 3 and min(leaf.shape[1:]) >= 32 and leaf.shape[1] % 32 == 0:
            count[0] += 1  # period-stacked (P, in, out)
            return jax.vmap(lambda w: quantize_q4_0(w.T))(jnp.asarray(leaf))
        return leaf

    return jax.tree_util.tree_map_with_path(q, params), count[0]


def main():
    cfg = reduced_config("granite-8b")
    params = init_params(cfg, jax.random.key(0))
    qparams, n_quant = quantize_params(params)
    print(f"[q4] quantized {n_quant} weight matrices to Q4_0 "
          f"({BYTES_PER_ELEM} bytes/element vs 4)")

    # 1) kernel-level: fused Q4 matmul (Pallas; interpreted on the CPU) vs
    #    float matmul
    w = params["period"][0]["mixer"]["wq"][0]          # (d, H*hd)
    qw = quantize_q4_0(jnp.asarray(w).T)
    x = jax.random.normal(jax.random.key(1), (8, w.shape[0]), jnp.float32)
    y_pallas = q4_matmul(x, qw)
    y_ref = ref.q4_matmul_ref(x, qw)
    y_float = x @ w
    kernel_err = float(jnp.abs(y_pallas - y_ref).max())
    quant_rel = float(jnp.abs(y_pallas - y_float).max() /
                      jnp.abs(y_float).max())
    print(f"[q4] pallas-vs-oracle max err {kernel_err:.2e}; "
          f"quantization rel err {quant_rel:.3f}")

    # 2) model-level: dequantized-weights forward vs float forward (the
    #    paper reports Q4_0 is accurate enough for llama2-7b; here we show
    #    logits stay close on the reduced config)
    def dq(l):
        if not hasattr(l, "packed"):
            return l
        if l.packed.ndim == 3:  # period-stacked
            return jnp.swapaxes(jax.vmap(dequantize_q4_0)(l), 1, 2).astype(cfg.cdtype)
        return dequantize_q4_0(l).T.astype(cfg.cdtype)

    deq = jax.tree_util.tree_map(dq, qparams,
                                 is_leaf=lambda l: hasattr(l, "packed"))
    toks = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab_size)
    lg_f = forward(cfg, params, toks).logits
    lg_q = forward(cfg, deq, toks).logits
    agree = float((jnp.argmax(lg_f, -1) == jnp.argmax(lg_q, -1)).mean())
    rel = float(jnp.linalg.norm(lg_f - lg_q) / jnp.linalg.norm(lg_f))
    print(f"[q4] greedy-token agreement float-vs-Q4: {agree:.1%} "
          f"(logits rel err {rel:.3f}; random-init logits are near-tied, "
          f"trained models agree far more)")

    # 3) online config tuning (the per-ISA table analogue)
    tm = TunedMatmul(KernelTuner(alpha=0.3, min_trials=1))
    for _ in range(4):
        tm.q4(x, qw)
    key = ("q4_matmul", shape_class(8, qw.out_features, x.shape[1]))
    print(f"[q4] tuner selected blocks {tm.tuner.best(key)} for shape "
          f"{shape_class(8, qw.out_features, x.shape[1])}")


if __name__ == "__main__":
    main()
