"""End-to-end training driver with fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --preset tiny \
      --steps 200 --ckpt-dir /tmp/ckpt

Features exercised here (the same code paths the dry-run lowers at pod
scale):
  * config-driven model construction (any assigned arch, or its reduced
    preset for CPU),
  * microbatched train step (remat + optional factored moments),
  * sharded lowering when >1 device is available (data x model mesh),
  * atomic checkpointing + automatic resume (kill the process mid-run and
    relaunch: it continues from the last step, data stream repositioned),
  * straggler telemetry: per-step wall times feed a repro.runtime
    RatioTable persisted next to the checkpoints (RatioStore), so ratios
    warm-start across restarts; at pod scale the UnevenBatchPlanner turns
    this table into per-pod microbatch counts — see examples/train_100m.py.
"""

from __future__ import annotations

import argparse
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore, save
from repro.configs import get_config, reduced_config
from repro.runtime import RatioStore, RatioTable
from repro.data import DataConfig, Prefetcher, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.training import AdamWConfig, init_opt_state, make_train_step


def build_mesh_if_useful():
    n = len(jax.devices())
    if n < 2:
        return None
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--preset", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch) if args.preset == "full" else reduced_config(args.arch)
    if cfg.embed_input or cfg.n_prefix:
        raise SystemExit("use examples/ for stub-frontend archs")

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps,
                          factored=cfg.param_count() > 50e9)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.global_batch,
                          microbatch=args.microbatch)
    data = SyntheticLM(data_cfg)

    params = init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params, opt_cfg)
    start_step = 0

    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            template = jax.eval_shape(lambda: {"params": params, "opt": opt})
            tree, meta = restore(args.ckpt_dir, last, template)
            params, opt = tree["params"], tree["opt"]
            start_step = last
            data.seek(meta["extra"]["data_step"])
            print(f"[train] resumed from step {last}")

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, remat=True))
    table = RatioTable(n_workers=1)  # per-pod table at scale
    store = (RatioStore(os.path.join(args.ckpt_dir, "ratios.json"))
             if args.ckpt_dir else None)
    if store is not None:
        try:
            if store.load_into(table):
                print("[train] warm-started performance ratios from",
                      store.path)
        except Exception as e:  # corrupt sidecar must not block training
            print(f"[train] ignoring unreadable ratio store ({e})")
    it = Prefetcher(iter(data), depth=2)

    t_start = time.time()
    for step in range(start_step, args.steps):
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        t0 = time.perf_counter()
        params, opt, metrics = step_fn(params, opt, batch)
        metrics["loss"].block_until_ready()
        dt = time.perf_counter() - t0
        table.update("train_step", np.array([dt]))
        if (step + 1) % args.log_every == 0:
            toks = args.global_batch * args.seq_len / dt
            print(f"[train] step {step + 1} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f} tok/s={toks:.0f}")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save(args.ckpt_dir, step + 1, {"params": params, "opt": opt},
                 extra={"data_step": data.step})
            store.save(table)
    if args.ckpt_dir:
        save(args.ckpt_dir, args.steps, {"params": params, "opt": opt},
             extra={"data_step": data.step})
        store.save(table)
    print(f"[train] done in {time.time() - t_start:.1f}s")
    it.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
