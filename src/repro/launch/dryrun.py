import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede every other import (jax locks device count at first init).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (SPMD partitioner succeeds),
  * per-device memory fits (memory_analysis),
  * and it yields the roofline terms (cost_analysis + HLO collective parse).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all            # every assigned cell
  python -m repro.launch.dryrun --all --multi-pod
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (
    ARCHS,
    SHAPES,
    ShapeSpec,
    cells,
    get_config,
    shape_supported,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze
from repro.models import abstract_params, abstract_state, forward
from repro.models.moe import default_capacity
from repro.sharding.specs import (
    activation_sharding,
    batch_shardings,
    opt_shardings,
    param_shardings,
    state_shardings,
)
from repro.training import AdamWConfig, init_opt_state, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# >=50B params: factored moments + bf16 mu (see training/optimizer.py).
FACTORED_THRESHOLD = 50e9


def _abstract(tree):
    return jax.tree.map(
        lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(cfg, shape: ShapeSpec, *, n_micro: int = 8) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.batch, shape.seq
    dt = cfg.cdtype
    if shape.kind == "train":
        mb = b // n_micro
        batch = {}
        if cfg.embed_input:
            batch["embeds"] = jax.ShapeDtypeStruct((n_micro, mb, s, cfg.d_model), dt)
            batch["labels"] = jax.ShapeDtypeStruct((n_micro, mb, s), jnp.int32)
        elif cfg.n_prefix:
            s_txt = s - cfg.n_prefix
            batch["tokens"] = jax.ShapeDtypeStruct((n_micro, mb, s_txt), jnp.int32)
            batch["prefix_embeds"] = jax.ShapeDtypeStruct(
                (n_micro, mb, cfg.n_prefix, cfg.d_model), dt)
            batch["labels"] = jax.ShapeDtypeStruct((n_micro, mb, s_txt), jnp.int32)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((n_micro, mb, s), jnp.int32)
            batch["labels"] = jax.ShapeDtypeStruct((n_micro, mb, s), jnp.int32)
        return {"batch": batch}
    if shape.kind == "prefill":
        if cfg.embed_input:
            return {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)}
        if cfg.n_prefix:
            return {
                "tokens": jax.ShapeDtypeStruct((b, s - cfg.n_prefix), jnp.int32),
                "prefix_embeds": jax.ShapeDtypeStruct(
                    (b, cfg.n_prefix, cfg.d_model), dt),
            }
        return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    # decode: one new token against a state of seq_len
    if cfg.embed_input:
        return {"embeds": jax.ShapeDtypeStruct((b, 1, cfg.d_model), dt)}
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def build_cell(cfg, shape: ShapeSpec, mesh, n_micro: int = 8):
    """Returns (fn, args, in_shardings) ready for jit().lower()."""
    params = _abstract(abstract_params(cfg))
    # decode is weight-bandwidth bound: serve-mode placement keeps weights
    # stationary (no FSDP gathers); train/prefill amortize FSDP gathers
    # over a large token volume.
    p_sh = param_shardings(mesh, params,
                           mode="serve" if shape.kind == "decode" else "train")
    specs = input_specs(cfg, shape, n_micro=n_micro)

    if shape.kind == "train":
        opt_cfg = AdamWConfig(
            factored=cfg.param_count() > FACTORED_THRESHOLD,
            total_steps=10_000,
        )
        opt = _abstract(jax.eval_shape(
            lambda p: init_opt_state(p, opt_cfg), params))
        o_sh = opt_shardings(mesh, opt, p_sh)
        b_sh = batch_shardings(mesh, specs["batch"], batch_dim=1)
        # capacity=None: moe_fwd derives the static per-dispatch-group
        # capacity from its local token count (global/16 under shard_map)
        big = cfg.param_count() > FACTORED_THRESHOLD
        step = make_train_step(cfg, opt_cfg, capacity=None, remat=True,
                               acc_dtype=jnp.bfloat16 if big else jnp.float32,
                               grad_shardings=p_sh)
        return step, (params, opt, specs["batch"]), (p_sh, o_sh, b_sh)

    if shape.kind == "prefill":
        state = _abstract(abstract_state(cfg, shape.batch, shape.seq))
        s_sh = state_shardings(mesh, state, shape.batch, phase="prefill")
        in_sh = [p_sh]
        args = [params]
        for k in ("tokens", "embeds", "prefix_embeds"):
            if k in specs:
                args.append(specs[k])
                in_sh.append(batch_shardings(mesh, specs[k], batch_dim=0))
        args.append(state)
        in_sh.append(s_sh)
        has_prefix = "prefix_embeds" in specs
        has_embeds = "embeds" in specs

        def prefill(params, *rest):
            i = 0
            tokens = embeds = prefix = None
            if not has_embeds:
                tokens = rest[i]; i += 1
            if has_embeds:
                embeds = rest[i]; i += 1
            if has_prefix:
                prefix = rest[i]; i += 1
            state = rest[i]
            out = forward(cfg, params, tokens, embeds=embeds,
                          prefix_embeds=prefix, state=state,
                          logits_mode="last")
            return out.logits, out.state

        return prefill, tuple(args), tuple(in_sh)

    # decode
    state = _abstract(abstract_state(cfg, shape.batch, shape.seq))
    s_sh = state_shardings(mesh, state, shape.batch, phase="decode")
    tok_key = "embeds" if cfg.embed_input else "tokens"
    tok_spec = specs[tok_key]
    t_sh = batch_shardings(mesh, tok_spec, batch_dim=0)
    from jax.sharding import NamedSharding, PartitionSpec as P
    off_sh = NamedSharding(mesh, P())
    offset = jax.ShapeDtypeStruct((), jnp.int32)
    use_embeds = cfg.embed_input

    def decode(params, tok, state, offset):
        out = forward(cfg, params,
                      None if use_embeds else tok,
                      embeds=tok if use_embeds else None,
                      state=state, pos_offset=offset, logits_mode="last")
        # the state rides the layer scan's carry: hold the updated state to
        # the input's shardings so the carry keeps them through the loop
        return out.logits, jax.lax.with_sharding_constraint(out.state, s_sh)

    return decode, (params, tok_spec, state, offset), (p_sh, t_sh, s_sh, off_sh)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_supported(cfg, shape_name):
        return {"arch": arch, "shape": shape_name, "status": "SKIP",
                "reason": "long_500k requires sub-quadratic attention"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_sh = build_cell(cfg, shape, mesh)

    t0 = time.time()
    with mesh, activation_sharding(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem_txt = ""
    try:
        mem_txt = str(compiled.memory_analysis())
    except Exception:
        pass
    roof = analyze(compiled, arch=arch, shape=shape, mesh=mesh, cfg=cfg)
    result = {
        "status": "OK",
        "mesh_shape": list(mesh.devices.shape),
        "multi_pod": multi_pod,
        "lower_seconds": round(t_lower, 2),
        "compile_seconds": round(t_compile, 2),
        "memory_analysis": mem_txt,
        **roof.to_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned cell in subprocesses")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape, ok in cells(include_skipped=True):
            mesh_tag = "2x16x16" if args.multi_pod else "16x16"
            tag = f"{arch} x {shape} x {mesh_tag}"
            if not ok:
                print(f"[dryrun] SKIP {tag} (long_500k needs sub-quadratic)")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", args.out]
            if args.multi_pod:
                cmd.append("--multi-pod")
            print(f"[dryrun] {tag} ...", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append(tag)
                print(f"[dryrun] FAIL {tag}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
            else:
                print(r.stdout.strip().splitlines()[-1])
        print(f"[dryrun] done; {len(failures)} failures")
        for f in failures:
            print("  FAIL", f)
        return 1 if failures else 0

    res = run_cell(args.arch, args.shape, args.multi_pod, args.out)
    if res["status"] == "OK":
        print(json.dumps({k: res[k] for k in (
            "arch", "shape", "mesh_shape", "compile_seconds", "flops",
            "hbm_bytes", "wire_bytes", "bottleneck", "t_compute", "t_memory",
            "t_collective", "peak_mem_bytes")}, default=str))
    else:
        print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
