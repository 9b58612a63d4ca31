#!/usr/bin/env python3
"""Compile every cell's decode and largest prefill program for a described
TPU v5e (no chip needed) and print what they hold in device memory.

  JAX_PLATFORMS=cpu python3 bench/fit.py [--workload NAME]

Each program is compiled alone: the bytes of one program's arguments,
outputs and temporaries, as ``memory_analysis()`` gives them, not what the
process holds besides.  A 16-layer step takes about a minute per program.
"""

import argparse
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness  # noqa: E402
from bench.model import make_weights, program_config, \
    program_params  # noqa: E402


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def fit(name: str, sharding) -> dict:
    from repro.models import init_slot_state, init_state
    from repro.serving.engine import step_programs

    cell = harness.load_cell(name)
    e, shape = cell.engine, cell.shape
    cfg = program_config(shape)
    params = _on(jax.eval_shape(
        lambda: program_params(make_weights(shape, 0))), sharding)
    slots = _on(jax.eval_shape(
        lambda: init_slot_state(cfg, e["slots"], e["max_seq"])), sharding)
    one = _on(jax.eval_shape(lambda: init_state(cfg, 1, e["max_seq"])),
              sharding)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    prefill, _, decode = step_programs(cfg)
    out = {}
    for prog, args in (
            ("_decode", (decode, params, i32(e["slots"], 1), slots,
                         i32(e["slots"]))),
            ("_prefill", (prefill, params, i32(1, e["prefill_chunk"]), one,
                          i32()))):
        fn, *rest = args
        mem = fn.lower(*rest).compile().memory_analysis()
        out[prog] = {"arguments": mem.argument_size_in_bytes,
                     "outputs": mem.output_size_in_bytes,
                     "aliased": mem.alias_size_in_bytes,
                     "temporaries": mem.temp_size_in_bytes}
        print(f"[fit] {name} {prog}: {out[prog]}", flush=True)
    return out


def main(argv=None) -> int:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])
    names = ([args.workload] if args.workload else
             [w["name"] for w in json.loads(
                 (REPO / "BENCHMARK.json").read_text())["workloads"]])
    print(json.dumps({n: fit(n, sharding) for n in names}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
