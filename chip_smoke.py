#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip, at granite-8b widths.

It drives the serving entry point's own wiring (``repro.launch.serve``): the
published widths of granite-8b in bf16, cut in depth, random weights made
from ``--seed``.  Phases, in one process:

  (a) the device as JAX reports it; fails unless the platform is "tpu"
  (b) the persistent compile cache
  (c) seeded requests through the continuous-batching engine, wall clock
  (d) one request's cached-path logits against a no-cache forward at the
      highest matmul precision
  (e) the Q4 and int8 Pallas kernels, compiled for the chip, against
      references at the granite-8b projection shapes
  (f) the same requests through one engine whose trunk runs the compiled
      Q4 Pallas projections

  python3 chip_smoke.py                # phases (a)-(f) on one chip
  python3 chip_smoke.py --four-chips   # four one-chip replicas against one

Latencies are printed as information, not as claims.  Any failed check
exits non-zero; the last line of standard output is then no result.  On
success it is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.device import (  # noqa: E402
    device_info,
    enable_compile_cache,
    resolve_interpret,
)
from repro.kernels.compiled import q4_blocks  # noqa: E402
from repro.kernels.int8_gemm import int8_gemm_pallas  # noqa: E402
from repro.kernels.q4_matmul import (  # noqa: E402
    q4_matmul_pallas,
    q4_matmul_pallas_db,
)
from repro.launch import serve  # noqa: E402
from repro.models import forward  # noqa: E402
from repro.quant.q4 import dequantize_q4_0, quantize_q4_0  # noqa: E402
from repro.serving import Request  # noqa: E402
from repro.serving.request import FinishReason  # noqa: E402

ARCH = "granite-8b"

# Logits of the cached path against the no-cache reference, as a share of
# the reference's largest magnitude.  bf16 keeps 8 significant bits (step
# 2^-8).  The engine and the reference round the bf16 residual stream and
# projection outputs at different points (chunk boundaries, cache reads,
# batch-16 against whole-sequence tiling), and the engine's f32 attention
# runs at default precision (one bf16 pass), so each layer may add about
# one step: n_layers * 2^-8.  A wrong cache position, rope offset or slot
# row moves logits by the order of their magnitude.
LOGIT_STEP = 2.0 ** -8
# Q4 kernels against dequantize + f32 matmul: both accumulate in f32, in a
# different order; over K <= 14336 products the worst case is K * 2^-24
# (8.5e-4) of the row's absolute sum.  A wrong nibble, group or scale is
# off by the order of the output itself.
Q4_RTOL = 1e-3


@dataclasses.dataclass(frozen=True)
class Size:
    """What the smoke run serves.  The default is the chip run; widths
    come from the preset and are never cut here."""

    preset: str = "full"
    layers: int = 16            # of granite-8b's 36
    slots: int = 16
    max_seq: int = 2048
    prompt_len: int = 320       # two prefill chunks: 256 + 64
    new_tokens: int = 48
    prefill_chunk: int = 256
    requests: int = 8
    trunk_layers: int = 2       # phase (f): the balanced trunk unrolls
    replica_slots: int = 8      # --four-chips: slots of every replica
    kernel_rows: int = 16       # decode GEMV rows (one per slot)
    int8_rows: int = 128
    kernel_shapes: tuple = ((4096, 4096), (4096, 14336), (14336, 4096),
                            (4096, 49152))   # (K, N): q/o, up, down, head
    interpret: bool = False


class LogitsTap:
    """Greedy sampler that keeps the (B, V) logits it is shown while
    ``rows`` is a list."""

    def __init__(self):
        self.rows = None
        self.finite = True

    def __call__(self, logits):
        if self.rows is not None:
            self.rows.append(logits)
        self.finite &= bool(jnp.all(jnp.isfinite(logits)))
        return jnp.argmax(logits, -1)


class Checks:
    """Numeric checks, evaluated together at the end so that one chip run
    reports every phase."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"[smoke] check {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)


def serve_args(size: Size, seed: int, *extra: str):
    return serve.parse_args([
        "--arch", ARCH, "--preset", size.preset,
        "--layers", str(size.layers), "--batch", str(size.slots),
        "--max-seq", str(size.max_seq), "--prompt-len", str(size.prompt_len),
        "--steps", str(size.new_tokens),
        "--prefill-chunk", str(size.prefill_chunk),
        "--requests", str(size.requests), "--seed", str(seed), *extra])


def served_ok(requests, n_new: int, vocab: int) -> bool:
    return all(r.finish_reason is FinishReason.LENGTH
               and r.n_generated == n_new
               and all(0 <= t < vocab for t in r.generated)
               for r in requests)


def warm_up(engine, prompt) -> float:
    """Serve one request so that every step program compiles; returns
    the seconds it took (compilation, mostly)."""
    t0 = time.perf_counter()
    engine.submit(Request(prompt=prompt, max_new_tokens=2,
                          arrival_time=engine.now))
    engine.run_until_idle()
    engine.poll_finished()
    return time.perf_counter() - t0


def timed_serve(args, engines, requests):
    """Serve ``requests`` through ``repro.launch.serve`` after the engines'
    warm-up: arrivals are shifted past the warm-up on the engine clock."""
    start = max(e.now for e in engines)
    for r in requests:
        r.arrival_time += start
    _, routed, report = serve.serve_requests(args, engines, requests)
    return routed, report


def phase_a() -> dict:
    dev = device_info()
    print(f"[smoke] (a) device: platform={dev['platform']} "
          f"kind={dev['kind']} count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        raise SystemExit(f"[smoke] (a) no TPU: JAX reports platform "
                         f"{dev['platform']!r}")
    return dev


def phase_b() -> None:
    print(f"[smoke] (b) compile cache: {enable_compile_cache()}", flush=True)


def print_cut(args, what: str) -> None:
    cfg = serve.model_config(args)
    print(f"[smoke] {what}: {cfg.name} ({args.preset}), {cfg.n_layers} of "
          f"{get_config(ARCH).n_layers} layers, d_model {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}; {args.batch} slots, max_seq "
          f"{args.max_seq}", flush=True)


def phase_c(size: Size, seed: int, tap: LogitsTap, check: Checks):
    args = serve_args(size, seed, "--machine", "wall")
    print_cut(args, "(c)-(d) model")
    t0 = time.perf_counter()
    cfg, params, max_seq = serve.build_model(args)
    jax.block_until_ready(params)
    print(f"[smoke] (c) weights made from seed {seed} in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    engines, _ = serve.build_replicas(
        args, cfg, params, max_seq,
        serve.replica_slot_counts(args.batch, args.replicas), sampler=tap)
    requests = serve.make_requests(args, cfg)
    secs = warm_up(engines[0], requests[0].prompt)
    print(f"[smoke] (c) warm-up (prefill x2, decode, adopt, reset "
          f"programs): {secs:.1f}s", flush=True)
    routed, report = timed_serve(args, engines, requests)
    print(f"[smoke] (c) {len(requests)} requests, prompt {size.prompt_len}, "
          f"{size.new_tokens} new tokens, routed={routed.tolist()}; "
          f"latencies below are information only", flush=True)
    for line in report.lines(prefix="[smoke] (c)"):
        print(line, flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[smoke] (c) device peak bytes in use: "
              f"{stats['peak_bytes_in_use']}", flush=True)
    check("c.served", served_ok(requests, size.new_tokens, cfg.vocab_size),
          f"{len(requests)} requests x {size.new_tokens} tokens")
    return cfg, params, engines[0], requests


@jax.jit
def _max_abs(a, b):
    return jnp.max(jnp.abs(a - b))


def phase_d(size: Size, cfg, params, engine, prompt, tap: LogitsTap,
            check: Checks) -> None:
    """Cached path (chunked prefill, slot adopt, decode at batch
    ``slots``) against one no-cache forward over the same tokens."""
    req = Request(prompt=prompt, max_new_tokens=size.new_tokens,
                  arrival_time=engine.now)
    tap.rows = []
    engine.submit(req)
    engine.step()       # admits the request: its slot is fixed from here
    slot = req.slot
    engine.run_until_idle()
    rows, tap.rows = tap.rows, None
    # one (1, V) row from the prefill's last chunk, then one (slots, V)
    # row per decode step
    got = jnp.concatenate([rows[0][0:1]] + [r[slot:slot + 1]
                                            for r in rows[1:]])
    n = req.n_generated
    tokens = jnp.asarray(req.tokens[:-1])[None, :]

    @jax.jit
    def reference(params, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(cfg, params, tokens).logits[0]

    want = reference(params, tokens)[size.prompt_len - 1:]
    if got.shape != want.shape or got.shape[0] != n:
        raise AssertionError(f"logits {got.shape} vs reference {want.shape}")
    scale = float(jnp.max(jnp.abs(want)))
    err = float(_max_abs(got, want))
    tol = cfg.n_layers * LOGIT_STEP
    check("d.logits", err <= tol * scale,
          f"{n} positions, max|engine - reference| = {err:.5g}, "
          f"{err / scale:.5g} of max|reference| = {scale:.5g}; "
          f"tolerance {tol:.5g} ({cfg.n_layers} layers x 2^-8)")
    # the engine's greedy pick is (within tolerance) the reference's best
    picked = jnp.asarray(req.generated)
    gap = jnp.max(want, -1) - jnp.take_along_axis(want, picked[:, None],
                                                  -1)[:, 0]
    agree = int(jnp.sum(gap == 0))
    check("d.greedy", float(jnp.max(gap)) <= tol * scale,
          f"reference argmax agrees at {agree}/{n} positions; largest "
          f"shortfall of the engine's pick {float(jnp.max(gap)):.5g}")


def phase_e(size: Size, seed: int, check: Checks) -> None:
    # test data comes from numpy: a device RNG program compiles for tens of
    # seconds per shape on the chip
    rng = np.random.default_rng(seed)
    for k, n in size.kernel_shapes:
        t0 = time.perf_counter()
        x = jnp.asarray(rng.standard_normal((size.kernel_rows, k),
                                            dtype=np.float32))
        qw = quantize_q4_0(jnp.asarray(
            rng.standard_normal((n, k), dtype=np.float32) * k ** -0.5))
        want = jnp.dot(x, dequantize_q4_0(qw).T,
                       precision=jax.lax.Precision.HIGHEST)
        scale = float(jnp.max(jnp.abs(want)))
        t_q4_ref = time.perf_counter() - t0
        blocks = q4_blocks(k)
        outs = {}
        for name, fn in (("q4", q4_matmul_pallas),
                         ("q4_db", q4_matmul_pallas_db)):
            t0 = time.perf_counter()
            y = jax.block_until_ready(
                fn(x, qw, blocks=blocks, interpret=size.interpret))
            secs = time.perf_counter() - t0
            outs[name] = y
            err = float(_max_abs(y, want))
            check(f"e.{name}.{k}x{n}", err <= Q4_RTOL * scale,
                  f"K={k} N={n} M={size.kernel_rows} blocks={blocks}: "
                  f"max|kernel - reference| {err:.5g} = "
                  f"{err / scale:.3g} of max|reference|, tolerance "
                  f"{Q4_RTOL:g}; first call {secs:.1f}s")
        same = bool(jnp.array_equal(outs["q4"], outs["q4_db"]))
        print(f"[smoke] (e) q4 plain and double-buffered bit-identical at "
              f"{k}x{n}: {same}", flush=True)
        t0 = time.perf_counter()
        a = rng.integers(0, 256, (size.int8_rows, k), dtype=np.uint8)
        b = rng.integers(-128, 128, (n, k), dtype=np.int8)
        y = np.asarray(int8_gemm_pallas(jnp.asarray(a), jnp.asarray(b),
                                        interpret=size.interpret), np.int64)
        t_int8 = time.perf_counter() - t0
        t0 = time.perf_counter()
        # exact: |sum| <= 255 * 128 * K < 2^53
        exact = (a.astype(np.float64) @ b.astype(np.float64).T).astype(
            np.int64)
        t_int8_ref = time.perf_counter() - t0
        check(f"e.int8.{k}x{n}", np.array_equal(y, exact),
              f"K={k} N={n} M={size.int8_rows}: s32 output against an "
              f"exact float64 product, max|difference| "
              f"{int(np.max(np.abs(y - exact)))}")
        print(f"[smoke] (e) {k}x{n} seconds: data and Q4 reference "
              f"{t_q4_ref:.1f}, int8 data and kernel {t_int8:.1f}, int8 "
              f"reference on the host {t_int8_ref:.1f}", flush=True)


def phase_f(size: Size, seed: int, tap: LogitsTap, check: Checks) -> None:
    trunk_size = dataclasses.replace(size, layers=size.trunk_layers)
    args = serve_args(trunk_size, seed, "--machine", "ultra-125h",
                      "--balanced-trunk", "--trunk-quant", "q4",
                      "--trunk-mode", "compiled")
    print_cut(args, "(f) model, cut further: the balanced trunk unrolls")
    cfg, params, max_seq = serve.build_model(args)
    t0 = time.perf_counter()
    engines, dispatchers = serve.build_replicas(
        args, cfg, params, max_seq,
        serve.replica_slot_counts(args.batch, args.replicas), sampler=tap)
    engine = engines[0]
    trunk = engine.balanced_trunk
    print(f"[smoke] (f) Q4 trunk quantized in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    interprets = [d.interpret for d in dispatchers]
    interprets.append(trunk._compiled().interpret)
    if any(i != size.interpret for i in interprets):
        raise AssertionError(f"dispatcher interpret flags {interprets}")
    requests = serve.make_requests(args, cfg)
    secs = warm_up(engine, requests[0].prompt)
    print(f"[smoke] (f) warm-up (compiled trunk programs): {secs:.1f}s",
          flush=True)
    routed, report = timed_serve(args, engines, requests)
    print(f"[smoke] (f) {len(requests)} requests through the compiled Q4 "
          f"trunk; latencies below are on the virtual {args.machine} clock",
          flush=True)
    for line in report.lines(prefix="[smoke] (f)"):
        print(line, flush=True)
    check("f.served", served_ok(requests, size.new_tokens, cfg.vocab_size),
          f"{len(requests)} requests x {size.new_tokens} tokens")
    check("f.finite", tap.finite, "every sampled logits row is finite")
    man = engine.manager
    hlo = engine._decode.lower(
        engine.params, jnp.zeros((man.n_slots, 1), jnp.int32), man.state,
        jnp.zeros((man.n_slots,), jnp.int32), engine._offsets,
    ).compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    check("f.kernels", n_calls > 0,
          f"compiled decode step holds {n_calls} tpu_custom_call "
          f"references")


def one_chip_phases(size: Size, seed: int, check: Checks) -> None:
    """Phases (c)-(f)."""
    tap = LogitsTap()
    t0 = time.perf_counter()
    cfg, params, engine, requests = phase_c(size, seed, tap, check)
    phase_d(size, cfg, params, engine, requests[0].prompt, tap, check)
    del params, engine, requests
    gc.collect()
    print(f"[smoke] (c)+(d) took {time.perf_counter() - t0:.1f}s",
          flush=True)
    t0 = time.perf_counter()
    phase_e(size, seed, check)
    print(f"[smoke] (e) took {time.perf_counter() - t0:.1f}s", flush=True)
    t0 = time.perf_counter()
    phase_f(size, seed, LogitsTap(), check)
    print(f"[smoke] (f) took {time.perf_counter() - t0:.1f}s", flush=True)


def four_chip_phases(size: Size, seed: int, check: Checks) -> None:
    """Four replicas, each committed to its own chip, behind the serve
    entry point's InflightDispatcher, against one replica serving the same
    requests.  Every replica has the same slot count, so each request runs
    the same programs either way and greedy tokens must match exactly."""
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"[smoke] --four-chips needs 4 devices, "
                         f"found {len(devices)}")
    runs = {}
    for n in (4, 1):
        args = serve_args(size, seed, "--machine", "wall",
                          "--replicas", str(n),
                          "--batch", str(n * size.replica_slots))
        print_cut(args, f"{n} replica(s)")
        if n == 4:
            cfg, params, max_seq = serve.build_model(args)
        engines, _ = serve.build_replicas(
            args, cfg, params, max_seq,
            serve.replica_slot_counts(args.batch, args.replicas))
        placed = []
        for e in engines:
            held = set()
            for leaf in jax.tree.leaves((e.params, e.manager.state)):
                held |= leaf.devices()
            placed.append(sorted(d.id for d in held))
        print(f"[smoke] {n} replica(s) of {size.replica_slots} slots; "
              f"devices of params and slot cache per replica: {placed}",
              flush=True)
        requests = serve.make_requests(args, cfg)
        t0 = time.perf_counter()
        routed, report = timed_serve(args, engines, requests)
        print(f"[smoke] {n} replica(s): routed={routed.tolist()} in "
              f"{time.perf_counter() - t0:.1f}s (compilation included); "
              f"latencies are information only", flush=True)
        for line in report.lines(prefix=f"[smoke] ({n} replicas)"):
            print(line, flush=True)
        check(f"served.{n}", served_ok(requests, size.new_tokens,
                                       cfg.vocab_size),
              f"{len(requests)} requests x {size.new_tokens} tokens")
        if n == 4:
            check("placement", placed == [[d.id] for d in devices],
                  f"replica i on device i only: {placed}")
        runs[n] = [list(r.generated) for r in requests]
        del engines
        gc.collect()
    same = [a == b for a, b in zip(runs[4], runs[1])]
    check("tokens", all(same),
          f"greedy tokens identical for {sum(same)}/{len(same)} requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica phase (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    size = Size()
    dev = phase_a()
    phase_b()
    if resolve_interpret() != size.interpret:
        raise AssertionError("Pallas kernels would run in interpret mode")
    check = Checks()
    phases = four_chip_phases if args.four_chips else one_chip_phases
    phases(size, args.seed, check)
    if check.failed:
        raise SystemExit(f"[smoke] failed checks: {check.failed}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
