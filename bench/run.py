#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

  python3 bench/run.py --workload granite-8b.chat --seed 7 --seconds 51 --trace 0

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  It runs only
on a TPU whose device kind is in ``bench/peaks.json``, with at least as
many chips as the cell asks for; anything else exits non-zero with no
result.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics (a profiler trace of the window's last
``trace_seconds``, from the cell file or ``--trace-seconds``; the trace and
the host's step records stay in ``bench/.trace/<cell>/``).
The last line of standard output is the result; the last lines of
standard error are the numbers compared with the reference, each beside
its limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]
# libtpu logs to /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CACHE_DIR = REPO / ".jax_cache"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="trace this much of the window instead of the "
                         "cell's trace_seconds (a short trace to keep)")
    return ap.parse_args(argv)


def chip_peaks(chips: int) -> dict:
    """The peaks of the chip JAX finds; exits when it is no TPU, is not in
    ``bench/peaks.json`` or there are fewer than ``chips``."""
    import jax

    devs = jax.devices()
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"[bench] no TPU: JAX reports platform "
                         f"{devs[0].platform!r} ({kind})")
    if kind not in peaks:
        raise SystemExit(f"[bench] no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"[bench] the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return peaks[kind]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, every program in it."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import harness

    cell = harness.load_cell(args.workload)
    if args.trace_seconds is not None:
        cell.engine = dict(cell.engine, trace_seconds=args.trace_seconds)
    peaks = chip_peaks(cell.chips)
    use_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), peaks=peaks,
                              t_start=T_START)
    harness.print_compared(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
