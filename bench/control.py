#!/usr/bin/env python3
"""Readings that the limit of a cell's check is set from, at the cell's own
size and load, on the chip, in one process:

  python3 bench/control.py --workload granite-8b.chat --seconds 51 \\
      --seeds 11,12,13 --control-seeds 11,12 --out chiprun_out/ctl.json

Every seed of ``--seeds`` is one run of the cell.  It reads the widest gap
of a served token below the reference's best logit (the program's reading)
and, over the same prompts and served tokens, the widest gap of the token
that the reference computed in each lower precision of ``--quants`` puts
first (the controls' readings).  On the seeds of ``--control-seeds`` the
run's own check judges the ``--control`` precision in the program's place:
its ``correct`` must come out false.  The benchmark's own runs never run
this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench.run import chip_peaks, use_compile_cache  # noqa: E402


def gap_readings(cell, w, sample, quants) -> dict:
    """Widest and mean gap of the served tokens, and with each precision
    of ``quants`` those of the tokens it puts first."""
    import numpy as np

    from bench import harness

    qs = (None,) + tuple(quants)
    per = [harness.served_gaps(cell, w, r, qs) for r in sample]
    out = {}
    for q in qs:
        gaps = np.concatenate([g[q] for g in per])
        out[q or "program"] = {"widest": float(gaps.max()),
                               "mean": float(gaps.mean()),
                               "tokens": int(gaps.size)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--quants", default="int8,fp8")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.load_cell(args.workload)
    peaks = chip_peaks(cell.chips)
    use_compile_cache()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    quants = tuple(q for q in args.quants.split(",") if q)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.run_cell(
            cell, seed, args.seconds, False, peaks=peaks, t_start=t0,
            control=args.control if seed in controls else None,
            on_check=lambda c, w, s: gap_readings(c, w, s, quants))
        row = {"seed": seed,
               "judged": args.control if seed in controls else "program",
               "correct": res["correct"], "compared": res["compared"],
               "readings": res["readings"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "failed": res["failed"],
               "seconds": time.perf_counter() - t0}
        print(f"[control] {json.dumps(row)}", flush=True)
        rows.append(row)
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rows, indent=1))
    for q in ("program",) + quants:
        widest = [r["readings"][q]["widest"] for r in rows]
        print(f"[control] {q}: widest gap {min(widest)} to {max(widest)} "
              f"over {len(widest)} seeds", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
