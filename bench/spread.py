#!/usr/bin/env python3
"""Run one cell in two sets of runs with the same seeds, each run a process
of its own, and report every metric's spread: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a share
of the median, per set.

  python3 bench/spread.py --workload granite-8b.chat --seconds 51 \\
      --seeds 11,12,13,14,15,16 --sets 2 --out chiprun_out/spread.json

This process never touches JAX, so each run has the chip to itself.  Bounds
are set from these spreads (PERF.md); ``trimmed`` is a set's spread with
its run farthest from the median left out.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def spread(values) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def trimmed_spread(values) -> float:
    """The spread once the run farthest from the median is left out."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])


def run_once(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=REPO, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        line = None
    return {"seed": seed, "rc": proc.returncode, "line": line,
            "seconds": time.perf_counter() - t0,
            "log": [l for l in lines if l.startswith("[bench]")],
            "stderr_tail": proc.stderr[-2000:] if line is None else ""}


def summary(runs) -> dict:
    ok = [r["line"] for r in runs if r["line"] is not None]
    names = sorted({k for line in ok for k in line["metrics"]})
    out = {}
    for name in names:
        vals = [line["metrics"][name]["value"] for line in ok
                if name in line["metrics"]]
        out[name] = {"median": statistics.median(vals), "values": vals,
                     "spread": spread(vals) if len(vals) >= 2 else None,
                     "trimmed": (trimmed_spread(vals) if len(vals) >= 3
                                 else None)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            r = run_once(args.workload, seed, args.seconds, args.trace)
            runs.append(r)
            print(f"[spread] set {k} {json.dumps(r)}", flush=True)
            out.write_text(json.dumps({"sets": sets + [runs]}, indent=1))
        sets.append(runs)
    report = {"workload": args.workload, "seconds": args.seconds,
              "seeds": seeds, "sets": sets,
              "summary": [summary(runs) for runs in sets]}
    out.write_text(json.dumps(report, indent=1))
    for k, s in enumerate(report["summary"]):
        for name, m in s.items():
            print(f"[spread] set {k} {name}: median {m['median']} spread "
                  f"{m['spread']} trimmed {m['trimmed']}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
