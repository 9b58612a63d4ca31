#!/usr/bin/env python3
"""Find the knee of an open-loop cell: serve its mix at a list of rates, one
window each, in one process, and print for each rate what was offered and
what was served.

  python3 bench/sweep.py --workload granite-8b.chat --seed 5 --seconds 20 \\
      --rates 3,4,5,6,7

The knee is the highest rate at which tokens are served as fast as they are
offered and the waiting queue does not grow across the window.  The engine
is drained between rates.  Run on the chip; the result is a table, not a
benchmark line.
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


def sweep(cell, seed: int, seconds: float, rates, log=print) -> list:
    import numpy as np

    from bench import harness, traffic
    from bench.model import make_weights, program_config, program_params
    from repro.launch import serve
    from repro.serving import InflightDispatcher

    shape, e = cell.shape, cell.engine
    w = make_weights(shape, seed)
    engines, _ = serve.build_replicas(
        harness._serve_args(cell, seed), program_config(shape),
        program_params(w), e["max_seq"], [e["slots"]])
    disp = InflightDispatcher(engines)
    drv = harness.Driver(disp)
    drv.submit(harness.Tracked(item=traffic.Item(
        due=0.0, prompt=harness.warm_up_prompt(e["prefill_chunk"],
                                               shape.vocab),
        max_new=2), due=harness.clock()))
    while disp.has_work:
        drv.step()
    rows = []
    for rate in rates:
        mix = dict(cell.mix, rate=rate)
        items = traffic.generate(mix, seed, seconds, shape.vocab)
        t_open = harness.clock() + float(mix.get("preroll_s", 0.0))
        everyone = [harness.Tracked(item=it, due=t_open + it.due)
                    for it in items]
        drv.steps.clear()
        harness._drive(drv, everyone, until=t_open)
        queued_open = len(drv.waiting)
        n0 = len(drv.steps)
        t_close = harness._drive(
            drv, [tr for tr in everyone if tr.submit_t is None],
            until=t_open + seconds)
        queued_close = len(drv.waiting)
        run = harness.Run(cell=cell, peaks={}, setup_s=0.0, t_open=t_open,
                          t_close=t_close, requests=everyone,
                          steps=drv.steps[n0:])
        due = [tr for tr in everyone if run.in_window(tr.due)]
        offered = sum(tr.item.prompt.size + tr.item.max_new for tr in due)
        row = {"rate": rate, "offered_tok_s": offered / run.window_s,
               "served_tok_s": harness.reader("served_tok_s")(run),
               "queued_at_open": queued_open,
               "queued_at_close": queued_close,
               "ttft_p95_ms": harness.reader("ttft_p95_ms")(run),
               "itl_p95_ms": harness.reader("itl_p95_ms")(run),
               "steps_per_s": len(run.steps) / run.window_s,
               "decode_rows_mean": float(np.mean(
                   [len(s.decode_kv) for s in run.steps] or [0]))}
        log(f"[sweep] {json.dumps(row)}")
        rows.append(row)
        while disp.has_work:           # drain before the next rate
            drv.step()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.load_cell(args.workload)
    chip_peaks(cell.chips)
    use_compile_cache()
    rows = sweep(cell, args.seed, args.seconds,
                 [float(r) for r in args.rates.split(",")])
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
