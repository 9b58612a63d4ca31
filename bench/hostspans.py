#!/usr/bin/env python3
"""Run one cell with the program's host spans kept in memory, and report
where the host's time in its steps went.

  python3 bench/hostspans.py --workload granite-8b.chat --seed 7 --seconds 51 --trace 0

The arguments are ``bench/run.py``'s, and the run is its run, with a
``repro.obs.HostSpans`` sink installed from set-up to the end and a
``repro.obs.CompileCounter`` listening.  The sink costs a few microseconds
a span, so its end-to-end numbers are not the benchmark's.  After the
result line of ``bench/run.py`` it prints one JSON line:

- ``longest``: the five longest ``dispatch.step`` spans of the window,
  each with the self time, by name, of every span inside it (a
  ``compile`` span is a program lowered or compiled in that step);
- ``steps``, ``spans_per_step`` and ``compiles`` in the window;
- ``hook_us``: what one span costs on this host, with no sink installed
  and the profiler off (``off``), and with a sink installed while the
  profiler records (``on``);
- with ``--trace 1``, ``idle_gaps`` (the traced window's longest device
  idle gaps, named by the innermost span around each), ``step_idle_ms``
  over ``traced_steps``, and ``idle_ms_per_step``: the window's device-idle time split by the
  innermost span over each stretch of it, per dispatcher step.

The sink's spans are written to ``--out`` (default
``bench/.trace/<cell>.host_spans.json``), for Perfetto.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
import run as run_py  # noqa: E402  (puts the repo and src on the path)

HOOK_SPANS = 20000


def hook_cost(trace_dir: Path) -> dict:
    """Microseconds per ``span`` (nested pairs, one with an int argument),
    with nothing installed and the profiler off, then with a sink installed
    while the profiler records."""
    import jax

    from repro.core import events
    from repro.obs import HostSpans

    def per_span() -> float:
        t0 = time.perf_counter()
        for _ in range(HOOK_SPANS // 2):
            with events.span("engine.decode", rows=32):
                with events.span("engine.decode.sync"):
                    pass
        return 1e6 * (time.perf_counter() - t0) / HOOK_SPANS

    prev = events.install_spans(None)
    per_span()                                  # warm
    off = per_span()
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    events.install_spans(HostSpans(capacity=HOOK_SPANS))
    try:
        on = per_span()
    finally:
        events.install_spans(prev)
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {"off": off, "on": on}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--out", default=None)
    own, rest = ap.parse_known_args(argv)
    args = run_py.parse_args(rest)

    from bench import harness, spans
    from repro.core import events
    from repro.obs import CompileCounter, HostSpans

    cell = harness.load_cell(args.workload)
    if args.trace_seconds is not None:
        cell.engine = dict(cell.engine, trace_seconds=args.trace_seconds)
    peaks = run_py.chip_peaks(cell.chips)
    run_py.use_compile_cache()
    sink = HostSpans(capacity=1 << 18)
    seen = {}
    with CompileCounter():
        try:
            result = harness.run_cell(
                cell, args.seed, args.seconds, bool(args.trace), peaks=peaks,
                t_start=T_START,
                engine_hook=lambda engine: events.install_spans(sink),
                on_run=lambda run: seen.update(run=run))
        finally:
            events.install_spans(None)
    harness.print_compared(result)
    print(json.dumps(result), flush=True)

    run = seen["run"]
    held = [s for s in sink.spans() if run.t_open <= s.start <= run.t_close]
    steps = sum(1 for s in held if s.name == "dispatch.step")
    report = {
        "seed": args.seed, "trace": args.trace,
        "longest": sink.longest(5, "dispatch.step", since=run.t_open,
                                until=run.t_close),
        "steps": steps,
        "spans_per_step": len(held) / steps if steps else None,
        "compiles": sum(1 for s in held if s.name == "compile"),
        "dropped": sink.dropped,
    }
    if run.trace is not None:
        program = spans.of_run(run) or []
        report["program_spans"] = len(program)
        report["idle_gaps"] = spans.breakdown(run.trace, program)["idle_gaps"]
        got = spans.idle_in(run.trace, program)
        report["step_idle_ms"] = (1e-6 * got[0] / got[1]) if got else None
        if got:
            report["traced_steps"] = got[1]
            split = spans.idle_by_span(run.trace, program)
            report["idle_ms_per_step"] = {
                k: 1e-6 * v / got[1]
                for k, v in sorted(split.items(), key=lambda kv: -kv[1])}
    out = Path(own.out or harness.TRACE_DIR / f"{cell.name}.host_spans.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    sink.write(str(out))
    report["hook_us"] = hook_cost(harness.TRACE_DIR / "hook_cost")
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
