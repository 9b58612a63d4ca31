"""The benchmark's harness: one run of one cell.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its engine shape and its check sit in
``bench/cells/<cell>.json``.  The mix's ``arrivals`` names its arrival
process, ``bench/arrivals/<arrivals>.py`` (``bench/traffic.py`` says what
one gives: due times relative to the window's opening, the same work for
every seed, prompt ids from the seed).  Every metric is read by a reader of
its own, ``bench/metrics/<name>.py`` (the part of the name before the first
dot).  So a new cell, traffic or metric is new files and entries, never an
edit here.

One run:

1. set-up: the seeded weights on the device, the engine that
   ``repro.launch.serve.build_replicas`` builds (wall clock, one replica)
   behind an ``InflightDispatcher``, one warm-up request whose prompt runs
   every prefill piece shape, then the mix's pre-roll of its own traffic;
2. the window: requests are submitted when due, the dispatcher is stepped
   while it has work and the host sleeps while it has none; each token is
   stamped with ``time.perf_counter()`` when the step that made it returns;
3. after it: requests due in the window, or admitted in it, that still
   lack a first token are followed until they get one; the log says how
   many submitted requests the window left unadmitted (a backlog's rest);
4. the check: the requests the run finished are sampled from the seed, the
   longest among them, and each served token is compared with the plain
   reference (``bench/references/<reference>.py``) once the engine is gone.
   With ``control`` the reference computed in that lower precision takes
   the program's place in the check, which must then fail it.

The engine's own clock (``engine.now``) is used only to stamp arrivals so
that admission works; no metric reads it.
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import load_module, traffic, xtrace
from .model import Shape, load_config, make_weights, program_config, \
    program_params

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
TRACE_DIR = BENCH / ".trace"
FOLLOW_UP_S = 60.0            # longest wait for a first token after the window

clock = time.perf_counter


# ----------------------------------------------------------------- cells --
@dataclass
class Cell:
    name: str
    chips: int
    shape: Shape
    reference: str                 # module name under bench/references
    mix: dict                      # the traffic file
    engine: dict                   # the cell file
    end_to_end: List[dict]         # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = REPO) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    shape, conf = load_config(BENCH, w["config"])
    return Cell(
        name=name, chips=int(w["chips"]), shape=shape,
        reference=conf["reference"],
        mix=json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        engine=json.loads((BENCH / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def reader(metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric before its first dot>.py``."""
    return load_module(BENCH / "metrics" / f"{metric.split('.')[0]}.py").read


def reference(name: str):
    return load_module(BENCH / "references" / f"{name}.py")


# ------------------------------------------------------------- records ---
@dataclass
class Tracked:
    """One request as the client sees it (host clock, seconds)."""

    item: traffic.Item
    due: float                           # absolute
    req: object = None                   # repro.serving.Request
    submit_t: Optional[float] = None
    lane_t: Optional[float] = None       # entered the prefill lane
    token_t: List[float] = field(default_factory=list)
    done_t: Optional[float] = None       # finished


@dataclass
class Step:
    """One dispatcher step, and the work it did."""

    t0: float
    t1: float
    prefill_len: int = 0
    prefill_start: int = 0
    decode_kv: List[int] = field(default_factory=list)
    generated: int = 0
    traced: bool = False


@dataclass
class Run:
    """What the metric readers see."""

    cell: Cell
    peaks: dict
    setup_s: float
    t_open: float
    t_close: float
    requests: List[Tracked]
    steps: List[Step]
    trace: Optional[xtrace.Trace] = None

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t_open <= t <= self.t_close

    def traced_steps(self) -> List[Step]:
        return [s for s in self.steps if s.traced]

    def matched(self, program: str) -> Optional[list]:
        """``(step, device execution)`` pairs of ``program`` (``"_prefill"``
        or ``"_decode"``) in the traced window, in order; None when the
        trace has no device plane or no step ran the program.  Raises when
        the device ran the program another number of times than the
        host's steps did: the reduction is then wrong, and no metric may
        be read from it."""
        if self.trace is None or not self.trace.modules:
            return None
        if program == "_prefill":
            steps = [s for s in self.traced_steps() if s.prefill_len]
        else:
            steps = [s for s in self.traced_steps() if s.decode_kv]
        execs = self.trace.executions(program)
        if len(steps) != len(execs):
            raise ValueError(
                f"{program}: {len(execs)} device executions in the traced "
                f"window, {len(steps)} host steps that ran it; "
                f"{self.trace.edges(program)}")
        return list(zip(steps, execs)) or None


# ------------------------------------------------------------ the driver --
class Driver:
    """Submits, steps and observes; owns the records."""

    def __init__(self, disp):
        self.disp = disp
        self.waiting: List[Tracked] = []       # submitted, not yet admitted
        self.active: List[Tracked] = []        # admitted, not finished
        self.steps: List[Step] = []
        self.tracing = False

    def submit(self, tr: Tracked) -> None:
        from repro.serving import Request

        with jax.profiler.TraceAnnotation("submit"):
            tr.req = Request(prompt=tr.item.prompt,
                             max_new_tokens=tr.item.max_new,
                             arrival_time=self.disp.now)
            self.disp.submit(tr.req)
            tr.submit_t = clock()
            self.waiting.append(tr)

    def step(self) -> Step:
        before = {id(t): (t.req.n_generated, t.req.prefill_done)
                  for t in self.active}
        t0 = clock()
        with jax.profiler.TraceAnnotation("step"):
            self.disp.step()
        t1 = clock()
        with jax.profiler.TraceAnnotation("observe"):
            st = Step(t0=t0, t1=t1, traced=self.tracing)
            # admission is FIFO in submit order: the admitted are a prefix
            while self.waiting and self.waiting[0].req.state.value != "waiting":
                tr = self.waiting.pop(0)
                tr.lane_t = t0
                self.active.append(tr)
                before[id(tr)] = (0, 0)
            still = []
            for tr in self.active:
                r = tr.req
                n0, p0 = before[id(tr)]
                if r.prefill_done != p0:
                    st.prefill_len, st.prefill_start = r.prefill_done - p0, p0
                new = r.n_generated - n0
                if new:
                    tr.token_t.extend([t1] * new)
                    st.generated += new
                    # a request whose prefill ended here got its first token
                    # from the prefill; any other new token is a decode row
                    if new > (1 if n0 == 0 else 0):
                        st.decode_kv.append(r.prompt_len + r.n_generated - 1)
                if r.state.value == "finished":
                    tr.done_t = t1
                else:
                    still.append(tr)
            self.active = still
            self.steps.append(st)
        return st


def _serve_args(cell: Cell, seed: int):
    from repro.launch import serve

    e = cell.engine
    return serve.parse_args([
        "--arch", cell.shape.name, "--preset", "full", "--machine", "wall",
        "--batch", str(e["slots"]), "--replicas", "1",
        "--max-seq", str(e["max_seq"]),
        "--prefill-chunk", str(e["prefill_chunk"]), "--seed", str(seed)])


def warm_up_prompt(chunk: int, vocab: int) -> np.ndarray:
    """A prompt of ``2 * chunk - 1`` tokens: prefilled in pieces of chunk,
    chunk/2, ..., 1, it runs every piece shape the engine can use once."""
    return (np.arange(2 * chunk - 1) % vocab).astype(np.int32)


class CompileCounter:
    """Counts programs lowered while ``on`` (a compile or a cache load)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.on, self.count = False, 0

    def __call__(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the harness's spans only
    opts.enable_hlo_proto = False
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peaks: dict, t_start: float, log=print,
             control: Optional[str] = None,
             engine_hook: Optional[Callable] = None,
             on_run: Optional[Callable] = None,
             on_check: Optional[Callable] = None,
             trace_dir: Path = TRACE_DIR) -> dict:
    """One run; returns the result line's dict.  ``control`` (a precision
    of the reference's ``QUANTS``) puts the reference computed at that
    precision in the program's place in the check; ``engine_hook(engine)``
    may replace the engine's step programs (tests plant faults with it);
    ``on_run(run)`` sees the records the metrics are read from;
    ``on_check(cell, weights, sample)`` adds readings of its own to the
    result, under ``"readings"``."""
    from repro.launch import serve
    from repro.serving import InflightDispatcher

    shape, e = cell.shape, cell.engine
    t_enter = clock()
    w = make_weights(shape, seed)
    jax.block_until_ready(w)
    t_weights = clock()
    args = _serve_args(cell, seed)
    engines, _ = serve.build_replicas(args, program_config(shape),
                                      program_params(w), e["max_seq"],
                                      [e["slots"]])
    engine = engines[0]
    if engine_hook is not None:
        engine_hook(engine)
    disp = InflightDispatcher(engines)
    drv = Driver(disp)

    # warm-up: every prefill piece shape, adopt, decode, reset, sampling
    warm = Tracked(item=traffic.Item(
        due=0.0, prompt=warm_up_prompt(e["prefill_chunk"], shape.vocab),
        max_new=2), due=clock())
    drv.submit(warm)
    while disp.has_work:
        drv.step()
    # what set-up made lives to the end: no full collection walks it again
    gc.collect()
    gc.freeze()
    t_warm = clock()
    log(f"[bench] set-up: start to harness {t_enter - t_start:.2f}s, "
        f"weights {t_weights - t_enter:.2f}s (seed {seed}), engine and "
        f"warm-up {t_warm - t_weights:.2f}s")

    items = traffic.generate(cell.mix, seed, seconds, shape.vocab)
    drv.steps.clear()
    with CompileCounter() as compiles:
        # the pre-roll: the mix's own traffic, so that the window opens on
        # a loaded engine
        t_open = clock() + float(cell.mix.get("preroll_s", 0.0))
        everyone = [Tracked(item=it, due=t_open + it.due) for it in items]
        _drive(drv, everyone, until=t_open)
        setup_s = t_open - t_start
        lead_in = len(drv.steps)

        compiles.on = True
        full_gcs = gc.get_stats()[2]["collections"]
        t_close = _drive(
            drv, [tr for tr in everyone if tr.submit_t is None],
            until=t_open + seconds,
            trace_at=(t_open + seconds - float(e["trace_seconds"])
                      if trace else None),
            trace_dir=trace_dir / cell.name)
        compiles.on = False
        full_gcs = gc.get_stats()[2]["collections"] - full_gcs
    window_steps = drv.steps[lead_in:]
    backlog_left = len(drv.waiting)

    # what the window owes a first token: requests due in it, and those it
    # admitted; they are followed until they get one
    owed = [tr for tr in everyone
            if t_open <= tr.due <= t_close
            or (tr.lane_t is not None and t_open <= tr.lane_t <= t_close)]
    t_follow = clock()
    while (any(not tr.token_t for tr in owed) and disp.has_work
           and clock() - t_follow < FOLLOW_UP_S):
        drv.step()
    failed = sum(1 for tr in owed if not tr.token_t)
    late = [1e3 * (tr.submit_t - tr.due) for tr in everyone
            if tr.submit_t is not None and t_open <= tr.due <= t_close]
    longest_step = max((s.t1 - s.t0 for s in window_steps), default=0.0)
    longest_gap = max((b.t0 - a.t1 for a, b in zip(window_steps,
                                                     window_steps[1:])),
                      default=0.0)
    log(f"[bench] window {t_close - t_open:.3f}s: {len(window_steps)} steps, "
        f"{len(owed)} requests owed a first token; backlog left: "
        f"{backlog_left} of {sum(tr.submit_t is not None for tr in everyone)}"
        f" submitted not admitted; programs compiled in the "
        f"window: {compiles.count}; full collections in the window: "
        f"{full_gcs}; generator late by p50 "
        f"{float(np.median(late)) if late else 0.0:.3f} ms, max "
        f"{max(late) if late else 0.0:.3f} ms; longest step "
        f"{1e3 * longest_step:.3f} ms, longest time between steps "
        f"{1e3 * longest_gap:.3f} ms")

    dev = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in dev[:cell.chips])

    run = Run(cell=cell, peaks=peaks, setup_s=setup_s, t_open=t_open,
              t_close=t_close, requests=everyone, steps=window_steps)
    if trace:
        run.trace = _load_trace(trace_dir / cell.name)
    if on_run is not None:
        on_run(run)
    metrics = {}
    try:
        for m in (cell.per_layer if trace else cell.end_to_end):
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    finally:
        if trace:
            keep_steps(trace_dir / cell.name, run, metrics)

    # the check, once the engine and its state are gone
    sample = check_sample(everyone, int(e["check_requests"]), seed)
    del drv, disp, engine, engines
    gc.unfreeze()
    gc.collect()
    t_check = clock()
    compared = compare(cell, w, sample, quant=control)
    readings = on_check(cell, w, sample) if on_check is not None else None
    limit = e.get("gap_limit")
    correct = (failed == 0 and compared["served_tokens"] > 0
               and compared["bad_requests"] == 0
               and limit is not None and compared["widest_gap"] <= limit)

    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(owed),
           "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        busy = run.trace.busy_s()
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = run.trace.window_s
        out["breakdown"] = breakdown(run.trace)
    if readings is not None:
        out["readings"] = readings
    out["compared"] = {
        "widest_gap": {"value": compared["widest_gap"], "limit": limit},
        "bad_requests": {"value": compared["bad_requests"], "limit": 0},
        "failed": {"value": failed, "limit": 0},
    }
    log(f"[bench] checked {compared['requests']} requests, "
        f"{compared['served_tokens']} {'served' if control is None else control}"
        f" tokens against the reference in {clock() - t_check:.2f}s")
    return out


def _drive(drv: Driver, pending: List[Tracked], until: float, *,
           trace_at: Optional[float] = None,
           trace_dir: Optional[Path] = None) -> float:
    """Submit ``pending`` as they fall due and step until ``until``;
    returns the time the last step ended.  With ``trace_at`` the profiler
    records from then, between steps, to the end: stopping it takes
    seconds, so that falls after the window.  The traced window opens one
    step after the profiler starts, so that the device's tracer records
    the window's first step whole."""
    disp = drv.disp
    i, n = 0, len(pending)
    profiled_from = None           # steps taken when the profiler started
    window_span = None
    now = clock()
    while now < until:
        if trace_at is not None and profiled_from is None and now >= trace_at:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=_profile_options())
            profiled_from = len(drv.steps)
        if (window_span is None and profiled_from is not None
                and len(drv.steps) > profiled_from):
            window_span = jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN)
            window_span.__enter__()
            drv.tracing = True
        while i < n and pending[i].due <= now:
            drv.submit(pending[i])
            i += 1
        if disp.has_work:
            drv.step()
        else:
            wake = [pending[i].due if i < n else until, until]
            if trace_at is not None and profiled_from is None:
                wake.append(trace_at)
            with jax.profiler.TraceAnnotation("sleep"):
                time.sleep(max(0.0, min(wake) - clock()))
        now = clock()
    while i < n and pending[i].due <= now:   # due during the last step
        drv.submit(pending[i])
        i += 1
    if profiled_from is not None:
        _stop_trace(drv, window_span)
    return now


def _stop_trace(drv: Driver, span) -> None:
    if span is not None:
        span.__exit__(None, None, None)
    jax.profiler.stop_trace()
    drv.tracing = False


def _load_trace(path: Path) -> Optional[xtrace.Trace]:
    found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    return xtrace.load(found[-1]) if found else None


def keep_steps(path: Path, run: Run, metrics: dict) -> None:
    """Beside the trace: the host's steps in the traced window and the
    per-layer metrics read from them (what a kept trace is tested with)."""
    path.mkdir(parents=True, exist_ok=True)
    (path / "steps.json").write_text(json.dumps({
        "cell": run.cell.name, "peaks": run.peaks,
        "steps": [asdict(s) for s in run.traced_steps()],
        "metrics": {k: v["value"] for k, v in metrics.items()}}, indent=1))


# ------------------------------------------------------------- the check --
def check_sample(requests: List[Tracked], k: int, seed: int) -> List:
    """Up to ``k`` finished requests: the longest, and the rest drawn from
    the seed."""
    done = [tr.req for tr in requests if tr.done_t is not None]
    if not done:
        return []
    done.sort(key=lambda r: (r.prompt_len + r.n_generated, r.request_id))
    longest, rest = done[-1], done[:-1]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


@jax.jit
def _gaps(logits, tokens):
    """How far each served token's logit lies below the row's best."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return best - got


def served_gaps(cell: Cell, w: dict, r, quants=(None,), rows=None) -> dict:
    """Reference logits over ``r``'s prompt and served tokens; returns for
    each precision of ``quants`` the gaps below the row's best of the
    served tokens (``None``) or of the tokens that the reference computed
    at that precision puts first, at every position of the same prompt
    and served tokens."""
    ref = reference(cell.reference)
    n = r.n_generated
    rows = rows or int(cell.mix["output"]["max"])
    toks = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
    args = (cell.shape, w, toks, r.prompt_len - 1, rows,
            int(cell.engine["max_seq"]))
    want = ref.served_logits(*args)
    out = {}
    for q in quants:
        if q is None:
            tokens = jnp.zeros((rows,), jnp.int32).at[:n].set(
                jnp.asarray(r.generated, jnp.int32))
        else:
            tokens = jnp.argmax(ref.served_logits(*args, quant=q), -1)
        out[q] = np.asarray(_gaps(want, tokens))[:n]
    return out


def compare(cell: Cell, w: dict, sample: List, quant=None) -> dict:
    """The widest gap over every served token of ``sample``; with
    ``quant``, over the tokens the control puts first instead."""
    widest, tokens, bad = 0.0, 0, 0
    for r in sample:
        ok = (r.n_generated == r.max_new_tokens
              and all(0 <= t < cell.shape.vocab for t in r.generated))
        if not ok:
            bad += 1
            continue
        g = served_gaps(cell, w, r, (quant,))[quant]
        widest = max(widest, float(g.max()))
        tokens += len(g)
    return {"widest_gap": widest, "served_tokens": tokens,
            "bad_requests": bad, "requests": len(sample)}


# ------------------------------------------------------------- breakdown --
def breakdown(tr: xtrace.Trace, chip: int = 0, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps, each named by the host span around it and the programs on either
    side."""
    lo, hi = tr.window
    ops = [e for e in tr.ops.get(chip) or tr.modules.get(chip, [])
           if e.end > lo and e.start < hi]
    by_op: Dict[str, float] = {}
    for e in ops:
        key = f"{e.module}/{e.name}" if e.module else e.name
        by_op[key] = by_op.get(key, 0.0) + e.dur * 1e-9
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    gaps = []
    cur_end, cur_mod = lo, "(window start)"
    for e in sorted(ops, key=lambda e: e.start):
        if e.start > cur_end:
            gaps.append((cur_end, e.start, cur_mod, e.module or e.name))
        if e.end > cur_end:
            cur_end, cur_mod = e.end, e.module or e.name
    if hi > cur_end:
        gaps.append((cur_end, hi, cur_mod, "(window end)"))
    named = []
    for s, t, before, after in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + t) / 2
        host = next((sp.name for sp in tr.spans
                     if sp.start <= mid <= sp.end), "other")
        named.append([f"{host}: {before} -> {after}", (t - s) * 1e-9])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": named}


def print_compared(result: dict) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
