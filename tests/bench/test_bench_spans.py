"""The program's host spans in the benchmark: the two readers that use
them (``step_idle_ms`` from the device trace, ``queue_wait_ms`` from the
engine's host-clock stamps) on records built by hand, the idle gaps named
by the innermost span, a traced tiny run on the CPU, and the whole
reduction on a trace recorded on a v5e with the program's spans, kept in
``bench/testdata``."""

import json
import types

import numpy as np
import pytest
from conftest import PEAKS, REPO, run_tiny, tiny_cell

from bench import harness, spans, xtrace
from repro.core import events
from repro.obs import HostSpans

MS = 1e6   # ns


def _ev(name, start, end, module=""):
    return xtrace.Event(name, start * MS, end * MS, module)


def _program():
    """The program's spans of two 44 ms dispatcher steps inside the
    harness's ``step`` spans, each a prefill piece then a decode."""
    def step(base, prefill_end, decode_start, decode_end):
        return [_ev("dispatch.step", base + 0.5, base + 44.5),
                _ev("engine.step", base + 0.6, base + 44.0),
                _ev("engine.schedule", base + 0.6, base + 0.7),
                _ev("engine.prefill", base + 0.7, prefill_end),
                _ev("engine.prefill.sync", base + 1.5, prefill_end),
                _ev("engine.decode", decode_start, decode_end),
                _ev("engine.decode.sync", decode_start + 0.2, decode_end),
                _ev("engine.finish", decode_end, base + 43.5),
                _ev("dispatch.feedback", base + 44.0, base + 44.4)]

    out = step(0.0, 15.0, 18.5, 41.0) + step(50.0, 58.0, 58.8, 81.0)
    return sorted(out, key=lambda e: e.start)


def _trace():
    """``test_bench_trace._trace``'s two steps (device: prefill 2-14 and
    decode 20-40 ms, then prefill 52-57 and decode 60-80 ms) with the
    program's spans."""
    mods = [_ev("jit__prefill(7)", 2, 14, "_prefill"),
            _ev("jit__decode(7)", 20, 40, "_decode"),
            _ev("jit__prefill(7)", 52, 57, "_prefill"),
            _ev("jit__decode(7)", 60, 80, "_decode")]
    ops = [xtrace.Event("fusion.1", m.start, m.end, m.module) for m in mods]
    host = [_ev("step", 0, 45), _ev("observe", 45, 50), _ev("step", 50, 95),
            _ev("sleep", 95, 100)]
    tr = xtrace.Trace(window=(0.0, 100 * MS), modules={0: mods},
                      ops={0: ops}, spans=host)
    tr.program_spans = _program()
    return tr


def _run(trace=None, requests=()):
    cell = tiny_cell()
    return harness.Run(cell=cell, peaks=PEAKS, setup_s=1.0, t_open=0.0,
                       t_close=0.1, requests=list(requests), steps=[],
                       trace=trace)


# ------------------------------------------------------------ the readers --
def test_step_idle_reads_idle_inside_dispatch_steps():
    tr = _trace()
    # step 1: 44 ms, of which the device ran 12 + 20; step 2: 44 ms, 5 + 20
    assert spans.idle_in(tr, tr.program_spans) == (
        pytest.approx(31 * MS), 2)
    value = harness.reader("step_idle_ms")(_run(tr))
    assert value == pytest.approx(15.5)
    # never more than the window's idle time
    idle = harness.reader("device_idle")(_run(tr)) / 100 * tr.window_s * 1e3
    assert 2 * value <= idle


def test_step_idle_clips_to_the_window_and_skips_steps_before_it():
    tr = _trace()
    tr.window = (10 * MS, 90 * MS)
    # only the second step starts in the window, which cuts it to 39.5 ms,
    # 25 of them busy
    assert harness.reader("step_idle_ms")(_run(tr)) == pytest.approx(14.5)


def test_step_idle_reads_nothing_without_program_spans_or_device():
    tr = _trace()
    tr.program_spans = []                    # a program without the spans
    assert harness.reader("step_idle_ms")(_run(tr)) is None
    tr = _trace()
    tr.modules, tr.ops = {}, {}              # a trace without a device
    assert harness.reader("step_idle_ms")(_run(tr)) is None
    assert harness.reader("step_idle_ms")(_run(None)) is None


def _requests(waits_ms, admitted_at=0.05):
    out = []
    for w in waits_ms:
        req = types.SimpleNamespace(host_admitted=admitted_at,
                                    host_queued=admitted_at - w * 1e-3)
        out.append(types.SimpleNamespace(req=req))
    return out


def test_queue_wait_is_the_90th_percentile_of_admitted_requests():
    waits = list(np.linspace(0.0, 500.0, 120))
    late = _requests([9000.0] * 5, admitted_at=0.5)   # after the window
    run = _run(requests=_requests(waits) + late)
    assert harness.reader("queue_wait_ms")(run) == pytest.approx(
        float(np.percentile(waits, 90)))


def test_queue_wait_needs_a_hundred_requests_and_the_stamps():
    assert harness.reader("queue_wait_ms")(
        _run(requests=_requests([10.0] * 99))) is None
    unstamped = [types.SimpleNamespace(req=types.SimpleNamespace())
                 for _ in range(150)]
    never_sent = [types.SimpleNamespace(req=None) for _ in range(3)]
    assert harness.reader("queue_wait_ms")(
        _run(requests=unstamped + never_sent)) is None


# ----------------------------------------------------------- the gaps -----
def test_breakdown_names_gaps_by_the_innermost_program_span():
    tr = _trace()
    b = spans.breakdown(tr, tr.program_spans)
    assert b["device_ops"] == harness.breakdown(tr)["device_ops"]
    assert b["idle_gaps"] == [
        ["engine.finish: _decode -> (window end)", pytest.approx(0.020)],
        ["observe: _decode -> _prefill", pytest.approx(0.012)],
        ["engine.step: _prefill -> _decode", pytest.approx(0.006)],
        ["engine.step: _prefill -> _decode", pytest.approx(0.003)],
        ["engine.prefill: (window start) -> _prefill",
         pytest.approx(0.002)]]


def test_breakdown_without_program_spans_is_the_harness_s():
    tr = _trace()
    assert spans.breakdown(tr, []) == harness.breakdown(tr)


def test_idle_split_by_the_innermost_span():
    """Every idle stretch goes to the span open over it: the window's 43 ms
    of idle time, split."""
    got = spans.idle_by_span(_trace(), _program())
    assert {k: v / MS for k, v in got.items()} == pytest.approx({
        "step": 2.0, "dispatch.step": 0.4, "engine.schedule": 0.2,
        "engine.prefill": 1.6, "engine.prefill.sync": 3.0,
        "engine.step": 5.3, "engine.decode": 0.4, "engine.decode.sync": 4.3,
        "engine.finish": 15.0, "dispatch.feedback": 0.8, "observe": 5.0,
        "sleep": 5.0})
    bare = _trace()
    bare.spans = []
    assert spans.idle_by_span(bare, []) == {"other": pytest.approx(43 * MS)}


# ------------------------------------------------- a traced run on the CPU --
def test_traced_tiny_run_holds_the_program_spans(tmp_trace):
    """The profile of a traced tiny run holds ``engine.step`` inside the
    harness's ``step``, and the in-memory ring's spans line up with the
    profiler's to within 1 ms once the clocks' offset is taken out."""
    sink = HostSpans()
    seen = {}
    try:
        run_tiny(tiny_cell(), trace=True, trace_dir=tmp_trace,
                 engine_hook=lambda engine: events.install_spans(sink),
                 on_run=lambda run: seen.update(run=run))
    finally:
        events.install_spans(None)
    run = seen["run"]
    found = sorted((tmp_trace / run.cell.name).glob(
        "plugins/profile/*/*.xplane.pb"))
    program = spans.load(found[-1])
    lo, hi = run.trace.window
    in_window = [s for s in program if lo <= s.start <= hi]
    names = {s.name for s in in_window}
    assert {"dispatch.step", "engine.step", "engine.schedule",
            "engine.prefill", "engine.decode", "engine.finish",
            "dispatch.feedback"} <= names
    steps = [s for s in run.trace.spans if s.name == "step"]
    for es in (s for s in in_window if s.name == "engine.step"):
        assert any(st.start <= es.start and es.end <= st.end
                   for st in steps), es

    traced = run.traced_steps()
    prof = [s for s in in_window if s.name == "dispatch.step"]
    mine = [s for s in sink.spans() if s.name == "dispatch.step"
            and traced[0].t0 <= s.start <= traced[-1].t1]
    assert len(prof) == len(mine) == len(traced) > 0
    offset = prof[0].start * 1e-9 - mine[0].start
    for p, m in zip(prof, mine):
        assert abs(p.start * 1e-9 - m.start - offset) < 1e-3
        assert abs(p.end * 1e-9 - m.end - offset) < 1e-3
    # no device plane here: the device reader reads nothing
    assert harness.reader("step_idle_ms")(run) is None


# ------------------------------- a trace kept from a v5e, with the spans --
KEPT = REPO / "bench" / "testdata" / "granite-8b.chat.spans"
KEPT_METRICS = ("decode_step_ms", "decode_roofline", "step_mfu",
                "device_idle", "step_idle_ms")
TREE = {"dispatch.step", "engine.step", "engine.schedule", "engine.prefill",
        "engine.prefill.sync", "engine.adopt", "engine.decode",
        "engine.decode.sync", "engine.finish", "dispatch.feedback"}


def _kept_run():
    """The kept trace, its program's spans and the host's steps recorded
    beside it, and the metrics that the run on the chip read from them."""
    kept = json.loads((KEPT / "steps.json").read_text())
    trace = xtrace.load(KEPT / "trace.xplane.pb.xz")
    trace.program_spans = spans.load(KEPT / "trace.xplane.pb.xz")
    run = harness.Run(
        cell=harness.load_cell(kept["cell"]), peaks=kept["peaks"],
        setup_s=0.0, t_open=0.0, t_close=0.0, requests=[],
        steps=[harness.Step(**s) for s in kept["steps"]], trace=trace)
    return run, kept["metrics"]


def test_kept_spans_trace_has_the_step_tree():
    """Every span of the step tree is there, each dispatcher step inside
    one of the harness's steps and each program span inside its
    dispatcher step."""
    run, _ = _kept_run()
    tr = run.trace
    lo, hi = tr.window
    program = [s for s in tr.program_spans if lo <= s.start <= hi]
    assert TREE <= {s.name for s in program}
    harness_steps = [s for s in tr.spans if s.name == "step"]
    outer = [s for s in program if s.name == "dispatch.step"]
    assert len(outer) == len(run.steps)
    for d in outer:
        assert any(h.start <= d.start and d.end <= h.end
                   for h in harness_steps)
    for s in program:
        assert any(d.start <= s.start and s.end <= d.end for d in outer), s


@pytest.mark.parametrize("name", KEPT_METRICS)
def test_kept_spans_trace_reads_as_on_the_chip(name):
    run, recorded = _kept_run()
    value = harness.reader(name)(run)
    assert value == pytest.approx(recorded[name], rel=1e-12)


def test_kept_spans_trace_places_the_idle_time():
    """The idle time inside dispatcher steps is at most the window's; the
    longest gaps are named by the program's spans; the idle time split by
    span adds up to the window's."""
    run, recorded = _kept_run()
    tr = run.trace
    idle_ms = recorded["device_idle"] / 100 * tr.window_s * 1e3
    per_step, steps = spans.idle_in(tr, tr.program_spans)
    assert recorded["step_idle_ms"] * steps <= idle_ms
    gaps = spans.breakdown(tr, tr.program_spans)["idle_gaps"]
    named = [n for n, _ in gaps if n.startswith(spans.PREFIXES)]
    assert len(gaps) == 10 and len(named) >= 8
    split = spans.idle_by_span(tr, tr.program_spans)
    assert sum(split.values()) * 1e-6 == pytest.approx(idle_ms, rel=1e-6)
    # the waits for the device's results come first
    assert max(split, key=split.get) in ("engine.decode.sync",
                                         "engine.prefill.sync")
