"""The reduction from trace events to the per-layer metrics: the pairing of
device executions with the host's steps, the device-time readers, the
roofline, the idle share and the breakdown, on a trace built by hand; the
reading of a profiler file, on one recorded here on the CPU; and the whole
reduction on a trace recorded on a v5e, kept in ``bench/testdata``."""

import json

import pytest
from conftest import PEAKS, REPO, run_tiny, tiny_cell

from bench import flops, harness, xtrace

MS = 1e6   # ns


def _trace():
    """Two engine steps of 50 ms: step 1 runs a 256-token prefill piece
    (12 ms) then a decode (20 ms); step 2 a 64-token piece (5 ms) and a
    decode (20 ms).  Ops fill the programs."""
    def ex(name, start, dur):
        return xtrace.Event(f"jit_{name}(7)", start * MS, (start + dur) * MS,
                            name)

    mods = [ex("_prefill", 2, 12), ex("_decode", 20, 20),
            ex("_prefill", 52, 5), ex("_decode", 60, 20)]
    ops = [xtrace.Event("fusion.1", m.start, m.end, m.module) for m in mods]
    spans = [xtrace.Event("step", 0, 45 * MS), xtrace.Event("observe",
                                                            45 * MS, 50 * MS),
             xtrace.Event("step", 50 * MS, 95 * MS),
             xtrace.Event("sleep", 95 * MS, 100 * MS)]
    return xtrace.Trace(window=(0.0, 100 * MS), modules={0: mods},
                        ops={0: ops}, spans=spans)


def _run(trace=None):
    cell = tiny_cell()
    cell.shape = harness.load_cell("granite-8b.chat").shape
    cell.engine = dict(cell.engine, prefill_chunk=256)
    steps = [harness.Step(0.0, 0.05, prefill_len=256, prefill_start=0,
                          decode_kv=[300, 700], traced=True),
             harness.Step(0.05, 0.10, prefill_len=64, prefill_start=256,
                          decode_kv=[301, 701, 1], traced=True)]
    return harness.Run(cell=cell, peaks=PEAKS, setup_s=1.0, t_open=0.0,
                       t_close=0.1, requests=[], steps=steps,
                       trace=trace or _trace())


def test_device_time_readers():
    run = _run()
    assert harness.reader("prefill_chunk_ms")(run) == pytest.approx(12.0)
    assert harness.reader("decode_step_ms")(run) == pytest.approx(20.0)
    # busy 12 + 20 + 5 + 20 = 57 of 100 ms
    assert harness.reader("device_idle")(run) == pytest.approx(43.0)


def test_roofline_and_mfu():
    run = _run()
    shape = run.cell.shape
    least = sum(max(flops.decode_flops(shape, kv) / 197e12,
                    flops.decode_bytes(shape, kv) / 819e9)
                for kv in ([300, 700], [301, 701, 1]))
    assert harness.reader("decode_roofline")(run) == pytest.approx(
        100 * least / 0.040)
    work = (flops.prefill_flops(shape, 256, 0)
            + flops.prefill_flops(shape, 64, 256)
            + flops.decode_flops(shape, [300, 700])
            + flops.decode_flops(shape, [301, 701, 1]))
    assert harness.reader("step_mfu")(run) == pytest.approx(
        100 * work / (0.1 * 197e12))


def test_counts_that_disagree_read_nothing():
    """A device that ran a program another number of times than the
    host's steps did fails the reader: the reduction is wrong."""
    tr = _trace()
    tr.modules[0] = tr.modules[0][:3]           # one decode missing
    run = _run(tr)
    for name in ("decode_step_ms", "decode_roofline"):
        with pytest.raises(ValueError, match="_decode: 1 device") as err:
            harness.reader(name)(run)
        # the message says where the executions and steps lie
        assert ("1 _decode in the profile, 0 starting before the window"
                in str(err.value))
        assert "host steps from 0.000 to 95.000" in str(err.value)
    assert harness.reader("prefill_chunk_ms")(run) == pytest.approx(12.0)


def test_execution_ending_past_the_window_is_the_last_steps():
    """The device's clock is aligned to the host's only roughly: the last
    step's execution may end just after the traced window does, and still
    pairs with that step."""
    tr = _trace()
    tr.window = (0.0, 79.5 * MS)                # the last decode ends at 80
    run = _run(tr)
    assert harness.reader("decode_step_ms")(run) == pytest.approx(20.0)


def test_execution_starting_before_the_window_is_the_first_steps():
    """Likewise the first traced step's first execution may seem to start
    just before the window does; it still pairs with that step."""
    tr = _trace()
    tr.window = (2.5 * MS, 100 * MS)            # the first piece starts at 2
    run = _run(tr)
    assert harness.reader("prefill_chunk_ms")(run) == pytest.approx(12.0)
    assert harness.reader("decode_step_ms")(run) == pytest.approx(20.0)


def test_execution_of_the_step_before_the_window_is_not_its():
    """The window opens one step after the profiler starts.  That step's
    execution lies in the profile and may seem to end just inside the
    window; it pairs with no step of the window."""
    tr = _trace()
    lead = xtrace.Event("jit__decode(7)", -19.5 * MS, 0.5 * MS, "_decode")
    tr.modules[0] = [lead] + tr.modules[0]
    run = _run(tr)
    assert harness.reader("decode_step_ms")(run) == pytest.approx(20.0)


def test_breakdown_names_gaps_by_host_span():
    b = harness.breakdown(_trace())
    assert b["device_ops"][0] == ["_decode/fusion.1", pytest.approx(0.040)]
    # longest first, each named by the host span around its middle
    assert b["idle_gaps"] == [
        ["step: _decode -> (window end)", pytest.approx(0.020)],
        ["observe: _decode -> _prefill", pytest.approx(0.012)],
        ["step: _prefill -> _decode", pytest.approx(0.006)],
        ["step: _prefill -> _decode", pytest.approx(0.003)],
        ["step: (window start) -> _prefill", pytest.approx(0.002)]]


def test_union():
    ev = [xtrace.Event("a", 0, 10), xtrace.Event("b", 5, 20),
          xtrace.Event("c", 30, 40), xtrace.Event("d", 35, 36)]
    assert xtrace.union_ns(ev, (0, 100)) == 30
    assert xtrace.union_ns(ev, (8, 32)) == 14
    assert xtrace.module_name("jit__decode(17)") == "_decode"


def test_profiler_file_on_the_cpu(tmp_trace):
    """A traced tiny run writes a profile; the reader finds the window and
    the harness's host spans in it (the CPU has no device plane).  The
    window opens one step after the profiler starts: that step, and no
    other, lies in the profile before it."""
    seen = {}
    run_tiny(tiny_cell(), trace=True, trace_dir=tmp_trace,
             on_run=lambda run: seen.update(run=run))
    tr = seen["run"].trace
    assert tr is not None and tr.window_s > 0
    assert {"step", "observe"} <= {sp.name for sp in tr.spans}
    before = [sp for sp in tr.spans
              if sp.name == "step" and sp.start < tr.window[0]]
    assert len(before) == 1 and before[0].end <= tr.window[0]
    inside = [sp for sp in tr.spans if sp.name == "step"
              and tr.window[0] <= sp.start <= tr.window[1]]
    assert len(inside) == len(seen["run"].traced_steps())
    assert tr.busy_s() is None and tr.modules == {}



# ------------------------------------------------ a trace kept from a v5e --
KEPT = REPO / "bench" / "testdata" / "granite-8b.chat"
KEPT_METRICS = ("decode_step_ms", "decode_roofline", "step_mfu",
                "device_idle")


def _kept_run():
    """The kept trace with the host's steps recorded beside it, and the
    metrics that the run on the chip read from them."""
    kept = json.loads((KEPT / "steps.json").read_text())
    trace = xtrace.load(KEPT / "trace.xplane.pb.xz")
    run = harness.Run(
        cell=harness.load_cell(kept["cell"]), peaks=kept["peaks"],
        setup_s=0.0, t_open=0.0, t_close=0.0, requests=[],
        steps=[harness.Step(**s) for s in kept["steps"]], trace=trace)
    return run, kept["metrics"]


def test_kept_trace_has_the_layout_the_reduction_reads():
    """One device plane whose programs are the engine's, ops that each fall
    in a program's execution, and the harness's host spans."""
    tr = _kept_run()[0].trace
    assert set(tr.modules) == {0}
    assert {"_prefill", "_decode"} <= {e.module for e in tr.modules[0]}
    assert tr.ops[0] and all(e.module for e in tr.ops[0])
    assert {"step", "observe"} <= {sp.name for sp in tr.spans}
    assert 0 < tr.busy_s() < tr.window_s


@pytest.mark.parametrize("name", KEPT_METRICS)
def test_kept_trace_reads_as_on_the_chip(name):
    """Each reader gives what it gave on the chip; a share lies within 0
    and 100%."""
    run, recorded = _kept_run()
    value = harness.reader(name)(run)
    assert value == pytest.approx(recorded[name], rel=1e-12)
    if name in ("decode_roofline", "step_mfu", "device_idle"):
        assert 0 < value < 100


def test_kept_trace_breakdown():
    """The device's time goes first to the decode program's ops; every idle
    gap lies inside a host span of the harness."""
    b = harness.breakdown(_kept_run()[0].trace)
    assert b["device_ops"][0][0].startswith("_decode/")
    assert len(b["idle_gaps"]) == 10
    assert all(name.split(":")[0] in xtrace.HOST_SPANS + ("other",)
               for name, _ in b["idle_gaps"])


# --------------------- a backlog run whose first execution precedes it --
EDGE = REPO / "bench" / "testdata" / "granite-8b.backlog.edge"
EDGE_METRICS = ("prefill_chunk_ms", "decode_step_ms", "decode_roofline",
                "step_mfu", "device_idle")


def _edge_run():
    """A traced backlog run on a v5e (granite-8b at 16 layers, 6 slots x
    8192; seed 2200001638) whose first traced step's ``_decode`` starts
    0.35 ms before the window opens on the device's clock, with the host's
    steps and the metrics the chip read."""
    kept = json.loads((EDGE / "steps.json").read_text())
    cell = harness.load_cell("granite-8b.chat")
    cell.engine = dict(cell.engine, slots=6, max_seq=8192, prefill_chunk=256)
    run = harness.Run(
        cell=cell, peaks=kept["peaks"], setup_s=0.0, t_open=0.0,
        t_close=0.0, requests=[],
        steps=[harness.Step(**s) for s in kept["steps"]],
        trace=xtrace.load(EDGE / "trace.xplane.pb.xz"))
    return run, kept["metrics"]


def test_kept_edge_trace_first_execution_precedes_the_window():
    """Taken by their start, the executions would miss the first step's
    ``_decode``, and the reader would raise as it once did on the chip
    ("143 device executions ..., 144 host steps"); taken by their
    midpoint, they pair with the host's steps, and the lead step's, whole
    in the profile before the window, is not taken."""
    run, _ = _edge_run()
    tr = run.trace
    lo, hi = tr.window
    decodes = [e for e in tr.modules[0] if e.module == "_decode"]
    inside = tr.executions("_decode")
    assert inside[0].start < lo < inside[0].end
    assert len([e for e in decodes if e.end <= lo]) == 1
    by_start = [e for e in decodes if lo <= e.start <= hi]
    host = [s for s in run.steps if s.decode_kv]
    assert len(inside) == len(host) == len(by_start) + 1


@pytest.mark.parametrize("name", EDGE_METRICS)
def test_kept_edge_trace_reads_as_on_the_chip(name):
    run, recorded = _edge_run()
    assert harness.reader(name)(run) == pytest.approx(recorded[name],
                                                      rel=1e-12)
