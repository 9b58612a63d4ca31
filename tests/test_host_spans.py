"""Host spans on the wall clock: the ``span`` hook of the events shim, the
``HostSpans`` ring, the span tree of one engine step, the host-clock
stamps on requests, and the compile counter."""

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import events as _ev
from repro.models import init_params
from repro.obs import CompileCounter, HostSpans, SpanTracer
from repro.serving import (ContinuousBatchingEngine, InflightDispatcher,
                           LinearPhaseCost, Request)

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.key(0))


@pytest.fixture
def sink():
    """A fresh ring, installed for the test and taken out after it."""
    ring = HostSpans()
    prev = _ev.install_spans(ring)
    yield ring
    _ev.install_spans(prev)


# -------------------------------------------------------------- the hook --
def test_hook_off_is_the_profiler_annotation_alone():
    assert _ev.SPANS is None
    outer = _ev.span("engine.step")
    assert type(outer) is jax.profiler.TraceAnnotation
    with outer:
        with _ev.span("engine.decode", rows=3):
            pass
    ring = HostSpans()
    prev = _ev.install_spans(ring)
    assert _ev.install_spans(prev) is ring and _ev.SPANS is None
    assert ring.spans() == []


def test_hook_on_nests_with_parents_and_int_args(sink):
    with _ev.span("dispatch.step"):
        with _ev.span("engine.step"):
            with _ev.span("engine.prefill", tokens=16, start=0):
                pass
            with _ev.span("engine.decode", rows=2):
                pass
        with _ev.span("dispatch.feedback"):
            pass
    got = sink.spans()
    assert [s.name for s in got] == ["dispatch.step", "engine.step",
                                     "engine.prefill", "engine.decode",
                                     "dispatch.feedback"]
    by = {s.name: s for s in got}
    assert by["dispatch.step"].parent is None
    assert by["engine.step"].parent == by["dispatch.step"].id
    assert by["engine.prefill"].parent == by["engine.step"].id
    assert by["engine.decode"].parent == by["engine.step"].id
    assert by["dispatch.feedback"].parent == by["dispatch.step"].id
    assert by["engine.prefill"].args == {"tokens": 16, "start": 0}
    assert by["engine.decode"].args == {"rows": 2}
    for s in got:
        assert s.start <= s.end
        if s.parent is not None:
            p = next(q for q in got if q.id == s.parent)
            assert p.start <= s.start and s.end <= p.end


def test_hook_refuses_arguments_that_are_not_ints(sink):
    with pytest.raises(TypeError, match="ints"):
        _ev.span("engine.decode", rows=2.0)
    with pytest.raises(TypeError, match="ints"):
        _ev.span("engine.decode", rows="2")
    assert sink.spans() == []


def test_ring_is_bounded():
    ring = HostSpans(capacity=4)
    prev = _ev.install_spans(ring)
    try:
        for i in range(10):
            with _ev.span("engine.step", i=i):
                pass
    finally:
        _ev.install_spans(prev)
    held = ring.spans()
    assert [s.args["i"] for s in held] == [6, 7, 8, 9]
    assert ring.dropped == 6
    with pytest.raises(ValueError):
        HostSpans(capacity=0)


def test_longest_gives_self_times_by_name(tmp_path):
    ring = HostSpans()
    # (id, name, start, end, parent): a 10 ms step holding a 6 ms engine
    # step, itself holding 4 ms of decode; a 3 ms step before it
    ring._ring.extend([
        ring_span(2, "engine.decode", 0.012, 0.016, 1),
        ring_span(1, "engine.step", 0.011, 0.017, 0),
        ring_span(0, "dispatch.step", 0.010, 0.020, None),
        ring_span(3, "dispatch.step", 0.000, 0.003, None),
    ])
    ring._next_id = 4
    top = ring.longest(1, "dispatch.step")
    assert len(top) == 1 and top[0]["ms"] == pytest.approx(10.0)
    assert top[0]["self_ms"] == pytest.approx(
        {"dispatch.step": 4.0, "engine.step": 2.0, "engine.decode": 4.0})
    assert [s["ms"] for s in ring.longest(5)] == pytest.approx([10.0, 3.0])
    assert ring.longest(5, "dispatch.step", since=0.005) == top
    path = tmp_path / "spans.json"
    ring.write(str(path))
    import json
    events = json.loads(path.read_text())["traceEvents"]
    assert [e["name"] for e in events][:2] == ["dispatch.step",
                                               "dispatch.step"]
    assert events[1]["dur"] == pytest.approx(1e4)


def ring_span(i, name, start, end, parent):
    from repro.obs import HostSpan
    return HostSpan(i, name, start, end, parent, {})


# ----------------------------------------------------- the engine's step --
def _nested(spans):
    """``(depth, name)`` of each span, by start."""
    by_id = {s.id: s for s in spans}

    def depth(s):
        return 0 if s.parent is None else 1 + depth(by_id[s.parent])

    return [(depth(s), s.name) for s in spans]


def test_engine_step_emits_the_span_tree_and_stamps(params, sink):
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, max_seq=32,
                                   prefill_chunk=4)
    disp = InflightDispatcher([eng])
    # a 5-token prompt takes two pieces (4 then 1); two tokens to make
    req = Request(prompt=np.arange(5), max_new_tokens=2)
    disp.submit(req)
    assert req.host_queued is not None and req.host_admitted is None
    disp.step()                                   # first piece
    assert req.host_admitted is not None and req.host_first_token is None
    disp.step()                                   # last piece, then decode
    assert req.prefill_pieces == 2 and req.n_generated == 2
    assert req.state.value == "finished"
    assert (req.host_queued <= req.host_admitted <= req.host_first_token)
    got = [s for s in sink.spans() if s.name != "compile"]
    first = [(0, "dispatch.step"), (1, "engine.step"),
             (2, "engine.schedule"), (2, "engine.prefill"),
             (3, "engine.prefill.sync"), (1, "dispatch.feedback")]
    second = [(0, "dispatch.step"), (1, "engine.step"),
              (2, "engine.schedule"), (2, "engine.prefill"),
              (3, "engine.prefill.sync"), (2, "engine.adopt"),
              (2, "engine.decode"), (3, "engine.decode.sync"),
              (2, "engine.finish"), (3, "engine.release"),
              (1, "dispatch.feedback")]
    assert _nested(got) == first + second
    prefill = [s for s in got if s.name == "engine.prefill"]
    assert [s.args for s in prefill] == [{"tokens": 4, "start": 0},
                                         {"tokens": 1, "start": 4}]
    decode = next(s for s in got if s.name == "engine.decode")
    assert decode.args == {"rows": 1}


def test_multi_lane_prefill_spans(params, sink):
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, max_seq=32,
                                   prefill_chunk=4, prefill_lanes=2)
    reqs = [Request(prompt=np.arange(4) + i, max_new_tokens=1)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert all(r.prefill_pieces == 1 and r.host_first_token is not None
               for r in reqs)
    got = [s for s in sink.spans() if s.name != "compile"]
    assert [n for _, n in _nested(got)] == [
        "engine.step", "engine.schedule", "engine.prefill",
        "engine.prefill.sync", "engine.adopt", "engine.release",
        "engine.adopt", "engine.release"]
    assert got[2].args == {"tokens": 8, "lanes": 2}


def test_cost_model_path_keeps_its_virtual_spans(params, sink):
    """With a cost model the engine writes its prefill and decode spans on
    the virtual clock under the host spans' names; the virtual tracer
    receives none of the host spans."""
    eng = ContinuousBatchingEngine(CFG, params, max_slots=2, max_seq=32,
                                   cost_model=LinearPhaseCost())
    eng.submit(Request(prompt=np.arange(6), max_new_tokens=2))
    tracer = SpanTracer()
    prev = _ev.install(tracer)
    try:
        eng.run_until_idle()
    finally:
        _ev.install(prev)
    virtual = [e["name"] for e in tracer.chrome_events() if e["ph"] == "X"]
    assert virtual == ["engine.prefill", "engine.decode"]
    host = {s.name for s in sink.spans()}
    assert {"engine.step", "engine.prefill", "engine.decode"} <= host


# ------------------------------------------------------ the compile counter --
def test_compile_counter_counts_one_compile_of_a_new_shape(sink):
    f = jax.jit(lambda x: x * 3 + 1)
    # inputs made on the host: jnp.ones would lower a program of its own
    x3, x7, x11, x13 = (np.ones(n, np.float32) for n in (3, 7, 11, 13))
    f(x3).block_until_ready()
    with CompileCounter() as counter:
        counter.on = True
        f(x3).block_until_ready()                    # compiled before
        assert counter.count == 0
        f(x7).block_until_ready()                    # a new shape
        assert counter.count == 1
        f(x7).block_until_ready()
        counter.on = False
        f(x11).block_until_ready()                   # off: not counted
    assert counter.count == 1
    f(x13).block_until_ready()                       # listener gone
    assert counter.count == 1
    compiles = [s for s in sink.spans() if s.name == "compile"]
    # a lowering and a backend compile for each of the two new shapes seen
    # while the counter listened
    assert len(compiles) == 4
    assert all(s.end >= s.start for s in compiles)
