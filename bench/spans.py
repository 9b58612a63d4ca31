"""The program's own host spans in a profiler trace.

The engine and the dispatcher open a ``jax.profiler.TraceAnnotation`` at
each boundary of a step (``repro.core.events.span``): ``dispatch.step``
around one dispatcher step, ``engine.step`` inside it, and in that
``engine.schedule``, ``engine.prefill`` (``engine.prefill.sync``),
``engine.adopt``, ``engine.decode`` (``engine.decode.sync``),
``engine.finish`` (``engine.release``), ``engine.feedback``; then
``dispatch.feedback``.  They lie on the same host line as the harness's
spans, on the clock of the device planes.  :func:`bench.xtrace.load` keeps
only the harness's spans (:data:`bench.xtrace.HOST_SPANS`), so this module
reads the program's from the same profile again.  A program without them
(an older commit) gives none, and what reads them reads nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path
from typing import List, Optional, Tuple

from bench import xtrace

__all__ = ["PREFIXES", "load", "of_run", "breakdown", "idle_in",
           "idle_by_span"]

PREFIXES = ("engine.", "dispatch.")


def _read(path: Path) -> Tuple[Optional[Tuple[float, float]],
                               List[xtrace.Event]]:
    """The traced window and the program's spans of the profile at
    ``path`` (host planes only)."""
    window, spans = None, []
    for plane in xtrace._profile(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xtrace.WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(PREFIXES):
                    spans.append(xtrace.Event(ev.name, ev.start_ns,
                                              ev.end_ns))
    spans.sort(key=lambda e: e.start)
    return window, spans


def load(path: Path) -> List[xtrace.Event]:
    """The program's spans of the profile at ``path``, by start."""
    return _read(path)[1]


def of_run(run) -> Optional[List[xtrace.Event]]:
    """The program's spans of a traced run: those its trace carries
    (``program_spans``, as a kept trace's test sets them), else those of the
    newest profile in the harness's trace directory for the cell, when that
    profile is the run's own (the same traced window).  None without a
    trace; empty when the program wrote none."""
    tr = run.trace
    if tr is None:
        return None
    got = getattr(tr, "program_spans", None)
    if got is None:
        from bench import harness

        got = []
        found = sorted((harness.TRACE_DIR / run.cell.name)
                       .glob("plugins/profile/*/*.xplane.pb"))
        if found:
            window, spans = _read(found[-1])
            if window == tuple(tr.window):
                got = spans
        tr.program_spans = got
    return got


def breakdown(trace: xtrace.Trace, program: List[xtrace.Event],
              chip: int = 0, top: int = 10) -> dict:
    """:func:`bench.harness.breakdown` with each idle gap named by the
    innermost span around its middle, the program's spans among the
    harness's.  Spans on one thread nest, so the innermost is the one that
    started last; with no program spans the names are the harness's."""
    from bench import harness

    merged = sorted(list(trace.spans) + list(program), key=lambda e: -e.start)
    return harness.breakdown(dataclasses.replace(trace, spans=merged),
                             chip=chip, top=top)


def _busy(events, window) -> List[Tuple[float, float]]:
    """The union of ``events`` clipped to ``window``, as sorted disjoint
    intervals."""
    lo, hi = window
    out: List[List[float]] = []
    for s, e in sorted((max(e.start, lo), min(e.end, hi)) for e in events
                       if e.end > lo and e.start < hi):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _covered(busy, starts, a: float, b: float) -> float:
    """How much of ``[a, b]`` the disjoint sorted intervals ``busy``
    cover."""
    total = 0.0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(busy) and busy[i][0] < b:
        s, e = busy[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


def idle_in(trace: xtrace.Trace, program: List[xtrace.Event],
            name: str = "dispatch.step") -> Optional[Tuple[float, int]]:
    """Device-idle nanoseconds inside the spans ``name`` that start in the
    traced window (each clipped to it), averaged over the chips that ran
    an operation, and the number of those spans; None when there are no
    such spans or no device operations."""
    lo, hi = trace.window
    inside = [(max(s.start, lo), min(s.end, hi)) for s in program
              if s.name == name and lo <= s.start <= hi]
    chips = [c for c in set(trace.ops) | set(trace.modules)
             if trace.ops.get(c) or trace.modules.get(c)]
    if not inside or not chips:
        return None
    idle = 0.0
    for c in chips:
        busy = _busy(trace.ops.get(c) or trace.modules.get(c, []),
                     trace.window)
        starts = [s for s, _ in busy]
        idle += sum((b - a) - _covered(busy, starts, a, b)
                    for a, b in inside)
    return idle / len(chips), len(inside)


def _innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the time the nested
    ``spans`` cover, each named by the innermost span there."""
    out: List[Tuple[float, float, str]] = []
    stack: List[xtrace.Event] = []
    t = 0.0
    for sp in sorted(spans, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= sp.start:
            top = stack.pop()
            out.append((t, top.end, top.name))
            t = top.end
        if stack:
            out.append((t, sp.start, stack[-1].name))
        stack.append(sp)
        t = sp.start
    while stack:
        top = stack.pop()
        out.append((t, top.end, top.name))
        t = top.end
    return [(a, b, n) for a, b, n in out if b > a]


def idle_by_span(trace: xtrace.Trace, program: List[xtrace.Event],
                 chip: int = 0) -> dict:
    """Device-idle nanoseconds of the traced window on ``chip``, split by
    the innermost host span (the harness's or the program's) over each
    stretch of it; ``"other"`` where no span is open."""
    lo, hi = trace.window
    busy = _busy(trace.ops.get(chip) or trace.modules.get(chip, []),
                 trace.window)
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    pieces = _innermost(list(trace.spans) + list(program))
    out: dict = {}
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            part = min(e, b) - max(s, a)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            k += 1
        if b - a > covered:
            out["other"] = out.get("other", 0.0) + (b - a - covered)
    return out
