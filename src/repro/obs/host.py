"""Wall-clock host spans kept in memory, and a compile counter.

:func:`repro.core.events.span` opens a ``jax.profiler.TraceAnnotation`` at
every instrumented boundary of the engine step.  When the profiler records,
those spans land in its trace beside the device's programs; :class:`HostSpans`
keeps them besides, for as long a run as its ring holds, so that a rare long
step is placed even when no profiler window caught it::

    from repro.core import events
    from repro.obs import HostSpans

    sink = HostSpans()
    events.install_spans(sink)
    ...                                   # serve
    events.install_spans(None)
    for step in sink.longest(5, "dispatch.step"):
        print(step["ms"], step["self_ms"])
    sink.write("host_spans.json")         # open at https://ui.perfetto.dev

Times are ``time.perf_counter()`` seconds: the host clock, not the virtual
one, so this module is no part of the deterministic tracing of
:mod:`repro.obs.trace`.  Spans nest on the thread that opens them, which is
the one driving the engine.

:class:`CompileCounter` counts the programs JAX lowers (each compile, and
each load from the persistent cache, lowers once); while a sink is
installed every lowering and backend compile also becomes a ``compile``
span in it.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

from repro.core import events as _ev

__all__ = ["HostSpan", "HostSpans", "CompileCounter"]

clock = time.perf_counter


class HostSpan(NamedTuple):
    """One finished span; ``parent`` is the ``id`` of the span that was
    open around it (None for a root)."""

    id: int
    name: str
    start: float              # seconds, host clock
    end: float
    parent: Optional[int]
    args: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


class _Open:
    """The context manager of one span while it is open."""

    __slots__ = ("sink", "ann", "name", "args", "id", "parent", "start")

    def __init__(self, sink, name, args, annotation):
        self.sink, self.name, self.args = sink, name, args
        self.ann = annotation(name, **args)

    def __enter__(self):
        self.ann.__enter__()
        sink = self.sink
        self.id = sink._next_id
        sink._next_id += 1
        stack = sink._stack
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self.start = clock()
        return self

    def __exit__(self, *exc):
        end = clock()
        sink = self.sink
        sink._stack.pop()
        sink._ring.append(HostSpan(self.id, self.name, self.start, end,
                                   self.parent, self.args))
        self.ann.__exit__(*exc)
        return False


class HostSpans:
    """A bounded ring of host spans; install with
    ``repro.core.events.install_spans``.  The oldest spans fall out once
    ``capacity`` are held (:attr:`dropped` counts them)."""

    def __init__(self, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._ring: deque = deque(maxlen=capacity)
        self._stack: List[int] = []
        self._next_id = 0

    # --------------------------------------------------------------- hooks --
    def span(self, name: str, args: dict, annotation) -> _Open:
        for v in args.values():
            if type(v) is not int:
                raise TypeError(f"span {name!r}: arguments are ints, got "
                                f"{type(v).__name__}")
        return _Open(self, name, args, annotation)

    def add(self, name: str, start: float, end: float, **args) -> None:
        """A span that has already ended, inside whichever span is open."""
        parent = self._stack[-1] if self._stack else None
        self._ring.append(HostSpan(self._next_id, name, start, end, parent,
                                   args))
        self._next_id += 1

    # ------------------------------------------------------------- queries --
    @property
    def dropped(self) -> int:
        return self._next_id - len(self._ring) - len(self._stack)

    def spans(self) -> List[HostSpan]:
        """Every span held, by start time."""
        return sorted(self._ring, key=lambda s: (s.start, s.id))

    def longest(self, k: int = 5, name: Optional[str] = None,
                since: float = float("-inf"),
                until: float = float("inf")) -> List[dict]:
        """The ``k`` longest spans named ``name`` (roots when None) that
        start within ``[since, until]``, longest first.  Each gives its
        milliseconds and ``self_ms``: per name, the self time (duration
        less its children's) of the span and of every span inside it."""
        held = list(self._ring)
        children: Dict[int, List[HostSpan]] = {}
        for s in held:
            if s.parent is not None:
                children.setdefault(s.parent, []).append(s)
        picked = [s for s in held
                  if (s.name == name if name is not None else s.parent is None)
                  and since <= s.start <= until]
        picked.sort(key=lambda s: -s.dur)
        out = []
        for root in picked[:k]:
            self_ms: Dict[str, float] = {}
            todo = [root]
            while todo:
                s = todo.pop()
                kids = children.get(s.id, [])
                mine = s.dur - sum(c.dur for c in kids)
                self_ms[s.name] = self_ms.get(s.name, 0.0) + 1e3 * mine
                todo.extend(kids)
            out.append({"name": root.name, "start": root.start,
                        "ms": 1e3 * root.dur, "args": dict(root.args),
                        "self_ms": self_ms})
        return out

    # -------------------------------------------------------------- export --
    def write(self, path: str) -> None:
        """Chrome/Perfetto ``trace_event`` JSON of every span held
        (microseconds of the host clock)."""
        events = [{"ph": "X", "pid": 1, "tid": 1, "name": s.name,
                   "ts": 1e6 * s.start, "dur": 1e6 * s.dur,
                   "args": dict(s.args, id=s.id, parent=s.parent)}
                  for s in self.spans()]
        with open(path, "w") as f:
            json.dump({"displayTimeUnit": "ms", "traceEvents": events}, f)
            f.write("\n")


class CompileCounter:
    """Counts the programs JAX lowers while :attr:`on` (a compile or a load
    from the persistent cache), as a ``jax.monitoring`` listener registered
    for the ``with`` block.  While a host-span sink is installed, every
    lowering and backend compile, on or off, also becomes a ``compile``
    span in it."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on, self.count = False, 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event != self.LOWER and event != self.BACKEND:
            return
        if self.on and event == self.LOWER:
            self.count += 1
        sink = _ev.SPANS
        if sink is not None:
            end = clock()
            sink.add("compile", end - duration, end)

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self)
