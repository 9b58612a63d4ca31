"""Reduce a profiler trace (``*.xplane.pb``) to the events the metrics read.

What the reduction expects of a TPU trace: one plane per chip named
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per program
execution (named after the jitted function, e.g. ``jit__decode(<id>)``)
and whose line ``XLA Ops`` has one event per HLO operation, with the
program's name in its ``hlo_module`` stat; and a host plane ``/host:CPU``
one of whose lines (a thread's) holds the ``jax.profiler.TraceAnnotation``
spans the harness writes.  All timestamps are nanoseconds on one clock.
A trace recorded on a v5e has this layout, except that its ops carry no
``hlo_module`` stat: an op then belongs to the program whose execution
holds its start.  ``bench/testdata/`` keeps that trace, compressed with
xz, for the tests.  ``python3 bench/xtrace.py <file>`` lists a trace's
planes and lines, to see what another chip or version writes.

Only the harness's spans (:data:`HOST_SPANS`) and the device lines are
kept.  The traced window is the harness's ``traced`` span.
"""

from __future__ import annotations

import bisect
import lzma
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["Event", "Trace", "load", "describe", "union_ns", "module_name",
           "HOST_SPANS", "WINDOW_SPAN"]

HOST_SPANS = ("submit", "step", "sleep", "observe")
WINDOW_SPAN = "traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    end: float        # ns
    module: str = ""  # the program an op or execution belongs to

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    window: Tuple[float, float]                       # ns, the traced span
    modules: Dict[int, List[Event]] = field(default_factory=dict)  # per chip
    ops: Dict[int, List[Event]] = field(default_factory=dict)      # per chip
    spans: List[Event] = field(default_factory=list)  # host, HOST_SPANS only

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def executions(self, name: str, chip: int = 0) -> List[Event]:
        """Executions of the jitted function ``name`` on ``chip`` whose
        midpoint lies inside the window, in order.  The device's clock is
        aligned to the host's only to some tenths of a millisecond, so an
        execution of the window's first or last step may seem to start
        before the window or end after it.  Each lies inside the host step
        that sent it and waited for it, a step of milliseconds, and the
        window opens one step after the profiler starts: the midpoint of
        every execution of the window's steps lies inside it, and that of
        any other outside."""
        lo, hi = self.window
        return [e for e in self.modules.get(chip, [])
                if e.module == name and lo <= (e.start + e.end) / 2 <= hi]

    def edges(self, name: str, chip: int = 0) -> str:
        """Where the executions of ``name`` and the host's steps lie
        against the window's edges, in ms: what a disagreement between
        device and host counts is read from."""
        lo, hi = self.window
        evs = [e for e in self.modules.get(chip, []) if e.module == name]
        steps = [e for e in self.spans if e.name == "step"
                 and lo <= e.start <= hi]
        inside = self.executions(name, chip)
        out = [f"{len(evs)} {name} in the profile, "
               f"{sum(e.start < lo for e in evs)} starting before the "
               f"window, {sum(e.end > hi for e in evs)} ending after it"]
        if inside:
            first, last = inside[0], inside[-1]
            out.append(f"the first inside runs {(first.start - lo) / 1e6:.3f}"
                       f" to {(first.end - lo) / 1e6:.3f} from the opening, "
                       f"the last ends {(hi - last.end) / 1e6:.3f} before "
                       f"the close")
        if steps:
            out.append(f"host steps from {(steps[0].start - lo) / 1e6:.3f} "
                       f"to {(steps[-1].end - lo) / 1e6:.3f}")
        return "; ".join(out)

    def busy_s(self) -> Optional[float]:
        """Seconds in which any operation ran, averaged over the chips that
        ran one; None when no chip did."""
        chips = set(self.ops) | set(self.modules)
        per_chip = [union_ns(self.ops.get(c) or self.modules.get(c, []),
                             self.window) for c in chips]
        per_chip = [b for b in per_chip if b > 0]
        if not per_chip:
            return None
        return sum(per_chip) / len(per_chip) * 1e-9


def module_name(name: str) -> str:
    """``jit__decode(12)`` -> ``_decode``."""
    name = re.sub(r"\(.*\)$", "", name.strip())
    return name[4:] if name.startswith("jit_") else name


def union_ns(events, window) -> float:
    """Length of the union of ``events`` clipped to ``window``."""
    lo, hi = window
    ivs = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                 if e.end > lo and e.start < hi)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _profile(path: Path):
    """The profile at ``path``, an ``.xplane.pb`` or one compressed with xz
    (``.xplane.pb.xz``)."""
    import jax

    path = Path(path)
    if path.suffix == ".xz":
        return jax.profiler.ProfileData.from_serialized_xspace(
            lzma.decompress(path.read_bytes()))
    return jax.profiler.ProfileData.from_file(str(path))


def load(path: Path) -> Trace:
    """Read ``path`` (see :func:`_profile`) into a :class:`Trace`."""
    data = _profile(path)
    modules: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    window = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in ("XLA Modules", "XLA Ops"):
                chip = int(m.group(1))
                out = modules if line.name == "XLA Modules" else ops
                evs = out.setdefault(chip, [])
                for ev in line.events:
                    if line.name == "XLA Modules":
                        mod = module_name(ev.name)
                    else:
                        mod = module_name(str(_stat(ev, "hlo_module") or ""))
                    evs.append(Event(ev.name, ev.start_ns, ev.end_ns, mod))
            elif m is None and plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append(Event(ev.name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
    for evs in list(modules.values()) + list(ops.values()):
        evs.sort(key=lambda e: e.start)
    for chip, evs in ops.items():
        ops[chip] = _owned(evs, modules.get(chip, []))
    spans.sort(key=lambda e: e.start)
    return Trace(window=window, modules=modules, ops=ops, spans=spans)


def _owned(ops: List[Event], execs: List[Event]) -> List[Event]:
    """``ops`` with a short name (``%fusion.3 = ...`` -> ``fusion.3``) and,
    where the trace does not name it, the program whose execution holds the
    op's start."""
    starts = [e.start for e in execs]
    out = []
    for op in ops:
        module = op.module
        if not module:
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.start < execs[i].end:
                module = execs[i].module
        out.append(Event(op.name.split(" = ")[0].lstrip("%"), op.start,
                         op.end, module))
    return out


def describe(path: Path) -> str:
    """Every plane and line of the trace at ``path``, with its event count
    and its first events' names and stats."""
    out = []
    for plane in _profile(path).planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            out.append(f"  LINE {line.name!r}: {len(evs)} events, "
                       f"{len(names)} names; first names {names[:12]}")
            for ev in evs[:3]:
                out.append(f"    {ev.name!r} start {ev.start_ns} dur "
                           f"{ev.duration_ns} stats {list(ev.stats)[:12]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(Path(sys.argv[1])))
