"""The program's own spans in a trace, reduced by interval containment.

The serving engine times each phase of its tick with a profiler TraceMe
(``repro.serving.telemetry.span``), so ``engine.tick`` and its phases sit
on the host timeline of the same profile as the device ops, on one clock.
``load_xplane`` keeps them in ``Trace.host`` beside the harness's own
annotations (``harness.*`` and ``engine.step``, which is not a program
span).  A trace recorded from a program without them holds none, and every
reduction here then finds nothing.
"""
from __future__ import annotations

import bisect
import dataclasses

from chipbench import trace as tr

TICK = "engine.tick"
DECODE_WAIT = "engine.decode.wait"
WAITS = (DECODE_WAIT, "engine.prefill.wait")  # the host blocked on the chip
ADMIT = "engine.admit"
REGISTER = "engine.kv.register"
PROGRAM = (TICK, "engine.schedule", ADMIT, "engine.prefill.dispatch",
           REGISTER, "engine.prefill.wait", "engine.decode.prepare",
           "engine.decode.dispatch", DECODE_WAIT, "engine.decode.emit",
           "engine.spec.draft", "engine.spec.verify", "engine.gc")
OUTSIDE = "outside program spans"


@dataclasses.dataclass
class Tick:
    a: float  # ns, trace clock
    b: float
    children: "list[tuple[str, float, float]]"  # (name, start, end)

    def covered_ns(self, names) -> float:
        """Time of the tick covered by its children named in ``names``."""
        return Intervals((a, b) for n, a, b in self.children
                         if n in names).within(self.a, self.b)


class Intervals:
    """Intervals merged into sorted, disjoint ones, for the length they
    cover of a stretch."""

    def __init__(self, intervals):
        self.merged = tr.union(intervals)
        self.starts = [a for a, _ in self.merged]

    def within(self, a: float, b: float) -> float:
        """Length of ``[a, b]`` the intervals cover."""
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        total = 0.0
        for s, e in self.merged[i:]:
            if s >= b:
                break
            total += max(0.0, min(e, b) - max(s, a))
        return total


def program(trace: tr.Trace, names=PROGRAM) -> "list[tuple]":
    """(name, start, end) of the program spans named in ``names`` that lie
    wholly in the traced window, by start."""
    return sorted(((n, s, s + d) for n, s, d in trace.host
                   if n in names and trace.t0 <= s and s + d <= trace.t1),
                  key=lambda h: h[1])


def decode_ticks(trace: tr.Trace) -> "list[Tick]":
    """The ``engine.tick`` spans wholly in the window that hold an
    ``engine.decode.wait``, each with the program spans inside it."""
    spans = program(trace)
    starts = [a for _, a, _ in spans]
    out = []
    for name, a, b in spans:
        if name != TICK:
            continue
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
        kids = [h for h in spans[lo:hi] if h[2] <= b and h[0] != TICK]
        if any(n == DECODE_WAIT for n, _, _ in kids):
            out.append(Tick(a, b, kids))
    return out


def device_busy(trace: tr.Trace, device: str) -> Intervals:
    """The window's op intervals on ``device``."""
    evs = trace.ops.get(device) or trace.modules.get(device, [])
    return Intervals((a, b) for _, a, b in tr._clip(evs, trace.t0,
                                                    trace.t1))


def idle_by_span(trace: tr.Trace, device: str) -> "dict[str, float]":
    """Seconds of the window in which ``device`` ran no op, each piece put
    down to the innermost program span over it (the shortest of those
    covering it), or to ``OUTSIDE``."""
    spans = program(trace)
    cuts = sorted({trace.t0, trace.t1} | {t for _, a, b in spans
                                          for t in (a, b)})
    cuts = [t for t in cuts if trace.t0 <= t <= trace.t1]
    # the innermost span of each stretch between consecutive cuts
    labels, opened = [], []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][1] <= a:
            opened.append(spans[j])
            j += 1
        opened = [h for h in opened if h[2] > a]
        inner = min(opened, key=lambda h: h[2] - h[1], default=None)
        labels.append((a, b, OUTSIDE if inner is None else inner[0]))
    busy = device_busy(trace, device)
    out: dict = {}
    for a, b, name in labels:
        idle = (b - a) - busy.within(a, b)
        if idle > 0:
            out[name] = out.get(name, 0.0) + idle * 1e-9
    return out
