"""From a profiler trace to numbers: the reduction every PR shares.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes into a
``Trace``: per device, the op events of its ``XLA Ops`` line and the
program events of its ``XLA Modules`` line; on the host, the harness's own
annotations (``harness.*``, ``engine.step``).  ``Trace`` round-trips through
JSON, so a small recorded trace can be checked in beside the tests.

The traced window is the host annotation ``harness.window``: every interval
is clipped to it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re

WINDOW = "harness.window"
HOST_PREFIXES = ("harness.", "engine.")


@dataclasses.dataclass
class Trace:
    # device name -> [(name, start_ns, dur_ns)]
    ops: "dict[str, list]"
    modules: "dict[str, list]"
    host: "list"  # [(name, start_ns, dur_ns)] harness annotations
    t0: float = 0.0  # window on the trace clock, ns
    t1: float = 0.0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def devices(self) -> "list[str]":
        return sorted(self.ops)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(**d)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU")


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO instruction
    (``%paged_decode_tpu.5 = bf16[...] custom-call(...)``): keep the
    instruction's name."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


# ops that only hold other ops (a layer scan's loop): their children are
# counted, so they are left out of the op totals
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                evs = [(op_name(e.name), float(e.start_ns),
                        float(e.duration_ns)) for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    for dev in modules:
        ops.setdefault(dev, [])
    win = [h for h in host if h[0] == WINDOW]
    if win:
        t0, t1 = win[0][1], win[0][1] + win[0][2]
    else:
        every = [s for evs in ops.values() for _, s, _ in evs]
        ends = [s + d for evs in ops.values() for _, s, d in evs]
        t0, t1 = (min(every), max(ends)) if every else (0.0, 0.0)
    return Trace(ops=ops, modules=modules, host=host, t0=t0, t1=t1)


def find_xplane(directory: str) -> "str | None":
    hits = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return hits[-1] if hits else None


def save(trace: Trace, path: str):
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# ------------------------------------------------------------- reductions


def _clip(evs, t0, t1):
    for name, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def union(intervals) -> "list[tuple[float, float]]":
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace, device: str) -> float:
    """Seconds of the window in which some op ran on ``device``."""
    evs = trace.ops.get(device) or trace.modules.get(device, [])
    return sum(b - a for a, b in union(
        (a, b) for _, a, b in _clip(evs, trace.t0, trace.t1))) * 1e-9


def mean_busy_s(trace: Trace) -> float:
    devs = trace.devices
    return sum(busy_s(trace, d) for d in devs) / len(devs) if devs else 0.0


def idle_gaps(trace: Trace, device: str, top: int = 10) -> "list":
    """The longest idle gaps of ``device`` in the window, each named by the
    host annotation (other than the window itself) that overlaps it most."""
    evs = trace.ops.get(device) or trace.modules.get(device, [])
    busy = union((a, b) for _, a, b in _clip(evs, trace.t0, trace.t1))
    gaps, prev = [], trace.t0
    for a, b in busy + [(trace.t1, trace.t1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [h for h in trace.host if h[0] != WINDOW]
    out = []
    for a, b in gaps[:top]:
        best, name = 0.0, "host: none"
        for hn, hs, hd in host:
            ov = min(b, hs + hd) - max(a, hs)
            if ov > best:
                best, name = ov, hn
        out.append([name, (b - a) * 1e-9])
    return out


def op_totals(trace: Trace, device: str, top: int = 10) -> "list":
    """Device ops with the most time in the window, instances of one op
    summed (``copy.71`` and ``copy.77`` are ``copy``), loops that only
    hold other ops left out: [[name, seconds]]."""
    tot: dict = {}
    for name, a, b in _clip(trace.ops.get(device, []), trace.t0, trace.t1):
        if CONTAINERS.match(name):
            continue
        base = re.sub(r"\.\d+$", "", name)
        tot[base] = tot.get(base, 0.0) + (b - a)
    return [[n, s * 1e-9] for n, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def events(trace: Trace, device: str, pattern: str, *, line: str = "ops",
           within: "str | None" = None) -> "list[tuple[float, float]]":
    """(start, end) of the events whose name matches ``pattern`` (a regular
    expression) and that lie wholly in the window; with ``within``, only
    those inside a program (module event) matching ``within``."""
    src = trace.ops if line == "ops" else trace.modules
    rx = re.compile(pattern)
    hits = [(s, s + d) for n, s, d in src.get(device, [])
            if rx.search(n) and trace.t0 <= s and s + d <= trace.t1]
    if within is None:
        return hits
    outer = sorted(events(trace, device, within, line="modules"))
    starts = [a for a, _ in outer]
    keep = []
    for a, b in hits:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and b <= outer[i][1]:
            keep.append((a, b))
    return keep


def total_s(spans) -> float:
    return sum(b - a for a, b in spans) * 1e-9


def busiest(trace: Trace, pattern: str, *, line: str = "ops",
            within: "str | None" = None) -> "tuple[str | None, list]":
    """The device with the most time in matching events, and its spans;
    (None, []) where no device has any."""
    best, spans = None, []
    for dev in trace.devices:
        s = events(trace, dev, pattern, line=line, within=within)
        if s and total_s(s) > total_s(spans):
            best, spans = dev, s
    return best, spans
