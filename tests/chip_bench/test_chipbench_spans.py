"""The reduction of the program's own spans: the engine's tick and its
phases on the profiler's host timeline, beside the device ops."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

import _tiny  # noqa: F401
from chipbench import spans
from chipbench import trace as tr
from chipbench.cells import load_module

MS = 1e6  # ns
DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("tick_host_ms", "tick_idle_ms", "kv_admit_ms")


def synthetic() -> tr.Trace:
    """Two chips over a 100 ms window.  A tick that admits a request, runs
    its chunk and reads its first token, then decodes; the harness between
    ticks; a second decode tick that admits one more request and ends in a
    collection; a third tick cut by the window's end."""
    host = [(tr.WINDOW, 0.0, 100 * MS)]
    for name, a, b in [
            ("engine.step", 0, 40.5),  # the harness's, not the program's
            ("engine.tick", 0, 40), ("engine.schedule", 0, 10),
            ("engine.admit", 1, 3), ("engine.prefill.dispatch", 3, 5),
            ("engine.kv.register", 5, 6), ("engine.prefill.wait", 6, 9),
            ("engine.decode.prepare", 10, 12),
            ("engine.decode.dispatch", 12, 13),
            ("engine.decode.wait", 13, 35), ("engine.decode.emit", 35, 40),
            ("harness.submit", 40.5, 50),
            ("engine.step", 50, 90.5),
            ("engine.tick", 50, 90), ("engine.schedule", 50, 51),
            ("engine.admit", 50.2, 50.8),
            ("engine.decode.prepare", 51, 53),
            ("engine.decode.dispatch", 53, 54),
            ("engine.decode.wait", 54, 84), ("engine.decode.emit", 84, 88),
            ("engine.gc", 88, 89),
            ("engine.tick", 95, 110), ("engine.decode.wait", 99, 105)]:
        host.append((name, a * MS, (b - a) * MS))
    ops = {"/device:TPU:0": [("fusion.1", 4 * MS, 5 * MS),
                             ("paged_decode_tpu.5", 12.5 * MS, 22.5 * MS),
                             ("paged_decode_tpu.5", 53.5 * MS, 30.5 * MS)],
           "/device:TPU:1": [("fusion.1", 5 * MS, 5 * MS),
                             ("paged_decode_tpu.5", 13.5 * MS, 22.5 * MS),
                             ("paged_decode_tpu.5", 54.5 * MS, 31.5 * MS)]}
    return tr.Trace(ops=ops, modules={}, host=host, t0=0.0, t1=100 * MS)


def read(name: str, trace):
    return load_module("metrics", name).read(SimpleNamespace(trace=trace))


def test_decode_ticks_are_whole_program_ticks_with_a_wait():
    ticks = spans.decode_ticks(synthetic())
    assert [(t.a / MS, t.b / MS) for t in ticks] == [(0, 40), (50, 90)]
    assert [n for n, _, _ in ticks[1].children] == [
        "engine.schedule", "engine.admit", "engine.decode.prepare",
        "engine.decode.dispatch", "engine.decode.wait",
        "engine.decode.emit", "engine.gc"]
    assert ticks[0].covered_ns(spans.WAITS) == pytest.approx(25 * MS)


@pytest.mark.parametrize("name,want", [
    # 40 - (3 + 22) and 40 - 30: the median of 15 and 10
    ("tick_host_ms", 12.5),
    # chip 0 idles 12.5 and 9.5 ms in the ticks, chip 1 12.5 and 8.5
    ("tick_idle_ms", 10.75),
    # admission 2 + 0.6 ms and one registration of 1 ms, two admissions
    ("kv_admit_ms", 1.8),
])
def test_readers_on_a_synthetic_trace(name, want):
    assert read(name, synthetic()) == pytest.approx(want)


def test_idle_is_put_down_to_the_innermost_program_span():
    got = spans.idle_by_span(synthetic(), "/device:TPU:0")
    want = {"engine.schedule": 2.4, "engine.admit": 2.6,
            "engine.prefill.dispatch": 1.0, "engine.decode.prepare": 4.0,
            "engine.decode.dispatch": 1.0, "engine.decode.emit": 9.0,
            "engine.gc": 1.0, "engine.tick": 1.0, spans.OUTSIDE: 20.0}
    assert got == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    t = synthetic()
    assert sum(got.values()) == pytest.approx(
        t.window_s - tr.busy_s(t, "/device:TPU:0"))


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_program_spans(name):
    """The trace recorded before the program wrote spans (the harness's
    ``engine.step`` annotations only), and no trace at all."""
    old = tr.load(os.path.join(DATA, "v5e_decode_closed.json.gz"))
    assert read(name, old) is None
    assert read(name, None) is None
    idle = spans.idle_by_span(old, "/device:TPU:0")
    assert list(idle) == [spans.OUTSIDE]


# A stretch of a traced run recorded on one TPU v5e chip
# (qwen2-0.5b.miobench-open, two decode ticks: one that admits a request,
# runs its 256-token chunk and reads its first token, one that only
# decodes), reduced by ``load_xplane`` and cut by time: the device's ops
# and programs, the harness's annotations and the program's spans.
RECORDED = os.path.join(DATA, "v5e_miobench_open_spans.json.gz")


def test_recorded_v5e_trace_with_program_spans():
    t = tr.load(RECORDED)
    dev = "/device:TPU:0"
    ticks = spans.decode_ticks(t)
    assert len(ticks) == 2
    # the ranges of a decode tick in the open cell's traced stretch
    # (PERF.md, section 5): host 2.9-30.6 ms, idle 4.1-29.1 ms, admission
    # 0.3-3 ms
    assert 2.9 <= read("tick_host_ms", t) <= 30.6
    assert 4.1 <= read("tick_idle_ms", t) <= 29.1
    assert 0.3 <= read("kv_admit_ms", t) <= 3.0
    # one clock: each step program ends before its tick's wait for it
    steps = tr.events(t, dev, r"step_fn", line="modules")
    assert len(steps) == 2
    for (a, b), tick in zip(sorted(steps), ticks):
        wait = next(h for h in tick.children if h[0] == spans.DECODE_WAIT)
        assert tick.a < a and b < wait[2]
    idle = spans.idle_by_span(t, dev)
    assert sum(idle.values()) == pytest.approx(
        t.window_s - tr.busy_s(t, dev))
    # what no child span covers is a small part of the tick's idle
    assert idle[spans.TICK] < 0.1 * sum(
        v for k, v in idle.items() if k != spans.OUTSIDE)
