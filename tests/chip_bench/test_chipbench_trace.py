"""The reduction from a profiler trace to the per-layer metrics."""
from __future__ import annotations

import os

import pytest

import _tiny  # noqa: F401
from chipbench import trace as tr

MS = 1e6  # ns


def synthetic() -> tr.Trace:
    """Two chips over a 100 ms window: decode programs of 20 ms holding a
    kernel and an all-gather, an idle gap while the host submits."""
    ops, modules = {}, {}
    for dev, shift in (("/device:TPU:0", 0.0), ("/device:TPU:1", 1.0)):
        ops[dev] = [("fusion.1", (0 + shift) * MS, 5 * MS),
                    ("paged_decode_kernel", (5 + shift) * MS, 10 * MS),
                    ("all-gather.3", (15 + shift) * MS, 5 * MS),
                    ("fusion.1", (50 + shift) * MS, 5 * MS),
                    ("paged_decode_kernel", (55 + shift) * MS, 10 * MS),
                    ("all-gather.3", (65 + shift) * MS, 5 * MS),
                    ("copy", (120 + shift) * MS, 5 * MS)]  # after the window
        modules[dev] = [("jit_step_fn(12)", (0 + shift) * MS, 20 * MS),
                        ("jit_step_fn(12)", (50 + shift) * MS, 20 * MS)]
    host = [(tr.WINDOW, 0.0, 100 * MS),
            ("engine.step", 0.0, 22 * MS),
            ("harness.submit", 22 * MS, 28 * MS),
            ("engine.step", 50 * MS, 22 * MS),
            ("harness.wait_arrival", 72 * MS, 28 * MS)]
    return tr.Trace(ops=ops, modules=modules, host=host, t0=0.0,
                    t1=100 * MS)


def test_busy_idle_and_gaps():
    t = synthetic()
    assert t.window_s == pytest.approx(0.1)
    assert tr.busy_s(t, "/device:TPU:0") == pytest.approx(0.040)
    assert tr.mean_busy_s(t) == pytest.approx(0.040)
    gaps = tr.idle_gaps(t, "/device:TPU:0")
    assert gaps[0] == ["harness.submit", pytest.approx(0.030)]
    assert gaps[1] == ["harness.wait_arrival", pytest.approx(0.030)]


def test_op_totals_and_program_events():
    t = synthetic()
    top = tr.op_totals(t, "/device:TPU:0")
    assert top[0] == ["paged_decode_kernel", pytest.approx(0.020)]
    dev, spans = tr.busiest(t, r"step_fn", line="modules")
    assert len(spans) == 2 and tr.total_s(spans) == pytest.approx(0.040)
    _, ag = tr.busiest(t, r"all-gather", within=r"step_fn")
    assert tr.total_s(ag) == pytest.approx(0.010)


def test_round_trip(tmp_path):
    t = synthetic()
    p = str(tmp_path / "t.json.gz")
    tr.save(t, p)
    u = tr.load(p)
    assert tr.busy_s(u, "/device:TPU:1") == tr.busy_s(t, "/device:TPU:1")
    assert [list(h) for h in u.host] == [list(h) for h in t.host]


# A stretch of a traced run recorded on one TPU v5e chip
# (qwen2-0.5b.decode-closed, two decode steps of the 64-slot batch), reduced
# by ``load_xplane`` and cut by time: op names, program names and the host
# annotations as the profiler wrote them.
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_decode_closed.json.gz")


def test_recorded_v5e_trace():
    t = tr.load(RECORDED)
    dev = "/device:TPU:0"
    assert t.devices == [dev]
    assert 0.34 < t.window_s < 0.36
    idle = 1 - tr.busy_s(t, dev) / t.window_s
    assert 0.0 < idle < 0.05
    steps = tr.events(t, dev, r"step_fn", line="modules")
    assert len(steps) == 2
    assert 0.150 < tr.total_s(steps) / 2 < 0.200
    kernel = tr.events(t, dev, r"paged_decode")
    assert len(kernel) == 2 * 24  # one call a layer a step
    assert len(tr.events(t, dev, r"paged_decode", within=r"step_fn")) == 48
    top = tr.op_totals(t, dev)
    assert top[0][0] == "paged_decode_tpu"
    assert not any(n.startswith("while") for n, _ in top)
    gaps = tr.idle_gaps(t, dev)
    assert gaps and all(name == "engine.step" for name, _ in gaps[:2])


def test_metric_readers_on_the_recorded_trace():
    from types import SimpleNamespace

    from chipbench.cells import load_module
    ctx = SimpleNamespace(trace=tr.load(RECORDED))
    step = load_module("metrics", "decode_step_ms").read(ctx)
    assert 150 < step < 200
    idle = load_module("metrics", "device_idle_share").read(ctx)
    assert 0 < idle < 5
    # no prefill chunk ran in this stretch, no all-gather on one chip
    assert load_module("metrics", "prefill_chunk_ms").read(ctx) is None
    assert load_module("metrics", "tp.allgather_ms").read(ctx) is None
