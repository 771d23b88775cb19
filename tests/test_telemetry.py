"""Continuum telemetry layer.

Covers the metrics registry primitives, Chrome-trace schema + lifecycle
span ordering under the virtual clock, zero-cost-when-disabled on the
decode hot path, dispatch-audit join correctness, the steady-state
recompile guard (warmed engines re-traced nothing across a mixed
replay), per-tier latency rollups, the trace_report CLI, and the
engine's phase spans in a ``jax.profiler`` trace and in the ``Tracer``.
"""
import gc
import glob
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving.cluster import Cluster, build_continuum
from repro.serving.engine import Request, ServingEngine
from repro.serving.request import ContinuumRequest
from repro.serving.telemetry import (
    MetricsRegistry,
    Telemetry,
    latency_summary,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SPEC = [(2, 1), (1, 1)]  # 1 cloud + 1 gpu edge


@pytest.fixture(scope="module")
def traced_world():
    """Small continuum with tracing on + one mixed replay already run."""
    tm = Telemetry(trace=True)
    handles = build_continuum(SPEC, seed=0, max_batch=2, max_seq=96,
                              telemetry=tm)
    cluster = Cluster(handles)
    _mixed_replay(cluster)
    return tm, cluster


def _mixed_replay(cluster, n_tasks: int = 6):
    """Submit a small spread of requests across both engines with audited
    predictions, drain, and collect — returns the measured records."""
    tm = cluster.telemetry
    rng = np.random.default_rng(0)
    t = 0.0
    for i in range(n_tasks):
        s = i % len(cluster.handles)
        h = cluster.handles[s]
        toks = rng.integers(1, h.cfg.vocab, 6 + 4 * (i % 3)).astype(np.int32)
        predicted, terms = h.predict_e2e_s(len(toks), 4)
        uid = cluster.submit(ContinuumRequest(
            tokens=toks, max_new_tokens=4, arrival_s=t, task=i, server=s,
            predicted_s=float(predicted)))
        if tm is not None:
            tm.record_dispatch(task=i, server=s, t=t, predicted_s=predicted,
                               uid=uid, terms=terms)
        t += 0.05
        cluster.advance_to(t)
    cluster.drain()
    return cluster.collect()


# ---------------------------------------------------------------- registry


def test_registry_primitives():
    m = MetricsRegistry()
    c = m.counter("hits")
    c.inc()
    c.inc(3)
    g = m.gauge("depth")
    g.set(7.5)
    h = m.histogram("lat")
    h.extend([1.0, 2.0, 3.0, 4.0])
    m.view("twice_hits", lambda: 2 * c.value)
    snap = m.snapshot()
    assert snap["hits"] == 4
    assert snap["depth"] == 7.5
    assert snap["twice_hits"] == 8
    assert snap["lat"]["count"] == 4
    assert snap["lat"]["p50"] == pytest.approx(2.5)
    # same name returns the same instrument, not a fresh one
    assert m.counter("hits") is c
    # reset zeroes stored instruments but keeps live views
    m.reset()
    assert c.value == 0 and h.count == 0
    assert m.snapshot()["twice_hits"] == 0


def test_latency_summary_shape():
    out = latency_summary([1.0, 2.0], [0.1, 0.2, 0.3], [2.0, 4.0])
    assert out["n_requests"] == 2
    assert out["ttft_p50_s"] == pytest.approx(1.5)
    assert out["e2e_mean_s"] == pytest.approx(3.0)
    empty = latency_summary([], [], [])
    assert empty["n_requests"] == 0 and empty["e2e_p95_s"] == 0.0


# ------------------------------------------------------------ trace schema


def test_trace_schema_and_lifecycle_ordering(traced_world, tmp_path):
    tm, cluster = traced_world
    trace = tm.to_json()
    events = trace["traceEvents"]
    assert events, "tracing was enabled but no events were recorded"
    # Chrome trace-event schema: every event carries ph/name/ts/pid/tid
    for ev in events:
        assert ev["ph"] in ("X", "i", "C", "M")
        assert "name" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    # process metadata names both engines
    names = {ev["args"]["name"] for ev in events
             if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert {h.name for h in cluster.handles} <= names

    # per-request lifecycle: uplink -> queue -> prefill -> decode ->
    # downlink, each span starting no earlier than the previous one
    order = ["uplink", "queue", "prefill", "decode", "downlink"]
    by_req: dict = {}
    for ev in events:
        if ev["ph"] == "X" and ev["name"] in order:
            by_req.setdefault((ev["pid"], ev["tid"]), {})[ev["name"]] = ev
    assert by_req, "no lifecycle spans recorded"
    for key, stages in by_req.items():
        assert set(stages) == set(order), f"request {key} missing stages"
        seq = [stages[n] for n in order]
        for a, b in zip(seq, seq[1:]):
            assert a["ts"] <= b["ts"], f"{a['name']} starts after {b['name']}"
            # spans chain: each stage begins where the previous one ended
            assert a["ts"] + a["dur"] <= b["ts"] + 1, \
                f"{a['name']} overlaps into {b['name']}"

    # engine ticks carry real virtual durations and are monotone per pid
    ticks: dict = {}
    for ev in events:
        if ev["ph"] == "X" and ev["name"] == "tick":
            ticks.setdefault(ev["pid"], []).append(ev["ts"])
    assert ticks
    for ts in ticks.values():
        assert ts == sorted(ts)

    # the export round-trips as plain JSON (Perfetto-loadable)
    path = tmp_path / "trace.json"
    tm.export(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["displayTimeUnit"] == "ms"
    assert len(loaded["traceEvents"]) == len(events)


def test_trace_report_cli(traced_world, tmp_path):
    from scripts.trace_report import main, report

    tm, _ = traced_world
    path = tmp_path / "trace.json"
    tm.export(str(path))
    out = report(json.loads(path.read_text()))
    assert "per-stage latency decomposition" in out
    assert "lifecycle/decode" in out and "transfer/uplink" in out
    assert "per-engine utilization" in out
    assert "cost-model calibration" in out
    assert main([str(path), "--top", "3"]) == 0


# ------------------------------------------------------- disabled-mode off


def test_disabled_telemetry_records_no_events():
    """Telemetry(trace=False) keeps the audit but allocates zero trace
    events; telemetry=None leaves the engine's tracer hook unset."""
    tm = Telemetry(trace=False)
    handles = build_continuum(SPEC[:1], seed=0, max_batch=2, max_seq=96,
                              telemetry=tm)
    cluster = Cluster(handles)
    recs = _mixed_replay(cluster, n_tasks=2)
    assert len(recs) == 2 and all(r["success"] for r in recs)
    assert tm.tracer.events == []          # no spans, ever
    assert tm.prediction_error()["n"] == 2  # ... but the audit still joins

    # hot-path guard: with no telemetry at all the engine keeps no tracer
    cfg = reduced(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        max_batch=2, max_seq=64)
    assert eng._tr is None
    req = Request(0, np.arange(1, 9).astype(np.int32), max_new_tokens=4)
    eng.submit(req)
    eng.run_until_drained()
    assert req.done and eng._tr is None


# ------------------------------------------------------------------- audit


def test_audit_join_and_prediction_error():
    tm = Telemetry(trace=False)
    u1 = tm.record_dispatch(task=1, server=0, t=0.0, predicted_s=2.0,
                            terms={"queue": 0.5, "decode": 1.5})
    u2 = tm.record_dispatch(task=2, server=1, t=0.1, predicted_s=1.0)
    u3 = tm.record_dispatch(task=3, server=0, t=0.2, predicted_s=5.0)
    tm.join_measured(u1, 1.0)            # +100% error
    tm.join_measured(u2, 2.0)            # -50% error
    tm.join_measured(u3, 9.0, completed=False)  # timeout: excluded
    recs = {r.uid: r for r in tm.audit_records()}
    assert recs[u1].terms["decode"] == 1.5
    assert recs[u1].measured_e2e_s == 1.0
    assert not recs[u3].completed
    err = tm.prediction_error()
    assert err["n"] == 2
    assert err["mean_abs_pct_err"] == pytest.approx(75.0)
    assert err["mean_signed_pct_err"] == pytest.approx(25.0)
    tm.reset()
    assert tm.prediction_error()["n"] == 0 and tm.audit_records() == []


def test_cluster_joins_measured_e2e(traced_world):
    """Every audited dispatch from the replay got its measured e2e joined
    at collect() and the prediction-error metric is well-formed."""
    tm, _ = traced_world
    recs = tm.audit_records()
    assert recs and all(r.completed and r.measured_e2e_s is not None
                        for r in recs)
    err = tm.prediction_error()
    assert err["n"] == len(recs)
    assert err["mean_abs_pct_err"] >= 0.0
    assert err["p95_abs_pct_err"] >= err["p50_abs_pct_err"] >= 0.0


# ------------------------------------------------- stats + tier rollups


def test_stats_are_registry_views(traced_world):
    tm, cluster = traced_world
    eng = cluster.handles[0].engine
    stats = eng.stats()
    for key in ("prefill_tokens_computed", "requests_finished",
                "xla_trace_events", "ticks"):
        assert key in stats
    # back-compat attribute accessors mirror the registry counters
    assert eng.prefill_tokens_computed == stats["prefill_tokens_computed"]
    ls = cluster.latency_stats()
    assert "tiers" in ls
    for tier in ("edge", "cloud"):
        assert ls["tiers"][tier]["n_requests"] >= 1
    # the tier rollup merges raw per-engine histograms: total matches
    total = sum(ls[h.name]["n_requests"] for h in cluster.handles)
    assert sum(t["n_requests"] for t in ls["tiers"].values()) == total


# -------------------------------------------------- recompile-guard test


def test_steady_state_no_recompiles(traced_world):
    """A warmed engine replaying a same-shaped mixed workload must trigger
    zero new XLA traces: the recompile-event counter stays 0 across the
    second replay and the jit cache sizes do not grow."""
    tm, cluster = traced_world
    cluster.reset()  # zeroes metrics; XLA caches + _traced persist
    sizes_before = [h.engine.jit_cache_sizes() for h in cluster.handles]
    recs = _mixed_replay(cluster)
    assert all(r["success"] for r in recs)
    for h, before in zip(cluster.handles, sizes_before):
        assert h.engine.metrics.snapshot()["xla_trace_events"] == 0, \
            f"{h.name} re-traced in steady state"
        assert h.engine.jit_cache_sizes() == before


# ------------------------------------------------------ engine phase spans


def _engine(telemetry=None, **kw):
    cfg = reduced(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    return ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                         max_batch=2, max_seq=64, prefill_chunk=8,
                         telemetry=telemetry, **kw)


def _requests(uid0: int, n: int = 3) -> "list[Request]":
    """Prompts of 12, 17 and 22 tokens: two or three 8-token chunks each,
    more requests than slots so that some wait for admission."""
    rng = np.random.default_rng(uid0)
    return [Request(uid0 + i, rng.integers(1, 100, 12 + 5 * i).astype(
        np.int32), max_new_tokens=3) for i in range(n)]


def _host_spans(trace_dir: str) -> "list[tuple]":
    """(name, start_ns, end_ns, stats) of every ``engine.*`` event on the
    host planes of the profile in ``trace_dir``, by start."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda h: h[1])


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The host spans of a warmed engine serving three requests, then a
    forced collection, all under the profiler."""
    eng = _engine()
    for r in _requests(100):
        eng.submit(r)
    eng.run_until_drained()  # compile every shape before the trace
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    try:
        for r in _requests(200):
            eng.submit(r)
        eng.run_until_drained()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(trace_dir)


def test_decode_phases_lie_inside_their_tick_in_order(profiled):
    phases = ["engine.decode.prepare", "engine.decode.dispatch",
              "engine.decode.wait", "engine.decode.emit"]
    ticks = [h for h in profiled if h[0] == "engine.tick"]
    assert ticks and all("step_num" in h[3] for h in ticks)
    assert all(h[3].get("tp") == 1 for h in ticks)  # the mesh width
    decode = [h for h in profiled if h[0] in phases]
    assert len(decode) == 4 * sum(
        1 for h in profiled if h[0] == "engine.decode.wait")
    for name, a, b, _ in decode:
        assert any(ta <= a and b <= tb for _, ta, tb, _ in ticks), name
    for _, ta, tb, _ in ticks:
        inside = [h[0] for h in decode if ta <= h[1] and h[2] <= tb]
        assert inside in ([], phases)


def test_admission_comes_before_the_first_chunk(profiled):
    admits = {h[3]["uid"]: h for h in profiled if h[0] == "engine.admit"}
    chunks: dict = {}
    for h in profiled:
        if h[0] == "engine.prefill.dispatch":
            chunks.setdefault(h[3]["uid"], h)
    assert sorted(admits) == sorted(chunks) == [200, 201, 202]
    for uid, (_, _, end, _) in admits.items():
        assert end <= chunks[uid][1]
    # every chunk's blocks are registered, every prompt's first token read
    assert sum(h[0] == "engine.kv.register" for h in profiled) == sum(
        h[0] == "engine.prefill.dispatch" for h in profiled)
    assert sorted(h[3]["uid"] for h in profiled
                  if h[0] == "engine.prefill.wait") == [200, 201, 202]


def test_forced_collection_is_a_gc_span(profiled):
    assert any(h[0] == "engine.gc" and h[3]["generation"] == 2
               for h in profiled)


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_tracer_receives_the_phase_spans(speculative):
    """With a tracing ``Telemetry`` each phase span also reaches the
    ``Tracer``, per-request ones on the request's row, and
    ``trace_report`` reads them."""
    from scripts.trace_report import report, stage_summary

    tm = Telemetry(trace=True)
    extra = {}
    if speculative:
        extra = dict(draft_config=reduced(get_config("qwen2-0.5b")),
                     spec_k=2)
    eng = _engine(tm, **extra)
    for r in _requests(300):
        eng.submit(r)
    eng.run_until_drained()
    trace = tm.to_json()
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"engine.tick", "engine.schedule", "engine.admit",
            "engine.prefill.dispatch", "engine.kv.register",
            "engine.prefill.wait"} <= names
    steps = ({"engine.spec.draft", "engine.spec.verify"} if speculative
             else {"engine.decode.prepare", "engine.decode.dispatch",
                   "engine.decode.wait", "engine.decode.emit"})
    assert steps <= names
    chunks = [e for e in spans if e["name"] == "engine.prefill.dispatch"]
    assert {e["tid"] for e in chunks} == {300, 301, 302}
    assert all(e["args"]["uid"] == e["tid"] for e in chunks)
    stages = {s["name"] for s in stage_summary(trace)}
    assert names <= stages
    text = report(trace)
    assert "engine/engine.prefill.dispatch" in text
    if speculative:
        assert "engine/engine.spec.verify" in text.split(
            "speculative decoding")[1]
