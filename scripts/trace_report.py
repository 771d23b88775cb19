"""Continuum trace report: per-stage latency decomposition + calibration.

Reads a trace JSON exported by ``repro.serving.telemetry.Telemetry``
(Chrome trace-event format plus the dispatch audit riding along as extra
top-level keys) and prints:

  * per-stage p50/p95 latency decomposition — every span category/name
    pair (uplink, queue, prefill, decode, downlink, tick, and the
    engine's phase spans ``engine.prefill.dispatch``, ``engine.decode.*``,
    ...) over its recorded durations;
  * per-engine utilization — busy fraction (span-covered time / trace
    horizon) per traced process, with a coarse timeline;
  * top-N slowest requests — by summed lifecycle span duration per
    (engine, request) thread, with their per-stage breakdown;
  * cost-model calibration — prediction-error percentiles from the
    dispatch audit (predicted vs. measured e2e), the paper's
    "latency is hard to predict" claim as a measured number.

Usage:
    python benchmarks/fig10_continuum_replay.py --trace t.json
    python scripts/trace_report.py t.json [--top 5]

The same file loads in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing`` for interactive inspection.
"""
from __future__ import annotations

import argparse
import json
from collections import defaultdict

import numpy as np

_US = 1e6


def _pct(xs, q):
    return float(np.percentile(xs, q)) if len(xs) else 0.0


def load_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spans(trace: dict) -> "list[dict]":
    return [ev for ev in trace.get("traceEvents", [])
            if ev.get("ph") == "X"]


def process_names(trace: dict) -> dict:
    """pid -> process name from the trace's metadata events."""
    return {ev["pid"]: ev["args"]["name"]
            for ev in trace.get("traceEvents", [])
            if ev.get("ph") == "M" and ev.get("name") == "process_name"}


def stage_summary(trace: dict) -> "list[dict]":
    """Per-(category, name) duration percentiles over every span."""
    groups: dict = defaultdict(list)
    for ev in spans(trace):
        groups[(ev.get("cat", "?"), ev["name"])].append(ev["dur"] / _US)
    out = []
    for (cat, name), durs in sorted(groups.items()):
        out.append({"cat": cat, "name": name, "count": len(durs),
                    "p50_s": _pct(durs, 50), "p95_s": _pct(durs, 95),
                    "total_s": float(np.sum(durs))})
    return out


def engine_utilization(trace: dict, buckets: int = 20) -> "list[dict]":
    """Busy fraction per engine from its ``tick`` spans: the share of the
    trace horizon covered by engine ticks (ticks only run while the
    engine has work), plus a coarse busy-fraction timeline."""
    names = process_names(trace)
    ticks: dict = defaultdict(list)
    horizon = 0.0
    for ev in spans(trace):
        horizon = max(horizon, (ev["ts"] + ev["dur"]) / _US)
        if ev["name"] == "tick":
            ticks[ev["pid"]].append((ev["ts"] / _US, ev["dur"] / _US))
    out = []
    for pid in sorted(ticks):
        ts = ticks[pid]
        busy = sum(d for _, d in ts)
        hist = np.zeros(buckets)
        if horizon > 0:
            w = horizon / buckets
            for t0, d in ts:
                b0, b1 = int(t0 / w), min(int((t0 + d) / w), buckets - 1)
                for b in range(b0, b1 + 1):  # overlap per bucket
                    lo, hi = b * w, (b + 1) * w
                    hist[b] += max(0.0, min(t0 + d, hi) - max(t0, lo))
            hist /= w
        out.append({"engine": names.get(pid, f"pid{pid}"),
                    "busy_s": busy,
                    "busy_frac": busy / horizon if horizon else 0.0,
                    "timeline": np.clip(hist, 0.0, 1.0)})
    return out


def queue_wait(trace: dict, buckets: int = 20) -> dict:
    """Admission-queue pressure: percentiles of the per-request ``queue``
    lifecycle span (submit -> batch admission) plus a fleet-aggregate
    depth timeline from the ``queue_depth`` counter samples each engine
    emits per tick."""
    waits = [ev["dur"] / _US for ev in spans(trace)
             if ev.get("cat") == "lifecycle" and ev["name"] == "queue"]
    samples = [(ev["ts"] / _US, sum(int(v) for v in
                                    ev.get("args", {}).values()))
               for ev in trace.get("traceEvents", [])
               if ev.get("ph") == "C" and ev["name"] == "queue_depth"]
    timeline = np.zeros(buckets)
    peak = 0
    if samples:
        horizon = max(t for t, _ in samples) or 1.0
        counts = np.zeros(buckets)
        for t, depth in samples:
            b = min(int(t / horizon * buckets), buckets - 1)
            timeline[b] += depth
            counts[b] += 1
            peak = max(peak, depth)
        timeline = np.divide(timeline, np.maximum(counts, 1))
    return {"n": len(waits), "p50_s": _pct(waits, 50),
            "p95_s": _pct(waits, 95), "max_s": max(waits, default=0.0),
            "samples": len(samples), "peak_depth": peak,
            "timeline": timeline}


def migration_traffic(trace: dict) -> "dict[str, dict]":
    """KV pages moved per engine, from ``kv_migrate`` spans: bytes/pages
    received (the span's pid is the destination) and sent (matched on the
    span's ``src`` process name)."""
    names = process_names(trace)
    traffic: dict = defaultdict(lambda: {"in_bytes": 0, "out_bytes": 0,
                                         "in_pages": 0, "moves": 0})
    for ev in spans(trace):
        if ev["name"] != "kv_migrate":
            continue
        a = ev.get("args", {})
        dst = traffic[names.get(ev["pid"], f"pid{ev['pid']}")]
        dst["in_bytes"] += int(a.get("bytes", 0))
        dst["in_pages"] += int(a.get("pages", 0))
        dst["moves"] += 1
        if a.get("src"):
            traffic[a["src"]]["out_bytes"] += int(a.get("bytes", 0))
    return dict(traffic)


def speculation(trace: dict, buckets: int = 20) -> "list[dict]":
    """Per-engine speculative-decoding rollup from the ``spec_tokens``
    counter samples each speculative engine emits per tick: drafted /
    accepted / wasted token totals, the overall acceptance rate, and a
    coarse acceptance-rate timeline (accepted/drafted per time bucket).
    Empty for traces without speculative engines."""
    names = process_names(trace)
    samples: dict = defaultdict(list)
    horizon = 0.0
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "C" and ev["name"] == "spec_tokens":
            a = ev.get("args", {})
            t = ev["ts"] / _US
            horizon = max(horizon, t)
            samples[ev["pid"]].append((t, int(a.get("drafted", 0)),
                                       int(a.get("accepted", 0)),
                                       int(a.get("emitted", 0))))
    out = []
    for pid in sorted(samples):
        ss = samples[pid]
        drafted = sum(s[1] for s in ss)
        accepted = sum(s[2] for s in ss)
        emitted = sum(s[3] for s in ss)
        dr, ac = np.zeros(buckets), np.zeros(buckets)
        for t, d, a, _ in ss:
            b = min(int(t / (horizon or 1.0) * buckets), buckets - 1)
            dr[b] += d
            ac[b] += a
        out.append({"engine": names.get(pid, f"pid{pid}"),
                    "drafted": drafted, "accepted": accepted,
                    "wasted": drafted - accepted, "emitted": emitted,
                    "acceptance": accepted / drafted if drafted else 0.0,
                    "timeline": np.divide(ac, np.maximum(dr, 1))})
    return out


def slow_requests(trace: dict, top: int = 5) -> "list[dict]":
    """Top-N slowest requests by summed lifecycle+transfer span time on
    their (engine, request-uid) thread."""
    names = process_names(trace)
    per_req: dict = defaultdict(dict)
    for ev in spans(trace):
        if ev.get("cat") not in ("lifecycle", "transfer"):
            continue
        per_req[(ev["pid"], ev["tid"])][ev["name"]] = ev["dur"] / _US
    ranked = sorted(per_req.items(), key=lambda kv: -sum(kv[1].values()))
    return [{"engine": names.get(pid, f"pid{pid}"), "uid": tid,
             "total_s": sum(stages.values()), "stages": stages}
            for (pid, tid), stages in ranked[:top]]


def _bar(frac_row, width: int = 1) -> str:
    glyphs = " .:-=+*#%@"
    return "".join(glyphs[min(int(f * (len(glyphs) - 1) + 0.5),
                              len(glyphs) - 1)] * width for f in frac_row)


def report(trace: dict, top: int = 5) -> str:
    lines = []
    stages = stage_summary(trace)
    lines.append("== per-stage latency decomposition (seconds) ==")
    if stages:
        lines.append(f"{'stage':<36}{'count':>7}{'p50':>10}{'p95':>10}"
                     f"{'total':>10}")
        for s in stages:
            lines.append(f"{s['cat'] + '/' + s['name']:<36}"
                         f"{s['count']:>7}{s['p50_s']:>10.4f}"
                         f"{s['p95_s']:>10.4f}{s['total_s']:>10.2f}")
    else:
        lines.append("(no spans recorded — was tracing enabled?)")

    util = engine_utilization(trace)
    lines.append("")
    lines.append("== per-engine utilization (tick-covered time) ==")
    for u in util:
        lines.append(f"{u['engine']:<36}{100 * u['busy_frac']:>6.1f}%  "
                     f"[{_bar(u['timeline'])}]")

    qw = queue_wait(trace)
    lines.append("")
    lines.append("== admission queue wait (submit -> batch admission) ==")
    if qw["n"]:
        lines.append(f"n={qw['n']}  p50={qw['p50_s']:.4f}s  "
                     f"p95={qw['p95_s']:.4f}s  max={qw['max_s']:.4f}s")
    else:
        lines.append("(no queue spans in this trace)")
    if qw["samples"]:
        depth = qw["timeline"]
        scale = max(float(depth.max()), 1.0)
        lines.append(f"fleet queue depth (mean of {qw['samples']} samples, "
                     f"peak {qw['peak_depth']}):")
        lines.append(f"{'depth':<10}{depth.mean():>6.2f} avg  "
                     f"[{_bar(np.clip(depth / scale, 0.0, 1.0))}]")

    traffic = migration_traffic(trace)
    if traffic:
        lines.append("")
        lines.append("== kv migration traffic (wire bytes, destination "
                     "precision) ==")
        for name in sorted(traffic):
            t = traffic[name]
            lines.append(f"{name:<36} in {t['in_bytes']:>9} B "
                         f"({t['in_pages']} pages, {t['moves']} moves)  "
                         f"out {t['out_bytes']:>9} B")

    spec = speculation(trace)
    if spec:
        lines.append("")
        lines.append("== speculative decoding (drafted / accepted / wasted "
                     "tokens, acceptance timeline) ==")
        for sp in spec:
            lines.append(
                f"{sp['engine']:<36}drafted {sp['drafted']:>6}  "
                f"accepted {sp['accepted']:>6}  wasted {sp['wasted']:>6}  "
                f"rate {sp['acceptance']:.3f}  [{_bar(sp['timeline'])}]")
        # join the draft/verify engine spans into the same p50/p95 view
        # as the rest of the stage decomposition
        for s in stages:
            if s["name"] in ("engine.spec.draft", "engine.spec.verify"):
                lines.append(f"{s['cat'] + '/' + s['name']:<36}"
                             f"n={s['count']:<6} p50={s['p50_s']:.4f}s  "
                             f"p95={s['p95_s']:.4f}s  "
                             f"total={s['total_s']:.2f}s")

    slow = slow_requests(trace, top)
    lines.append("")
    lines.append(f"== top-{top} slow requests ==")
    for r in slow:
        parts = ", ".join(f"{k}={v:.4f}" for k, v in
                          sorted(r["stages"].items(), key=lambda kv: -kv[1]))
        lines.append(f"uid {r['uid']:>5} on {r['engine']:<32}"
                     f"{r['total_s']:>9.4f}s  ({parts})")

    err = trace.get("prediction_error") or {}
    lines.append("")
    lines.append("== cost-model calibration (predicted vs measured e2e) ==")
    if err.get("n"):
        lines.append(f"n={err['n']}  "
                     f"mean|err|={err['mean_abs_pct_err']:.1f}%  "
                     f"p50|err|={err['p50_abs_pct_err']:.1f}%  "
                     f"p95|err|={err['p95_abs_pct_err']:.1f}%  "
                     f"bias={err['mean_signed_pct_err']:+.1f}%")
    else:
        lines.append("(no completed audited dispatches in this trace)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_json", help="Telemetry.export output")
    ap.add_argument("--top", type=int, default=5,
                    help="slow requests to list (default 5)")
    args = ap.parse_args(argv)
    print(report(load_trace(args.trace_json), top=args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
