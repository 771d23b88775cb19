"""Fig. 14 (repo extension): speculative decoding across the continuum.

Decode is one token per tick per slot, so the per-tick decode roofline
is the hard ITL floor under every e2e number the QLMIO tradeoff
optimizes.  Draft-k/verify-once speculation attacks that floor: a small
draft model proposes ``spec_k`` tokens, the target scores them in one
paged multi-token verify pass (kernels/paged_verify.py), and each tick
emits 1..k+1 bit-identical greedy tokens.  In the continuum it is also
a new split point — an edge engine can run the draft steps and ship
only token ids uplink while the cloud verifies — which the router
prices as a fourth dispatch shape next to raw-ship/edge-encode (PR 4)
and prefill-here/decode-there (PR 7).

Three policies over the same bursty MIOBench arrival trace, on a fleet
of live ``ServingEngine``s sharing one reduced arch + weight init:

  * **all_cloud**   — every request to the plain cloud handle (the
                      one-token-per-tick ITL floor);
  * **cloud_spec**  — every request to the cloud handle with colocated
                      speculation (draft + verify on the same device);
  * **qlmio_spec**  — QLMIO utility over every dispatch shape: pure
                      per-server, colocated speculation, and the
                      edge-drafts/cloud-verifies pair, each priced by
                      ``Cluster.predict_spec_e2e_s`` with the verify
                      engine's live measured acceptance rate fed back.

The speculative engines really draft/verify (the emitted stream is the
verify pass's argmax), while the virtual clock charges
``cost_model.speculative_tick_s`` — so the measured ITL reduction is
acceptance-discounted by what the draft model actually achieves, not by
an assumed rate.

CI-smoke entry: ``python benchmarks/fig14_speculative.py --smoke
--trace out.json`` finishes on CPU in about a minute and asserts the
speculative policies beat all-cloud on measured mean ITL at an
equal-or-better completion rate, with live acceptance telemetry in the
exported trace.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import emit  # noqa: E402
from benchmarks.fig10_continuum_replay import analytic_predictors  # noqa: E402

from repro.serving.cluster import Cluster, EngineHandle  # noqa: E402
from repro.serving.request import ContinuumRequest  # noqa: E402
from repro.serving.telemetry import Telemetry  # noqa: E402
from repro.sim import cost_model as cm  # noqa: E402
from repro.sim.miobench import SERVER_CLASSES, generate  # noqa: E402

ARCH = "qwen2-0.5b"
SPEC_K = 2  # draft depth: k=2 keeps the verify overhead below the
#             expected acceptance gain at the ~0.5 rate the reduced
#             draft actually achieves (see kernel_bench speculative)

BUDGETS = {
    "smoke": dict(n_tasks=200, users=24, burst=6, burst_gap_s=0.40,
                  decode_cap=12, prompt_cap=40),
    "fast": dict(n_tasks=800, users=64, burst=8, burst_gap_s=0.40,
                 decode_cap=12, prompt_cap=40),
    "paper": dict(n_tasks=3377, users=192, burst=10, burst_gap_s=0.35,
                  decode_cap=14, prompt_cap=48),
}

W_QUALITY = 4.0


def build_fleet(tm: Telemetry) -> "list[EngineHandle]":
    """One edge tier + three cloud handles over the same reduced arch and
    shared weights: plain decode, colocated speculation, and a verify
    handle whose draft steps are priced on the edge device (the
    edge-drafts/cloud-verifies shape — only token ids ride the uplink)."""
    edge_dev = cm.DEVICES["jetson_orin_nano"]
    cloud_dev = cm.DEVICES["rtx3090ti"]
    draft_prof = cm.MODELS["qwen3vl-2b"]
    cloud_prof = cm.MODELS["qwen3vl-8b"]
    kw = dict(seed=0, telemetry=tm,
              payload_bytes=2 * cm.PAYLOAD_BYTES["text"])
    cloud_kw = dict(is_cloud=True, max_batch=4, **kw)
    return [
        EngineHandle("edge-0 (jetson/plain)", ARCH, edge_dev, draft_prof,
                     is_cloud=False, **kw),
        EngineHandle("cloud-plain (3090ti)", ARCH, cloud_dev, cloud_prof,
                     **cloud_kw),
        EngineHandle("cloud-spec (3090ti)", ARCH, cloud_dev, cloud_prof,
                     draft_profile=draft_prof, spec_k=SPEC_K, **cloud_kw),
        EngineHandle("cloud-spec-edgedraft (3090ti)", ARCH, cloud_dev,
                     cloud_prof, draft_profile=draft_prof,
                     draft_device=edge_dev, spec_k=SPEC_K, **cloud_kw),
    ]


def run():
    budget = "smoke" if "--smoke" in sys.argv[1:] else \
        os.environ.get("BENCH_BUDGET", "smoke")
    trace_path = None
    argv = sys.argv[1:]
    if "--trace" in argv:
        trace_path = argv[argv.index("--trace") + 1]
    b = BUDGETS[budget]
    bench = generate(seed=0, n_tasks=b["n_tasks"])
    _, b_hat = analytic_predictors(bench)
    rng = np.random.default_rng(0)
    tasks = [int(t) for t in rng.choice(bench.tasks.n, b["users"],
                                        replace=False)]

    t0 = time.time()
    tm = Telemetry(trace=trace_path is not None)
    handles = build_fleet(tm)
    cluster = Cluster(handles)
    vocab = handles[0].cfg.vocab
    class_devices = [d for d, _ in SERVER_CLASSES]
    cls = np.array([class_devices.index(h.device.name) for h in handles])
    # speculative pairs whose *priced* draft device matches the handle's
    # configured one (charged tick == predicted tick by construction):
    # colocated cloud speculation and the edge-drafts/cloud-verifies pair
    spec_pairs = []
    for sv, hv in enumerate(handles):
        if hv.spec_tick_s is None:
            continue
        if hv.draft_device is hv.device:
            spec_pairs.append((sv, sv))
        else:
            spec_pairs.extend(
                (sa, sv) for sa, ha in enumerate(handles)
                if sa != sv and ha.device.name == hv.draft_device.name)
    print(f"fig14,continuum,{len(handles)}_live_engines,arch,{ARCH},"
          f"spec_k,{SPEC_K},spec_pairs,{spec_pairs},"
          f"build_s,{time.time() - t0:.1f}")

    def prompt(task: int) -> np.ndarray:
        L = int(np.clip(bench.tasks.text_len[task], 1, b["prompt_cap"]))
        r = np.random.default_rng(1_000_003 * (task + 1))
        return r.integers(0, vocab, L).astype(np.int32)

    def gen_budget(task: int, server: int) -> int:
        out = cm.expected_out_tokens(handles[server].profile,
                                     float(bench.tasks.difficulty[task]))
        return int(np.clip(round(out / 40.0), 4, b["decode_cap"]))

    def replay(policy: str):
        """policy: 'all_cloud' | 'cloud_spec' | 'qlmio_spec'."""
        cluster.reset()
        n_spec = 0
        for k, task in enumerate(tasks):
            t = (k // b["burst"]) * b["burst_gap_s"]
            cluster.advance_to(t)
            toks = prompt(task)
            if policy == "all_cloud":
                s, draft_server = 1, None
            elif policy == "cloud_spec":
                s, draft_server = 2, 2
            else:
                # (total_s, quality, server, draft_server) per shape
                shapes = []
                for si, h in enumerate(handles):
                    if h.spec_tick_s is not None:
                        continue  # spec handles dispatch via their pair
                    tot, _ = h.predict_e2e_s(len(toks),
                                             gen_budget(task, si))
                    shapes.append((tot, float(b_hat[task, cls[si]]),
                                   si, None))
                for sa, sv in spec_pairs:
                    r = cluster.predict_spec_e2e_s(
                        sa, sv, len(toks), gen_budget(task, sv))
                    if r is None:
                        continue
                    shapes.append((r[0], float(b_hat[task, cls[sv]]),
                                   sv, sa))
                norm = max(min(e[0] for e in shapes), 1e-6)
                best = max(shapes, key=lambda e: -e[0] / norm
                           + W_QUALITY * (3.0 * e[1] - 2.0))
                _, _, s, draft_server = best
            n_spec += draft_server is not None
            quality_ok = int(bench.score[task, int(cls[s])]) == 1
            budget_tok = gen_budget(task, s)
            predicted, terms = handles[s].predict_e2e_s(
                len(toks), budget_tok)
            uid = cluster.submit(ContinuumRequest(
                tokens=toks, max_new_tokens=budget_tok, arrival_s=t,
                task=task, quality_ok=quality_ok, server=s,
                draft_server=draft_server, predicted_s=float(predicted)))
            tm.record_dispatch(task=task, server=s, t=t,
                               predicted_s=predicted, uid=uid, terms=terms)
        cluster.drain()
        recs = cluster.collect()
        itl = [(r["e2e_s"] - r["ttft_s"]) / (r["n_tokens"] - 1)
               for r in recs if r["success"] and r["n_tokens"] > 1]
        acc = {h.name: h.engine.acceptance_rate() for h in handles
               if getattr(h.engine, "speculative", False)
               and h.engine.stats()["spec_tokens_drafted"] > 0}
        return {"mean_itl_s": float(np.mean(itl)),
                "p95_itl_s": float(np.percentile(itl, 95)),
                "mean_e2e_s": float(np.mean([r["e2e_s"] for r in recs])),
                "completion_rate": float(np.mean(
                    [r["success"] for r in recs])),
                "n_spec_dispatches": int(n_spec),
                "acceptance": acc}

    results = {}
    print("fig14,policy,mean_itl_s,p95_itl_s,mean_e2e_s,completion_rate,"
          "spec_dispatches")
    for name in ("all_cloud", "cloud_spec", "qlmio_spec"):
        r = replay(name)
        results[name] = r
        print(f"fig14,{name},{r['mean_itl_s']:.5f},{r['p95_itl_s']:.5f},"
              f"{r['mean_e2e_s']:.3f},{r['completion_rate']:.3f},"
              f"{r['n_spec_dispatches']}")
        if name == "qlmio_spec" and trace_path is not None:
            tm.export(trace_path)
            n_verify = sum(e.get("name") == "engine.spec.verify"
                           for e in tm.tracer.events)
            print(f"fig14,trace,{trace_path},verify_spans,{n_verify}")

    ac, cs, qs = (results["all_cloud"], results["cloud_spec"],
                  results["qlmio_spec"])
    red = 1.0 - qs["mean_itl_s"] / max(ac["mean_itl_s"], 1e-12)
    acc_rates = list(qs["acceptance"].values())
    mean_acc = float(np.mean(acc_rates)) if acc_rates else 0.0
    print(f"fig14,headline,itl_reduction_vs_all_cloud,{red:.3f},"
          f"acceptance,{mean_acc:.3f},wall_s,{time.time() - t0:.1f}")
    emit("fig14_speculative", {"fig14": {
        "results": results,
        "itl_reduction_vs_all_cloud": red,
        "completion_spec": qs["completion_rate"],
        "acceptance_rate": mean_acc,
        "n_spec_dispatches": qs["n_spec_dispatches"],
    }})
    # acceptance: speculation must lower the measured mean ITL at an
    # equal-or-better completion rate, via real (live-verified, traced)
    # speculative dispatches with a live-measured acceptance rate
    assert qs["mean_itl_s"] < ac["mean_itl_s"], \
        f"qlmio_spec ITL {qs['mean_itl_s']:.5f} !< " \
        f"all_cloud {ac['mean_itl_s']:.5f}"
    assert cs["mean_itl_s"] < ac["mean_itl_s"]
    assert qs["completion_rate"] >= ac["completion_rate"]
    assert qs["n_spec_dispatches"] > 0, "no speculative dispatches"
    assert 0.0 < mean_acc <= 1.0
    return results


if __name__ == "__main__":
    run()
