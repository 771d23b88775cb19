"""Serving driver: a cloud-edge continuum of real model engines behind the
QLMIO router, with health tracking, hedging, and fault injection.

  PYTHONPATH=src python -m repro.launch.serve --requests 24 --fail-server 1

The demo cluster serves ``reduced()`` configs so that it runs anywhere;
``chip_smoke.py`` wraps a full-width engine in the same ``EngineServer``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.router import QLMIORouter, ServerHandle


class EngineServer(ServerHandle):
    """A real ServingEngine wrapped as a continuum server.  'Latency' is the
    engine tick count scaled by a device-speed factor, so that the router's
    view does not depend on the host that steps the engine."""

    def __init__(self, name, engine: ServingEngine, speed: float,
                 model_id: int, device_id: int, is_cloud: bool, fail=False):
        self.engine = engine
        self.cfg = engine.model.cfg
        self.speed = speed
        self.fail = fail
        self.uid = 0
        super().__init__(name=name, model_id=model_id, device_id=device_id,
                         is_cloud=is_cloud, execute=self._execute)

    def _execute(self, task: int):
        if self.fail:
            return 240.0, False
        rng = np.random.default_rng((task, self.model_id))
        prompt = rng.integers(0, self.cfg.vocab, 16).astype(np.int32)
        self.uid += 1
        req = Request(self.uid, prompt, max_new_tokens=8)
        self.engine.submit(req)
        t0 = self.engine.ticks
        while not req.done:
            self.engine.step()
        ticks = self.engine.ticks - t0
        return ticks / self.speed, True


def _demo_engine(arch: str) -> ServingEngine:
    model = build_model(reduced(get_config(arch)))
    params = model.init(jax.random.PRNGKey(0))
    return ServingEngine(model, params, max_batch=2, max_seq=96)


def build_cluster(fail_server: int | None = None):
    servers = [
        EngineServer("edge-0 (jetson/qwen2-0.5b)", _demo_engine("qwen2-0.5b"),
                     2.0, 0, 0, False, fail=fail_server == 0),
        EngineServer("edge-1 (3090ti/llama3.2-3b)",
                     _demo_engine("llama3.2-3b"), 8.0, 1, 1, False,
                     fail=fail_server == 1),
        EngineServer("cloud (pod/chameleon-34b)",
                     _demo_engine("chameleon-34b"), 32.0, 2, 2, True,
                     fail=fail_server == 2),
    ]
    return servers


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--fail-server", type=int, default=None)
    args = ap.parse_args()

    enable_compile_cache()
    servers = build_cluster(args.fail_server)
    # simple analytic predictors for the demo (speed-based)
    speeds = np.array([s.speed for s in servers])
    milp = lambda task, s: 8.0 / speeds[s]
    mgqp = lambda task, s: [0.7, 0.85, 0.95][s]
    router = QLMIORouter(list(servers), milp, mgqp)
    t0 = time.time()
    ok = 0
    for task in range(args.requests):
        rec = router.dispatch(task)
        ok += rec["ok"]
        print(f"[serve] task {task} -> {servers[rec['server']].name} "
              f"lat={rec['latency']:.2f} ok={rec['ok']} "
              f"hedged={rec['hedged']}", flush=True)
    per_server = np.bincount([r["server"] for r in router.log],
                             minlength=len(servers))
    print(f"[serve] {ok}/{args.requests} ok in {time.time()-t0:.0f}s; "
          f"dispatch counts {per_server.tolist()}")
    for s in servers:
        st = s.engine.stats()
        if st.get("paged"):
            print(f"[serve] {s.name}: paged KV "
                  f"{st['kv_cache_bytes'] / 1e6:.1f} MB, "
                  f"prefix hits {st['prefix_hits']}, "
                  f"reused {st['prefix_tokens_reused']} tok, "
                  f"computed {st['prefill_tokens_computed']} tok")
    if args.fail_server is not None:
        assert per_server[args.fail_server] <= router.health.fail_threshold, \
            "router failed to drain traffic from the failed server"
        print(f"[serve] failed server {args.fail_server} drained after "
              f"{per_server[args.fail_server]} attempts (fault tolerance OK)")


if __name__ == "__main__":
    main()
