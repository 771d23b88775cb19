"""Serve full-width models through the serving engine's main path on a TPU.

  python chip_smoke.py           # one chip: qwen2-0.5b, phases A, B and C
  python chip_smoke.py --tp 4    # four chips: codeqwen1.5-7b at TP=4

One chip.  ``qwen2-0.5b`` exactly as ``configs/qwen2_0_5b.py`` states it,
with random bf16 weights from a seed, served by a paged ``ServingEngine``
(8 slots, 2048 positions, chunked prefill, prefix cache):

  A  cloud class: bf16 page pool; 8 requests of 16 to 1500 prompt tokens,
     two of them sharing a 640-token prefix and one carrying an embedding
     span (an image's 197 patch rows), plus a few tasks dispatched through
     ``QLMIORouter`` to an ``EngineServer`` wrapping the same engine;
  B  edge class: the same with the int8 page pool;
  C  speculation: A's engine with a 4-layer cut of the same model drafting
     3 tokens a tick, so the verify kernel and its rollback run.

Four chips.  (a) ``codeqwen1.5-7b`` at full depth, TP=4 (14.5 GB of bf16
weights, more than one chip holds beside a cache), created under its
shardings; (b) the same widths cut to 8 layers at TP=4 and at TP=1 on the
same requests, compared token for token and logit for logit.

Every phase fails the run when a request comes back short, when the lowered
decode or verify step holds no Mosaic kernel (``tpu_custom_call``: the XLA
gather path ran instead), or when served logits leave their tolerance
against a float32 full-forward reference.  The last line of standard output
is one JSON object naming the device; it is printed only if every phase
passed.  There is no CPU mode: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.distributed.tp import ShardedServing, serving_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import EngineServer  # noqa: E402
from repro.models import build_model, lm  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.serving.router import QLMIORouter  # noqa: E402
from repro.serving.segments import EmbedSegment, TextSegment  # noqa: E402

SEED = 0
MAX_BATCH = 8
MAX_SEQ = 2048
PAGE = 16
PREFILL_CHUNK = 256
NEW_TOKENS = 32
KV_BUDGET_BYTES = 2 << 30  # qwen2-0.5b: ~10,900 pages of 16 tokens
TP_NUM_PAGES = 513  # codeqwen1.5-7b, 32 layers: 4.3 GB, 1.1 GB a chip
DRAFT_LAYERS = 4
SPEC_K = 3
SHARED_PREFIX = 640
IMAGE_TOKENS = 197  # one ViT-B/16 image: 196 patches + CLS
# text prompt lengths; the two prefix sharers and the image request are
# added by make_requests
TEXT_PROMPTS = (16, 100, 333, 1000, 1500)
TP_PROMPTS = (64, 300, 900, 1500)
ROUTER_TASKS = 3

# Largest |served - reference| logit, over every vocabulary entry at every
# generated position.  Random weights give logits of standard deviation ~1.
# bf16 keeps 8 significant bits, a relative step of 2^-8, and rounding the
# activations of 24 layers drifts the residual stream by a few 1e-2.  On a
# CPU, this model at these widths (vocabulary cut to 8192, 8 tokens out)
# served logits within 0.10 of the reference with the bf16 pool and within
# 0.14 with the int8 pool; with its weights rounded to float8 (e4m3) it
# missed by 0.74 to 1.08.  The tolerances leave room for the larger sample
# here (150k x 32 logits a request) and for the chip's own rounding, and
# stay below what a float8 cut of the weights gives.
BF16_LOGIT_TOL = 0.25
# int8 pages round each K/V element by up to absmax/254 of its row, about
# twice bf16's step, on top of bf16 compute.
INT8_LOGIT_TOL = 0.4
# TP=4 and TP=1 run the same bf16 arithmetic; they may differ only by how
# XLA tiles each sharded matmul, i.e. by bf16 rounding order.  A sharding
# fault (wrong head, wrong shard gathered) moves logits by O(1).
TP_LOGIT_TOL = BF16_LOGIT_TOL


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def report(**kv):
    print(json.dumps(kv), flush=True)


def check_mosaic(step, *args, what: str):
    """The lowered step must call the Pallas TPU kernel: on any other
    backend the model takes the XLA gather path, which this rules out."""
    text = step.lower(*args).as_text()
    require("tpu_custom_call" in text,
            f"{what}: no tpu_custom_call in the lowered step (the XLA "
            "gather path would run instead of the Mosaic kernel)")


def _decode_batch(engine: ServingEngine, width: int = 0) -> dict:
    """All-masked decode (``width`` 0) or verify batch of the engine's
    shapes, for lowering its steps."""
    B = engine.max_batch
    shape = (B, width) if width else (B,)
    return {"tokens": jnp.zeros(shape, jnp.int32),
            "pos": jnp.zeros(B, jnp.int32),
            "block_tables": jnp.full((B, engine.max_blocks), -1, jnp.int32)}


# ------------------------------------------------------------- requests


def make_requests(cfg):
    """The smoke workload: ``(first, late)`` where ``late`` shares its
    first SHARED_PREFIX tokens with ``first[-1]`` and is submitted once
    that request decodes, so its prompt hits the prefix cache."""
    rng = np.random.default_rng(SEED)

    def text(n):
        return rng.integers(0, cfg.vocab, n).astype(np.int32)

    first = [Request(uid, text(n), max_new_tokens=NEW_TOKENS)
             for uid, n in enumerate(TEXT_PROMPTS)]
    uid = len(first)
    # image patch rows at the embedding table's scale (init std d^-0.5)
    image = (rng.standard_normal((IMAGE_TOKENS, cfg.d_model))
             * cfg.d_model ** -0.5).astype(np.float32)
    first.append(Request(uid, segments=[TextSegment(text(24)),
                                        EmbedSegment(image),
                                        TextSegment(text(48))],
                         max_new_tokens=NEW_TOKENS))
    prefix = text(SHARED_PREFIX)
    first.append(Request(uid + 1, np.concatenate([prefix, text(120)]),
                         max_new_tokens=NEW_TOKENS))
    late = Request(uid + 2, np.concatenate([prefix, text(64)]),
                   max_new_tokens=NEW_TOKENS)
    return first, late


def serve(engine: ServingEngine, first, late=None):
    """Submit ``first``, then ``late`` once ``first[-1]`` decodes; drain."""
    max_ticks = 10_000
    for r in first:
        engine.submit(r)
    if late is not None:
        donor = first[-1]
        t0 = engine.ticks
        while engine.slot_of_request(donor.uid) is None and not donor.done:
            engine.step()
            require(engine.ticks - t0 < max_ticks, "donor never decoded")
        engine.submit(late)
    engine.run_until_drained(max_ticks=max_ticks, keep_finished=True)
    reqs = list(first) + ([late] if late is not None else [])
    for r in reqs:
        require(r.done and len(r.output) == r.max_new_tokens
                and len(r.logits) == r.max_new_tokens,
                f"request {r.uid}: {len(r.output)} of {r.max_new_tokens} "
                "tokens")
    return reqs


# ------------------------------------------------------------- reference


@functools.partial(jax.jit, static_argnames=("cfg", "n_out"))
def _reference_logits(params, tokens, embeds, embed_mask, start, *, cfg,
                      n_out):
    h = lm.forward_hidden(cfg, params, {"tokens": tokens[None],
                                        "embeds": embeds[None],
                                        "embed_mask": embed_mask[None]},
                          remat=False)
    return lm.last_logits(cfg, params,
                          jax.lax.dynamic_slice_in_dim(h[0], start, n_out))


class Reference:
    """Float32 full forward over prompt plus served tokens, no cache and no
    kernel, at ``highest`` matmul precision (a float32 matmul on a TPU
    otherwise runs in bf16 passes).  The weights are the served bf16 ones,
    widened."""

    def __init__(self, cfg, params):
        self.cfg = dataclasses.replace(cfg, act_dtype="float32")
        self.params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def logits(self, req: Request) -> np.ndarray:
        """[n_out, V] logits behind each of ``req.output``: the causal
        forward over prompt + output[:-1], read from the last prompt
        position on (padding sits after every position read).  Every
        request is padded to MAX_SEQ: one shape, one compile."""
        T, n = len(req.tokens), len(req.output)
        seq = np.concatenate([np.maximum(req.tokens, 0),
                              np.asarray(req.output[:-1], np.int64)])
        tokens = np.zeros(MAX_SEQ, np.int32)
        tokens[:len(seq)] = seq
        embeds = np.zeros((MAX_SEQ, self.cfg.d_model), np.float32)
        mask = np.zeros(MAX_SEQ, bool)
        if req.features is not None:
            embeds[:T] = req.features
            mask[:T] = req.embed_mask
        with jax.default_matmul_precision("highest"):
            out = _reference_logits(self.params, jnp.asarray(tokens),
                                    jnp.asarray(embeds), jnp.asarray(mask),
                                    T - 1, cfg=self.cfg, n_out=n)
        return np.asarray(out)

    def max_error(self, reqs) -> float:
        return max(float(np.abs(np.stack(r.logits) - self.logits(r)).max())
                   for r in reqs)


def checked(reqs):
    """The requests compared with the reference: the image request, the
    prefix-cache hit and the longest prompt."""
    by_uid = {r.uid: r for r in reqs}
    return [by_uid[len(TEXT_PROMPTS)], by_uid[len(TEXT_PROMPTS) + 2],
            by_uid[len(TEXT_PROMPTS) - 1]]


# ------------------------------------------------------------- one chip


def build_engine(model, params, **kw) -> ServingEngine:
    return ServingEngine(model, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                         page_size=PAGE, kv_budget_bytes=KV_BUDGET_BYTES,
                         prefill_chunk=PREFILL_CHUNK, prefix_caching=True,
                         return_logits=True, **kw)


def phase_serve(name: str, cfg, params, ref: Reference, *,
                kv_dtype: str = "bf16", speculative: bool = False,
                compare_to: "dict | None" = None) -> dict:
    """Phases A (bf16), B (int8) and C (speculative): serve the workload,
    check the kernels, counts and logits; return what to report, with the
    greedy outputs under ``outputs`` (and how many equal ``compare_to``'s,
    another phase's outputs)."""
    model = build_model(cfg)
    kw = {"kv_dtype": kv_dtype}
    if speculative:
        n = DRAFT_LAYERS
        kw.update(draft_config=dataclasses.replace(
                      cfg, name=f"{cfg.name}-draft{n}", n_layers=n),
                  draft_params={**params, "layers": jax.tree.map(
                      lambda a: a[:n], params["layers"])},
                  spec_k=SPEC_K)
    engine = build_engine(model, params, **kw)
    first, late = make_requests(cfg)
    reqs = serve(engine, first, late)
    stats = engine.stats()
    require(not speculative or stats["spec_tokens_drafted"] > 0,
            f"{name}: nothing drafted")
    check_mosaic(engine._step, engine.params, engine.cache,
                 _decode_batch(engine), what=f"{name} decode step")
    if speculative:
        check_mosaic(engine._verify_step, engine.params, engine.cache,
                     _decode_batch(engine, SPEC_K + 1),
                     what=f"{name} verify step")
    out = {"phase": name, "kv_dtype": kv_dtype, "requests": len(reqs),
           "tokens_out": sum(len(r.output) for r in reqs),
           "prefix_hits": stats["prefix_hits"],
           "prefix_tokens_reused": stats["prefix_tokens_reused"],
           "acceptance_rate": stats["acceptance_rate"]}
    if name == "A":
        out["router_ok"] = dispatch_via_router(engine)
    tol = INT8_LOGIT_TOL if kv_dtype == "int8" else BF16_LOGIT_TOL
    err = ref.max_error(checked(reqs))
    out.update(max_logit_err=err, logit_tol=tol)
    require(err <= tol, f"{name}: logit error {err} > {tol}")
    out["outputs"] = {r.uid: list(r.output) for r in reqs}
    if compare_to is not None:
        same = sum(out["outputs"][u] == o for u, o in compare_to.items())
        out["token_identical_to_A"] = f"{same}/{len(compare_to)}"
    return out


def dispatch_via_router(engine: ServingEngine) -> int:
    """Route a few tasks through QLMIORouter to an EngineServer wrapping
    ``engine``; every one must come back ok."""
    server = EngineServer("cloud", engine, speed=1.0, model_id=0,
                          device_id=0, is_cloud=True)
    router = QLMIORouter([server], lambda task, s: 10.0,
                         lambda task, s: 0.9)
    ok = sum(router.dispatch(task)["ok"] for task in range(ROUTER_TASKS))
    require(ok == ROUTER_TASKS, f"router: {ok} of {ROUTER_TASKS} tasks ok")
    return ok


# ------------------------------------------------------------- four chips


def tp_requests(cfg):
    rng = np.random.default_rng(SEED)
    return [Request(uid, rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for uid, n in enumerate(TP_PROMPTS)]


def tp_engine(model, params, mesh) -> ServingEngine:
    return ServingEngine(model, params, max_batch=len(TP_PROMPTS),
                         max_seq=MAX_SEQ, page_size=PAGE,
                         num_pages=TP_NUM_PAGES,
                         prefill_chunk=PREFILL_CHUNK, return_logits=True,
                         mesh=mesh)


def phase_tp_full(cfg, tp: int) -> dict:
    """(a): the full-depth model at TP=``tp``, weights and page pool created
    under their shardings."""
    model = build_model(cfg)
    mesh = serving_mesh(tp)
    params = ShardedServing(model, mesh).init_params(
        jax.random.PRNGKey(SEED))
    engine = tp_engine(model, params, mesh)
    reqs = serve(engine, tp_requests(cfg))
    require(all(np.isfinite(np.stack(r.logits)).all() for r in reqs),
            "TP: non-finite logits")
    check_mosaic(engine._step, engine.params, engine.cache,
                 _decode_batch(engine), what=f"TP={tp} decode step")
    return {"phase": f"tp{tp}_full", "layers": cfg.n_layers,
            "requests": len(reqs),
            "tokens_out": sum(len(r.output) for r in reqs)}


def phase_tp_compare(cfg, tp: int) -> dict:
    """(b): the same requests at TP=``tp`` and on one device, same weights;
    logits compared at every position whose inputs agree."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    one = serve(tp_engine(model, params, None), tp_requests(cfg))
    gc.collect()
    mesh = serving_mesh(tp)
    sharded = serve(tp_engine(model, params, mesh), tp_requests(cfg))
    identical, err = 0, 0.0
    for a, b in zip(one, sharded):
        identical += a.output == b.output
        # logits[t] follows output[:t]: comparable up to the first
        # differing token, inclusive
        n = next((t for t, (x, y) in enumerate(zip(a.output, b.output))
                  if x != y), len(a.output) - 1) + 1
        err = max(err, max(float(np.abs(x - y).max())
                           for x, y in zip(a.logits[:n], b.logits[:n])))
    require(err <= TP_LOGIT_TOL,
            f"TP={tp} vs TP=1: logit difference {err} > {TP_LOGIT_TOL}")
    return {"phase": f"tp{tp}_vs_tp1", "layers": cfg.n_layers,
            "requests": len(one), "token_identical": identical,
            "max_logit_diff": err, "logit_tol": TP_LOGIT_TOL}


# ------------------------------------------------------------- main


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache hits skip
    them)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def run_phase(clock: CompileClock, fn, *args, **kw) -> dict:
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args, **kw)
    out.update(compile_s=clock.seconds - c0,
               phase_s=time.perf_counter() - t0,
               peak_bytes_in_use=jax.devices()[0].memory_stats()
               .get("peak_bytes_in_use"))
    report(**{k: v for k, v in out.items() if k != "outputs"})
    gc.collect()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, choices=(4,), default=None,
                    help="run only the four-chip tensor-parallel path")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    need = args.tp or 1
    if len(devices) < need:
        print(f"chip_smoke: --tp {need} needs {need} chips, found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    report(device_kind=dev.device_kind, platform=dev.platform,
           count=len(devices), compile_cache=cache_dir)
    clock = CompileClock()

    if args.tp:
        cfg = get_config("codeqwen1.5-7b")
        run_phase(clock, phase_tp_full, cfg, args.tp)
        run_phase(clock, phase_tp_compare,
                  dataclasses.replace(cfg, n_layers=8), args.tp)
    else:
        cfg = get_config("qwen2-0.5b")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(SEED))
        ref = Reference(cfg, params)
        a = run_phase(clock, phase_serve, "A", cfg, params, ref)
        run_phase(clock, phase_serve, "B", cfg, params, ref,
                  kv_dtype="int8")
        run_phase(clock, phase_serve, "C", cfg, params, ref,
                  speculative=True, compare_to=a["outputs"])

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
