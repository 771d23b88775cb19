"""Kernel microbenchmarks.

On CPU, wall-clock measures the interpret path (not TPU performance), so we
report (a) correctness error vs. oracle and (b) the analytic TPU roofline
time for each kernel's workload: FLOPs / 197 TF and bytes / 819 GB/s, the
numbers the §Perf iterations use.

``python benchmarks/kernel_bench.py serving`` runs only the serving-engine
prefill benchmark (mixed-length workload, TTFT/ITL percentiles + XLA
compile counts); ``... serving paged_kv`` adds the analytic paged-KV
memory/throughput section — the CI smoke entry.  ``--json PATH`` writes
every section that ran to one JSON file, the input of the CI benchmark
regression gate (``scripts/check_bench.py`` vs. ``benchmarks/
baseline.json``).  ``--profile DIR`` wraps the timing loops in
``jax.profiler.trace``: the XLA/TPU profile lands in ``DIR`` (open with
TensorBoard or Perfetto), next to the serving-layer traces
``fig10_continuum_replay.py --trace`` exports.
"""
import contextlib
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit

from repro.distributed.tp import serving_mesh
from repro.kernels import ops, ref
from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16
from repro.serving.kv_cache import kv_token_bytes


def _roof(flops, bytes_):
    return max(flops / PEAK_FLOPS_BF16, bytes_ / HBM_BW)


def serving_prefill_bench():
    """Mixed-prompt-length serving workload: the bucketed + chunked prefill
    scheduler vs. the legacy path (exact-shape monolithic prefill).

    The legacy path retraces prefill for every distinct prompt length (a
    recompile storm) and a long prompt's monolithic prefill stalls every
    decoding slot for the whole tick; the fix bounds traces to the bucket
    count and spreads prefill over a per-tick token budget.  Reported:
    wall-clock TTFT/ITL p50/p95 per mode, prefill trace (compile) counts,
    and total wall time — on CPU the wall numbers are dominated by exactly
    the XLA compiles the bucketing removes, which is the point.
    """
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serving.engine import Request, ServingEngine

    cfg = reduced(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [3, 5, 9, 13, 17, 23, 29, 31, 37, 41, 45, 49, 53, 57, 60, 62]
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]
    modes = {
        "chunked": dict(prefill_chunk=16),          # the fix (default path)
        "chunked_int8": dict(prefill_chunk=16, kv_dtype="int8"),
        "bucketed_monolithic": dict(prefill_chunk=0),
        "legacy": dict(prefill_chunk=0, bucket_prompts=False),
    }
    print("serving,mode,ttft_p50_ms,ttft_p95_ms,itl_p50_ms,itl_p95_ms,"
          "prefill_traces,wall_s")
    out = {}
    for mode, kw in modes.items():
        eng = ServingEngine(model, params, max_batch=4, max_seq=64,
                            paged=True, page_size=8, **kw)
        t0 = time.time()
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, max_new_tokens=8))
        eng.run_until_drained(keep_finished=True)
        wall = time.time() - t0
        lat = eng.latency_stats()
        traces = eng.prefill_trace_count()
        out[mode] = {**lat, "prefill_traces": traces, "wall_s": wall,
                     **{k: v for k, v in eng.stats().items()
                        if k.startswith("prefill")}}
        print(f"serving,{mode},{lat['ttft_p50_s']*1e3:.1f},"
              f"{lat['ttft_p95_s']*1e3:.1f},{lat['itl_p50_s']*1e3:.1f},"
              f"{lat['itl_p95_s']*1e3:.1f},{traces},{wall:.1f}")
    ratio = (out["legacy"]["ttft_p95_s"]
             / max(out["chunked"]["ttft_p95_s"], 1e-9))
    print(f"serving,ttft_p95_speedup_chunked_vs_legacy,{ratio:.2f}x,"
          f"traces {out['legacy']['prefill_traces']}"
          f"->{out['chunked']['prefill_traces']}")
    emit("serving_prefill", {"workload_lens": lens, "modes": out,
                             "ttft_p95_speedup": ratio})
    return out


def paged_kv_bench():
    """KV memory footprint + decode throughput (analytic, deterministic):
    dense pads every slot to max_seq while the paged pool sizes to the
    workload's live tokens, and the int8 pool (kv_dtype="int8": symmetric
    per-row int8 values + fp32 scales, repro/kernels/quant.py) carries
    ``Dh + 4`` bytes per head row against bf16's ``2 * Dh`` — halving the
    per-tick decode KV stream *and* the pool footprint.  Workload: 8
    slots, lengths 0.5-8k, max_seq 8k, L=32 layers of the flash-decode
    shape used in ``run``."""
    H, Hkv, D, bs_pg = 8, 2, 128, 64
    L, max_seq = 32, 8192
    lens = [512, 1024, 1536, 2048, 3072, 4096, 6144, 8192]
    tok_bytes = kv_token_bytes(L, Hkv, D, "bf16")  # K+V bf16, all layers
    tok_bytes_i8 = kv_token_bytes(L, Hkv, D, "int8")
    layer_bytes = kv_token_bytes(1, Hkv, D, "bf16")  # decode streams 1 layer
    layer_bytes_i8 = kv_token_bytes(1, Hkv, D, "int8")
    dense_bytes = len(lens) * max_seq * tok_bytes
    paged_pages = sum(-(-n // bs_pg) for n in lens)
    paged_bytes = (1 + paged_pages) * bs_pg * tok_bytes
    int8_bytes = (1 + paged_pages) * bs_pg * tok_bytes_i8
    dense_step_s = _roof(2 * 2 * H * D * sum(lens),
                         sum(max_seq for _ in lens) * layer_bytes)
    paged_step_s = _roof(2 * 2 * H * D * sum(lens),
                         sum(lens) * layer_bytes)
    int8_step_s = _roof(2 * 2 * H * D * sum(lens),
                        sum(lens) * layer_bytes_i8)
    print("paged_kv,metric,dense,paged,ratio")
    print(f"paged_kv,kv_bytes_per_layer_stack,{dense_bytes},{paged_bytes},"
          f"{dense_bytes / paged_bytes:.2f}")
    print(f"paged_kv,decode_roofline_tok_s,{len(lens) / dense_step_s:.0f},"
          f"{len(lens) / paged_step_s:.0f},"
          f"{dense_step_s / paged_step_s:.2f}")
    print("paged_kv,metric,bf16,int8,ratio")
    print(f"paged_kv,kv_bytes_per_token,{tok_bytes},{tok_bytes_i8},"
          f"{tok_bytes / tok_bytes_i8:.2f}")
    print(f"paged_kv,int8_decode_roofline_tok_s,"
          f"{len(lens) / paged_step_s:.0f},{len(lens) / int8_step_s:.0f},"
          f"{paged_step_s / int8_step_s:.2f}")
    return emit("paged_kv_memory", {
        "workload_lens": lens, "max_seq": max_seq, "block_size": bs_pg,
        "dense_kv_bytes": dense_bytes, "paged_kv_bytes": paged_bytes,
        "memory_ratio": dense_bytes / paged_bytes,
        "dense_decode_tok_s": len(lens) / dense_step_s,
        "paged_decode_tok_s": len(lens) / paged_step_s,
        "int8": {
            "kv_bytes_per_token_bf16": tok_bytes,
            "kv_bytes_per_token_int8": tok_bytes_i8,
            "kv_bytes_per_token_ratio": tok_bytes / tok_bytes_i8,
            "pool_bytes_int8": int8_bytes,
            "pool_bytes_ratio": paged_bytes / int8_bytes,
            "decode_tok_s": len(lens) / int8_step_s,
            "decode_tok_s_ratio": paged_step_s / int8_step_s,
        },
    })


def speculative_bench():
    """Multi-token verification vs k+1 sequential paged decode steps.

    The verify kernel scores ``k`` drafted tokens plus the last accepted
    token in one pass: the paged KV stream is read *once* for all k+1
    query rows, where sequential decode re-reads it every step — so the
    roofline tokens/s scales ~(k+1)x on the memory-bound side, in bf16
    and (halved stream) fused-dequant int8.  What the decode loop
    actually gains is acceptance-discounted: a tick emits
    ``expected_accepted(k, a)`` tokens (cost_model), so the effective
    ITL is swept over acceptance rates here.  Correctness: CPU-interpret
    kernel vs the jnp gather oracle, finite + max-err reported per k."""
    from repro.kernels.quant import quantize_kv
    from repro.sim.cost_model import expected_accepted

    B, H, Hkv, D = 1, 8, 2, 128
    S2, bs_pg = 8192, 64
    NB = S2 // bs_pg
    rng = np.random.default_rng(7)
    kp = jnp.asarray(rng.normal(size=(1 + NB, bs_pg, Hkv, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(1 + NB, bs_pg, Hkv, D)), jnp.bfloat16)
    bt = jnp.arange(1, NB + 1, dtype=jnp.int32)[None]  # [1, NB]
    kp8, kps = quantize_kv(kp)
    vp8, vps = quantize_kv(vp)
    layer = kv_token_bytes(1, Hkv, D, "bf16")
    layer_i8 = kv_token_bytes(1, Hkv, D, "int8")
    out = {"workload": f"B{B}xS{S2}xH{H}xbs{bs_pg}"}
    print("speculative,k,seq_tok_s,verify_tok_s,speedup,verify_tok_s_int8,"
          "max_err,max_err_int8")
    for k in (2, 4, 8):
        T = k + 1
        # sequential: T decode passes, each streams the whole paged KV
        seq_s = T * _roof(2 * 2 * H * S2 * D, B * S2 * layer)
        seq_s_i8 = T * _roof(2 * 2 * H * S2 * D, B * S2 * layer_i8)
        # verify: one pass, KV streamed once for all T query rows
        ver_s = _roof(2 * 2 * H * T * S2 * D, B * S2 * layer)
        ver_s_i8 = _roof(2 * 2 * H * T * S2 * D, B * S2 * layer_i8)
        # correctness on a prefix+draft layout: drafts occupy the last T
        # positions of the sequence, queries attend causally over both
        pos = jnp.full((B,), S2 - T, jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.bfloat16)
        o = ops.paged_verify(q, kp, vp, bt, pos)
        err = float(jnp.max(jnp.abs(
            o.astype(jnp.float32)
            - ref.paged_verify_ref(q, kp, vp, bt, pos)
            .astype(jnp.float32))))
        o8 = ops.paged_verify_quant(q, kp8, vp8, kps, vps, bt, pos)
        err8 = float(jnp.max(jnp.abs(
            o8.astype(jnp.float32)
            - ref.paged_verify_quant_ref(q, kp8, vp8, kps, vps, bt, pos)
            .astype(jnp.float32))))
        out[f"k{k}"] = {
            "seq_tok_s": T / seq_s, "verify_tok_s": T / ver_s,
            "verify_speedup": seq_s / ver_s,
            "seq_tok_s_int8": T / seq_s_i8,
            "verify_tok_s_int8": T / ver_s_i8,
            "verify_speedup_int8": seq_s_i8 / ver_s_i8,
            "max_err": err, "max_err_int8": err8,
            # acceptance-swept effective ITL: one verify tick emits
            # expected_accepted(k, a) tokens on average
            "effective_itl_us": {
                f"a{a:.1f}": ver_s / float(expected_accepted(k, a)) * 1e6
                for a in (0.3, 0.5, 0.7, 0.9)},
        }
        r = out[f"k{k}"]
        print(f"speculative,{k},{r['seq_tok_s']:.0f},"
              f"{r['verify_tok_s']:.0f},{r['verify_speedup']:.2f},"
              f"{r['verify_tok_s_int8']:.0f},{err:.2e},{err8:.2e}")
    return emit("speculative_verify", out)


def run():
    rng = np.random.default_rng(0)
    rows = []

    # flash attention, one v5e-chip-sized tile of work
    B, S, H, Hkv, D = 1, 2048, 8, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)
    t0 = time.time()
    o = ops.flash_attention(q, k, v, block_q=256, block_k=256)
    err = float(jnp.max(jnp.abs(
        o.astype(jnp.float32)
        - ref.flash_attention_ref(q, k, v).astype(jnp.float32))))
    flops = 2 * 2 * B * H * S * S / 2 * D
    byts = (q.size + 2 * k.size + o.size) * 2
    rows.append(("flash_attention", f"B{B}xS{S}xH{H}xD{D}", err,
                 _roof(flops, byts), time.time() - t0))

    # flash decode
    S2 = 8192
    q1 = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(B, S2, Hkv, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(B, S2, Hkv, D)), jnp.bfloat16)
    cpos = jnp.broadcast_to(jnp.arange(S2), (B, S2)).astype(jnp.int32)
    pos = jnp.full((B,), S2 - 1, jnp.int32)
    t0 = time.time()
    o = ops.flash_decode(q1, kc, vc, cpos, pos, block_k=512)
    err = float(jnp.max(jnp.abs(
        o.astype(jnp.float32)
        - ref.flash_decode_ref(q1, kc, vc, cpos, pos).astype(jnp.float32))))
    flops = 2 * 2 * B * H * S2 * D
    byts = 2 * kc.size * 2
    rows.append(("flash_decode", f"B{B}xS{S2}xH{H}", err, _roof(flops, byts),
                 time.time() - t0))

    # paged flash decode: same contraction as flash_decode but K/V gathered
    # through a block table over a page pool (repro/serving/kv_cache.py)
    bs_pg = 64
    NB = S2 // bs_pg
    n_pages = 1 + NB  # null page + one sequence's pages
    kp = jnp.asarray(rng.normal(size=(n_pages, bs_pg, Hkv, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(n_pages, bs_pg, Hkv, D)), jnp.bfloat16)
    bt = jnp.arange(1, NB + 1, dtype=jnp.int32)[None]  # [1, NB]
    t0 = time.time()
    o = ops.paged_decode(q1, kp, vp, bt, pos)
    err = float(jnp.max(jnp.abs(
        o.astype(jnp.float32)
        - ref.paged_decode_ref(q1, kp, vp, bt, pos).astype(jnp.float32))))
    flops = 2 * 2 * B * H * S2 * D
    byts = 2 * B * S2 * Hkv * D * 2  # K+V bf16: same bytes, no gather copy
    paged_roof = _roof(flops, byts)
    rows.append(("paged_decode", f"B{B}xS{S2}xH{H}xbs{bs_pg}", err,
                 paged_roof, time.time() - t0))

    # fused-dequant paged decode: pages stay int8 in HBM (half the KV
    # stream), per-row fp32 scales ride as extra VMEM operands
    from repro.kernels.quant import quantize_kv
    kp8, kps = quantize_kv(kp)
    vp8, vps = quantize_kv(vp)
    t0 = time.time()
    o = ops.paged_decode_quant(q1, kp8, vp8, kps, vps, bt, pos)
    err = float(jnp.max(jnp.abs(
        o.astype(jnp.float32)
        - ref.paged_decode_quant_ref(q1, kp8, vp8, kps, vps, bt,
                                     pos).astype(jnp.float32))))
    byts_i8 = B * S2 * kv_token_bytes(1, Hkv, D, "int8")
    rows.append(("paged_decode_int8", f"B{B}xS{S2}xH{H}xbs{bs_pg}", err,
                 _roof(flops, byts_i8), time.time() - t0))

    paged = paged_kv_bench()
    spec = speculative_bench()

    # SSD scan
    b2, S3, h2, p2, n2 = 1, 1024, 8, 64, 64
    x = jnp.asarray(rng.normal(size=(b2, S3, h2, p2)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.9, (b2, S3, h2)), jnp.float32)
    a_neg = -jnp.asarray(rng.uniform(0.1, 1.0, (h2,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b2, S3, n2)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b2, S3, n2)), jnp.float32)
    t0 = time.time()
    y = ops.ssd_scan(x, dt, a_neg, Bm, Cm, chunk=256)
    err = float(jnp.max(jnp.abs(y - ref.ssd_scan_ref(x, dt, a_neg, Bm, Cm))))
    Q = 256
    flops = b2 * h2 * (S3 / Q) * (2 * Q * Q * n2 + 2 * Q * Q * p2
                                  + 4 * Q * p2 * n2)
    byts = 4 * (x.size + Bm.size + Cm.size + y.size)
    rows.append(("mamba2_ssd", f"S{S3}xh{h2}xp{p2}xn{n2}", err,
                 _roof(flops, byts), time.time() - t0))

    # grouped matmul
    E, C, K, N = 16, 256, 1024, 1024
    xg = jnp.asarray(rng.normal(size=(E, C, K)), jnp.bfloat16)
    wg = jnp.asarray(rng.normal(size=(E, K, N)), jnp.bfloat16)
    t0 = time.time()
    g = ops.grouped_matmul(xg, wg)
    err = float(jnp.max(jnp.abs(
        g.astype(jnp.float32)
        - ref.grouped_matmul_ref(xg, wg).astype(jnp.float32))))
    rows.append(("moe_gmm", f"E{E}x{C}x{K}x{N}", err,
                 _roof(2 * E * C * K * N, 2 * (xg.size + wg.size + g.size)),
                 time.time() - t0))

    # rmsnorm
    xr = jnp.asarray(rng.normal(size=(4096, 2048)), jnp.bfloat16)
    sc = jnp.asarray(rng.normal(size=(2048,)), jnp.float32)
    t0 = time.time()
    r = ops.rmsnorm(xr, sc)
    err = float(jnp.max(jnp.abs(
        r.astype(jnp.float32)
        - ref.rmsnorm_ref(xr, sc).astype(jnp.float32))))
    rows.append(("rmsnorm", "4096x2048", err,
                 _roof(4 * xr.size, 2 * 2 * xr.size), time.time() - t0))

    print("kernel,name,workload,max_err_vs_oracle,tpu_roofline_us,"
          "cpu_interpret_s")
    for name, wl, err, roof_s, wall in rows:
        print(f"kernel,{name},{wl},{err:.2e},{roof_s*1e6:.1f},{wall:.1f}")
    emit("kernel_bench", {"rows": [
        {"name": n, "workload": w, "err": e, "tpu_roofline_us": r_ * 1e6,
         "cpu_wall_s": wl} for n, w, e, r_, wl in rows]})
    serving = serving_prefill_bench()
    return {"kernels": {n: {"workload": w, "err": e,
                            "tpu_roofline_us": r_ * 1e6, "cpu_wall_s": wl}
                        for n, w, e, r_, wl in rows},
            "paged_kv": paged, "speculative": spec, "serving": serving}


def _flag_value(args: "list[str]", flag: str) -> "str | None":
    if flag not in args:
        return None
    i = args.index(flag)
    if i + 1 >= len(args):
        raise SystemExit(f"kernel_bench: {flag} needs a value")
    value = args[i + 1]
    del args[i:i + 2]
    return value


def main(argv: "list[str]") -> dict:
    """CLI: positional section names (``serving``, ``paged_kv``; none =
    full kernel sweep) + optional ``--json PATH`` writing every section
    that ran to one file for ``scripts/check_bench.py``, and optional
    ``--profile DIR`` recording a ``jax.profiler.trace`` around the
    timing loops (kernel-level XLA/TPU profile)."""
    args = list(argv)
    json_path = _flag_value(args, "--json")
    profile_dir = _flag_value(args, "--profile")
    sections = [a for a in args if not a.startswith("-")]
    unknown = [s for s in sections
               if s not in ("serving", "paged_kv", "speculative")]
    if unknown:
        raise SystemExit(f"kernel_bench: unknown section(s) {unknown}; "
                         "available: serving, paged_kv, speculative "
                         "(none = full sweep)")
    out = {}
    with contextlib.ExitStack() as stack:
        if profile_dir is not None:
            # a profile that was asked for and cannot start fails the run
            stack.enter_context(jax.profiler.trace(profile_dir))
            print(f"kernel_bench: profiling to {profile_dir}")
        if "paged_kv" in sections:
            out["paged_kv"] = paged_kv_bench()
        if "speculative" in sections:
            out["speculative"] = speculative_bench()
        if "serving" in sections:
            out["serving"] = serving_prefill_bench()
        if not sections:
            out = run()  # full sweep: kernels + paged_kv + serving
    if json_path:
        # provenance block so a checked-in results file says what ran it:
        # numbers from an emulated host mesh vs a real accelerator are
        # not comparable, and mesh shape pins the TP width benchmarked
        dev = jax.devices()[0]
        out["meta"] = {
            "backend": jax.default_backend(),
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "mesh_shape": dict(serving_mesh(jax.device_count()).shape),
            "sections": sections or ["full_sweep"],
        }
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"kernel_bench: wrote {json_path}")
    return out


if __name__ == "__main__":
    main(sys.argv[1:])
