"""Int8-quantized KV cache: quantizer bounds, fused-dequant kernel parity
vs the jnp oracles, engine-level greedy-token agreement vs bf16, scale
bookkeeping under CoW / eviction / prefix hits, byte-budget admission
accounting, and the decode-loop overhead satellites (cache buffer
donation, on-device argmax)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops, ref
from repro.kernels.quant import dequantize_kv, quantize_kv
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import kv_token_bytes
from repro.sim import cost_model as cm


def _rng(seed=0):
    return np.random.default_rng(seed)


@pytest.fixture(scope="module")
def qwen():
    cfg = reduced(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _serve(model, params, prompts, *, max_new_tokens=5, **kw):
    eng = ServingEngine(model, params, max_batch=2, max_seq=64, **kw)
    reqs = [Request(i, p, max_new_tokens=max_new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, [tuple(r.output) for r in reqs]


# -------------------------------------------------------------- quantizer


def test_quantize_roundtrip_bound():
    x = jnp.asarray(_rng(1).normal(size=(3, 5, 4, 32)) * 7.0, jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]
    back = dequantize_kv(q, s)
    # symmetric rounding: error <= scale/2 = absmax/254 per row
    bound = jnp.max(jnp.abs(x), -1, keepdims=True) / 254.0 + 1e-6
    assert bool(jnp.all(jnp.abs(back - x) <= bound))


def test_quantize_zero_rows_safe():
    x = jnp.zeros((2, 4, 16))
    q, s = quantize_kv(x)
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s == 1.0))
    assert bool(jnp.all(dequantize_kv(q, s) == 0.0))


# -------------------------------------------------- fused-dequant kernels


def _quant_case(rng, B, S, H, Hkv, D, bs, lens, masked):
    """q, bf16 and int8 pools, block table and positions.  ``lens`` None:
    every slot's table full, positions drawn from [S/2, S).  Otherwise
    slot b holds ``lens[b]`` positions on its first pages, and a masked
    slot has a null table and ``pos`` 0."""
    NB = S // bs
    P = 1 + B * NB
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), jnp.bfloat16)
    bt = np.arange(1, 1 + B * NB, dtype=np.int32).reshape(B, NB)
    if lens is None:
        pos = rng.integers(S // 2, S, B)
    else:
        pos = np.asarray(lens) - 1
        for b, n in enumerate(lens):
            bt[b, -(-n // bs):] = -1
        bt[list(masked)] = -1
        pos[list(masked)] = 0
    return q, k, v, bt, jnp.asarray(pos, jnp.int32)


# (B, S, H, Hkv, D, bs, window, lens, masked); lens None: full tables
QUANT_CASES = [
    pytest.param(2, 96, 8, 2, 64, 16, 0, None, (), id="2-96-8-2-64-16-0"),
    pytest.param(1, 64, 4, 4, 32, 8, 24, None, (), id="1-64-4-4-32-8-24"),
    # ragged: one position, page boundaries, a masked row; NB 32 with 1-3
    # live pages; 16-page compute blocks over 20 live pages
    pytest.param(6, 512, 8, 2, 64, 16, 0, (1, 16, 32, 40, 320, 0), (5,),
                 id="ragged-long-table-masked"),
    # window 40 at position 150: the first visible block is block 6
    pytest.param(2, 192, 8, 2, 64, 16, 40, (151, 100), (),
                 id="window-past-block0"),
    # one TP=4 shard of codeqwen1.5-7b: 8 query heads over one KV head of
    # 128 (one position a copied row), ragged, a masked row
    pytest.param(3, 192, 8, 1, 128, 16, 0, (33, 160, 0), (2,),
                 id="tp4-shard-8-1-128"),
]


@pytest.mark.parametrize("B,S,H,Hkv,D,bs,window,lens,masked", QUANT_CASES)
def test_paged_decode_quant_kernel_parity(B, S, H, Hkv, D, bs, window, lens,
                                          masked):
    q, k, v, bt, pos = _quant_case(_rng(7), B, S, H, Hkv, D, bs, lens,
                                   masked)
    bt = jnp.asarray(bt)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    out = np.asarray(ops.paged_decode_quant(q, k8, v8, ks, vs, bt, pos,
                                            window=window), np.float32)
    want = ref.paged_decode_quant_ref(q, k8, v8, ks, vs, bt, pos,
                                      window=window)
    seen = np.setdiff1d(np.arange(B), masked)
    np.testing.assert_allclose(out[seen], np.asarray(want, np.float32)[seen],
                               atol=5e-3, rtol=5e-3)
    assert (out[list(masked)] == 0).all()  # no live page: zeros
    # and the dequant noise vs the full-precision pool stays int8-sized
    full = ref.paged_decode_ref(q, k, v, bt, pos, window=window)
    np.testing.assert_allclose(out[seen], np.asarray(full, np.float32)[seen],
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("window", [0, 40])
def test_paged_decode_quant_skips_dead_pages(window):
    """Pages no slot can see, the null page among them, are never read:
    with NaN scales they leave the output equal to the reference on the
    clean pool, and finite."""
    B, S, bs = 5, 192, 16
    q, k, v, bt, pos = _quant_case(_rng(5), B, S, 8, 2, 64, bs,
                                   (1, 16, 151, 60, 0), (4,))
    live = set()
    for row, p in zip(bt, np.asarray(pos)):
        first = max(int(p) - window + 1, 0) // bs if window else 0
        live.update(int(x) for x in row[first:int(p) // bs + 1] if x >= 0)
    dead = np.asarray(sorted(set(range(k.shape[0])) - live))
    assert 0 in dead
    bt = jnp.asarray(bt)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    want = ref.paged_decode_quant_ref(q, k8, v8, ks, vs, bt, pos,
                                      window=window)
    out = ops.paged_decode_quant(q, k8, v8, ks.at[dead].set(jnp.nan),
                                 vs.at[dead].set(jnp.nan), bt, pos,
                                 window=window)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:4], np.asarray(want, np.float32)[:4],
                               atol=5e-3, rtol=5e-3)
    assert (out[4] == 0).all()


def test_paged_decode_quant_masks_unallocated():
    """-1 table entries (clamped to the null page) must not leak the null
    page's garbage values or scales into the output."""
    rng = _rng(3)
    B, H, Hkv, D, bs = 1, 4, 2, 32, 8
    P = 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), jnp.bfloat16)
    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    # poison the null page with huge scales
    ks = ks.at[0].set(1e6)
    vs = vs.at[0].set(1e6)
    bt = jnp.asarray([[1, -1, -1]], jnp.int32)
    pos = jnp.asarray([bs - 1], jnp.int32)
    out = ops.paged_decode_quant(q, k8, v8, ks, vs, bt, pos)
    want = ref.paged_decode_quant_ref(q, k8, v8, ks, vs, bt, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-3, rtol=5e-3)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


@pytest.mark.parametrize("B,S,H,Hkv,D,window", [
    (2, 96, 8, 2, 64, 0),
    (1, 70, 8, 1, 64, 0),  # padding path: scales padded alongside K/V
    (2, 128, 4, 4, 32, 24),
])
def test_flash_decode_quant_kernel_parity(B, S, H, Hkv, D, window):
    rng = _rng(11)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)
    k8, ks = quantize_kv(kc)
    v8, vs = quantize_kv(vc)
    cpos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    pos = jnp.asarray(rng.integers(S // 2, S, B), jnp.int32)
    out = ops.flash_decode_quant(q, k8, v8, ks, vs, cpos, pos,
                                 window=window, block_k=32)
    want = ref.flash_decode_quant_ref(q, k8, v8, ks, vs, cpos, pos,
                                      window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-3, rtol=5e-3)


# ------------------------------------------------------- engine: int8 path


def test_abstract_paged_cache_int8_layout(qwen):
    cfg, model, _ = qwen
    abstract = model.abstract_paged_cache(8, 4, kv_dtype="int8")
    assert abstract["k_pages"].dtype == jnp.int8
    assert abstract["v_pages"].dtype == jnp.int8
    shape = (cfg.n_layers, 8, 4, cfg.n_kv_heads)
    assert abstract["k_scales"].shape == shape
    assert abstract["k_scales"].dtype == jnp.float32
    with pytest.raises(ValueError):
        model.abstract_paged_cache(8, 4, kv_dtype="fp4")


# Largest |int8 - bf16| served logit.  Per-row symmetric int8 rounds each
# K/V element by at most absmax/254 (test_quantize_roundtrip_bound), about
# twice bf16's relative step; through this 2-layer model that moves logits
# of standard deviation ~1 by at most 0.065 on this workload, so 0.15 keeps
# a 2x margin.  A scale applied to the wrong row or page moves them by O(1).
INT8_LOGIT_TOL = 0.15


@pytest.mark.parametrize("prefill_chunk", [64, 0], ids=["chunked",
                                                        "monolithic"])
def test_engine_int8_greedy_agreement(qwen, prefill_chunk):
    """The int8 engine's served logits agree with the bf16 engine's within
    INT8_LOGIT_TOL at every generated position whose inputs agree.  Tokens
    are not compared: with random weights rounding may flip a near-tied
    argmax, after which the two engines decode different sequences."""
    cfg, model, params = qwen
    rng = _rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (7, 19, 33, 12)]

    def served(kv_dtype):
        eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                            prefill_chunk=prefill_chunk, kv_dtype=kv_dtype,
                            return_logits=True)
        reqs = [Request(i, p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        return reqs

    for a, b in zip(served("bf16"), served("int8")):
        assert len(a.logits) == len(a.output) == len(b.logits) == 5
        # logits[t] follows output[:t]: comparable up to the first
        # differing token, inclusive
        n = next((t for t, (x, y) in enumerate(zip(a.output, b.output))
                  if x != y), len(a.output) - 1) + 1
        err = max(float(np.abs(x - y).max())
                  for x, y in zip(a.logits[:n], b.logits[:n]))
        assert err <= INT8_LOGIT_TOL, (a.uid, err)


def test_engine_int8_halves_cache_bytes(qwen):
    cfg, model, params = qwen
    e_bf = ServingEngine(model, params, max_batch=2, max_seq=64)
    e_i8 = ServingEngine(model, params, max_batch=2, max_seq=64,
                         kv_dtype="int8")
    want = (kv_token_bytes(cfg.n_layers, cfg.n_kv_heads, cfg.hd, "bf16")
            / kv_token_bytes(cfg.n_layers, cfg.n_kv_heads, cfg.hd, "int8"))
    assert e_bf.kv_cache_bytes() / e_i8.kv_cache_bytes() == \
        pytest.approx(want)
    assert e_i8.stats()["kv_dtype"] == "int8"


def test_int8_needs_paged_backend(qwen):
    _, model, params = qwen
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(model, params, paged=False, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(model, params, kv_dtype="fp8")


def test_int8_prefix_hit_token_identical(qwen):
    """A warm prefix-cache hit must reproduce the cold run exactly: the
    chunked path reads every cache row back dequantized (write-then-
    quantize), so hit pages hold bit-identical values to a cold scatter."""
    cfg, model, params = qwen
    prompt = _rng(5).integers(0, cfg.vocab, 33).astype(np.int32)
    eng, (cold,) = _serve(model, params, [prompt], kv_dtype="int8")
    warm = Request(99, prompt, max_new_tokens=5)
    eng.submit(warm)
    eng.run_until_drained()
    assert tuple(warm.output) == cold
    assert eng.pool.hits > 0


def test_int8_cow_carries_scales(qwen):
    """A fully-cached prompt re-admission copies its final page (copy-on-
    write) — values *and* scale rows must move together or the recomputed
    last token dequantizes garbage."""
    cfg, model, params = qwen
    prompt = _rng(9).integers(0, cfg.vocab, 16).astype(np.int32)
    eng, (cold,) = _serve(model, params, [prompt], kv_dtype="int8",
                          prefill_chunk=0, bucket_prompts=False,
                          page_size=8)
    assert eng.pool.cow_copies == 0
    warm = Request(99, prompt, max_new_tokens=5)
    eng.submit(warm)
    eng.run_until_drained()
    assert eng.pool.cow_copies >= 1  # unaligned reuse split a shared page
    assert tuple(warm.output) == cold


def test_int8_eviction_then_recompute(qwen):
    """After the LRU evicts a parked prefix, resubmitting its prompt must
    recompute cleanly into recycled pages (stale scales overwritten)."""
    cfg, model, params = qwen
    rng = _rng(13)
    prompt = rng.integers(0, cfg.vocab, 17).astype(np.int32)
    eng = ServingEngine(model, params, max_batch=1, max_seq=32,
                        kv_dtype="int8", page_size=8, num_pages=9)
    first = Request(0, prompt, max_new_tokens=4)
    eng.submit(first)
    eng.run_until_drained()
    # churn the pool with distinct prompts until the original is evicted
    for i in range(1, 5):
        eng.submit(Request(i, rng.integers(0, cfg.vocab, 17)
                           .astype(np.int32), max_new_tokens=4))
    eng.run_until_drained()
    assert eng.pool.evictions > 0
    again = Request(50, prompt, max_new_tokens=4)
    eng.submit(again)
    eng.run_until_drained()
    assert tuple(again.output) == tuple(first.output)


def test_kv_budget_doubles_page_count(qwen):
    """The admission-control dividend: a fixed device byte budget buys
    ~2x the pages under int8 (2*Dh/(Dh+4) exactly)."""
    cfg, model, params = qwen
    budget = 1 << 20
    e_bf = ServingEngine(model, params, max_seq=64, kv_budget_bytes=budget)
    e_i8 = ServingEngine(model, params, max_seq=64, kv_budget_bytes=budget,
                         kv_dtype="int8")
    assert e_bf.pool.num_pages == max(2, 1 + budget // e_bf.page_bytes())
    assert e_i8.pool.num_pages == max(2, 1 + budget // e_i8.page_bytes())
    want = e_bf.page_bytes() / e_i8.page_bytes()
    assert e_i8.pool.num_pages / e_bf.pool.num_pages == \
        pytest.approx(want, rel=0.05)
    assert want > 1.4  # reduced head dim; 1.94x at Dh=128


# --------------------------------------- decode-loop overhead satellites


def _donation_supported():
    probe = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.zeros((8,), jnp.float32)
    probe(x)
    return x.is_deleted()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_step_donates_cache(qwen, kv_dtype):
    """The per-tick jitted decode step must not pay a full KV-cache copy:
    the cache pytree is donated, so the pre-tick buffers are consumed
    (live-buffer check) and the step stays a single XLA trace."""
    if not _donation_supported():
        pytest.skip("backend does not support buffer donation")
    cfg, model, params = qwen
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        kv_dtype=kv_dtype)
    eng.submit(Request(0, _rng(2).integers(0, cfg.vocab, 9)
                       .astype(np.int32), max_new_tokens=6))
    while not any(s is not None for s in eng.slots):
        eng.step()  # finish prefill; decode starts next tick
    before = dict(eng.cache)
    eng.step()
    deleted = {name: leaf.is_deleted() for name, leaf in before.items()}
    assert all(deleted.values()), f"copied (not donated): {deleted}"
    assert eng.jit_cache_sizes().get("_step") == 1
    eng.run_until_drained()


def test_chunked_prefill_donates_cache(qwen):
    if not _donation_supported():
        pytest.skip("backend does not support buffer donation")
    cfg, model, params = qwen
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        prefill_chunk=16, prefill_budget=16)
    eng.submit(Request(0, _rng(4).integers(0, cfg.vocab, 40)
                       .astype(np.int32), max_new_tokens=2))
    before = dict(eng.cache)
    eng.step()  # first prefill chunk runs inside this tick
    assert any(t is not None for t in eng.prefill_tasks)
    assert all(leaf.is_deleted() for leaf in before.values())
    eng.run_until_drained()


def test_on_device_argmax_matches_logits_path(qwen):
    """Default decode returns [B] token ids argmaxed on device; the
    return_logits escape hatch must produce identical tokens (and expose
    the full [B, vocab] logits to the host)."""
    cfg, model, params = qwen
    rng = _rng(6)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 23)]
    _, out_ids = _serve(model, params, prompts)
    _, out_logits = _serve(model, params, prompts, return_logits=True)
    assert out_ids == out_logits


def test_step_returns_token_ids_shape(qwen):
    """The decode-step transfer is [B] int32, not [B, vocab] floats."""
    cfg, model, params = qwen
    eng = ServingEngine(model, params, max_batch=2, max_seq=64)
    eng.submit(Request(0, _rng(8).integers(0, cfg.vocab, 5)
                       .astype(np.int32), max_new_tokens=4))
    while not any(s is not None for s in eng.slots):
        eng.step()
    out, cache = eng._step(eng.params, eng.cache,
                           _rebuild_batch(eng))
    eng.cache = cache
    assert out.shape == (eng.max_batch,) and out.dtype == jnp.int32
    eng.run_until_drained()


def _rebuild_batch(eng):
    """Minimal decode batch for the active slots (mirrors engine.step)."""
    tokens = np.zeros(eng.max_batch, np.int32)
    pos = np.zeros(eng.max_batch, np.int64)
    tables = np.full_like(eng.tables, -1)
    for i, r in enumerate(eng.slots):
        if r is not None:
            tokens[i] = r.output[-1]
            pos[i] = eng.pos[i]
            tables[i] = eng.tables[i]
    return {"tokens": jnp.asarray(tokens),
            "pos": jnp.asarray(pos, jnp.int32),
            "block_tables": jnp.asarray(tables)}


# ------------------------------------------------- cost model: bytes chain


def test_cost_model_kv_bytes_chain():
    """bytes/token -> decode_s -> concurrency: int8 roughly halves the
    per-token KV stream, speeds context-heavy decode, and ~doubles the
    sequences a device's HBM budget can hold resident."""
    mdl = cm.MODELS["qwen3vl-8b"]
    dev = cm.DEVICES["jetson_orin_nano"]
    b16 = cm.kv_bytes_per_token(mdl, "bf16")
    i8 = cm.kv_bytes_per_token(mdl, "int8")
    L, hkv, dh = mdl.kv_layout
    assert b16 == 2.0 * L * hkv * dh * 2
    assert b16 / i8 == pytest.approx(2 * dh / (dh + 4))
    # context-free decode_s reproduces the legacy weights-only term
    legacy = 10 * mdl.n_active * mdl.bytes_per_param / (dev.mem_bw * cm._EFF)
    assert cm.decode_s(dev, mdl, 10) == pytest.approx(legacy)
    # with context, int8 decodes strictly faster
    assert cm.decode_s(dev, mdl, 10, context_tokens=4096, kv_dtype="int8") \
        < cm.decode_s(dev, mdl, 10, context_tokens=4096, kv_dtype="bf16")
    # and fits ~2x the sequences in the same KV budget (on a device the
    # weights actually fit; a too-small device reports 0 concurrency)
    big = cm.DEVICES["rtx5090"]
    c16 = cm.kv_concurrency(big, mdl, 4096, "bf16")
    c8 = cm.kv_concurrency(big, mdl, 4096, "int8")
    assert c16 >= 1 and c8 >= 1.8 * c16
    assert cm.kv_concurrency(dev, mdl, 4096) == 0  # 8 GB HBM < 8 GB weights
    # latency_s default stays the calibrated legacy aggregate
    base = cm.latency_s(dev, mdl, 64, 0.5)
    assert cm.latency_s(dev, mdl, 64, 0.5, kv_dtype="bf16") > base
    assert cm.latency_s(dev, mdl, 64, 0.5, kv_dtype="int8") < \
        cm.latency_s(dev, mdl, 64, 0.5, kv_dtype="bf16")


def test_cluster_edge_tiers_default_int8():
    from repro.serving.cluster import build_continuum
    handles = build_continuum([(0, 1), (2, 1)], max_seq=48)
    edge, cloud = handles
    assert not edge.is_cloud and edge.kv_dtype == "int8"
    assert cloud.is_cloud and cloud.kv_dtype == "bf16"
    assert edge.engine.kv_dtype == "int8"
    # the tick cost prices the precision: same profile on the same device
    # would tick slower at bf16 (more KV bytes streamed per token)
    from repro.serving.cluster import EngineHandle
    edge_bf = EngineHandle("edge-bf16", "qwen2-0.5b", edge.device,
                           edge.profile, kv_dtype="bf16", max_seq=48)
    assert edge.decode_tick_s < edge_bf.decode_tick_s
    # recurrent-family edge servers (dense cache) must fall back to bf16
    # instead of crashing on the paged-only int8 default
    xl = EngineHandle("edge-xlstm", "xlstm-1.3b", edge.device,
                      edge.profile, max_seq=48)
    assert xl.kv_dtype == "bf16" and not xl.engine.paged
