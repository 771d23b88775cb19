"""Tensor-parallel sharded serving (distributed/tp.py): bit-identical
greedy decode under shard_map at TP 1/2/4 across {dense, MoE} x
{bf16, int8} x {chunked, monolithic} prefill x {spec on, off}, the
replicated-attention and expert-ff fallback layouts, cross-mesh
migration (TP=4 -> TP=1), and the ShardingPlan pspec rules the layouts
are built from (heads vs KV-sequence fallback, paged-pool leaves,
recurrent states, ZeRO-1 placement, never-pad)."""
import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.distributed.sharding import _leaf_pspec, make_plan
from repro.distributed.tp import ShardedServing, serving_mesh
from repro.models import build_model
from repro.nn.spec import TensorSpec
from repro.serving.engine import Request, ServingEngine

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def llama():
    cfg = reduced(get_config("llama3.2-3b"))  # dense, GQA
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe():
    cfg = reduced(get_config("qwen2-moe-a2.7b"))  # MoE + shared expert
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


_PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [9, 8, 7, 6, 5]]


def _serve(model, params, *, tp=0, max_new_tokens=8, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    if tp:
        kw["mesh"] = serving_mesh(tp)
    eng = ServingEngine(model, params, **kw)
    reqs = [Request(i, np.asarray(p, np.int32), max_new_tokens=max_new_tokens)
            for i, p in enumerate(_PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, [tuple(r.output) for r in reqs]


# ------------------------------------------------------------ layouts


@needs_mesh
def test_tp_shards_layout(llama, moe):
    lcfg, lmodel, _ = llama
    mcfg, mmodel, _ = moe
    # dense GQA: heads + kv heads + dense mlp all divide
    assert ShardedServing(lmodel, serving_mesh(2)).tp_shards == (
        "heads", "kv_heads", "mlp")
    # MoE: experts divide -> expert parallelism, dense-mlp rule unused
    sh = ShardedServing(mmodel, serving_mesh(2)).tp_shards
    assert "experts" in sh and "expert_ff" not in sh
    # TP=1 mesh runs the plain model (no collectives at all)
    s1 = ShardedServing(lmodel, serving_mesh(1))
    assert s1.tp_shards == () and s1.local_model is lmodel
    # d_model not divisible (tp=3): nothing output-column-shards
    s3 = ShardedServing(lmodel, serving_mesh(3))
    assert lcfg.d_model % 3 != 0 and s3.tp_shards == ()
    # kv heads not divisible: attention stays replicated, mlp still shards
    mqa = build_model(dataclasses.replace(lcfg, n_kv_heads=1))
    assert ShardedServing(mqa, serving_mesh(2)).tp_shards == ("mlp",)
    # experts not divisible but every expert's ff is: expert-ff fallback
    e6 = build_model(dataclasses.replace(mcfg, n_experts=6))
    sh = ShardedServing(e6, serving_mesh(4)).tp_shards
    assert "expert_ff" in sh and "experts" not in sh
    if mcfg.shared_ff:
        assert "shared_ff" in sh


@needs_mesh
def test_param_pspecs_output_column(llama, moe):
    """Projections closing a sharded dim hold full contraction rows and
    1/tp output columns; openings stay column-parallel; vocab replicated."""
    _, lmodel, _ = llama
    sv = ShardedServing(lmodel, serving_mesh(2))
    ps = sv.param_pspecs
    layer = ps["layers"]
    assert layer["attn"]["wo"] == P(None, None, "model")
    assert layer["attn"]["wq"] == P(None, None, "model")
    assert layer["mlp"]["w_down"] == P(None, None, "model") or \
        layer["mlp"].get("w2") == P(None, None, "model")
    assert ps["embed"]["table"] == P(None, None)  # replicated logits

    _, mmodel, _ = moe
    me = ShardedServing(mmodel, serving_mesh(2))
    moe_ps = me.param_pspecs["layers"]["moe"]
    # expert parallelism: every expert leaf sharded on E, incl. w_down
    assert moe_ps["w_down"] == P(None, "model", None, None)
    mcfg = mmodel.cfg
    ff = ShardedServing(build_model(dataclasses.replace(mcfg, n_experts=6)),
                        serving_mesh(4))
    ffl = ff.param_pspecs["layers"]["moe"]
    # expert-ff fallback: gate/up on f, down on its d output columns
    assert ffl["w_gate"] == P(None, None, None, "model")
    assert ffl["w_down"] == P(None, None, None, "model")
    if mcfg.shared_ff:
        assert ffl["shared_down"] == P(None, None, "model")


# ------------------------------------------- bit-identical token streams


@needs_mesh
@pytest.mark.parametrize("kv_dtype,tp", [
    ("bf16", 1), ("bf16", 2), ("bf16", 4), ("int8", 2), ("int8", 4)])
def test_tp_token_identity_dense(llama, kv_dtype, tp):
    _, model, params = llama
    _, base = _serve(model, params, kv_dtype=kv_dtype)
    _, got = _serve(model, params, tp=tp, kv_dtype=kv_dtype)
    assert got == base


@needs_mesh
@pytest.mark.parametrize("kv_dtype,tp", [("bf16", 2), ("bf16", 4),
                                         ("int8", 2)])
def test_tp_token_identity_moe(moe, kv_dtype, tp):
    _, model, params = moe
    _, base = _serve(model, params, kv_dtype=kv_dtype)
    _, got = _serve(model, params, tp=tp, kv_dtype=kv_dtype)
    assert got == base


@needs_mesh
@pytest.mark.parametrize("chunk", [0, 8])
def test_tp_token_identity_prefill_paths(llama, chunk):
    """Monolithic (chunk=0) and chunked prefill both bit-match."""
    _, model, params = llama
    _, base = _serve(model, params, prefill_chunk=chunk)
    _, got = _serve(model, params, tp=2, prefill_chunk=chunk)
    assert got == base


@needs_mesh
def test_tp_token_identity_speculative(llama):
    """Self-draft speculation on a TP=2 mesh (sharded verify kernel path)
    still emits exactly the unsharded spec-off stream."""
    cfg, model, params = llama
    _, base = _serve(model, params)
    eng, got = _serve(model, params, tp=2, draft_config=cfg,
                      draft_seed=123, spec_k=3)
    assert got == base
    st = eng.stats()
    assert st["speculative"] and st["spec_tokens_drafted"] > 0


@needs_mesh
def test_tp_token_identity_replicated_attention(llama):
    """kv heads not divisible -> attention/pool replicated, mlp sharded;
    decode must still bit-match."""
    cfg, _, _ = llama
    mqa_cfg = dataclasses.replace(cfg, n_kv_heads=1)
    model = build_model(mqa_cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, base = _serve(model, params)
    eng, got = _serve(model, params, tp=2)
    assert got == base
    assert not eng._tp.kv_sharded


@needs_mesh
def test_tp_token_identity_expert_ff_fallback(moe):
    """E % tp != 0: every expert's ff dim (and the shared expert) shards
    instead — the make_plan fallback, exercised end to end."""
    cfg, _, _ = moe
    e6_cfg = dataclasses.replace(cfg, n_experts=6)
    model = build_model(e6_cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, base = _serve(model, params)
    eng, got = _serve(model, params, tp=4)
    assert got == base
    assert "expert_ff" in eng._tp.tp_shards


# ------------------------------------------------- cross-mesh migration


@needs_mesh
def test_cross_mesh_migration_tp4_to_tp1(llama):
    """Prefill + partial decode on a TP=4 mesh, evacuate, resume on an
    unsharded engine: the snapshot gathers to host and re-shards into the
    destination layout, so the stream is bit-identical end to end."""
    cfg, model, params = llama
    prompt = np.random.default_rng(0).integers(
        1, cfg.vocab, 23).astype(np.int64)

    B = ServingEngine(model, params, max_batch=2, max_seq=64, page_size=8)
    base_req = Request(0, prompt.copy(), max_new_tokens=10)
    B.submit(base_req)
    B.run_until_drained()
    base = tuple(base_req.output)
    B.reset_prefix_cache()

    A = ServingEngine(model, params, max_batch=2, max_seq=64, page_size=8,
                      mesh=serving_mesh(4))
    req = Request(1, prompt.copy(), max_new_tokens=10)
    A.submit(req)
    for _ in range(10_000):
        slot = A.slot_of_request(1)
        if slot is not None and len(req.output) >= 4:
            break
        A.step()
    assert tuple(req.output) == base[:len(req.output)]
    A.evacuate(1)
    B.submit(req)
    B.run_until_drained()
    assert tuple(req.output) == base


# -------------------------------------------------- ShardingPlan rules


def _mesh2():
    dev = jax.devices()
    if len(dev) >= 2:
        arr = np.asarray(dev[:2]).reshape(2, 1)
    else:  # degenerate 1x1 mesh still exercises the rule logic
        arr = np.asarray(dev[:1]).reshape(1, 1)
    return Mesh(arr, ("model", "data"))


def test_plan_heads_vs_seq_fallback(llama):
    cfg, _, _ = llama
    mesh = _mesh2()
    sz = mesh.shape["model"]
    plan = make_plan(cfg, mesh)
    L, B, S, Dh = cfg.n_layers, 2, 32, cfg.hd

    kv = np.zeros((L, B, S, cfg.n_kv_heads, Dh), np.float32)
    cache = plan.cache(cfg, {"k": kv, "v": kv})
    if cfg.n_kv_heads % sz == 0:
        assert cache["k"].spec == P(None, "data", None, "model", None)
    # MQA: kv-head axis can't shard -> KV-sequence fallback on S
    mqa = dataclasses.replace(cfg, n_kv_heads=1)
    kv1 = np.zeros((L, B, S, 1, Dh), np.float32)
    c1 = plan.cache(mqa, {"k": kv1})["k"].spec
    assert c1[3] is None and c1[2] == "model"


def test_plan_paged_pool_leaves(llama):
    cfg, _, _ = llama
    mesh = _mesh2()
    sz = mesh.shape["model"]
    plan = make_plan(cfg, mesh)
    L, pages, bs, Hkv = cfg.n_layers, 6, 8, cfg.n_kv_heads
    pool = {"k_pages": np.zeros((L, pages, bs, Hkv, cfg.hd), np.float32),
            "k_scales": np.zeros((L, pages, bs, Hkv), np.float32)}
    out = plan.cache(cfg, pool)
    if Hkv % sz == 0:
        # kv heads shard; the page axis must never shard (host-side CoW,
        # scatters and snapshot export all index it)
        assert out["k_pages"].spec == P(None, None, None, "model", None)
        assert out["k_scales"].spec == P(None, None, None, "model")
    # Hkv=1 pool: falls back to the in-page sequence axis
    p1 = {"k_pages": np.zeros((L, pages, bs, 1, cfg.hd), np.float32)}
    spec1 = plan.cache(cfg, p1)["k_pages"].spec
    assert spec1[1] is None and spec1[3] is None
    if bs % sz == 0:
        assert spec1[2] == "model"


def test_plan_recurrent_state_leaves(llama):
    cfg, _, _ = llama
    mesh = _mesh2()
    sz = mesh.shape["model"]
    plan = make_plan(cfg, mesh)
    # conv state [L, taps, B, d]: batch at its named index, widest
    # divisible trailing dim on model
    leaf = np.zeros((cfg.n_layers, 4, 2, 64), np.float32)
    spec = plan.cache(cfg, {"conv": leaf})["conv"].spec
    if 2 % mesh.shape["data"] == 0:
        assert spec[2] == "data"
    assert spec[3] == ("model" if 64 % sz == 0 else None)


def test_plan_zero1_opt_state(llama):
    cfg, _, model_ = llama
    mesh = _mesh2()
    plan = make_plan(cfg, mesh)
    spec = {"w": TensorSpec((8, 64), ("embed", "mlp"), "normal"),
            "b": TensorSpec((64,), ("mlp",), "zeros")}
    opt = plan.opt_state(spec)
    # moments reuse the param pspec plus `data` on the first free dim
    wspec = opt.m["w"].spec
    assert wspec[1] == "model"  # mlp rule
    assert wspec[0] == "data"  # ZeRO-1 slot on the free embed dim
    assert opt.m["w"] is opt.v["w"] is not None
    # scalar step stays replicated
    assert opt.step.spec == P()


def test_plan_never_pads():
    mesh = _mesh2()
    sz = mesh.shape["model"]
    rules = {"mlp": "model", None: None}
    # any dim the axis does not divide stays unsharded, never padded
    odd = TensorSpec((sz * 3 + 1,), ("mlp",), "zeros")
    assert _leaf_pspec(odd, rules, mesh) == P(None)
    even = TensorSpec((sz * 4,), ("mlp",), "zeros")
    assert _leaf_pspec(even, rules, mesh) == P("model" if sz > 1 else None)


def test_plan_expert_fallback_divisibility(moe):
    """make_plan's expert fallback: E % model != 0 shards each expert's
    ff dim through the mlp rule — but only when that dim divides too."""
    cfg, _, _ = moe
    mesh = _mesh2()
    sz = mesh.shape["model"]
    if sz == 1:
        pytest.skip("needs a >1 model axis")
    e_bad = dataclasses.replace(cfg, n_experts=sz + 1)
    plan = make_plan(e_bad, mesh)
    assert plan.rules["experts"] is None
    assert (plan.rules["mlp"] == "model") == (
        e_bad.moe_ff % sz == 0 and
        (not e_bad.shared_ff or e_bad.shared_ff % sz == 0))
    # expert ff does not divide either: the mlp rule must drop too
    ff_bad = dataclasses.replace(cfg, n_experts=sz + 1, moe_ff=sz * 3 + 1)
    assert make_plan(ff_bad, mesh).rules["mlp"] is None


# ------------------------------------------------ cost model / continuum


def test_cost_model_tp_terms():
    """tp=1 is a bitwise no-op on every calibrated baseline; tp>1 divides
    the streamed bytes / FLOPs and adds the ici collective term."""
    from repro.sim import cost_model as cm
    dev, prof = cm.DEVICES["rtx5090"], cm.MODELS["qwen3vl-8b"]
    base_d = cm.decode_s(dev, prof, 64.0, context_tokens=512,
                         kv_dtype="int8")
    assert cm.decode_s(dev, prof, 64.0, context_tokens=512,
                       kv_dtype="int8", tp=1) == base_d
    d2 = cm.decode_s(dev, prof, 64.0, context_tokens=512,
                     kv_dtype="int8", tp=2)
    d4 = cm.decode_s(dev, prof, 64.0, context_tokens=512,
                     kv_dtype="int8", tp=4)
    assert d4 < d2 < base_d

    base_p = cm.prefill_s(dev, prof, 256.0)
    assert cm.prefill_s(dev, prof, 256.0, tp=1) == base_p
    assert cm.prefill_s(dev, prof, 256.0, tp=4) < base_p

    base_v = cm.verify_s(dev, prof, 4, context_tokens=512)
    assert cm.verify_s(dev, prof, 4, context_tokens=512, tp=1) == base_v
    assert cm.verify_s(dev, prof, 4, context_tokens=512, tp=4) < base_v

    assert cm.tp_collective_s(dev, prof, 64.0, 1) == 0.0
    # collectives grow with width; the compute/bytes split shrinks —
    # so sufficiently narrow interconnects eventually stop paying off
    c2 = cm.tp_collective_s(dev, prof, 64.0, 2)
    c8 = cm.tp_collective_s(dev, prof, 64.0, 8)
    assert 0.0 < c2 < c8
    slow = dataclasses.replace(dev, ici_bw=1e6)
    assert cm.decode_s(slow, prof, 64.0, tp=8) > cm.decode_s(
        slow, prof, 64.0)


def test_continuum_tp_knob():
    """build_continuum(tp=N) shards only the cloud class; the TP handle's
    tick costs shrink, which is exactly what the router prices."""
    from repro.serving.cluster import build_continuum
    spec = [(0, 1), (2, 1)]
    flat = build_continuum(spec, backend="sim", max_batch=2, max_seq=96)
    tp4 = build_continuum(spec, backend="sim", max_batch=2, max_seq=96,
                          tp=4)
    # edge tier untouched (bitwise — the tp=1 path is the verbatim
    # single-device expression)
    assert tp4[0].tp == 1
    assert tp4[0].decode_tick_s == flat[0].decode_tick_s
    assert tp4[0].prefill_tok_s == flat[0].prefill_tok_s
    # cloud tier: both phases get faster, by less than the ideal 4x
    assert tp4[1].tp == 4
    assert tp4[1].decode_tick_s < flat[1].decode_tick_s
    assert tp4[1].prefill_tok_s < flat[1].prefill_tok_s
    assert tp4[1].decode_tick_s > flat[1].decode_tick_s / 4
    # dict form shards a chosen class
    per = build_continuum(spec, backend="sim", max_batch=2, max_seq=96,
                          tp={0: 2})
    assert per[0].tp == 2 and per[1].tp == 1


@needs_mesh
def test_continuum_live_tp_engine(llama):
    """Live backend: the tp knob hands the engine a real host mesh."""
    from repro.serving.cluster import build_continuum
    handles = build_continuum([(0, 1)], backend="live", max_batch=2,
                              max_seq=64, tp={0: 2})
    h = handles[0]
    assert h.engine.mesh is not None and h.engine._tp.tp == 2
    assert h.decode_tick_s > 0


# ------------------------------------------------------ gathered bytes


@needs_mesh
def test_gathered_bytes_counts_scan_iterations_and_the_costlier_branch():
    """``gathered_bytes``: a gather whose result is F bytes a chip moves
    (n - 1) x F over its group of n; a ``scan`` body counts once per
    iteration, a ``cond`` its costlier branch."""
    import jax.numpy as jnp
    from repro.distributed.tp import gathered_bytes
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))

    def body(x):  # x: [2, 8] float32 a chip
        def step(c, _):  # [2, 32] float32 gathered: 256 B a chip
            return c, jax.lax.all_gather(c, "model", axis=1, tiled=True)
        _, ys = jax.lax.scan(step, x, None, length=3)
        z = jax.lax.cond(  # [4, 2, 8] float32: 256 B a chip, or nothing
            x[0, 0] > 0, lambda v: jax.lax.all_gather(v, "model"),
            lambda v: jnp.zeros((4,) + v.shape, v.dtype), x)
        return ys, z

    f = jax.shard_map(body, mesh=mesh, in_specs=P(None, "model"),
                      out_specs=(P(), P()), check_vma=False)
    jaxpr = jax.jit(f).trace(jnp.ones((2, 32), jnp.float32)).jaxpr.jaxpr
    assert gathered_bytes(jaxpr, 4) == 3 * (3 * 256) + 3 * 256


def test_tp_gather_bytes_stay_zero_on_one_device(llama):
    _, model, params = llama
    eng, _ = _serve(model, params)
    snap = eng.metrics.snapshot()
    assert eng.tp == 1
    assert snap["tp_gather_bytes"] == snap["tp_gather_bytes_decode"] == 0
