"""The cloud tier's shard on the CPU: codeqwen1.5-7b's head ratios (32
query heads over 4 KV heads) at TP=4 over four host devices, so that each
shard holds 8 query heads over one KV head.  The pool's shards, each one
whole KV head by its values, whatever the order of the storage axes;
chunked prefill, then decode through the paged pool, against the plain
float32 reference's full forward pass in logits; the ``tp_gather_bytes``
counters against a hand count; the ``tp`` argument of the ``engine.tick``
span; and the ``tp.gather_gbps`` reader on a synthetic trace."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _tiny
from chipbench import harness, weights
from chipbench import trace as tr
from chipbench.cells import load_module

from repro.distributed.tp import ShardedServing, serving_mesh
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.telemetry import Telemetry

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

SEED = 2**32 + 1500  # more than 32 bits, as a run's seed may be
TP, PAGE, CHUNK, NEW = 4, 16, 32, 12
PROMPTS = (40, 20, 9)  # chunks of 32 + 8, 20 and 9 tokens
# published head ratios and head dim 16: d 512 = 32 heads x 16
MODEL = dict(hidden_size=512, intermediate_size=256, num_hidden_layers=2,
             vocab_size=512)
# Limits on |program - reference| over every served position's logits
# (logits of magnitude up to ~4 here).  The program serves bfloat16
# weights and activations: each rounding keeps 8 bits (2^-9 relative), so
# its logits stray by a few hundredths at most (read: 0.039-0.049 widest,
# 0.007-0.008 mean); the same reference with every matrix in float8
# e4m3, the nearest precision below, strays ~8x further (0.34-0.46
# widest, 0.068-0.072 mean) and fails both.
MAX_ERR, MEAN_ERR = 0.1, 0.02


def _config() -> dict:
    cfg = _tiny.load("configs/codeqwen1.5-7b-tp4.json")
    cfg["model"].update(MODEL)
    return cfg


@pytest.fixture(scope="module")
def served():
    """The TP=4 engine after serving three prompts, its requests, and the
    trace of its tracer."""
    cfg = _config()
    model = build_model(harness.arch_config(cfg))
    mesh = serving_mesh(TP)
    params = weights.make_params(model.abstract(), SEED,
                                 ShardedServing(model, mesh).param_shardings)
    tm = Telemetry(trace=True)
    eng = ServingEngine(model, params, max_batch=4, max_seq=128,
                        page_size=PAGE, prefill_chunk=CHUNK, mesh=mesh,
                        return_logits=True, telemetry=tm)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, MODEL["vocab_size"], n).astype(
        np.int32), max_new_tokens=NEW) for i, n in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return SimpleNamespace(cfg=cfg, eng=eng, reqs=reqs, trace=tm.to_json())


def _reference_logits(m: dict, ids, fp8: bool = False) -> np.ndarray:
    """The reference's full forward pass over ``ids``, at every position
    (with ``fp8``, every matrix rounded to float8 e4m3 as its control)."""
    ref = load_module("references", "qwen2")
    src = weights.Source(ref.param_shapes(m), SEED)
    top = src.top()
    x = top["embed"]["table"][jnp.asarray(ids)]
    kw = dict(H=m["num_attention_heads"], Hkv=m["num_key_value_heads"],
              eps=m["rms_norm_eps"], theta=m["rope_theta"])
    for layer in range(m["num_hidden_layers"]):
        w = src.layer(layer)
        x = ref._layer(x, ref._fp8_tree(w) if fp8 else w, **kw)
    head = ref.fp8_round(top["lm_head"], -2) if fp8 else top["lm_head"]
    return np.asarray(jnp.matmul(ref._rms(x, top["final_s"], kw["eps"]),
                                 head, precision=jax.lax.Precision.HIGHEST))


def _nonzero_sorted(a) -> np.ndarray:
    a = np.asarray(a, np.float32).ravel()
    return np.sort(a[a != 0])


def assert_one_kv_head_a_shard(logical: np.ndarray, stored: jax.Array):
    """Each of ``stored``'s shards holds exactly one whole KV head of the
    logical view ``logical`` ``[L, P, PAGE, Hkv, D]``, whatever the order
    of the storage axes: one shard a head, each with an equal share of the
    pool's bytes, room for every logical element (a layout may pad), and a
    one-to-one map from shards to heads by their sorted nonzero values
    (unwritten pages and any padding read zero)."""
    shards = stored.addressable_shards
    hkv = logical.shape[3]
    assert len(shards) == hkv
    assert all(s.data.nbytes * hkv == stored.nbytes for s in shards)
    assert stored.size >= logical.size
    heads = [_nonzero_sorted(logical[..., h, :]) for h in range(hkv)]
    assert all(h.size for h in heads)
    held = []
    for s in shards:
        vals = _nonzero_sorted(s.data)
        held.append([h for h in range(hkv)
                     if np.array_equal(vals, heads[h])])
    assert sorted(map(tuple, held)) == [(h,) for h in range(hkv)], held


def test_each_shard_holds_eight_query_heads_over_one_kv_head(served):
    local = served.eng._tp.local_model.cfg
    assert (local.n_heads, local.n_kv_heads, local.hd) == (8, 1, 16)
    L, P = MODEL["num_hidden_layers"], served.eng.pool.num_pages
    export = served.eng.model.export_paged_kv(served.eng.cache, range(P))
    for name in ("k_pages", "v_pages"):
        assert export[name].shape == (L, P, PAGE, 4, 16)
        assert_one_kv_head_a_shard(export[name], served.eng.cache[name])


_SYN = (2, 8, PAGE, 4, 16)  # L, P, PAGE, Hkv, D


def _head_major(x: np.ndarray) -> np.ndarray:
    """``[L, P, 16, Hkv, 16]`` as ``[L, Hkv, P, 2, 128]``: the kernel's rows
    of 8 positions x 16 dims, each head's pages together."""
    L, P, bs, hkv, d = x.shape
    return x.transpose(0, 3, 1, 2, 4).reshape(L, hkv, P, 2, bs * d // 2)


@pytest.mark.parametrize("storage, axis, one_head_a_shard", [
    ("page_major", 3, True),     # today's pool, split on its head axis
    ("head_major", 1, True),     # [L, 4, P, 2, 128], split on axis 1
    ("page_major", 1, False),    # split along pages
    ("page_major", 2, False),    # split along in-page positions
    ("page_major", 4, False),    # split along the head dim
], ids=["heads", "head_major", "pages", "positions", "head_dim"])
def test_shard_check_on_synthetic_pools(storage, axis, one_head_a_shard):
    rng = np.random.default_rng(SEED)
    n = int(np.prod(_SYN))
    logical = ((rng.permutation(n) + 1) / n).astype(np.float32).reshape(_SYN)
    host = _head_major(logical) if storage == "head_major" else logical
    spec = [None] * host.ndim
    spec[axis] = "model"
    stored = jax.device_put(host, jax.sharding.NamedSharding(
        serving_mesh(TP), jax.sharding.PartitionSpec(*spec)))
    if one_head_a_shard:
        assert_one_kv_head_a_shard(logical, stored)
    else:
        with pytest.raises(AssertionError):
            assert_one_kv_head_a_shard(logical, stored)


def test_prefill_then_paged_decode_match_the_reference(served):
    m = served.cfg["model"]
    for r in served.reqs:
        assert len(r.output) == NEW and len(r.logits) == NEW
        ids = np.concatenate([r.tokens, np.asarray(r.output[:-1], np.int32)])
        n = len(r.tokens)
        got = np.stack(r.logits)  # first from prefill, then decode steps
        want = _reference_logits(m, ids)[n - 1:]
        err = np.abs(got - want)
        assert err.max() <= MAX_ERR and err.mean() <= MEAN_ERR, (
            n, err.max(), err.mean())
        # the limits part the program from a float8 computation
        ctl = np.abs(_reference_logits(m, ids, fp8=True)[n - 1:] - want)
        assert ctl.max() > MAX_ERR and ctl.mean() > MEAN_ERR


def test_tp_gather_bytes_counted_by_hand(served):
    """Each layer gathers, per token, the attention heads' outputs
    (H x Dh), the attention projection's output (d), the MLP's hidden
    (ff) and its output (d), in bf16; each of the 4 chips receives the 3
    shards it lacks.  A decode step computes every slot of the batch; a
    prefill chunk its bucket (16 or 32 tokens)."""
    m = served.cfg["model"]
    d, ff, L = m["hidden_size"], m["intermediate_size"], m[
        "num_hidden_layers"]
    per_token = L * (d + d + ff + d) * 2 * (TP - 1)  # H x Dh = d here
    steps = len({t for r in served.reqs for t in r.token_times[1:]})
    chunk_tokens = (32 + 16) + 32 + 16  # buckets of 40 = 32 + 8, 20, 9
    s = served.eng.metrics.snapshot()
    assert s["tp_gather_bytes_decode"] == steps * 4 * per_token
    assert s["tp_gather_bytes_prefill"] == chunk_tokens * per_token
    assert s["tp_gather_bytes"] == (s["tp_gather_bytes_decode"]
                                    + s["tp_gather_bytes_prefill"])


def test_tick_span_names_the_mesh_width(served):
    ticks = [e for e in served.trace["traceEvents"]
             if e.get("name") == "engine.tick"]
    assert ticks and all(e["args"]["tp"] == TP for e in ticks)


# ------------------------------------------------------ the gbps reader

MS = 1e6  # ns


def _trace() -> tr.Trace:
    """Two chips, two decode-step programs each in a 100 ms window; chip 1
    spends 4 ms a step in all-gathers, chip 0 3 ms."""
    ops, modules = {}, {}
    for dev, ag in (("/device:TPU:0", 3.0), ("/device:TPU:1", 4.0)):
        ops[dev], modules[dev] = [], []
        for t in (0.0, 50.0):
            modules[dev].append(("jit_step_fn(7)", t * MS, 20 * MS))
            ops[dev] += [("paged_decode_tpu.1", t * MS, 10 * MS),
                         ("all-gather.2", (t + 10) * MS, ag * MS)]
        # a prefill chunk's gathers are not the decode step's
        modules[dev].append(("jit_prefill_chunk_paged(3)", 80 * MS, 10 * MS))
        ops[dev].append(("all-gather.9", 81 * MS, 5 * MS))
    return tr.Trace(ops=ops, modules=modules,
                    host=[(tr.WINDOW, 0.0, 100 * MS)], t0=0.0, t1=100 * MS)


def _ctx(**kw) -> SimpleNamespace:
    """Three decode steps since the window opened at t0 = 10 s (stamps 11,
    12 and 13 s): first tokens and tokens before t0 are no decode step of
    the window."""
    recs = [SimpleNamespace(req=SimpleNamespace(token_times=tt)) for tt in
            ([9.0, 9.5, 11.0, 12.0, 13.0], [10.5, 12.0, 13.0], [11.5])]
    base = dict(trace=_trace(), recs=recs, t0=10.0, chips=2,
                counters={"tp_gather_bytes_decode": 3 * 64_000_000})
    base.update(kw)
    return SimpleNamespace(**base)


def test_gather_gbps_reader_on_a_synthetic_trace():
    read = load_module("metrics", "tp.gather_gbps").read
    # 64 MB a step over 2 chips in 4 ms (the busiest chip): 8 GB/s
    assert read(_ctx()) == pytest.approx(32e6 / 4e-3 * 1e-9)
    # a program without the counter (or at TP=1), an untraced run: nothing
    assert read(_ctx(counters={})) is None
    assert read(_ctx(counters={"tp_gather_bytes_decode": 0})) is None
    assert read(_ctx(trace=None)) is None
