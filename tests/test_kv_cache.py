"""Paged KV-cache subsystem: pool invariants, prefix trie, CoW/LRU,
paged-decode kernel parity, and end-to-end paged-vs-dense engine equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops, ref
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import (BlockPool, BlockTable, NULL_PAGE,
                                    OutOfPagesError)

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------- block pool


def test_pool_alloc_free_refcount():
    pool = BlockPool(num_pages=6, block_size=4)
    assert pool.num_free() == 5  # page 0 reserved as null page
    pages = [pool.alloc() for _ in range(5)]
    assert NULL_PAGE not in pages
    assert len(set(pages)) == 5 and pool.num_free() == 0
    with pytest.raises(OutOfPagesError):
        pool.alloc()
    pool.release(pages[2])
    assert pool.num_free() == 1
    p = pool.alloc()
    assert p == pages[2]  # recycled
    pool.release(p)
    with pytest.raises(ValueError):
        pool.release(p)  # double free


def test_pool_shared_refcounts():
    pool = BlockPool(num_pages=4, block_size=4)
    p = pool.alloc()
    pool.retain(p)
    assert pool.ref[p] == 2
    pool.release(p)
    assert pool.ref[p] == 1 and pool.num_free() == 2  # still held
    pool.release(p)
    assert pool.num_free() == 3


def test_block_table_capacity_and_free():
    pool = BlockPool(num_pages=8, block_size=4)
    table = BlockTable(pool)
    table.ensure_capacity(10)  # 3 pages of 4
    assert len(table.pages) == 3
    assert table.slot_of(9) == (table.pages[2], 1)
    used = pool.pages_in_use()
    table.free()
    assert pool.pages_in_use() == used - 3 and table.pages == []


# -------------------------------------------------------------- prefix trie


def test_prefix_lookup_hit_and_partial():
    pool = BlockPool(num_pages=12, block_size=4)
    toks = np.arange(10)  # 2 full blocks + partial
    pages = [pool.alloc() for _ in range(2)]
    pool.register_prefix(toks, pages)
    hit, n = pool.lookup_prefix(toks)
    assert hit == pages and n == 8
    for p in hit:
        assert pool.ref[p] == 2
    # diverging second block: only the first block hits
    other = toks.copy()
    other[5] += 1
    hit2, n2 = pool.lookup_prefix(other)
    assert hit2 == pages[:1] and n2 == 4
    # completely different prompt: miss
    hit3, n3 = pool.lookup_prefix(np.arange(100, 108))
    assert hit3 == [] and n3 == 0


def test_prefix_lru_eviction_drops_trie_entry():
    pool = BlockPool(num_pages=3, block_size=2)  # 2 usable pages
    toks = np.arange(4)
    pages = [pool.alloc() for _ in range(2)]
    pool.register_prefix(toks, pages)
    for p in pages:
        pool.release(p)  # ref 0 -> parked in LRU, still hittable
    hit, n = pool.lookup_prefix(toks)
    assert n == 4
    for p in hit:
        pool.release(p)
    # exhaust the pool: both cached pages must be evicted (LRU first)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == set(pages) and pool.evictions == 2
    hit, n = pool.lookup_prefix(toks)
    assert n == 0  # trie entries dropped with the pages


def test_peek_prefix_has_no_side_effects():
    """Admission-control peeks must not count hits or take references
    (queued requests re-check every tick while waiting for capacity)."""
    pool = BlockPool(num_pages=8, block_size=4)
    toks = np.arange(8)
    pages = [pool.alloc(), pool.alloc()]
    pool.register_prefix(toks, pages)
    for _ in range(5):
        assert pool.peek_prefix(toks) == pages
    assert pool.hits == 0 and pool.misses == 0
    assert all(pool.ref[p] == 1 for p in pages)
    assert pool.peek_prefix(np.arange(100, 104)) == []


def test_cow_on_shared_or_registered_page():
    pool = BlockPool(num_pages=6, block_size=4)
    p = pool.alloc()
    # sole unregistered owner: write in place
    same, copied = pool.ensure_writable(p)
    assert same == p and not copied
    # registered prefix page: must copy even with ref 1
    pool.register_prefix(np.arange(4), [p])
    new, copied = pool.ensure_writable(p)
    assert copied and new != p and pool.cow_copies == 1
    pool.release(p)  # caller releases the original after copying


# ------------------------------------------------------------ kernel parity


def _paged_case(rng, B, H, Hkv, D, bs, NB, lens, masked, dtype):
    """q, pools, block table and positions: slot b holds ``lens[b]``
    positions on distinct random pages (page 0, the null page, never
    used); a masked slot has a null table and ``pos`` 0, as the engine
    gives a free or mid-prefill slot."""
    P = 1 + B * NB
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, bs, Hkv, D)), dtype)
    lens = rng.integers(bs, NB * bs, B) if lens is None else np.asarray(lens)
    bt = np.full((B, NB), -1, np.int32)
    perm = rng.permutation(np.arange(1, P))
    used = 0
    for b, n in enumerate(lens):
        if b in masked:
            continue
        nb = -(-int(n) // bs)
        bt[b, :nb] = perm[used:used + nb]
        used += nb
    pos = np.where(np.isin(np.arange(B), masked), 0, lens - 1)
    return q, kp, vp, bt, jnp.asarray(pos, jnp.int32)


def _dead_pages(bt, pos, bs, window):
    """Every page that holds no position a slot can see: never allocated,
    the null page, or wholly before a slot's window."""
    live = set()
    for row, p in zip(bt, np.asarray(pos)):
        first = max(int(p) - window + 1, 0) // bs if window else 0
        live.update(int(x) for x in row[first:int(p) // bs + 1] if x >= 0)
    return np.asarray(sorted(set(range(bt.size + 1)) - live))


# (B, H, Hkv, D, bs, window, NB, lens, masked); lens None draws them
PAGED_CASES = [
    pytest.param(2, 8, 2, 64, 16, 0, 5, None, (), id="2-8-2-64-16-0"),  # GQA
    pytest.param(2, 4, 4, 32, 8, 24, 5, None, (),
                 id="2-4-4-32-8-24"),  # MHA + sliding window
    pytest.param(1, 8, 1, 64, 32, 0, 5, None, (), id="1-8-1-64-32-0"),  # MQA
    # ragged lengths in one batch: one position, ends on a page boundary
    # (16, 32, 128 positions), and a masked row (null table, pos 0)
    pytest.param(6, 8, 2, 64, 16, 0, 12, (1, 16, 32, 17, 128, 0), (5,),
                 id="ragged-masked"),
    # a long table (NB 32) with 1-3 live pages
    pytest.param(3, 8, 2, 64, 16, 0, 32, (5, 20, 48), (),
                 id="long-table-short"),
    # window 40 at position 150: the first visible block is block 6
    pytest.param(2, 8, 2, 64, 16, 40, 12, (151, 100), (),
                 id="window-past-block0"),
    # 16-page compute blocks (D 64) over 20 and 13 live pages
    pytest.param(2, 8, 2, 64, 16, 0, 24, (320, 200), (), id="blocks-ragged"),
    # D 128: one position a copied row (D 64 packs two)
    pytest.param(2, 4, 2, 128, 16, 0, 6, (40, 90), (), id="d128"),
    # one TP=4 shard of codeqwen1.5-7b: 8 query heads over one KV head of
    # 128, ragged over page boundaries, a masked row
    pytest.param(3, 8, 1, 128, 16, 0, 12, (33, 160, 0), (2,),
                 id="tp4-shard-8-1-128"),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,bs,window,NB,lens,masked", PAGED_CASES)
def test_paged_decode_kernel_parity(B, H, Hkv, D, bs, window, NB, lens,
                                    masked, dtype):
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-4)
    q, kp, vp, bt, pos = _paged_case(RNG, B, H, Hkv, D, bs, NB, lens,
                                     masked, dtype)
    bt = jnp.asarray(bt)
    out = np.asarray(ops.paged_decode(q, kp, vp, bt, pos, window=window),
                     np.float32)
    want = np.asarray(ref.paged_decode_ref(q, kp, vp, bt, pos, window=window),
                      np.float32)
    seen = np.setdiff1d(np.arange(B), masked)
    np.testing.assert_allclose(out[seen], want[seen], **tol)
    assert (out[list(masked)] == 0).all()  # no live page: zeros


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 40])
def test_paged_decode_kernel_skips_dead_pages(window, dtype):
    """Pages no slot can see, the null page among them, are never read:
    filled with NaN they leave the output equal to the reference on the
    clean pool, and finite.  A walk over every block slot multiplies their
    V by p = 0, and 0 * NaN is NaN."""
    B, H, Hkv, D, bs, NB = 5, 8, 2, 64, 16, 12
    q, kp, vp, bt, pos = _paged_case(np.random.default_rng(11), B, H, Hkv,
                                     D, bs, NB, (1, 16, 151, 60, 0), (4,),
                                     dtype)
    dead = _dead_pages(bt, pos, bs, window)
    assert 0 in dead
    bt = jnp.asarray(bt)
    want = ref.paged_decode_ref(q, kp, vp, bt, pos, window=window)
    out = ops.paged_decode(q, kp.at[dead].set(jnp.nan),
                           vp.at[dead].set(jnp.nan), bt, pos, window=window)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(out[:4], np.asarray(want, np.float32)[:4],
                               atol=tol, rtol=tol)
    assert (out[4] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_kernel_masks_table_holes(dtype):
    """An unallocated entry inside a slot's live range is neither copied
    nor seen, as the reference masks it; its V rows cannot leak."""
    q, kp, vp, bt, pos = _paged_case(RNG, 2, 8, 2, 64, 16, 6, (60, 90), (),
                                     dtype)
    bt[0, 1] = bt[1, 0] = -1
    bt = jnp.asarray(bt)
    out = ops.paged_decode(q, kp, vp.at[0].set(jnp.nan), bt, pos)
    want = ref.paged_decode_ref(q, kp, vp, bt, pos)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_paged_decode_kernel_rejects_head_dim_off_lanes():
    """A page is copied in whole 128-lane rows: a head dim that neither
    divides 128 nor is a multiple of it is refused, not mis-read."""
    q, kp, vp, bt, pos = _paged_case(RNG, 1, 2, 1, 80, 16, 2, (20,), (),
                                     jnp.float32)
    with pytest.raises(ValueError, match="head dim 80"):
        ops.paged_decode(q, kp, vp, jnp.asarray(bt), pos)


def test_paged_matches_dense_flash_decode():
    """Gathering pages into a dense cache reproduces flash_decode exactly."""
    B, H, Hkv, D, bs, NB = 2, 4, 2, 32, 8, 4
    P = 1 + B * NB
    q = jnp.asarray(RNG.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(P, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(P, bs, Hkv, D)), jnp.float32)
    bt = np.arange(1, 1 + B * NB, dtype=np.int32).reshape(B, NB)
    lens = np.array([NB * bs, NB * bs - 3])
    pos = jnp.asarray(lens - 1, jnp.int32)
    kc = np.asarray(kp)[bt].reshape(B, NB * bs, Hkv, D)
    vc = np.asarray(vp)[bt].reshape(B, NB * bs, Hkv, D)
    cpos = np.broadcast_to(np.arange(NB * bs), (B, NB * bs)).astype(np.int32)
    paged = ops.paged_decode(q, kp, vp, jnp.asarray(bt), pos)
    dense = ref.flash_decode_ref(q, jnp.asarray(kc), jnp.asarray(vc),
                                 jnp.asarray(cpos), pos)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=2e-4, rtol=2e-4)


# ------------------------------------------------------------ engine parity


def _mk(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma3-1b"])
def test_engine_paged_matches_dense(arch):
    """Token-identical outputs on a mixed prompt-length stream, both for
    full attention (qwen2) and local:global windows (gemma3)."""
    cfg, model, params = _mk(arch)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (6, 21, 33, 9, 16)]
    outs = {}
    for paged in (False, True):
        eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                            paged=paged, page_size=8)
        reqs = [Request(i, p, max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        outs[paged] = {r.uid: tuple(r.output) for r in reqs}
    assert outs[True] == outs[False]


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma3-1b"])
def test_engine_prefix_cache_savings(arch):
    """Shared-prefix workload: later requests skip prefix recomputation and
    still produce the exact dense-engine outputs.  gemma3 exercises the
    sliding-window local:global layers across the prefix/suffix boundary
    of the suffix-only prefill."""
    cfg, model, params = _mk(arch)
    rng = np.random.default_rng(4)
    shared = rng.integers(0, cfg.vocab, 24).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab, 4).astype(np.int32)])
               for _ in range(4)]
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        paged=True, page_size=8)
    reqs = [Request(i, p, max_new_tokens=4) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    total_prompt = sum(len(p) for p in prompts)
    assert eng.prefix_tokens_reused >= 3 * 24  # requests 2-4 reuse 3 blocks
    assert eng.prefill_tokens_computed < total_prompt
    assert eng.pool.hits > 0
    dense = ServingEngine(model, params, max_batch=2, max_seq=64,
                          paged=False)
    dreqs = [Request(i, p, max_new_tokens=4) for i, p in enumerate(prompts)]
    for r in dreqs:
        dense.submit(r)
    dense.run_until_drained()
    assert [tuple(r.output) for r in reqs] == \
        [tuple(r.output) for r in dreqs]


def test_engine_cow_on_fully_cached_prompt():
    """An identical repeated prompt exercises the copy-on-write path (last
    prompt token recomputed into a shared page) and matches exactly."""
    cfg, model, params = _mk("qwen2-0.5b")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)  # 2 full blocks
    eng = ServingEngine(model, params, max_batch=1, max_seq=64,
                        paged=True, page_size=8)
    reqs = [Request(i, prompt.copy(), max_new_tokens=3) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert eng.pool.cow_copies >= 1
    assert tuple(reqs[0].output) == tuple(reqs[1].output)


def test_engine_pages_released_and_reused():
    cfg, model, params = _mk("qwen2-0.5b")
    rng = np.random.default_rng(6)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        paged=True, page_size=8, prefix_caching=False)
    for i in range(6):
        eng.submit(Request(i, rng.integers(0, cfg.vocab, 20).astype(np.int32),
                           max_new_tokens=3))
    eng.run_until_drained()
    assert eng.pool.pages_in_use() == 0  # everything returned to the pool
    assert all(t is None for t in eng.block_tables)


def test_engine_admission_counts_lru_hit_pages():
    """Regression: a prefix hit whose pages are parked in the LRU shrinks
    the allocatable supply when retained; admission must count that or a
    later decode-growth alloc of another active slot crashes mid-stream."""
    cfg, model, params = _mk("qwen2-0.5b")
    rng = np.random.default_rng(8)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        paged=True, page_size=8, num_pages=1 + 7)
    warm = rng.integers(0, cfg.vocab, 32).astype(np.int32)  # 4 full blocks
    eng.submit(Request(0, warm, max_new_tokens=1))
    eng.run_until_drained()  # prefix now parked in the LRU
    # A holds 1 page and will grow by 3; B's prefix hit retains 4 LRU pages
    eng.submit(Request(1, rng.integers(0, cfg.vocab, 8).astype(np.int32),
                       max_new_tokens=24))
    eng.submit(Request(2, np.concatenate(
        [warm, rng.integers(0, cfg.vocab, 6).astype(np.int32)]),
        max_new_tokens=12))
    done = eng.run_until_drained()  # crashed with OutOfPagesError before
    assert {r.uid for r in done} == {1, 2}


def test_engine_paged_rejects_non_attn_family():
    cfg = reduced(get_config("zamba2-2.7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ServingEngine(model, params, paged=True)
    eng = ServingEngine(model, params, max_batch=2, max_seq=64)
    assert not eng.paged  # auto-falls back to dense
