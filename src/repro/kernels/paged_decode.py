"""Pallas TPU paged flash-decode: one query token vs. a block-table KV cache.

Unlike ``flash_decode`` (contiguous [B, S] cache), K/V live in a shared page
pool ``[P, bs, Hkv, D]`` and each sequence addresses its pages through a
block table ``[B, NB]`` (-1 = unallocated).  The table and the per-sequence
positions ride in as *scalar prefetch* operands (SMEM); the pool, viewed as
``[Hkv, P, bs, D]``, stays in HBM and the kernel copies exactly the pages a
sequence holds, so the gathered [B, NB*bs] cache view of the XLA path never
materializes.

Grid is (B, Hkv): one grid step per sequence and kv head, all G = H/Hkv
query heads of the kv head processed together.  A step's cost follows the
sequence's live pages, not the table's length: it walks block slots
``first .. last`` only, where ``last = pos // bs`` (trailing unallocated
entries trimmed, so a masked row with a null table walks nothing) and, with
a ``window``, ``first`` is the block holding ``pos - window + 1``.  The walk
goes in compute blocks of 128 copied rows (16 pages at bs 16, D 64; 8 at D
128), so each parity's scores fill one 128-lane row: a
``fori_loop`` with that dynamic trip count, each block's pages copied
HBM->VMEM by ``make_async_copy`` into one half of a double buffer while the
previous block computes.  Unallocated entries (-1) and block slots past
``last`` are never copied: their V rows are zeroed in the buffer and their
scores masked.  The running (m, l, acc) flash-softmax state is fp32 in the
loop carry; a sequence with no live position returns zeros.

A copy must span whole 128-lane rows, so a page travels as ``[bs / pack,
pack * D]``: ``pack = 128 / D`` consecutive positions side by side in one
row where D < 128 (a reshape of the same bytes; D = 64 gives two), one
position a row where D is a multiple of 128.  The query comes
as ``pack`` copies, copy ``e`` in lanes ``e*D ..``, so ``q_e @ k_rows^T``
scores the positions of parity ``e``; all parities share one softmax state,
and the accumulator's ``pack`` lane groups are folded into the first at the
end.

``paged_decode_quant_tpu`` is the fused-dequant variant for the int8 page
pool (``repro/kernels/quant.py``): K/V pages stay int8 in HBM and the
per-row fp32 scales are copied beside them through the *same* page ids,
laid out like the pages (each position's scale repeated over its D lanes),
so each compute block dequantizes in-registers right after the copy.  The
flash-softmax state and accumulation are fp32 either way; only the K/V
load path changes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _pack(bs: int, D: int) -> int:
    """Positions side by side in one 128-lane copied row."""
    if D % LANES == 0:
        return 1
    if LANES % D or bs % (LANES // D):
        raise ValueError(f"head dim {D} with {bs}-token pages: a page must "
                         "fill whole 128-lane rows")
    return LANES // D


def _page_rows(x, pack: int):
    """[Hkv, P, bs, D] -> [Hkv, P, bs // pack, pack * D]: ``pack``
    consecutive positions side by side in each row."""
    Hkv, P, bs, D = x.shape
    return x.reshape(Hkv, P, bs // pack, pack * D)


def _kernel(bt_ref, pos_ref, q_ref, k_hbm, v_hbm, *refs, scale, block_size,
            pages_per_block, pack, head_dim, window, quant):
    if quant:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem = refs
    else:
        o_ref, k_buf, v_buf, sem = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    bs, ppb, D = block_size, pages_per_block, head_dim
    rp = bs // pack  # copied rows per page
    rows = ppb * rp
    W = k_buf.shape[-1]
    nb = bt_ref.shape[1]
    pos = pos_ref[b]
    first = jnp.maximum(pos - window + 1, 0) // bs if window else 0
    # trailing unallocated entries hold nothing: a masked row walks no block
    last = jax.lax.while_loop(
        lambda j: (j >= first) & (bt_ref[b, jnp.maximum(j, 0)] < 0),
        lambda j: j - 1, jnp.minimum(pos // bs, nb - 1))
    n_blocks = (last - first + ppb) // ppb

    def page(i, p):
        """(live, page id) of block slot ``p`` of compute block ``i``."""
        j = first + i * ppb + p
        pid = bt_ref[b, jnp.minimum(j, nb - 1)]
        return (j <= last) & (pid >= 0), jnp.maximum(pid, 0)

    def copies(pid, slot, p):
        pairs = [(k_hbm, k_buf), (v_hbm, v_buf)]
        if quant:
            pairs += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]
        return [pltpu.make_async_copy(src.at[h, pid], dst.at[slot, p],
                                      sem.at[slot]) for src, dst in pairs]

    def start(i, slot):
        def one(p, carry):
            live, pid = page(i, p)

            @pl.when(live)
            def _():
                for c in copies(pid, slot, p):
                    c.start()
            return carry

        jax.lax.fori_loop(0, ppb, one, 0)

    def wait(i, slot):
        """Wait for block ``i``'s copies and zero the V rows of its slots
        that were not copied (stale VMEM: p = 0 times NaN is NaN); return
        which of the block's rows, along lanes, hold a live page."""
        row_page = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) // rp

        def one(p, seen):
            live, pid = page(i, p)

            @pl.when(live)
            def _():
                for c in copies(pid, slot, p):
                    c.wait()

            @pl.when(jnp.logical_not(live))
            def _():
                v_buf[slot, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
                if quant:
                    vs_buf[slot, p] = jnp.zeros(vs_buf.shape[2:],
                                                vs_buf.dtype)
            return jnp.where((row_page == p) & live, 1, seen)

        seen = jax.lax.fori_loop(0, ppb, one,
                                 jnp.zeros((1, rows), jnp.int32))
        return seen > 0

    q = q_ref[0, 0].astype(jnp.float32)  # [pack, G, W]
    G = q.shape[1]
    lane_group = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // D

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start(i + 1, 1 - slot)

        live = wait(i, slot)
        k = k_buf[slot].astype(jnp.float32).reshape(rows, W)
        v = v_buf[slot].astype(jnp.float32).reshape(rows, W)
        if quant:  # int8 pages: in-register dequant, fp32 onward
            k = k * ks_buf[slot].reshape(rows, W)
            v = v * vs_buf[slot].reshape(rows, W)
        # row r holds positions base + r*pack + e, e the lane group
        base = (first + i * ppb) * bs
        r = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        s, valid = [], []
        for e in range(pack):
            cpos = base + r * pack + e
            ok = live & (cpos <= pos)
            if window:
                ok &= (pos - cpos) < window
            s_e = jax.lax.dot_general(q[e], k, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            s.append(jnp.where(ok, s_e * scale, NEG_INF))  # [G, rows]
            valid.append(ok)
        m_new = functools.reduce(
            jnp.maximum, [s_e.max(-1, keepdims=True) for s_e in s], m_prev)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr
        acc_new = acc_prev * corr
        for e in range(pack):
            p = jnp.where(valid[e], jnp.exp(s[e] - m_new), 0.0)
            l_new += p.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_new += jnp.where(lane_group == e, pv, 0.0)  # [G, W]
        return m_new, l_new, acc_new

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32), jnp.zeros((G, W), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    # fold lane group e onto group 0; the shifts {e*D} are closed under
    # negation mod W, so the sum does not depend on the rotate's direction
    out = acc
    for e in range(1, pack):
        out += pltpu.roll(acc, W - e * D, 1)
    o_ref[0, 0] = (out / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_decode_call(q, pools, block_tables, pos, *, bs, pack, window,
                       interpret):
    """One ``pallas_call`` over grid (B, Hkv).  ``pools`` are the [Hkv, P,
    bs / pack, W] operands left in HBM (K, V, and for int8 their scales),
    each copied page by page into a double buffer in VMEM."""
    B, H, D = q.shape
    Hkv, _, _, W = pools[0].shape
    G = H // Hkv
    qg = q.reshape(B, Hkv, G, D)
    # copy e of the query in lanes e*D .. (e+1)*D, zeros elsewhere
    qp = jnp.stack([jnp.pad(qg, ((0, 0),) * 3 + ((e * D, W - (e + 1) * D),))
                    for e in range(pack)], axis=2)  # [B, Hkv, pack, G, W]
    ppb = min(max(1, LANES * pack // bs), block_tables.shape[1])
    kernel = functools.partial(
        _kernel, scale=D ** -0.5, block_size=bs, pages_per_block=ppb,
        pack=pack, head_dim=D, window=window, quant=len(pools) == 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, pos
        grid=(B, Hkv),
        in_specs=[pl.BlockSpec((1, 1, pack, G, W),
                               lambda b, h, *_: (b, h, 0, 0, 0))]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools),
        out_specs=pl.BlockSpec((1, 1, G, W), lambda b, h, *_: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb) + x.shape[2:], x.dtype)
                        for x in pools] + [pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32), qp, *pools)
    return out[..., :D].reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_tpu(q, k_pages, v_pages, block_tables, pos, *,
                     window: int = 0, interpret: bool = False):
    """q [B,H,D]; k_pages/v_pages [P,bs,Hkv,D]; block_tables [B,NB] int32
    (-1 = unallocated); pos [B] int32 current positions."""
    bs, D = k_pages.shape[1], k_pages.shape[3]
    pack = _pack(bs, D)
    kt = k_pages.transpose(2, 0, 1, 3)  # [Hkv, P, bs, D]
    vt = v_pages.transpose(2, 0, 1, 3)
    pools = tuple(_page_rows(x, pack) for x in (kt, vt))
    return _paged_decode_call(q, pools, block_tables, pos, bs=bs, pack=pack,
                              window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_quant_tpu(q, k_pages, v_pages, k_scales, v_scales,
                           block_tables, pos, *, window: int = 0,
                           interpret: bool = False):
    """Fused-dequant paged decode over an int8 page pool.

    q [B,H,D]; k_pages/v_pages [P,bs,Hkv,D] **int8**; k_scales/v_scales
    [P,bs,Hkv] float32 per-row symmetric scales (repro/kernels/quant.py);
    block_tables [B,NB] int32 (-1 = unallocated); pos [B] int32.  Pages
    and scales are copied through the same page ids, so each compute
    block brings its int8 pages + their scales into VMEM and dequantizes
    in-registers; nothing bf16-sized ever leaves HBM.
    """
    bs, D = k_pages.shape[1], k_pages.shape[3]
    pack = _pack(bs, D)
    kt = k_pages.transpose(2, 0, 1, 3)  # [Hkv, P, bs, D] int8
    vt = v_pages.transpose(2, 0, 1, 3)
    # [Hkv, P, bs, D]: each position's scale over its head dim, so the
    # scales take the pages' row layout
    kst, vst = (jnp.broadcast_to(s.astype(jnp.float32).transpose(2, 0, 1)
                                 [..., None], kt.shape)
                for s in (k_scales, v_scales))
    pools = tuple(_page_rows(x, pack) for x in (kt, vt, kst, vst))
    return _paged_decode_call(q, pools, block_tables, pos, bs=bs, pack=pack,
                              window=window, interpret=interpret)
