"""Pallas TPU paged flash-decode: one query token vs. a block-table KV cache.

Unlike ``flash_decode`` (contiguous [B, S] cache), K/V live in a shared page
pool ``[P, bs, Hkv, D]`` and each sequence addresses its pages through a
block table ``[B, NB]`` (-1 = unallocated).  The table and the per-sequence
positions ride in as *scalar prefetch* operands, so the BlockSpec index maps
can dereference ``table[b, j]`` and DMA exactly the page each grid cell
needs — the gathered [B, NB*bs] cache view of the XLA path never
materializes in HBM.

Grid is (B, Hkv, NB); like ``flash_decode`` the KV axis is sequential with
running (m, l, acc) flash-softmax state in VMEM scratch, and all G = H/Hkv
query heads of a kv head are processed together.  Unallocated blocks clamp
to page 0 (the engine's reserved null page) and are masked out, so their
DMA is wasted bandwidth but never wrong.

``paged_decode_quant_tpu`` is the fused-dequant variant for the int8 page
pool (``repro/kernels/quant.py``): K/V pages stay int8 in HBM — halving
the per-tick KV stream, which is what bounds decode — and the per-row
fp32 scales ride in as extra VMEM operands addressed by the *same*
block-table index map, so each grid cell dequantizes its page
in-registers right after the DMA.  The flash-softmax state and
accumulation are fp32 either way; only the K/V load path changes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, block_size, window, ks_ref=None, vs_ref=None):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bs, D]
    v = v_ref[0, 0].astype(jnp.float32)
    if ks_ref is not None:  # int8 page: in-register dequant, fp32 onward
        k = k * ks_ref[0, 0, 0][:, None]  # [bs] scales over the head dim
        v = v * vs_ref[0, 0, 0][:, None]
    pos = pos_ref[b]
    page = bt_ref[b, j]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # logical position of page entry t is j*bs + t (2D iota: TPU-safe)
    cpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    valid = (page >= 0) & (cpos <= pos)
    if window:
        valid &= (pos - cpos) < window
    s = jnp.where(valid, s, NEG_INF)  # [G, bs] via [1, bs] broadcast
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _quant_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale, block_size,
                  window):
    """Positional-ref adapter: same body, int8 K/V + scale operands."""
    _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, scale=scale, block_size=block_size, window=window,
            ks_ref=ks_ref, vs_ref=vs_ref)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_tpu(q, k_pages, v_pages, block_tables, pos, *,
                     window: int = 0, interpret: bool = False):
    """q [B,H,D]; k_pages/v_pages [P,bs,Hkv,D]; block_tables [B,NB] int32
    (-1 = unallocated); pos [B] int32 current positions."""
    B, H, D = q.shape
    P, bs, Hkv, _ = k_pages.shape
    NB = block_tables.shape[1]
    G = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, Hkv, G, D)
    kt = k_pages.transpose(2, 0, 1, 3)  # [Hkv, P, bs, D]
    vt = v_pages.transpose(2, 0, 1, 3)
    block_tables = block_tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def page_map(b, h, j, bt_ref, pos_ref):
        return (h, jnp.maximum(bt_ref[b, j], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, pos
        grid=(B, Hkv, NB),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), page_map),
            pl.BlockSpec((1, 1, bs, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_size=bs, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, pos, qg, kt, vt)
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_quant_tpu(q, k_pages, v_pages, k_scales, v_scales,
                           block_tables, pos, *, window: int = 0,
                           interpret: bool = False):
    """Fused-dequant paged decode over an int8 page pool.

    q [B,H,D]; k_pages/v_pages [P,bs,Hkv,D] **int8**; k_scales/v_scales
    [P,bs,Hkv] float32 per-row symmetric scales (repro/kernels/quant.py);
    block_tables [B,NB] int32 (-1 = unallocated); pos [B] int32.  Pages
    and scales are addressed by the same block-table index map, so each
    grid cell DMAs its int8 page + its [bs] scale rows and dequantizes
    in-registers; nothing bf16-sized ever leaves HBM.
    """
    B, H, D = q.shape
    P, bs, Hkv, _ = k_pages.shape
    NB = block_tables.shape[1]
    G = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, Hkv, G, D)
    kt = k_pages.transpose(2, 0, 1, 3)  # [Hkv, P, bs, D] int8
    vt = v_pages.transpose(2, 0, 1, 3)
    # [Hkv, P, 1, bs]: a page's scale row is then a (1, bs) tile, whose
    # last two dims equal the array's, as Mosaic requires of a block
    kst = k_scales.astype(jnp.float32).transpose(2, 0, 1)[:, :, None]
    vst = v_scales.astype(jnp.float32).transpose(2, 0, 1)[:, :, None]
    block_tables = block_tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def page_map(b, h, j, bt_ref, pos_ref):
        return (h, jnp.maximum(bt_ref[b, j], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, pos
        grid=(B, Hkv, NB),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), page_map),
            pl.BlockSpec((1, 1, bs, D), page_map),
            pl.BlockSpec((1, 1, 1, bs), page_map),
            pl.BlockSpec((1, 1, 1, bs), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_quant_kernel, scale=scale, block_size=bs,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, pos, qg, kt, vt, kst, vst)
    return out.reshape(B, H, D)
