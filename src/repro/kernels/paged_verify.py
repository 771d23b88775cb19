"""Pallas TPU paged multi-token verify: T query tokens vs. a block-table KV.

Speculative decoding scores a slot's k drafted tokens in *one* pass: the
engine first scatters the drafts' K/V into the paged pool (the same
write-then-attend shape as ``Model.prefill_chunk_paged``), then this
kernel attends every draft position over prefix + drafts with a causal
per-row mask.  Row ``t`` of the query block sits at logical position
``pos[b] + t`` and may see cache entries up to and including itself —
so the accept/reject decision downstream (models/api.verify_step_paged)
sees exactly the attention a sequential decode of the same tokens would.

Layout mirrors ``paged_decode``: K/V pages ``[P, bs, Hkv, D]``, block
tables ``[B, NB]`` (-1 = unallocated) and positions ride in as scalar
prefetch so the BlockSpec index maps DMA exactly the page each grid cell
needs.  The only new ingredient is the query block: all T tokens ×
G = H/Hkv query heads of one kv head are flattened to ``T*G`` rows, and
the causal offset of a row is recovered in-kernel as ``row // G`` — the
flash-softmax state simply grows from [G, ...] to [T*G, ...] scratch.

``paged_verify_quant_tpu`` is the fused-dequant int8 variant; like
``paged_decode_quant_tpu`` the per-row fp32 scales ride in as extra
operands addressed by the same block-table index map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, block_size, window, group_size,
            ks_ref=None, vs_ref=None):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [T*G, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bs, D]
    v = v_ref[0, 0].astype(jnp.float32)
    if ks_ref is not None:  # int8 page: in-register dequant, fp32 onward
        k = k * ks_ref[0, 0, 0][:, None]  # [bs] scales over the head dim
        v = v * vs_ref[0, 0, 0][:, None]
    pos = pos_ref[b]
    page = bt_ref[b, j]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # row r of the query block is draft token r // G at position
    # pos + r // G; page entry t is at logical position j*bs + t
    row_pos = pos + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], 1), 0) // group_size  # [T*G, 1]
    cpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    valid = (page >= 0) & (cpos <= row_pos)  # [T*G, bs] causal per row
    if window:
        valid &= (row_pos - cpos) < window
    s = jnp.where(valid, s, NEG_INF)
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
                  window, group_size):
    """Positional-ref adapter: same body, int8 K/V + scale operands."""
    _kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, scale=scale, block_size=block_size, window=window,
            group_size=group_size, ks_ref=ks_ref, vs_ref=vs_ref)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_tpu(q, k_pages, v_pages, block_tables, pos, *,
                     window: int = 0, interpret: bool = False):
    """q [B,T,H,D] draft-position queries; k_pages/v_pages [P,bs,Hkv,D];
    block_tables [B,NB] int32 (-1 = unallocated); pos [B] int32 — the
    logical position of each sequence's *first* query token (query t
    attends causally up to pos + t)."""
    B, T, H, D = q.shape
    P, bs, Hkv, _ = k_pages.shape
    NB = block_tables.shape[1]
    G = H // Hkv
    scale = D ** -0.5
    # [B,T,Hkv,G,D] -> [B,Hkv,T*G,D]: all T tokens of a kv head together
    qg = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, T * G, D)
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
            pl.BlockSpec((1, 1, T * G, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), page_map),
            pl.BlockSpec((1, 1, bs, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, T * G, D),
                               lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_size=bs,
                          window=window, group_size=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, T * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, pos, qg, kt, vt)
    return out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, T, H, D)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_quant_tpu(q, k_pages, v_pages, k_scales, v_scales,
                           block_tables, pos, *, window: int = 0,
                           interpret: bool = False):
    """Fused-dequant multi-token verify over an int8 page pool.

    q [B,T,H,D]; k_pages/v_pages [P,bs,Hkv,D] **int8**; k_scales/v_scales
    [P,bs,Hkv] float32 per-row symmetric scales (repro/kernels/quant.py);
    block_tables [B,NB] int32; pos [B] int32 first-query positions.
    """
    B, T, H, D = q.shape
    P, bs, Hkv, _ = k_pages.shape
    NB = block_tables.shape[1]
    G = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, T * G, D)
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
            pl.BlockSpec((1, 1, T * G, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), page_map),
            pl.BlockSpec((1, 1, bs, D), page_map),
            pl.BlockSpec((1, 1, 1, bs), page_map),
            pl.BlockSpec((1, 1, 1, bs), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, T * G, D),
                               lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_quant_kernel, scale=scale, block_size=bs,
                          window=window, group_size=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, T * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, pos, qg, kt, vt, kst, vst)
    return out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, T, H, D)
