"""Pallas TPU flash-decode: one query token vs. a long KV cache.

The GPU trick here is split-KV with a warp-shuffle reduction; the TPU-native
equivalent processes KV blocks sequentially per (batch, kv-head) grid cell
with running (m, l, acc) in VMEM scratch, and processes all G = H/Hkv query
heads of a kv head together so the s = q k^T contraction has an MXU-friendly
row count.  Sharded-KV stat combination across chips is done by the caller
(one psum over partial (m, l, o) — see repro/serving).

``flash_decode_quant_tpu`` is the fused-dequant variant for int8 caches
(repro/kernels/quant.py): K/V stay int8 in HBM and the per-row fp32
scales ride as extra VMEM operands sliced by the same KV-block index map,
so dequantization happens in-registers after the DMA.  Flash-softmax
state and accumulation are fp32 either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, cpos_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, block_k, window, ks_ref=None, vs_ref=None):
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    if ks_ref is not None:  # int8 cache: in-register dequant, fp32 onward
        k = k * ks_ref[0, 0][:, None]  # [bk] scales over the head dim
        v = v * vs_ref[0, 0][:, None]
    cpos = cpos_ref[0]  # [bk]
    pos = pos_ref[0]  # scalar current position
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = (cpos >= 0) & (cpos <= pos)
    if window:
        valid &= (pos - cpos) < window
    s = jnp.where(valid[None, :], s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def flash_decode_tpu(q, k_cache, v_cache, cache_positions, pos, *,
                     window: int = 0, block_k: int = 512,
                     interpret: bool = False):
    """q [B,H,D]; caches [B,S,Hkv,D]; cache_positions [B,S]; pos [B]."""
    B, H, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = H // Hkv
    scale = D ** -0.5
    block_k = min(block_k, S)
    nk = -(-S // block_k)
    pk = nk * block_k - S
    if pk:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        cache_positions = jnp.pad(cache_positions, ((0, 0), (0, pk)),
                                  constant_values=-1)
    qg = q.reshape(B, Hkv, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)  # [B,Hkv,S',D]
    vt = v_cache.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k,
                          window=window),
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,)),  # pos
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, block_k), lambda b, h, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos, qg, kt, vt, cache_positions)
    return out.reshape(B, H, D)


def _quant_kernel(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, cpos_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale, block_k, window):
    """Positional-ref adapter: same body, int8 K/V + scale operands."""
    _kernel(pos_ref, q_ref, k_ref, v_ref, cpos_ref, o_ref, m_scr, l_scr,
            acc_scr, scale=scale, block_k=block_k, window=window,
            ks_ref=ks_ref, vs_ref=vs_ref)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def flash_decode_quant_tpu(q, k_cache, v_cache, k_scales, v_scales,
                           cache_positions, pos, *, window: int = 0,
                           block_k: int = 512, interpret: bool = False):
    """Fused-dequant flash decode over an int8 contiguous cache.

    q [B,H,D]; caches [B,S,Hkv,D] **int8**; k_scales/v_scales [B,S,Hkv]
    float32 per-row symmetric scales; cache_positions [B,S]; pos [B].
    """
    B, H, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = H // Hkv
    scale = D ** -0.5
    block_k = min(block_k, S)
    nk = -(-S // block_k)
    pk = nk * block_k - S
    k_scales = k_scales.astype(jnp.float32)
    v_scales = v_scales.astype(jnp.float32)
    if pk:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_scales = jnp.pad(k_scales, ((0, 0), (0, pk), (0, 0)))
        v_scales = jnp.pad(v_scales, ((0, 0), (0, pk), (0, 0)))
        cache_positions = jnp.pad(cache_positions, ((0, 0), (0, pk)),
                                  constant_values=-1)
    qg = q.reshape(B, Hkv, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)  # [B,Hkv,S',D] int8
    vt = v_cache.transpose(0, 2, 1, 3)
    kst = k_scales.transpose(0, 2, 1)  # [B,Hkv,S']
    vst = v_scales.transpose(0, 2, 1)

    out = pl.pallas_call(
        functools.partial(_quant_kernel, scale=scale, block_k=block_k,
                          window=window),
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,)),  # pos
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, j: (b, h, j)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, j: (b, h, j)),
            pl.BlockSpec((1, block_k), lambda b, h, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos, qg, kt, vt, kst, vst, cache_positions)
    return out.reshape(B, H, D)
