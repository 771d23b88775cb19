"""Pallas TPU flash attention (causal / sliding-window / GQA).

Grid: (batch, heads, q_blocks, k_blocks) with the k dimension innermost and
"arbitrary" semantics — running (m, l, acc) live in VMEM scratch across k
steps and the output block is written on the last k step.  Block shapes are
128-aligned so the q @ k^T and p @ v contractions are MXU-shaped.

Fully-masked (q, k) block pairs are skipped with ``pl.when`` — the causal and
sliding-window structure is honored block-wise, like the pure-JAX lowering
path in repro/models/attention.py (which is also the numerical oracle, see
kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, seq_q: int,
            seq_k: int, causal: bool, window: int, q_offset: int):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = q_offset + iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = jk * block_k
    k_hi = k_lo + block_k - 1
    live_block = True
    if causal:
        live_block = k_lo <= q_hi
    if window:
        live_block = live_block & ((q_lo - k_hi) < window)

    @pl.when(live_block)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_tpu(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """q [B,Sq,H,D]; k,v [B,Sk,Hkv,D] -> [B,Sq,H,D]."""
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = H // Hkv
    scale = D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = -(-Sq // block_q)
    nk = -(-Sk // block_k)
    pq, pk = nq * block_q - Sq, nk * block_k - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qt = q.transpose(0, 2, 1, 3)  # [B,H,Sq',D]
    kt = k.transpose(0, 2, 1, 3)  # [B,Hkv,Sk',D]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=Sq, seq_k=Sk, causal=causal, window=window,
        q_offset=Sk - Sq if causal else 0)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
