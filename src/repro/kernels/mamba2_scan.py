"""Pallas TPU Mamba2 SSD chunked scan.

The GPU reference is a fused Triton kernel with a sequential elementwise
recurrence; the TPU-native version processes chunks as MXU matmuls
(intra-chunk quadratic block + state outer products) with the carried state
[P, N] living in VMEM scratch across the sequential chunk grid dimension.

Grid: (batch, heads, chunks) — chunks "arbitrary" (sequential), state scratch
persists across them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # [Q, P] (already dt-discretized)
    a = a_ref[0, 0, 0].astype(jnp.float32)  # [Q] log-decay
    B = b_ref[0, 0].astype(jnp.float32)  # [Q, N]
    C = c_ref[0, 0].astype(jnp.float32)  # [Q, N]
    a_cum = jnp.cumsum(a)  # [Q]

    # intra-chunk: y_diag = (C B^T * L) x, L[t,s] = exp(acum_t - acum_s) tril
    seg = a_cum[:, None] - a_cum[None, :]
    tril = (jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
            >= jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1))
    L = jnp.where(tril, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    state = state_scr[...]  # [P, N]
    y += jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(a_cum)[:, None]
    # state update
    decay = jnp.exp(a_cum[-1] - a_cum)  # [Q]
    new_state = jax.lax.dot_general(x, B * decay[:, None],
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(a_cum[-1]) + new_state
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_tpu(x, dt, a_neg, B, C, *, chunk: int = 256,
                 interpret: bool = False):
    """Same contract as repro.models.mamba2.ssd_chunked (y only).

    x [b,S,h,p]; dt [b,S,h] (>0); a_neg [h]; B, C [b,S,n] -> y [b,S,h,p].
    """
    b, S, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, S)
    nc = S // chunk
    assert nc * chunk == S, (S, chunk)
    a = (dt * a_neg[None, None, :]).transpose(0, 2, 1)  # [b,h,S]
    xd = (x * dt[..., None]).transpose(0, 2, 1, 3)  # [b,h,S,p]
    a_c = a.reshape(b, h, nc, chunk)
    x_c = xd.reshape(b, h, nc, chunk, p)
    B_c = B.reshape(b, nc, chunk, n)
    C_c = C.reshape(b, nc, chunk, n)

    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_c, a_c, B_c, C_c)
    return y.reshape(b, h, S, p).transpose(0, 2, 1, 3)
