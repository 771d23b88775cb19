"""Pallas TPU grouped matmul for MoE expert compute.

[E, C, K] x [E, K, N] -> [E, C, N]: one expert per grid row, tiled over the
(C, N) output with a sequential K reduction in fp32 VMEM scratch.  Tiles are
128-aligned for the MXU.  This is the contraction produced by the sort-based
dispatch in repro/models/moe.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, w_ref, o_ref, acc_scr):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_n", "block_k",
                                             "interpret"))
def grouped_matmul_tpu(x, w, *, block_c: int = 128, block_n: int = 128,
                       block_k: int = 512, interpret: bool = False):
    """x [E, C, K]; w [E, K, N] -> [E, C, N]."""
    E, C, K = x.shape
    _, _, N = w.shape
    bc, bn, bk = min(block_c, C), min(block_n, N), min(block_k, K)
    nc, nn, nk = -(-C // bc), -(-N // bn), -(-K // bk)
    if nc * bc - C:
        x = jnp.pad(x, ((0, 0), (0, nc * bc - C), (0, 0)))
    if nk * bk - K:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, nk * bk - K)))
        w = jnp.pad(w, ((0, 0), (0, nk * bk - K), (0, 0)))
    if nn * bn - N:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, nn * bn - N)))

    out = pl.pallas_call(
        _kernel,
        grid=(E, nc, nn, nk),
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, nc * bc, nn * bn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :C, :N]
