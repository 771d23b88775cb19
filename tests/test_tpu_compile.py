"""Compile the served Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel body in Python and accepts block shapes and
layouts that the Mosaic compiler refuses, so the interpret-mode parity tests
cannot show that a kernel will build for the chip.  These tests compile each
kernel on the decode and verify paths ahead of time for one chip of a
described ``v5e:2x2`` topology (no chip needed) and check that the compiled
program contains the Mosaic kernel.

Two shape sets: qwen2-0.5b at its published widths (14 query / 2 KV heads of
64) and one TP=4 shard of codeqwen1.5-7b (8 query heads over its one KV head
of 128, the published 32 over 4 split four ways), both
with 16-token pages, a 2048-token block table and the 8-slot decode batch;
the decode kernels, whose walk follows each slot's live pages, also at an
8192-token table.

The topology is described inside a fixture and never at import: only one
process may load the TPU library at a time, and the test workers all import
this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_decode import paged_decode_quant_tpu, paged_decode_tpu
from repro.kernels.paged_verify import paged_verify_quant_tpu, paged_verify_tpu

B, PAGE, MAX_SEQ, SPEC_K = 8, 16, 2048, 3

# (H, Hkv, D) per shape set
SHAPES = {"qwen2-0.5b": (14, 2, 64), "codeqwen1.5-7b-tp4-shard": (8, 1, 128)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _args(sharding, shape_set, *, verify, quant, max_seq=MAX_SEQ):
    H, Hkv, D = SHAPES[shape_set]
    nb = max_seq // PAGE
    num_pages = 1 + B * nb

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    q = sds((B, SPEC_K + 1, H, D) if verify else (B, H, D), jnp.bfloat16)
    pages = sds((num_pages, PAGE, Hkv, D), jnp.int8 if quant else jnp.bfloat16)
    args = [q, pages, pages]
    if quant:
        scales = sds((num_pages, PAGE, Hkv), jnp.float32)
        args += [scales, scales]
    args += [sds((B, nb), jnp.int32), sds((B,), jnp.int32)]
    return args


KERNELS = {
    "paged_decode": (paged_decode_tpu, False, False),
    "paged_decode_quant": (paged_decode_quant_tpu, False, True),
    "paged_verify": (paged_verify_tpu, True, False),
    "paged_verify_quant": (paged_verify_quant_tpu, True, True),
}


@pytest.mark.parametrize("shape_set", list(SHAPES))
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_kernel_compiles_for_v5e(kernel, shape_set, one_chip,
                                 no_compile_cache):
    fn, verify, quant = KERNELS[kernel]
    args = _args(one_chip, shape_set, verify=verify, quant=quant)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape_set", list(SHAPES))
@pytest.mark.parametrize("kernel", ["paged_decode", "paged_decode_quant"])
def test_decode_kernel_compiles_for_v5e_long_table(kernel, shape_set,
                                                   one_chip,
                                                   no_compile_cache):
    fn, verify, quant = KERNELS[kernel]
    args = _args(one_chip, shape_set, verify=verify, quant=quant,
                 max_seq=8192)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
