"""Weights from the seed: the program's whole tree in one call, and the
reference's layer-by-layer regeneration, hold the same bits."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import _tiny
from chipbench import weights
from chipbench.cells import load_module
from chipbench.harness import arch_config
from repro.models import build_model


def test_layer_regeneration_matches_the_served_tree():
    cfg = _tiny.load("configs/qwen2-0.5b.json")
    cfg["model"].update(_tiny.TINY_MODEL)
    model = build_model(arch_config(cfg))
    seed = 2**40 + 3
    served = weights.make_params(model.abstract(), seed)
    shapes = load_module("references", "qwen2").param_shapes(cfg["model"])
    assert {p: tuple(a.shape) for p, a in
            weights.flatten(served).items()} == shapes
    src = weights.Source(shapes, seed)
    flat = weights.flatten(served)
    for layer in (0, cfg["model"]["num_hidden_layers"] - 1):
        got = weights.flatten(src.layer(layer))
        for path, arr in got.items():
            want = flat["layers/" + path][layer].astype(jnp.float32)
            np.testing.assert_array_equal(np.asarray(arr), np.asarray(want))
    for path, arr in weights.flatten(src.top()).items():
        np.testing.assert_array_equal(
            np.asarray(arr), np.asarray(flat[path].astype(jnp.float32)))
    assert all(a.dtype == jnp.bfloat16 for a in flat.values())
    # another seed, other weights
    other = weights.make_params(model.abstract(), seed + 1)
    assert not np.array_equal(np.asarray(other["embed"]["table"]),
                              np.asarray(served["embed"]["table"]))


def test_seed_key_takes_large_seeds():
    a = weights.seed_key(2**33 + 5)
    b = weights.seed_key(5)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))
