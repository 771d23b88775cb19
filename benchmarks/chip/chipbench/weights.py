"""Weights from the seed, made by the benchmark and not by the program.

Every leaf of the served parameter tree is drawn from its own key,
``fold_in(seed key, crc32(path))``; a leaf stacked over layers draws layer
``l`` from ``fold_in(leaf key, l)``.  So the program's whole tree is made in
one jitted call on the device, in bfloat16, under the shardings it is served
with; and the reference regenerates any single layer by itself, in the same
bits, without taking anything from the program.

The rule for a leaf depends only on its name and shape:

* ``table`` ([V, d]): normal, standard deviation d^-0.5;
* a norm scale (name ends in ``_s``): 1 + 0.1 * normal;
* any other vector (a bias): 0.2 * normal;
* a matrix [fan_in, fan_out]: normal, standard deviation fan_in^-0.5.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STACKED = "layers"  # top-level group whose leaves carry a leading [L] axis


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number up to 2**63: the low 32 bits seed
    the key, the high bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, path: str, shape: tuple) -> jax.Array:
    """One leaf (one layer's slice for stacked leaves) in float32."""
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return 1.0 + 0.1 * z if name.endswith("_s") else 0.2 * z
    if name == "table":
        return z * shape[-1] ** -0.5
    return z * shape[-2] ** -0.5


def flatten(tree, prefix: str = "") -> dict:
    """{path: leaf} of a nested dict, paths joined by '/'."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def _leaf(key, path: str, shape: tuple, dtype) -> jax.Array:
    lk = _leaf_key(key, path)
    if path.startswith(STACKED + "/"):
        keys = jax.vmap(lambda i: jax.random.fold_in(lk, i))(
            jnp.arange(shape[0]))
        return jax.vmap(lambda k: _draw(k, path, shape[1:]))(keys).astype(
            dtype)
    return _draw(lk, path, shape).astype(dtype)


def make_params(abstract, seed: int, shardings=None):
    """The served tree (``abstract``: the program's ShapeDtypeStruct tree)
    made in one jitted call on the device, each leaf in its served dtype,
    under ``shardings`` where given."""
    flat = flatten(abstract)

    def build(key):
        return unflatten({p: _leaf(key, p, tuple(s.shape), s.dtype)
                          for p, s in flat.items()})

    fn = jax.jit(build, out_shardings=shardings)
    return fn(seed_key(seed))


class Source:
    """The served weights regenerated one piece at a time, as served
    (rounded to ``dtype``) and widened to float32: the reference's view.
    ``shapes``: {path: shape} of the served tree."""

    def __init__(self, shapes: dict, seed: int, dtype=jnp.bfloat16):
        self.seed, self.dtype = seed, dtype
        self.stacked = {p: tuple(s[1:]) for p, s in shapes.items()
                        if p.startswith(STACKED + "/")}
        self.rest = {p: tuple(s) for p, s in shapes.items()
                     if not p.startswith(STACKED + "/")}
        self._layer = jax.jit(self._build_layer)
        self._top = jax.jit(self._build_top)

    def _served(self, key, p, shape):
        return _draw(key, p, shape).astype(self.dtype).astype(jnp.float32)

    def _build_layer(self, key, i):
        return unflatten({p[len(STACKED) + 1:]: self._served(
            jax.random.fold_in(_leaf_key(key, p), i), p, s)
            for p, s in self.stacked.items()})

    def _build_top(self, key):
        return unflatten({p: self._served(_leaf_key(key, p), p, s)
                          for p, s in self.rest.items()})

    def layer(self, i: int) -> dict:
        return self._layer(seed_key(self.seed), jnp.asarray(i, jnp.int32))

    def top(self) -> dict:
        return self._top(seed_key(self.seed))

