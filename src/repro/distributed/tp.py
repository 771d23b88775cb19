"""Tensor-parallel sharded serving: the Model serving surface under shard_map.

``ShardedServing`` wraps the hot jitted entry points of ``models/api.Model``
(``prefill``, ``prefill_with_prefix``, ``serve_step_paged``,
``verify_step_paged``, ``prefill_chunk_paged``) in ``shard_map`` over a 1-D
``model`` mesh, so a serving engine can spread one replica's weights and
paged KV pool across ``tp`` devices.

Every collective is an **all-gather — pure data movement, zero
arithmetic — so sharded decode is bitwise identical to single-device
decode** at any width.  Megatron-style row-parallel projections (split-K
fp32 partials + psum) would halve the wire traffic, but their partial
sums round in a different order than XLA's fused matmul and flip greedy
argmax on near-ties; instead every second projection is sharded on its
*output* columns with the full contraction dim kept local
(``lm._col_gathered``):

  * attention: q/kv heads split over ``model`` (column-parallel qkv,
    exact local per-head attention); ``wo`` holds all H*Dh rows and 1/tp
    of the d_model output columns, gather-matmul-gather.  The paged
    pool's ``Hkv`` axis carries the head split, laid out by
    ``ShardingPlan.cache``, so the per-shard pool is just a narrower pool
    and every host-side page operation (CoW copies, scatters, snapshot
    export/import — all indexing the *unsharded* page axis 1) works
    untouched;
  * dense mlp: column-parallel gate/up, output-column-parallel down;
  * MoE: the router stays replicated (bit-identical top-k everywhere);
    expert parallelism slices the dispatch buffer per-rank and all-gathers
    expert outputs, falling back to sharding every expert's ff dim (and
    the down projection's output columns) when ``E % tp != 0`` (the
    ``make_plan`` expert-fallback rule);
  * embedding / lm_head: replicated (``vocab`` rule overridden to None),
    so last-token logits are identical on every shard and the greedy
    argmax needs no collective.

The *local* model inside each shard_map body is an ordinary ``Model`` whose
config holds the per-shard dimensions (``n_heads / tp`` etc.) plus
``tp_axis``/``tp_shards`` telling the forward pass where to gather — no
model-code fork, just ``dataclasses.replace``.

When the kv heads do not divide ``tp``, attention (and its pool) stays
replicated while the mlp/expert dims still shard — decode stays correct,
only the attention memory win is lost (the dense-cache KV-sequence
fallback of ``ShardingPlan.cache`` has no paged-compute analogue; see
README "Tensor-parallel serving").

Snapshots gather to host numpy (``export_paged_kv``) and re-shard on
adoption via the destination pool's own layout, which is what makes
cross-mesh migration (TP=4 cloud -> TP=1 edge) bit-identical for free.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.extend.core as jex
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import (ShardingPlan, _leaf_pspec, make_plan)
from repro.models.api import Model
from repro.nn.spec import tree_map_specs

Tree = Any


def gathered_bytes(jaxpr: jex.Jaxpr, n_devices: int) -> int:
    """Bytes the ``all_gather``s of a traced program move, summed over its
    ``n_devices`` chips.  A gather whose result is F bytes on each of the n
    chips of its group hands each chip the n - 1 shards it lacks, so it
    moves (n - 1) x F per group.  A gather in a ``scan`` counts once per
    iteration, in a ``cond`` the costliest branch counts, and in a
    ``while`` body once (its trip count is not known from shapes)."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "all_gather":
            n = int(eqn.params["axis_size"])
            total += (n - 1) * (n_devices // n) * sum(
                v.aval.size * v.aval.dtype.itemsize for v in eqn.outvars)
            continue
        subs = [gathered_bytes(j, n_devices) for j in _sub_jaxprs(eqn.params)]
        if name == "cond":
            total += max(subs, default=0)
        else:
            total += sum(subs) * (eqn.params["length"] if name == "scan"
                                  else 1)
    return total


def _sub_jaxprs(params: dict):
    for v in params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(j, jex.ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, jex.Jaxpr):
                yield j


def serving_mesh(tp: int, devices=None) -> Mesh:
    """1-D ``model`` mesh of ``tp`` devices (plus a size-1 ``data`` axis so
    the ``make_plan`` batch rules stay well-formed).  On CPU hosts, spawn
    the devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before importing jax."""
    devices = list(jax.devices() if devices is None else devices)
    if tp < 1 or tp > len(devices):
        raise ValueError(f"tp={tp} needs {tp} devices, have {len(devices)} "
                         "(set --xla_force_host_platform_device_count)")
    return Mesh(np.asarray(devices[:tp]).reshape(tp, 1), ("model", "data"))


@dataclasses.dataclass(frozen=True)
class ShardedServing:
    """Sharded view of one ``Model``'s serving surface over ``mesh``.

    Construction is cheap (layout decisions only); the shard_map wrappers
    trace lazily under the engine's ``jax.jit`` exactly like the unsharded
    methods they shadow.
    """
    model: Model
    mesh: Mesh

    @property
    def cfg(self) -> ArchConfig:
        return self.model.cfg

    @functools.cached_property
    def tp(self) -> int:
        return int(self.mesh.shape["model"])

    # ------------------------------------------------------------- layout
    @functools.cached_property
    def tp_shards(self) -> "tuple[str, ...]":
        """Which components actually shard at this width — every entry is
        gated on divisibility, mirroring ``make_plan``'s never-pad rule."""
        cfg, tp = self.cfg, self.tp
        shards: "list[str]" = []
        if tp == 1:
            # nothing to split: run the plain model inside shard_map (no
            # collectives at all) so a TP=1 mesh is trivially
            # bit-identical to the unsharded engine
            return ()
        # output-column modes also split d_model (wo / down projections
        # hold 1/tp of their d_model output columns)
        d_ok = cfg.d_model % tp == 0
        if d_ok and cfg.n_heads % tp == 0 and cfg.n_kv_heads % tp == 0:
            shards += ["heads", "kv_heads"]
        if cfg.n_experts:
            if cfg.n_experts % tp == 0:
                shards.append("experts")
            elif d_ok and cfg.moe_ff % tp == 0 and (
                    not cfg.shared_ff or cfg.shared_ff % tp == 0):
                # the make_plan expert-ff fallback, serving-side
                shards.append("expert_ff")
                if cfg.shared_ff:
                    shards.append("shared_ff")
        elif d_ok and cfg.d_ff and cfg.d_ff % tp == 0:
            shards.append("mlp")
        return tuple(shards)

    @functools.cached_property
    def kv_sharded(self) -> bool:
        return "kv_heads" in self.tp_shards

    @functools.cached_property
    def plan(self) -> ShardingPlan:
        """Serving plan: the training rules with vocab/embed pinned
        replicated (identical logits on every shard -> argmax without a
        collective) and each component rule matching ``tp_shards``."""
        sh = self.tp_shards
        override = {
            "vocab": None,
            "embed": None,
            "heads": "model" if "heads" in sh else None,
            "kv_heads": "model" if "kv_heads" in sh else None,
            "experts": "model" if "experts" in sh else None,
            "mlp": "model" if ("mlp" in sh or "expert_ff" in sh) else None,
            "batch": ("data",),
        }
        return make_plan(self.cfg, self.mesh, rules_override=override)

    @functools.cached_property
    def local_model(self) -> Model:
        """The per-shard model: same arch, 1/tp of every sharded dim, and
        ``tp_axis``/``tp_shards`` marking where the forward pass reduces.
        ``head_dim`` is pinned explicitly — the local ``d_model /
        n_heads`` fallback would be wrong once heads shrink."""
        cfg, tp, sh = self.cfg, self.tp, self.tp_shards
        if not sh:  # tp == 1 (or nothing divisible): plain replicated model
            return self.model
        upd: dict = dict(tp_axis="model", tp_shards=sh, head_dim=cfg.hd)
        if "heads" in sh:
            upd.update(n_heads=cfg.n_heads // tp,
                       n_kv_heads=cfg.n_kv_heads // tp)
        if "mlp" in sh:
            upd["d_ff"] = cfg.d_ff // tp
        if "expert_ff" in sh:
            upd["moe_ff"] = cfg.moe_ff // tp
            if "shared_ff" in sh:
                upd["shared_ff"] = cfg.shared_ff // tp
        # "experts": n_experts stays global — moe_apply reads the local
        # expert count off the sharded w_gate leaf and the (replicated)
        # router still sees all E logits
        return Model(dataclasses.replace(cfg, **upd))

    # ------------------------------------------------------------- params
    @functools.cached_property
    def param_pspecs(self) -> Tree:
        """Per-leaf pspecs.  Projections that *close* a sharded dim (wo,
        mlp/expert down) are laid out output-column-parallel — full
        contraction rows, 1/tp of the trailing ``embed`` columns — so the
        local matmul after an input all-gather is exact (see
        ``lm._col_gathered``).  Everything else follows the plan rules
        (column-parallel openings, expert-sharded MoE leaves,
        replicated vocab/norms)."""
        rules, mesh, sh = self.plan.rules, self.mesh, self.tp_shards

        def leaf(_p, s):
            ax = s.axes
            if len(ax) >= 2 and ax[-1] == "embed" and (
                    (ax[-2] == "heads" and "heads" in sh)
                    or (ax[-2] == "mlp" and ("mlp" in sh or "expert_ff" in sh
                                             or "shared_ff" in sh))):
                return P(*([None] * (len(ax) - 1) + ["model"]))
            return _leaf_pspec(s, rules, mesh)

        return tree_map_specs(leaf, self.model.spec)

    @functools.cached_property
    def param_shardings(self) -> Tree:
        return jax.tree.map(lambda ps: NamedSharding(self.mesh, ps),
                            self.param_pspecs)

    def init_params(self, key) -> Tree:
        """``Model.init`` with every leaf created under its sharding, so
        each device only ever holds its own shard: a model whose weights
        exceed one device's memory can be initialised at all."""
        return jax.jit(self.model.init,
                       out_shardings=self.param_shardings)(key)

    def shard_params(self, params: Tree) -> Tree:
        return jax.tree.map(jax.device_put, params, self.param_shardings)

    # ------------------------------------------------------------- caches
    def cache_shardings(self, cache_tree: dict) -> dict:
        """NamedShardings for the paged pool leaves.  ``ShardingPlan.cache``
        lays the pool out when the kv heads shard; otherwise the pool is
        replicated (its in-page sequence fallback is a *storage* layout —
        the paged compute path cannot split offsets within a page)."""
        if not self.kv_sharded:
            return {k: NamedSharding(self.mesh, P()) for k in cache_tree}
        return self.plan.cache(self.cfg, cache_tree)

    def _cache_pspecs(self, cache_tree: dict) -> dict:
        return {k: s.spec
                for k, s in self.cache_shardings(cache_tree).items()}

    @functools.cached_property
    def _kv_pspec(self) -> P:
        """Dense fresh-KV leaves [L, B, S, Hkv, Dh] out of the prefill
        paths: sharded on the kv-head axis exactly like the pool, so the
        engine's host-side scatter lines the shards up for free."""
        if self.kv_sharded:
            return P(None, None, None, "model", None)
        return P()

    # ---------------------------------------------------------- wrappers
    @staticmethod
    def _rep(tree: Tree) -> Tree:
        return jax.tree.map(lambda _: P(), tree)

    def _smap(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def gather_bytes(self, jitted, *args) -> int:
        """Bytes the all-gathers of ``jitted``'s program move at these
        argument shapes, summed over chips (``gathered_bytes``).  Traced
        through ``jitted.trace``, whose trace the following call reuses, so
        the count adds no second trace of the program."""
        return gathered_bytes(jitted.trace(*args).jaxpr.jaxpr,
                              self.mesh.size)

    def prefill(self, params, batch):
        """Monolithic/bucketed prefill (``Model.prefill``), sharded."""
        local = self.local_model
        kv = self._kv_pspec
        f = self._smap(lambda p, b: local.prefill(p, b),
                       (self.param_pspecs, self._rep(batch)),
                       (P(), {"k": kv, "v": kv, "pos_map": P()}))
        return f(params, batch)

    def prefill_with_prefix(self, params, batch, prefix_k, prefix_v):
        local = self.local_model
        kv = self._kv_pspec
        f = self._smap(
            lambda p, b, pk, pv: local.prefill_with_prefix(p, b, pk, pv),
            (self.param_pspecs, self._rep(batch), kv, kv),
            (P(), (kv, kv)))
        return f(params, batch, prefix_k, prefix_v)

    def serve_step_paged(self, params, cache, batch):
        local = self.local_model
        cs = self._cache_pspecs(cache)
        f = self._smap(lambda p, c, b: local.serve_step_paged(p, c, b),
                       (self.param_pspecs, cs, self._rep(batch)),
                       (P(), cs))
        return f(params, cache, batch)

    def verify_step_paged(self, params, cache, batch):
        local = self.local_model
        cs = self._cache_pspecs(cache)
        f = self._smap(lambda p, c, b: local.verify_step_paged(p, c, b),
                       (self.param_pspecs, cs, self._rep(batch)),
                       (P(), cs))
        return f(params, cache, batch)

    def prefill_chunk_paged(self, params, cache, batch):
        local = self.local_model
        cs = self._cache_pspecs(cache)
        f = self._smap(lambda p, c, b: local.prefill_chunk_paged(p, c, b),
                       (self.param_pspecs, cs, self._rep(batch)),
                       (P(), cs))
        return f(params, cache, batch)
