"""Unified model zoo: one stack covering all 10 assigned architectures.

Families:
  * ``attn``         — dense / MoE / VLM decoder-only transformers
                       (qwen2, codeqwen, llama3, gemma3, chameleon, qwen2-moe,
                       granite-moe), homogeneous scan-over-layers with traced
                       per-layer flags for gemma3's 5:1 local:global pattern.
  * ``mamba_hybrid`` — zamba2: 9 groups of 6 Mamba2 layers, one *shared*
                       (weight-reused) attention+MLP block applied at the end
                       of each group on concat(x, x0).
  * ``xlstm``        — 6 groups of (7 mLSTM + 1 sLSTM) blocks.
  * ``encdec``       — whisper: full-attention encoder over precomputed frame
                       embeddings (frontend stub) + causal decoder with
                       cross-attention.

Every family exposes: spec / forward (train logits path) / prefill (build KV
or recurrent state cache, return last-token logits) / decode_step (one token).
All sequence-quadratic work goes through the chunked flash path, so nothing
ever materializes an [S, S] tensor — this is what lets 32k/500k shapes lower
with bounded per-device memory in the dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import moe as moe_lib
from repro.models import mamba2 as m2
from repro.models import xlstm as xl
from repro.models.attention import decode_attention, flash_attention
from repro.nn.layers import apply_rope, rope_frequencies
from repro.nn.spec import TensorSpec

Tree = Any


# ------------------------------------------------------------------ helpers


def embed_tokens(cfg: ArchConfig, params, tokens):
    """Token-table lookup in the activation dtype (+ gemma embed scale).

    The single place token ids become vectors — every prefill/decode entry
    point routes through here, so everything past it operates on
    embeddings and is modality-agnostic.
    """
    dt = jnp.dtype(cfg.act_dtype)
    x = params["embed"]["table"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    return x


def embed_inputs(cfg: ArchConfig, params, tokens, embeds=None,
                 embed_mask=None):
    """Entry-point embedding: token lookup + embedding-span injection.

    ``tokens`` [..., S] int; ``embeds`` [..., S, d] optionally carries
    precomputed embedding spans (image patches / audio frames — see
    repro/serving/segments.py) with ``embed_mask`` [..., S] True at
    injected positions.  Masked positions take the ``embeds`` row *as-is*
    (encoder outputs are already at model scale — no embed_scale);
    unmasked positions take the token lookup.  Token ids are clamped to 0
    first so the bookkeeping key ids of embedding positions (negative by
    construction) can ride the same array.
    """
    x = embed_tokens(cfg, params, jnp.maximum(tokens, 0))
    if embeds is not None:
        x = jnp.where(embed_mask[..., None], embeds.astype(x.dtype), x)
    return x


def _norm(p, x, kind: str, prefix: str):
    eps = 1e-6
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return (y * p[prefix + "_s"].astype(jnp.float32)
                + p[prefix + "_b"].astype(jnp.float32)).astype(x.dtype)
    var = jnp.mean(xf * xf, -1, keepdims=True)
    scale = p[prefix + "_s"].astype(jnp.float32)
    if kind == "rmsnorm_zero":
        scale = scale + 1.0
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _norm_spec(L, dim, kind, prefix):
    stack = (L,) if L else ()
    ax = ("layers",) if L else ()
    init = "zeros" if kind == "rmsnorm_zero" else "ones"
    out = {prefix + "_s": TensorSpec(stack + (dim,), ax + ("embed",), init)}
    if kind == "layernorm":
        out[prefix + "_b"] = TensorSpec(stack + (dim,), ax + ("embed",), "zeros")
    return out


def _head_rms(x, scale):
    """Per-head qk-norm. x [..., Dh], scale [Dh]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, -1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _act(name):
    if name == "silu_glu":
        return jax.nn.silu
    if name in ("gelu_glu", "gelu"):
        return functools.partial(jax.nn.gelu, approximate=True)
    raise ValueError(name)


def _col_gathered(x, w, cfg: ArchConfig, dt):
    """``x @ w`` where ``x``'s last dim and ``w``'s *output* columns are
    both TP-sharded (``w`` holds the full contraction dim but 1/tp of the
    output columns).

    Two all-gathers — pure data movement, no arithmetic — rebuild the
    replicated input and output around one exact local matmul: every
    output element is the full-contraction dot product computed on
    exactly one shard, so the result is **bitwise identical** to the
    unsharded matmul (XLA's dot gives bitwise column-sliceable results).
    Megatron-style row-parallel + psum would be cheaper on the wire but
    rounds split-K partial sums differently, breaking the engine's
    token-identical-under-sharding contract.
    """
    full = jax.lax.all_gather(x, cfg.tp_axis, axis=x.ndim - 1, tiled=True)
    y = full @ w.astype(dt)
    return jax.lax.all_gather(y, cfg.tp_axis, axis=y.ndim - 1, tiled=True)


def _attn_out(pl_attn, cfg: ArchConfig, o, dt):
    """Attention output projection ``o @ wo``.  TP-sharded heads hand in
    the local heads' outputs; wo holds all H*Dh rows but a 1/tp slice of
    the d_model output columns (see ``_col_gathered``)."""
    if cfg.tp_axis and "heads" in cfg.tp_shards:
        return _col_gathered(o, pl_attn["wo"], cfg, dt)
    return o @ pl_attn["wo"].astype(dt)


# ------------------------------------------------------------- spec builders


def attn_spec(cfg: ArchConfig, L: int, d: int, *, cross: bool = False,
              stack=None):
    """Attention weights (optionally stacked over L layers)."""
    stack = (L,) if L else ()
    ax = ("layers",) if L else ()
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    sc = d ** -0.5
    p = {
        "wq": TensorSpec(stack + (d, H * Dh), ax + ("embed", "heads"), "normal", sc),
        "wk": TensorSpec(stack + (d, Hkv * Dh), ax + ("embed", "kv_heads"), "normal", sc),
        "wv": TensorSpec(stack + (d, Hkv * Dh), ax + ("embed", "kv_heads"), "normal", sc),
        "wo": TensorSpec(stack + (H * Dh, cfg.d_model), ax + ("heads", "embed"),
                         "normal", (H * Dh) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = TensorSpec(stack + (H * Dh,), ax + ("heads",), "zeros")
        p["bk"] = TensorSpec(stack + (Hkv * Dh,), ax + ("kv_heads",), "zeros")
        p["bv"] = TensorSpec(stack + (Hkv * Dh,), ax + ("kv_heads",), "zeros")
    if cfg.qk_norm:
        p["qn"] = TensorSpec(stack + (Dh,), ax + (None,), "ones")
        p["kn"] = TensorSpec(stack + (Dh,), ax + (None,), "ones")
    return p


def mlp_spec(cfg: ArchConfig, L: int, d: int, ff: int):
    stack = (L,) if L else ()
    ax = ("layers",) if L else ()
    sc, sc2 = d ** -0.5, ff ** -0.5
    if cfg.act == "gelu":  # plain MLP with biases (whisper)
        return {
            "w1": TensorSpec(stack + (d, ff), ax + ("embed", "mlp"), "normal", sc),
            "b1": TensorSpec(stack + (ff,), ax + ("mlp",), "zeros"),
            "w2": TensorSpec(stack + (ff, d), ax + ("mlp", "embed"), "normal", sc2),
            "b2": TensorSpec(stack + (d,), ax + ("embed",), "zeros"),
        }
    return {
        "w_gate": TensorSpec(stack + (d, ff), ax + ("embed", "mlp"), "normal", sc),
        "w_up": TensorSpec(stack + (d, ff), ax + ("embed", "mlp"), "normal", sc),
        "w_down": TensorSpec(stack + (ff, d), ax + ("mlp", "embed"), "normal", sc2),
    }


def build_spec(cfg: ArchConfig) -> Tree:
    d, V, L = cfg.d_model, cfg.vocab, cfg.n_layers
    spec: dict = {"embed": {"table": TensorSpec((V, d), ("vocab", "embed"), "embed",
                                                scale=d ** -0.5)}}
    spec.update(_norm_spec(0, d, cfg.norm, "final"))
    if not cfg.tie_embeddings:
        spec["lm_head"] = TensorSpec((d, V), ("embed", "vocab"), "normal",
                                     scale=d ** -0.5)

    if cfg.block_kind == "attn" and not cfg.cross_attention:
        layer = {}
        layer.update(_norm_spec(L, d, cfg.norm, "ln1"))
        layer.update(_norm_spec(L, d, cfg.norm, "ln2"))
        if cfg.post_norms:
            layer.update(_norm_spec(L, d, cfg.norm, "pn1"))
            layer.update(_norm_spec(L, d, cfg.norm, "pn2"))
        layer["attn"] = attn_spec(cfg, L, d)
        if cfg.n_experts:
            layer["moe"] = moe_lib.moe_spec(L, d, cfg.n_experts, cfg.moe_ff,
                                            cfg.shared_ff)
        else:
            layer["mlp"] = mlp_spec(cfg, L, d, cfg.d_ff)
        spec["layers"] = layer

    elif cfg.block_kind == "mamba_hybrid":
        groups, per = L // cfg.shared_attn_every, cfg.shared_attn_every
        m = m2.mamba2_spec(L, d, cfg.d_inner, cfg.ssm_state, cfg.ssm_headdim,
                           cfg.conv_width)
        # reshape stacked L dim -> (groups, per) for the nested scan
        spec["mamba"] = jax.tree.map(
            lambda s: TensorSpec((groups, per) + s.shape[1:],
                                 ("layers", None) + s.axes[1:], s.init, s.scale),
            m, is_leaf=lambda x: isinstance(x, TensorSpec))
        shared_cfg = dataclasses.replace(cfg, qkv_bias=False, qk_norm=False)
        shared = {"attn": attn_spec(shared_cfg, 0, 2 * d)}  # input concat(x, x0)
        shared.update(_norm_spec(0, 2 * d, cfg.norm, "ln1"))
        shared.update(_norm_spec(0, cfg.d_model, cfg.norm, "ln2"))
        shared["mlp"] = mlp_spec(cfg, 0, d, cfg.d_ff)
        spec["shared_attn"] = shared

    elif cfg.block_kind == "xlstm":
        per = cfg.mlstm_per_slstm
        groups = L // (per + 1)
        spec["mlstm"] = xl.mlstm_spec((groups, per), d, int(cfg.proj_factor * d),
                                      cfg.n_heads, cfg.conv_width)
        spec["slstm"] = xl.slstm_spec((groups,), d, cfg.n_heads)

    elif cfg.cross_attention:  # whisper enc-dec
        Le = cfg.encoder_layers
        enc = {"attn": attn_spec(cfg, Le, d)}
        enc.update(_norm_spec(Le, d, cfg.norm, "ln1"))
        enc.update(_norm_spec(Le, d, cfg.norm, "ln2"))
        enc["mlp"] = mlp_spec(cfg, Le, d, cfg.d_ff)
        spec["encoder"] = enc
        spec.update(_norm_spec(0, d, cfg.norm, "enc_final"))
        dec = {"attn": attn_spec(cfg, L, d), "xattn": attn_spec(cfg, L, d)}
        dec.update(_norm_spec(L, d, cfg.norm, "ln1"))
        dec.update(_norm_spec(L, d, cfg.norm, "lnx"))
        dec.update(_norm_spec(L, d, cfg.norm, "ln2"))
        dec["mlp"] = mlp_spec(cfg, L, d, cfg.d_ff)
        spec["layers"] = dec
    else:
        raise ValueError(cfg.block_kind)
    return spec


# --------------------------------------------------------------- layer flags


def static_layer_windows(cfg: ArchConfig):
    """Per-layer python-static (is_global, window) list."""
    L = cfg.n_layers
    if cfg.attn_pattern == "local_global" and cfg.global_every:
        return [((i % cfg.global_every) == cfg.global_every - 1)
                for i in range(L)]
    return [True] * L


def _rope_tables(cfg: ArchConfig, max_len: int):
    """Returns (rope_local, rope_global); identical unless the arch uses a
    different theta for global layers (gemma3)."""
    cos_l, sin_l = rope_frequencies(cfg.hd, max_len, cfg.rope_theta)
    if cfg.rope_theta_global:
        cos_g, sin_g = rope_frequencies(cfg.hd, max_len, cfg.rope_theta_global)
    else:
        cos_g, sin_g = cos_l, sin_l
    return (cos_l, sin_l), (cos_g, sin_g)


# -------------------------------------------------------- attention sub-block


def _qkv(pl, cfg, xn, B, S):
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = xn.dtype
    q = xn @ pl["wq"].astype(dt)
    k = xn @ pl["wk"].astype(dt)
    v = xn @ pl["wv"].astype(dt)
    if "bq" in pl:
        q, k, v = q + pl["bq"].astype(dt), k + pl["bk"].astype(dt), v + pl["bv"].astype(dt)
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if "qn" in pl:
        q = _head_rms(q, pl["qn"])
        k = _head_rms(k, pl["kn"])
    return q, k, v


def _mlp(pl, cfg, xn):
    dt = xn.dtype
    act = _act(cfg.act)
    tp = bool(cfg.tp_axis) and "mlp" in cfg.tp_shards
    if "w1" in pl:  # plain
        h = act(xn @ pl["w1"].astype(dt) + pl["b1"].astype(dt))
        if tp:  # b2 is replicated, added once to the gathered output
            return _col_gathered(h, pl["w2"], cfg, dt) + pl["b2"].astype(dt)
        return h @ pl["w2"].astype(dt) + pl["b2"].astype(dt)
    h = act(xn @ pl["w_gate"].astype(dt)) * (xn @ pl["w_up"].astype(dt))
    if tp:
        return _col_gathered(h, pl["w_down"], cfg, dt)
    return h @ pl["w_down"].astype(dt)


def _ffn(pl, cfg, x):
    """MLP or MoE sub-block with residual, on [B,S,d]."""
    B, S, d = x.shape
    xn = _norm(pl, x, cfg.norm, "ln2")
    if cfg.n_experts:
        xt = xn.reshape(B * S, d)

        def one_chunk(t):
            return moe_lib.moe_apply(pl["moe"], t, top_k=cfg.top_k,
                                     norm_topk=cfg.norm_topk,
                                     capacity_factor=cfg.capacity_factor,
                                     act=_act(cfg.act),
                                     dispatch_axes=cfg.moe_dispatch_axes,
                                     tp_axis=cfg.tp_axis,
                                     tp_shards=cfg.tp_shards)

        nc = cfg.moe_scan_chunks
        if nc and (B * S) % nc == 0 and (B * S) // nc >= 4 * cfg.n_experts:
            # bound the [E, C, d] dispatch buffers: scan token chunks
            xc = xt.reshape(nc, (B * S) // nc, d)
            _, yc = jax.lax.scan(lambda _, t: (None, one_chunk(t)), None, xc)
            y = yc.reshape(B, S, d)
        else:
            y = one_chunk(xt).reshape(B, S, d)
    else:
        y = _mlp(pl["mlp"], cfg, xn)
    if cfg.post_norms:
        y = _norm(pl, y, cfg.norm, "pn2")
    return x + y


# ---------------------------------------------------------------- attn family


def _attn_layer_train(cfg, pl, x, rope, window, positions, pkv=None):
    """One layer; ``window`` is python-static (0 = full causal).

    ``pkv`` optionally carries this layer's already-rope'd prefix K/V
    ``[B, Spre, Hkv, Dh]`` — the suffix queries then attend to
    ``concat(prefix, suffix)`` with the causal diagonal shifted by Spre
    (``flash_attention``'s default ``q_offset = Sk - Sq``).  Only the
    suffix K/V is returned; the prefix is already cached by the caller.
    """
    cos, sin = rope
    B, S, _ = x.shape
    xn = _norm(pl, x, cfg.norm, "ln1")
    q, k, v = _qkv(pl["attn"], cfg, xn, B, S)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    ka, va = k, v
    if pkv is not None:
        ka = jnp.concatenate([pkv[0].astype(k.dtype), k], 1)
        va = jnp.concatenate([pkv[1].astype(v.dtype), v], 1)
    o = flash_attention(q, ka, va, causal=True, window=window)
    o = _attn_out(pl["attn"], cfg, o.reshape(B, S, -1), x.dtype)
    if cfg.post_norms:
        o = _norm(pl, o, cfg.norm, "pn1")
    x = x + o
    return _ffn(pl, cfg, x), (k, v)


def _regroup_layers(cfg: ArchConfig, tree):
    """Split a stacked [L, ...] layer tree into ([G, P, ...], [tail, ...])."""
    P_ = cfg.global_every
    L = cfg.n_layers
    G = L // P_
    n_full = G * P_
    grouped = jax.tree.map(
        lambda a: a[:n_full].reshape((G, P_) + a.shape[1:]), tree)
    tail = jax.tree.map(lambda a: a[n_full:], tree)
    return grouped, tail, G, P_, L - n_full


def attn_forward(cfg: ArchConfig, params, tokens, *, remat=True,
                 return_cache=False, prefix_kv=None, embeds=None,
                 embed_mask=None):
    """tokens [B,S] -> final hidden [B,S,d] (+ optional stacked KV cache).

    ``prefix_kv = (k, v)`` with shapes [L, B, Spre, Hkv, Dh] turns this
    into a *suffix* prefill: the S tokens sit at absolute positions
    [Spre, Spre+S) and attend to the cached prefix K/V without recomputing
    it (the paged serving engine's prefix-cache hit path).  The returned
    cache covers only the suffix.

    ``embeds``/``embed_mask`` optionally inject precomputed embedding
    spans (``embed_inputs``); everything below the embedding boundary is
    identical for token and embedding positions, so a text-only prompt
    produces bit-identical logits through either path.
    """
    B, S = tokens.shape
    x = embed_inputs(cfg, params, tokens, embeds, embed_mask)
    offset = 0 if prefix_kv is None else prefix_kv[0].shape[2]
    positions = offset + jnp.arange(S)
    rope_l, rope_g = _rope_tables(cfg, offset + S)

    if cfg.attn_pattern != "local_global":
        def body(x, xs):
            pl, pkv = (xs, None) if prefix_kv is None else (xs[0], xs[1:])
            y, kv = _attn_layer_train(cfg, pl, x, rope_g, 0, positions,
                                      pkv=pkv)
            return y, kv if return_cache else None

        f = jax.checkpoint(body) if remat else body
        xs = params["layers"] if prefix_kv is None else \
            (params["layers"],) + tuple(prefix_kv)
        x, kvs = jax.lax.scan(f, x, xs)
        x = _norm(params, x, cfg.norm, "final")
        return (x, kvs) if return_cache else x

    # local:global pattern (gemma3): scan over period-sized groups with
    # python-static windows, so fully-masked attention blocks are pruned
    grouped, tail, G, P_, n_tail = _regroup_layers(cfg, params["layers"])
    if prefix_kv is None:
        pk_g = pv_g = pk_t = pv_t = None
    else:
        (pk_g, pk_t), (pv_g, pv_t) = [
            (a[:G * P_].reshape((G, P_) + a.shape[1:]), a[G * P_:])
            for a in prefix_kv]

    def gbody(x, xs):
        pg = xs[0] if prefix_kv is not None else xs
        kvs = []
        for idx in range(P_):
            pl = jax.tree.map(lambda a: a[idx], pg)
            pkv = None if prefix_kv is None else (xs[1][idx], xs[2][idx])
            is_g = idx == P_ - 1
            x, kv = _attn_layer_train(cfg, pl, x, rope_g if is_g else rope_l,
                                      0 if is_g else cfg.window, positions,
                                      pkv=pkv)
            kvs.append(kv)
        if return_cache:
            return x, jax.tree.map(lambda *xs_: jnp.stack(xs_), *kvs)
        return x, None

    f = jax.checkpoint(gbody) if remat else gbody
    gxs = grouped if prefix_kv is None else (grouped, pk_g, pv_g)
    x, kv_groups = jax.lax.scan(f, x, gxs)
    tail_kvs = []
    for t in range(n_tail):
        pl = jax.tree.map(lambda a: a[t], tail)
        pkv = None if prefix_kv is None else (pk_t[t], pv_t[t])
        step = functools.partial(_attn_layer_train, cfg, pl, rope=rope_l,
                                 window=cfg.window, positions=positions,
                                 pkv=pkv)
        x, kv = (jax.checkpoint(lambda x_: step(x_))(x) if remat
                 else step(x))
        tail_kvs.append(kv)
    x = _norm(params, x, cfg.norm, "final")
    if not return_cache:
        return x
    k = jnp.concatenate(
        [kv_groups[0].reshape((G * P_,) + kv_groups[0].shape[2:])]
        + [kv[0][None] for kv in tail_kvs], 0)
    v = jnp.concatenate(
        [kv_groups[1].reshape((G * P_,) + kv_groups[1].shape[2:])]
        + [kv[1][None] for kv in tail_kvs], 0)
    return x, (k, v)


# --------------------------------------------------------------- zamba2 family


def _shared_attn_apply(cfg, ps, x, x0, ropes, positions, *, kv_cache=None,
                       pos_scalar=None):
    """Shared attention+MLP block on concat(x, x0). Returns (y, kv or None)."""
    B = x.shape[0]
    dt = x.dtype
    cat = jnp.concatenate([x, x0], -1)
    if cat.ndim == 2:  # decode: [B, 2d]
        cat = cat[:, None]
    S = cat.shape[1]
    xn = _norm(ps, cat, cfg.norm, "ln1")
    q, k, v = _qkv(ps["attn"], cfg, xn, B, S)
    (cos, sin), _ = ropes
    if kv_cache is None:
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        o = flash_attention(q, k, v, causal=True)
        kv = (k, v)
        o = o.reshape(B, S, -1) @ ps["attn"]["wo"].astype(dt)
    else:
        kc, vc, cpos = kv_cache
        q = apply_rope(q, cos, sin, pos_scalar[:, None])[:, 0]
        k = apply_rope(k, cos, sin, pos_scalar[:, None])[:, 0]
        slot = pos_scalar
        kc = kc.at[jnp.arange(B), slot].set(k.astype(kc.dtype))
        vc = vc.at[jnp.arange(B), slot].set(v[:, 0].astype(vc.dtype))
        o = decode_attention(q, kc, vc, cpos, pos_scalar,
                     repeat_kv=cfg.decode_repeat_kv)
        kv = (kc, vc)
        o = o.reshape(B, -1) @ ps["attn"]["wo"].astype(dt)
    y = x + o.reshape(x.shape)
    yn = _norm(ps, y, cfg.norm, "ln2")
    y = y + _mlp(ps["mlp"], cfg, yn).reshape(x.shape)
    return y, kv


def zamba2_forward(cfg: ArchConfig, params, tokens, *, remat=True,
                   return_cache=False):
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    x0 = x
    positions = jnp.arange(S)
    ropes = _rope_tables(cfg, S)

    def group(x, pm):
        def inner(xc, pl):
            y, st = m2.mamba2_forward(pl, xc, n_state=cfg.ssm_state,
                                      headdim=cfg.ssm_headdim,
                                      chunk=cfg.scan_chunk)
            return xc + y, st if return_cache else None

        fi = jax.checkpoint(inner) if remat else inner
        x, states = jax.lax.scan(fi, x, pm)
        y, kv = _shared_attn_apply(cfg, params["shared_attn"], x, x0, ropes,
                                   positions)
        return y, (states, kv) if return_cache else None

    x, caches = jax.lax.scan(group, x, params["mamba"])
    x = _norm(params, x, cfg.norm, "final")
    return (x, caches) if return_cache else x


# ---------------------------------------------------------------- xlstm family


def xlstm_forward(cfg: ArchConfig, params, tokens, *, remat=True,
                  return_cache=False):
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens)

    def group(x, pg):
        pm, psl = pg

        def inner(xc, pl):
            y, st = xl.mlstm_block(pl, xc, nh=cfg.n_heads,
                                   chunk=cfg.scan_chunk,
                                   gather_qkv=cfg.xlstm_gather_qkv)
            return y, st if return_cache else None

        fi = jax.checkpoint(inner) if remat else inner
        x, mstates = jax.lax.scan(fi, x, pm)
        x, sstate = xl.slstm_block(psl, x, nh=cfg.n_heads)
        return x, (mstates, sstate) if return_cache else None

    x, caches = jax.lax.scan(group, x, (params["mlstm"], params["slstm"]))
    x = _norm(params, x, cfg.norm, "final")
    return (x, caches) if return_cache else x


# --------------------------------------------------------------- whisper family


def whisper_encode(cfg: ArchConfig, params, frames, *, remat=True):
    """frames [B, Se, d] precomputed (conv frontend stub)."""
    B, Se, d = frames.shape
    x = frames.astype(jnp.dtype(cfg.act_dtype))
    pos = jnp.arange(Se)
    # sinusoidal positions
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half) / (half - 1) * jnp.log(10000.0))
    pe = jnp.concatenate([jnp.sin(pos[:, None] * freqs[None]),
                          jnp.cos(pos[:, None] * freqs[None])], -1)
    x = x + pe[None].astype(x.dtype)

    def body(x, pl):
        xn = _norm(pl, x, cfg.norm, "ln1")
        q, k, v = _qkv(pl["attn"], cfg, xn, B, Se)
        o = flash_attention(q, k, v, causal=False)
        x = x + o.reshape(B, Se, -1) @ pl["attn"]["wo"].astype(x.dtype)
        xn = _norm(pl, x, cfg.norm, "ln2")
        return x + _mlp(pl["mlp"], cfg, xn), None

    f = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(f, x, params["encoder"])
    return _norm(params, x, cfg.norm, "enc_final")


def whisper_decode_forward(cfg: ArchConfig, params, tokens, enc, *, remat=True,
                           return_cache=False):
    B, S = tokens.shape
    d = cfg.d_model
    x = embed_tokens(cfg, params, tokens)
    pos = jnp.arange(S)
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half) / (half - 1) * jnp.log(10000.0))
    pe = jnp.concatenate([jnp.sin(pos[:, None] * freqs[None]),
                          jnp.cos(pos[:, None] * freqs[None])], -1)
    x = x + pe[None].astype(x.dtype)
    Se = enc.shape[1]

    def body(x, pl):
        xn = _norm(pl, x, cfg.norm, "ln1")
        q, k, v = _qkv(pl["attn"], cfg, xn, B, S)
        o = flash_attention(q, k, v, causal=True)
        x = x + o.reshape(B, S, -1) @ pl["attn"]["wo"].astype(x.dtype)
        xn = _norm(pl, x, cfg.norm, "lnx")
        q2, _, _ = _qkv(pl["xattn"], cfg, xn, B, S)
        enc_n = enc
        k2 = (enc_n @ pl["xattn"]["wk"].astype(x.dtype)).reshape(B, Se, cfg.n_kv_heads, cfg.hd)
        v2 = (enc_n @ pl["xattn"]["wv"].astype(x.dtype)).reshape(B, Se, cfg.n_kv_heads, cfg.hd)
        if "bk" in pl["xattn"]:
            k2 = k2 + pl["xattn"]["bk"].astype(x.dtype).reshape(cfg.n_kv_heads, cfg.hd)
            v2 = v2 + pl["xattn"]["bv"].astype(x.dtype).reshape(cfg.n_kv_heads, cfg.hd)
        o2 = flash_attention(q2, k2, v2, causal=False)
        x = x + o2.reshape(B, S, -1) @ pl["xattn"]["wo"].astype(x.dtype)
        xn = _norm(pl, x, cfg.norm, "ln2")
        kv = (k, v, k2, v2) if return_cache else None
        return x + _mlp(pl["mlp"], cfg, xn), kv

    f = jax.checkpoint(body) if remat else body
    x, kvs = jax.lax.scan(f, x, params["layers"])
    x = _norm(params, x, cfg.norm, "final")
    return (x, kvs) if return_cache else x


# ------------------------------------------------------------------ losses


def chunked_xent(cfg: ArchConfig, params, hidden, labels, *, chunk=512):
    """Per-token mean cross-entropy without a full [B,S,V] logits tensor."""
    B, S, d = hidden.shape
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"])
    chunk = min(chunk, S)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hc = hidden.reshape(B, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    def step(acc, inp):
        h, y = inp
        logits = (h @ head.astype(h.dtype)).astype(jnp.float32)
        if cfg.logit_softcap:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        valid = (y >= 0).astype(jnp.float32)
        loss = ((lse - gold) * valid).sum()
        return (acc[0] + loss, acc[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def last_hidden(h, length=None):
    """Select the true last-token hidden state of a (possibly padded) batch.

    h [B, S, d]; ``length`` [B] int32 true sequence lengths when the batch
    is padded to a shape bucket (serving prefill), else None for ``h[:, -1]``.
    """
    if length is None:
        return h[:, -1]
    B = h.shape[0]
    return h[jnp.arange(B), length - 1]


def prompt_pos_map(length, S):
    """pos_map row for a bucket-padded prompt: position for the first
    ``length`` entries, -1 (= empty, masked at decode) for the padding."""
    B = length.shape[0]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return jnp.where(pos < length[:, None], pos, -1)


def last_logits(cfg: ArchConfig, params, hidden_last):
    """hidden_last [B, d] -> [B, V] fp32 logits."""
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = (hidden_last @ head.astype(hidden_last.dtype)).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def forward_hidden(cfg: ArchConfig, params, batch, *, remat=True):
    """Dispatch per family; returns final hidden states [B,S,d].
    ``batch["embeds"]``/``batch["embed_mask"]`` inject embedding spans on
    the attention family, as in ``attn_forward``."""
    if cfg.cross_attention:
        enc = whisper_encode(cfg, params, batch["encoder_frames"], remat=remat)
        return whisper_decode_forward(cfg, params, batch["tokens"], enc,
                                      remat=remat)
    if cfg.block_kind == "mamba_hybrid":
        return zamba2_forward(cfg, params, batch["tokens"], remat=remat)
    if cfg.block_kind == "xlstm":
        return xlstm_forward(cfg, params, batch["tokens"], remat=remat)
    return attn_forward(cfg, params, batch["tokens"], remat=remat,
                        embeds=batch.get("embeds"),
                        embed_mask=batch.get("embed_mask"))


def train_loss(cfg: ArchConfig, params, batch, *, remat=True):
    h = forward_hidden(cfg, params, batch, remat=remat)
    return chunked_xent(cfg, params, h, batch["labels"])
