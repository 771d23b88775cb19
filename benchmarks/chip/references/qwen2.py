"""Plain reference for the Qwen2 architecture (Qwen2, Qwen1.5, CodeQwen1.5).

Written from the published description (arXiv:2407.10671; the Hugging Face
``Qwen2ForCausalLM``), in float32 ``jax.numpy`` at ``highest`` matmul
precision, with no cache, no kernel and no batching of requests: each
sequence is one causal forward pass from position 0.

  x = embed(ids), with embedding-span rows put in place of the lookup
  per layer:  h = rms(x) * ln1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
              rotary (half split) on q and k; query head i reads kv head
              i // (heads / kv_heads); causal softmax(q k^T / sqrt(Dh)) v
              x += o Wo;  h = rms(x) * ln2;  x += (silu(h Wg) * h Wu) Wd
  logits = rms(x) * final  @  embed^T (tied) or lm_head

It runs layer by layer, regenerating each layer's weights from the seed
(``chipbench.weights``), so a model that does not fit a chip in float32 as a
whole still fits one layer at a time.  It imports nothing of the program.

``control=True`` runs a second stream beside it with every matrix rounded to
float8 (e4m3, one scale per output column): the nearest precision below the
bfloat16 that the configuration serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def param_shapes(m: dict) -> dict:
    """{path: shape} of the served tree, in the layout the weights are
    generated in ([L] leading on every per-layer leaf)."""
    d, L, V = m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]
    H, Hkv, ff = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["intermediate_size"])
    Dh = d // H
    out = {"embed/table": (V, d), "final_s": (d,),
           "layers/ln1_s": (L, d), "layers/ln2_s": (L, d),
           "layers/attn/wq": (L, d, H * Dh), "layers/attn/wk": (L, d, Hkv * Dh),
           "layers/attn/wv": (L, d, Hkv * Dh),
           "layers/attn/wo": (L, H * Dh, d),
           "layers/attn/bq": (L, H * Dh), "layers/attn/bk": (L, Hkv * Dh),
           "layers/attn/bv": (L, Hkv * Dh),
           "layers/mlp/w_gate": (L, d, ff), "layers/mlp/w_up": (L, d, ff),
           "layers/mlp/w_down": (L, ff, d)}
    if not m["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, h, Dh]: rotate the two halves (the 'rotate_half' layout)."""
    Dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    f = pos[:, None].astype(jnp.float32) * inv[None]
    c, s = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, w, *, H, Hkv, eps, theta):
    """One decoder layer over one sequence x [S, d]."""
    S, d = x.shape
    Dh = w["attn"]["wq"].shape[-1] // H
    mm = functools.partial(jnp.matmul, precision=HI)
    h = _rms(x, w["ln1_s"], eps)
    q = (mm(h, w["attn"]["wq"]) + w["attn"]["bq"]).reshape(S, H, Dh)
    k = (mm(h, w["attn"]["wk"]) + w["attn"]["bk"]).reshape(S, Hkv, Dh)
    v = (mm(h, w["attn"]["wv"]) + w["attn"]["bv"]).reshape(S, Hkv, Dh)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    G = H // Hkv
    qg = q.reshape(S, Hkv, G, Dh)
    s = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) * Dh ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI).reshape(S, H * Dh)
    x = x + mm(o, w["attn"]["wo"])
    h = _rms(x, w["ln2_s"], eps)
    g = jax.nn.silu(mm(h, w["mlp"]["w_gate"])) * mm(h, w["mlp"]["w_up"])
    return x + mm(g, w["mlp"]["w_down"])


def fp8_round(w: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one absmax scale per slice along the
    other axes (``axis``: the contraction axis), and widen again."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _fp8_tree(w: dict) -> dict:
    return jax.tree_util.tree_map_with_path(
        lambda p, a: fp8_round(a, -2) if a.ndim == 2 else a, w)


class Reference:
    """``gaps(seqs)``: for each sequence, at each served position, how far
    the served token's reference logit lies below the reference's best."""

    def __init__(self, model: dict, weights, *, max_seq: int, device=None):
        self.m = model
        self.w = weights  # chipbench.weights.Source over param_shapes()
        self.max_seq = max_seq
        self.device = device or jax.devices()[0]
        self.eps = float(model["rms_norm_eps"])
        kw = dict(H=model["num_attention_heads"],
                  Hkv=model["num_key_value_heads"], eps=self.eps,
                  theta=float(model["rope_theta"]))
        self._layers = jax.jit(
            lambda xs, w: jax.lax.map(lambda x: _layer(x, w, **kw), xs))
        self._fp8 = jax.jit(_fp8_tree)

    def _embed(self, table, seqs):
        N, S = len(seqs), self.max_seq
        ids = np.zeros((N, S), np.int32)
        for i, s in enumerate(seqs):
            ids[i, :len(s["ids"])] = np.maximum(s["ids"], 0)
        x = jnp.take(table, jnp.asarray(ids), axis=0)
        for i, s in enumerate(seqs):
            if s.get("rows") is not None:
                at = jnp.asarray(np.nonzero(s["mask"])[0])
                x = x.at[i, at].set(jnp.asarray(s["rows"], jnp.float32))
        return x

    def gaps(self, seqs, control: bool = False):
        """``seqs``: dicts with ``ids`` (prompt followed by the served
        tokens but the last), ``n_prompt``, ``served`` and optionally
        ``mask``/``rows`` (embedding-span positions and their rows).
        Returns per sequence an array of gaps (and, with ``control``, the
        gaps of the tokens the float8 stream puts first)."""
        with jax.default_device(self.device):
            top = self.w.top()
            table = top["embed"]["table"]
            x = self._embed(table, seqs)
            xc = x if not control else self._embed(
                fp8_round(table, -1), seqs)
            for layer in range(self.m["num_hidden_layers"]):
                w = self.w.layer(layer)
                x = self._layers(x, w)
                if control:
                    xc = self._layers(xc, self._fp8(w))
                del w
            head = (table.T if self.m["tie_word_embeddings"]
                    else top["lm_head"])
            out, outc = [], []
            for i, s in enumerate(seqs):
                n = len(s["served"])
                pad = max(16, 1 << (n - 1).bit_length())
                served = np.zeros(pad, np.int32)
                served[:n] = s["served"]
                g, gc = _gap(x[i], top["final_s"], head,
                             jnp.asarray(s["n_prompt"] - 1, jnp.int32),
                             jnp.asarray(served), self.eps,
                             xc[i] if control else None,
                             fp8_round(head, -2) if control else None)
                out.append(np.asarray(g)[:n])
                if control:
                    outc.append(np.asarray(gc)[:n])
        return (out, outc) if control else out


@jax.jit
def _gap(x, final_s, head, lo, served, eps, xc=None, headc=None):
    """Gaps at rows lo, lo+1, ... of one sequence's hidden states."""
    idx = jnp.minimum(lo + jnp.arange(served.shape[0]), x.shape[0] - 1)
    logits = jnp.matmul(_rms(x[idx], final_s, eps), head, precision=HI)
    best = logits.max(-1)
    g = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    if xc is None:
        return g, None
    pick = jnp.argmax(jnp.matmul(_rms(xc[idx], final_s, eps), headc,
                                 precision=HI), -1)
    gc = best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
    return g, gc
