"""Operations and bytes the work needs, from the model's sizes and the
harness's own requests; never from the program's implementation.

``model`` is a configuration's ``model`` block (Hugging Face key names).
"""
from __future__ import annotations


def _dims(m: dict):
    d, H, Hkv = (m["hidden_size"], m["num_attention_heads"],
                 m["num_key_value_heads"])
    return d, H, Hkv, d // H


def layer_matmul_params(m: dict) -> int:
    """Multiply-accumulate weights one token meets in one decoder layer."""
    d, H, Hkv, Dh = _dims(m)
    return (d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d
            + 3 * d * m["intermediate_size"])


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def attention_flops(m: dict, context: int) -> int:
    """q k^T and p v of one token against ``context`` positions, all
    layers: 2 * 2 * context * head_dim per query head."""
    d, H, Hkv, Dh = _dims(m)
    return 4 * context * Dh * H * m["num_hidden_layers"]


def token_flops(m: dict, context: int, head: bool = True) -> int:
    """Model FLOPs of one token at ``context`` positions (itself
    included): 2 per matmul weight, the attention, and the output head
    where that token's logits are computed."""
    f = 2 * layer_matmul_params(m) * m["num_hidden_layers"]
    if head:
        f += 2 * head_params(m)
    return f + attention_flops(m, context)


def paged_decode_work(m: dict, live: "list[int]", *, tp: int = 1,
                      kv_bytes: int = 2, max_blocks: int = 0
                      ) -> "tuple[float, float]":
    """(FLOPs, bytes) of one layer's paged-decode attention on one chip,
    for active slots holding ``live`` cached positions each (the new token
    included): the K and V of those positions for this chip's kv heads,
    q and the output of its query heads, and each slot's block-table row.
    """
    d, H, Hkv, Dh = _dims(m)
    h, hkv = H // tp, max(1, Hkv // tp)
    n = sum(live)
    flops = 4.0 * n * h * Dh
    byts = (2.0 * n * hkv * Dh * kv_bytes  # K and V read
            + 2.0 * len(live) * h * Dh * 2  # q in, output out (bf16)
            + len(live) * (max_blocks + 1) * 4)  # block table row, pos
    return flops, byts


def least_time(flops: float, byts: float, peak: dict) -> "tuple[float, str]":
    """The least time the chip could take, and which bound sets it."""
    tc, tm = flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
