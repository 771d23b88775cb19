"""A tiny copy of the chip benchmark's cells, for runs on the CPU.

``tiny_here(tmp)`` writes a ``BENCHMARK.json`` and cut-down configuration
and mix files into ``tmp``, beside links to the real references and metric
readers, so the harness runs end to end at a size a test can hold.  Only
the sizes are cut; every code path is the benchmark's own.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

OPEN, CLOSED, TP = "tiny.open", "tiny.closed", "tiny-tp.closed"

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
TINY_SERVING = dict(max_batch=4, max_seq=256, prefill_chunk=32,
                    prefill_budget=64)


def load(name: str) -> dict:
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


def _dump(obj, *path):
    os.makedirs(os.path.dirname(os.path.join(*path)), exist_ok=True)
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f, indent=1)


def tiny_here(tmp: str, *, rate_per_s: float = 4.0,
              model: "dict | None" = None) -> str:
    """Cut-down cells in ``tmp`` (a checkout root and the benchmark's
    directory at once), ``model`` overriding the tiny sizes; returns
    ``tmp``."""
    os.makedirs(tmp, exist_ok=True)
    for kind in ("references", "metrics"):
        link = os.path.join(tmp, kind)
        if not os.path.exists(link):
            os.symlink(os.path.join(BENCH, kind), link)
    cfg = load("configs/qwen2-0.5b.json")
    cfg["model"].update(TINY_MODEL, **(model or {}))
    cfg["serving"].update(TINY_SERVING)
    cfg["check"].update(sample_requests=4, min_tokens_compared=10)
    _dump(cfg, tmp, "configs", "tiny.json")
    tp = load("configs/codeqwen1.5-7b-tp4.json")
    tp["model"].update(TINY_MODEL, **(model or {}))
    tp["model"]["num_key_value_heads"] = 4
    tp["serving"].update(TINY_SERVING, chips=4, tp=4)
    tp["check"].update(sample_requests=4, min_tokens_compared=10)
    _dump(tp, tmp, "configs", "tiny-tp.json")
    mix = load("traffic/miobench-open.json")
    mix["media"] = [{"kind": "image", "rows": 12, "share": 0.6},
                    {"kind": "audio", "rows": 40, "share": 0.2}]
    mix["prefix_tokens"] = 16
    mix["arrivals"]["rate_per_s"] = rate_per_s
    mix["text_tokens"].update(median=10, max=24)
    mix["output_tokens"].update(median=6, min=2, max=16)
    _dump(mix, tmp, "traffic", "open.json")
    closed = load("traffic/decode-closed.json")
    closed["text_tokens"].update(median=40, min=8, max=100)
    closed["output_tokens"].update(min=8, max=24)
    _dump(closed, tmp, "traffic", "closed.json")
    bench = load(os.path.join("..", "..", "BENCHMARK.json"))
    # the tiny TP cell reports what the closed-loop cell reports
    rename = {"qwen2-0.5b.miobench-open": [OPEN],
              "qwen2-0.5b.decode-closed": [CLOSED, TP],
              "codeqwen1.5-7b-tp4.decode-closed": []}
    bench["workloads"] = [
        {"name": OPEN, "config": "tiny", "traffic": "open", "chips": 1,
         "why": "tiny"},
        {"name": CLOSED, "config": "tiny", "traffic": "closed", "chips": 1,
         "why": "tiny"},
        {"name": TP, "config": "tiny-tp", "traffic": "closed", "chips": 4,
         "why": "tiny"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in rename[w]})
    _dump(bench, tmp, "BENCHMARK.json")
    return tmp
