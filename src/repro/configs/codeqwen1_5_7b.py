"""codeqwen1.5-7b [dense] — qwen1.5 arch, GQA kv=4 (8 query heads a KV
head), QKV bias, untied head; 7,250,284,544 parameters.
[hf:Qwen/CodeQwen1.5-7B]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="codeqwen1.5-7b",
    family="dense",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=4,
    d_ff=13440,
    vocab=92416,
    qkv_bias=True,
    rope_theta=1e6,
    source="hf:Qwen/CodeQwen1.5-7B; hf",
)
