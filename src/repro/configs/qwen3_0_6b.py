"""qwen3-0.6b [dense]: qk_norm, GQA.

28L d_model=1024 16H (GQA kv=8, head_dim=128) d_ff=3072 vocab=151936
[hf:Qwen/Qwen3-0.6B config.json].  Upstream ties the LM head to the
embedding (`tie_word_embeddings`); this repo keeps two tables of the same
shape, so the parameter count carries one extra vocab x d_model matrix.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-0.6b", family="dense",
    n_layers=28, d_model=1024, n_heads=16, n_kv_heads=8, d_head=128,
    d_ff=3072, vocab=151936, qk_norm=True,
    rope_theta=1e6,
)

SMOKE = ModelConfig(
    name="qwen3-smoke", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=128, vocab=256, qk_norm=True, dtype="float32",
)
