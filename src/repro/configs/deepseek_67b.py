"""DeepSeek-67B — dense Llama-arch decoder [arXiv:2401.02954; hf].

Every layer is the same GQA-attention + SwiGLU block, so the structural
period is 1 and ``depth_cut(CONFIG, n)`` keeps a whole model at any depth:
all widths below stay as published and only ``n_layers`` shrinks.  At 4
layers the bf16 weights (embedding and head included) come to ≈ 8.9 GB,
which one 16 GB TPU v5e chip holds with room for KV cache.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-67b",
    family="dense",
    n_layers=95,
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    d_ff=22016,
    vocab_size=102400,
    activation="swiglu",
    param_dtype="bfloat16",
    optimizer="adamw",
)
