"""qwen3-4b — dense GQA with qk-norm, head_dim 128 [hf:Qwen/Qwen3-4B; hf]."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-4b",
    family="dense",
    n_layers=36,
    d_model=2560,
    n_heads=32,
    n_kv_heads=8,
    d_head=128,            # qwen3 fixes head_dim=128 (q proj 2560 -> 4096)
    d_ff=9728,
    vocab=151936,
    qk_norm=True,
    act="swiglu",
    norm="rmsnorm",
    rope_theta=1_000_000.0,
    tie_embeddings=True,   # tie_word_embeddings in the published config
    source="hf:Qwen/Qwen3-4B; hf",
)
