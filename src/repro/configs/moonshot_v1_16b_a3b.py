"""moonshot-v1-16b-a3b [moe]: a GQA stand-in at Moonlight-16B-A3B's widths,
not its published attention. 48L d_model=2048 16H (kv=16) vocab=163840, MoE
64 experts top-6 (expert d_ff=1408).

The published Moonlight-16B-A3B (hf:moonshotai/Moonlight-16B-A3B) is 27
layers of latent attention (MLA, DeepSeek-V3-style); this config keeps plain
grouped-query heads, so it exercises the expert layer at those widths and
nothing of MLA. The latent-attention layer now exists (``models/mla.py``,
served for DeepSeek-V2-Lite through ``LatentModelConfig``); the shapes here
are kept as they were.
"""

from repro.configs.base import (
    DECODE_32K, PREFILL_32K, TRAIN_4K, LayerSpec, MoEConfig, ModelConfig,
)

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    d_model=2048,
    n_layers=48,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=1408,
    vocab=163840,
    layer_pattern=(LayerSpec(kind="attn", ffn="moe"),),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, capacity_factor=1.25),
    rope_theta=50000.0,
    tie_embeddings=False,
    max_seq_len=131072,
)

SMOKE = ModelConfig(
    name="moonshot-smoke",
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    head_dim=16,
    d_ff=32,
    vocab=512,
    layer_pattern=(LayerSpec(kind="attn", ffn="moe"),),
    moe=MoEConfig(num_experts=8, top_k=2, d_ff=32, capacity_factor=2.0,
                  num_shared_experts=1),
    tie_embeddings=False,
    max_seq_len=1024,
    compute_dtype="float32",
)

SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K)  # full attention: no long_500k
