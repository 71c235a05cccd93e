"""Mixtral 8x7B: 8 SwiGLU experts per layer, top-2 [arXiv:2401.04088].

Published config.json of mistralai/Mixtral-8x7B-v0.1: 32 layers, hidden
4096, 32 query and 8 KV heads of 128, expert FFN 14336, vocab 32000,
``rope_theta`` 1e6, RMSNorm eps 1e-5, ``sliding_window`` null (dense
causal attention, the field's 0 here).
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="mixtral-8x7b", family="moe",
    n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
    d_ff=14336, vocab=32000, n_experts=8, topk=2, rope_theta=1e6,
)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="mixtral-smoke", family="moe",
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
        d_ff=256, vocab=256, n_experts=4, topk=2,
    )
