"""mixtral-8x7b [moe] — 8 experts top-2 in every layer, full attention.

32L d_model=4096 32H (GQA kv=8) head_dim 128 d_ff=14336 vocab=32000,
RoPE theta 1e6, RMSNorm eps 1e-5, untied head.  ``config.json`` has
``"sliding_window": null``: every layer attends to the whole prefix.
[https://huggingface.co/mistralai/Mixtral-8x7B-v0.1, arXiv:2401.04088]
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="mixtral-8x7b",
    family="moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    vocab=32000,
    pattern=("moe",),
    n_periods=32,
    rope_theta=1000000.0,
    moe=MoEConfig(n_experts=8, top_k=2),
    source="https://huggingface.co/mistralai/Mixtral-8x7B-v0.1",
    subquadratic=False,
)
