"""Granite-4.0-H-Small (32B-A9B) — Mamba2 layers with one NoPE GQA layer in
ten, sparse experts with a shared expert in every layer
[hf:ibm-granite/granite-4.0-h-small]. Of 40 layers, 5, 15, 25 and 35 are
attention (no position embedding); the rest are Mamba2 (128 heads of 64,
state 128, one group, conv 4 with bias, D per head). Every layer's FFN:
72 experts of width 768, top-10, plus a shared expert of width 1536.
Granite's four scalar multipliers are not modelled here (the benchmark
folds them into the weights, bench/families/granitemoehybrid.py)."""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small", family="hybrid",
    num_layers=40, d_model=4096, num_heads=32, num_kv_heads=8, head_dim=128,
    d_ff=768, vocab_size=100352, tie_embeddings=True, norm_eps=1e-5,
    position_embedding="nope",
    layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    num_experts=72, experts_per_token=10, shared_expert=True,
    d_ff_shared=1536,
    ssm_state=128, ssm_heads=128, ssm_head_dim=64, ssm_expand=2,
    ssm_chunk=256, ssm_conv_bias=True, ssm_head_d=True,
)
