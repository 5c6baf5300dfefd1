"""Phi-3 (`model_type` phi3): dense GQA with RoPE and a SwiGLU FFN
(`_decoder.py`, with no multipliers)."""
from bench.families._decoder import (  # noqa: F401
    arch_config, attention_flops, decode_flops, decode_kv_bytes, logits,
    prefill_attention_bytes, prefill_flops, sizes, tiny, tree)
