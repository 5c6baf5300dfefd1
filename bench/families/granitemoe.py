"""granite-3.0 MoE (`model_type` granitemoe): GQA with RoPE, sparse
experts under softmax top-k routing renormalised over the picks, and
granite's four multipliers folded into the program's weights
(`_decoder.py`)."""
from bench.families._decoder import (  # noqa: F401
    arch_config, attention_flops, decode_flops, decode_kv_bytes, logits,
    prefill_attention_bytes, prefill_flops, sizes, tiny, tree)
