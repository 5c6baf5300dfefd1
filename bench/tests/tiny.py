"""Tiny stand-ins for the cells' configurations and mixes, for CPU tests:
the same families and code paths at d_model 64, two layers."""
import copy

from bench import traffic, weights


def conf(name: str) -> dict:
    c = weights.load_config(name)
    out = dict(c, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=503,
               intermediate_size=32 if c.get("num_local_experts") else 96)
    if c.get("num_local_experts"):
        out.update(num_local_experts=8, num_experts_per_tok=2)
    return out


def mix(name: str, limit: float) -> dict:
    m = copy.deepcopy(traffic.load_mix(name))
    for k in ("median", "min", "max"):
        m["prompt_len"][k] = m["prompt_len"][k] // 16
    m["output_len"].update(median=12, min=4, max=24)
    m["engine"].update(slots=4, max_seq_len=512, pool_pages=129)
    m["check"].update(sample_tokens=40, served_gap_limit=limit)
    if m["arrivals"]["process"] == "batch":
        m["arrivals"]["count"] = 24
    else:
        m["arrivals"]["rate_per_s"] = 2.0
    return m


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
