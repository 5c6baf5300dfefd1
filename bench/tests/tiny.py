"""Tiny stand-ins for the cells' configurations and mixes, for CPU tests:
the same families and code paths at d_model 64 (each family's `tiny()`)."""
import copy

from bench import traffic, weights
from bench.families import family


def conf(name: str) -> dict:
    c = weights.load_config(name)
    return family(c["model_type"]).tiny(c)


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
