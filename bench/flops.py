"""Operations and bytes of the work a cell asks for, from published sizes.

Counts follow the configuration file (`weights.sizes`), never the
program's own counters, so that no change to the program can move them.
Each count is the family's (`bench/families/<model_type>.py`, picked by
`s["model_type"]`); the decoder families count so:

* a forward token costs 2 FLOPs per weight it multiplies, counting only
  the `k` experts the router picks (never the ones a dropless dispatch
  computes and zeroes), the router, and the LM head over the published
  vocabulary (not the program's padding);
* causal attention costs 2·2·H·hd FLOPs per (query, visible key) pair;
* a decode step reads, per layer, K and V of every live position of the
  slot (its context and the token it writes), `kv_bytes` bytes a value.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench.families import family

ROOT = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads((ROOT / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def attention_flops(s: dict, pairs: int) -> int:
    """Score and value products over `pairs` (query, visible key) pairs,
    all layers."""
    return family(s["model_type"]).attention_flops(s, pairs)


def causal_pairs(T: int, start: int = 0) -> int:
    """Visible pairs for queries at positions start .. T-1 (each sees
    itself and everything before it)."""
    return T * (T + 1) // 2 - start * (start + 1) // 2


def prefill_flops(s: dict, T: int) -> int:
    """A prompt of T tokens, and the first generated token."""
    return family(s["model_type"]).prefill_flops(s, T)


def decode_flops(s: dict, ctx: int) -> int:
    """One generated token whose input sits at position `ctx` (it attends
    to ctx + 1 keys)."""
    return family(s["model_type"]).decode_flops(s, ctx)


def decode_kv_bytes(s: dict, ctx: int, kv_bytes: int) -> int:
    """K and V read by one decode step of one slot at position `ctx`."""
    return family(s["model_type"]).decode_kv_bytes(s, ctx, kv_bytes)


def prefill_attention_bytes(s: dict, T: int, in_bytes: int = 2,
                            out_bytes: int = 4) -> int:
    """Least bytes of a causal prefill's attention, all layers: q, k, v
    read once, the output written once."""
    return family(s["model_type"]).prefill_attention_bytes(
        s, T, in_bytes, out_bytes)
