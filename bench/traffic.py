"""The one traffic generator: a mix file under `bench/traffic/` → requests.

A mix file is JSON. Its `arrivals` say when requests are due:
  {"process": "poisson", "rate_per_s": r}  open loop, ceil(r · seconds)
                                           requests over the window
  {"process": "batch", "count": n}         offline: n requests due at t=0
`prompt_len` and `output_len` are length distributions:
  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  {"dist": "uniform", "min": a, "max": b}
Token ids are uniform over the configuration's vocabulary; prompts share
nothing.

Every seed gets the same work in another order. Lengths and inter-arrival
gaps are the distribution's quantiles at (i + 1/2) / block for each block
of `block` requests (default: all of them), shuffled by the seed within the
block; only the order and the token ids change with the seed. So runs with
different seeds measure the same amount of work, and any prefix of an
offline queue holds nearly the same mix.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

ROOT = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    return json.loads((ROOT / "traffic" / f"{name}.json").read_text())


@dataclasses.dataclass(frozen=True)
class Spec:
    due_s: float
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """The distribution's quantiles at (i + 1/2) / n, rounded, clipped."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"]) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _blocked(dist: dict, n: int, block: int, rng) -> np.ndarray:
    out = []
    while len(out) < n:
        out.extend(rng.permutation(_quantiles(dist, block)))
    return np.asarray(out[:n])


def count(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        return math.ceil(arr["rate_per_s"] * seconds)
    if arr["process"] == "batch":
        return int(arr["count"])
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> list[Spec]:
    n = count(mix, seconds)
    block = int(mix.get("block", n))
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    plen = _blocked(mix["prompt_len"], n, block, rng)
    olen = _blocked(mix["output_len"], n, block, rng)
    if mix["arrivals"]["process"] == "poisson":
        # exponential gaps by quantile: -ln(1 - u) / rate
        u = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-u) / mix["arrivals"]["rate_per_s"])
        due = np.cumsum(gaps)
    else:
        due = np.zeros(n)
    return [Spec(float(due[i]),
                 rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                 int(olen[i])) for i in range(n)]


def longest(mix: dict) -> int:
    """Longest prompt + output a request of this mix can ask for."""
    return int(mix["prompt_len"]["max"] + mix["output_len"]["max"])
