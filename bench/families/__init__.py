"""Model families: one file per architecture, found by the configuration
file's `model_type` (`bench/families/<model_type>.py`).

A family file provides, for the configurations of its `model_type`:

* `sizes(conf)`: the numbers every other part of the benchmark reads,
  under short names; it carries `model_type`, `V` (the published
  vocabulary), `Vp` (the program's padded one), `window` (0 for none) and
  whatever the family's own functions read;
* `arch_config(conf, name)`: the program's `ArchConfig`;
* `tree(sizes)`: the program's parameter layout, a `bench.weights.Leaf`
  (shape and draw) at each weight;
* `logits(weights, tokens, *, s, fp8, block)`: the plain float32
  reference of the published function (`bench/reference/plain.py`);
* the counts `prefill_flops(s, T)`, `decode_flops(s, ctx)`,
  `decode_kv_bytes(s, ctx, kv_bytes)`, `attention_flops(s, pairs)` and
  `prefill_attention_bytes(s, T, in_bytes, out_bytes)` (`bench/flops.py`);
* `tiny(conf)`: the d_model-64 stand-in of a configuration, for CPU tests.

A new architecture is a new file here; nothing lists the families.
Files whose names start with `_` hold code that families share.
"""
from __future__ import annotations

import importlib
import sys
from pathlib import Path

DIR = Path(__file__).resolve().parent
PROVIDES = ("sizes", "arch_config", "tree", "logits", "prefill_flops",
            "decode_flops", "decode_kv_bytes", "attention_flops",
            "prefill_attention_bytes", "tiny")


def names() -> list[str]:
    """The model types that have a family file."""
    return sorted(p.stem for p in DIR.glob("*.py")
                  if not p.stem.startswith("_"))


def family(model_type: str):
    """The module bench/families/<model_type>.py."""
    mod = sys.modules.get(f"{__name__}.{model_type}")
    if mod is not None:
        return mod
    if model_type not in names():
        raise ValueError(f"no family file for model_type {model_type!r}; "
                         f"bench/families/ has {names()}")
    mod = importlib.import_module(f"{__name__}.{model_type}")
    missing = [f for f in PROVIDES if not callable(getattr(mod, f, None))]
    if missing:
        del sys.modules[mod.__name__]
        raise TypeError(f"bench/families/{model_type}.py lacks {missing}")
    return mod
