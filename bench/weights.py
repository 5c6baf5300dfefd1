"""A configuration file's sizes, and seeded weights made on the device.

A configuration under `bench/configs/` holds the model's published
`config.json` keys. Its `model_type` names the family file that reads it
(`bench/families/<model_type>.py`): `sizes()` gives the numbers every
other part of the benchmark uses (FLOP counts, the reference, the
traffic's vocabulary), and `arch_config()` maps them onto the program's
`ArchConfig`. `make_weights()` draws the weights from the run's seed in one
jitted call, in the program's parameter layout (the family's `tree()`), in
bfloat16: the benchmark makes them, the program and the reference both
read them.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Callable

import numpy as np

from bench.families import family

ROOT = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((ROOT / "configs" / f"{name}.json").read_text())


def sizes(conf: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    return family(conf["model_type"]).sizes(conf)


def arch_config(conf: dict, name: str):
    """The program's ArchConfig for a configuration file."""
    return family(conf["model_type"]).arch_config(conf, name)


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One weight of a family's `tree()`: its shape and how it is drawn.
    With no `draw`, a matrix (fan_in > 0) is N(0, 1/fan_in) and a gain
    (fan_in 0) is 1 + N(0, 0.1²); a `draw` maps (key, shape) to float32
    values (`uniform`, or a family's own)."""
    shape: tuple
    fan_in: int = 0
    draw: Callable | None = None


def uniform(lo: float, hi: float) -> Callable:
    """A draw uniform in [lo, hi)."""
    import jax
    import jax.numpy as jnp

    return lambda key, shape: jax.random.uniform(key, shape, jnp.float32,
                                                 lo, hi)


def key_from_seed(seed: int):
    """A JAX key from any whole-number seed (64 bits and more)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def make_weights(conf: dict, seed: int, dtype=None):
    """Every weight the program is given, drawn from `seed` on the
    default device in one jitted call, leaf i of the family's `tree()` (in
    flatten order) from the seed's key folded with i. Drawn by default so,
    granite's residual branches outweigh the raw embedding and the logits
    spread by about 1 around no favourite: drawing its published weights
    so instead would let granite's ×12 embedding dominate the tied head,
    and the model would repeat its input token by a margin no rounding
    upsets (bench/families/_decoder.py)."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    leaves, treedef = jax.tree.flatten(
        family(conf["model_type"]).tree(sizes(conf)))

    def draw(key, leaf: Leaf):
        if leaf.draw is not None:
            return leaf.draw(key, leaf.shape)
        r = jax.random.normal(key, leaf.shape, jnp.float32)
        return r * leaf.fan_in ** -0.5 if leaf.fan_in else 1.0 + 0.1 * r

    @jax.jit
    def init(key):
        return jax.tree.unflatten(treedef, [
            draw(jax.random.fold_in(key, i), leaf).astype(dtype)
            for i, leaf in enumerate(leaves)])

    return init(key_from_seed(seed))
