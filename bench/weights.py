"""A configuration file's sizes, and seeded weights made on the device.

A configuration under `bench/configs/` holds the model's published
`config.json` keys. `sizes()` reads the numbers every other part of the
benchmark uses (FLOP counts, the reference, the traffic's vocabulary), and
`arch_config()` maps them onto the program's `ArchConfig`. `make_weights()`
draws the weights from the run's seed in one jitted call, in the program's
parameter layout, in bfloat16: the benchmark makes them, the program and
the reference both read them.

Granite's four scalar multipliers are folded into the weights the program
is given, so that the program, which has none of them, computes the
published function exactly. Scaling the residual stream by 1/m_emb (norms
see no difference once their eps is divided by m_emb²), the published
model with weights W is the program with weights P where W = scale · P:
q projection hd^-0.5 / m_att, attention output and expert down projections
m_emb / m_res, final norm gain logits_scaling (`published_scale()`). The
reference computes the published function on W.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    return json.loads((ROOT / "configs" / f"{name}.json").read_text())


def sizes(conf: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    hd = int(conf.get("head_dim") or d // H)
    V = int(conf["vocab_size"])
    return {
        "L": int(conf["num_hidden_layers"]), "d": d, "H": H,
        "KVH": int(conf["num_key_value_heads"]), "hd": hd,
        "F": int(conf["intermediate_size"]),
        "E": int(conf.get("num_local_experts") or 0),
        "k": int(conf.get("num_experts_per_tok") or 0),
        "V": V,
        # the program pads the vocabulary to a multiple of 2048 rows
        # (ArchConfig.padded_vocab) and masks the padding logits
        "Vp": V if V % 2048 == 0 or V < 2048 else -(-V // 2048) * 2048,
        "eps": float(conf["rms_norm_eps"]),
        "theta": float(conf["rope_theta"]),
        "tied": bool(conf.get("tie_word_embeddings", False)),
        "window": int(conf.get("sliding_window") or 0),
        "m_emb": float(conf.get("embedding_multiplier", 1.0)),
        "m_att": float(conf.get("attention_multiplier", hd ** -0.5)),
        "m_res": float(conf.get("residual_multiplier", 1.0)),
        "m_logit": float(conf.get("logits_scaling", 1.0)),
    }


def published_scale(s: dict) -> dict:
    """Scalar that turns each folded program weight into the published
    model's weight (1 where it is absent)."""
    res = s["m_emb"] / s["m_res"]
    return {"wq": s["hd"] ** -0.5 / s["m_att"], "wo": res, "wd": res,
            "final_norm": s["m_logit"]}


def arch_config(conf: dict, name: str):
    """The program's ArchConfig for a configuration file."""
    from repro.models.config import ArchConfig

    s = sizes(conf)
    cfg = ArchConfig(
        name=name, family="moe" if s["E"] else "dense",
        num_layers=s["L"], d_model=s["d"], num_heads=s["H"],
        num_kv_heads=s["KVH"], head_dim=s["hd"], d_ff=s["F"],
        vocab_size=s["V"], num_experts=s["E"], experts_per_token=s["k"],
        rope_theta=s["theta"], norm_eps=s["eps"] / s["m_emb"] ** 2,
        tie_embeddings=s["tied"])
    if cfg.padded_vocab != s["Vp"]:
        raise ValueError("the program pads the vocabulary to "
                         f"{cfg.padded_vocab} rows, the benchmark to {s['Vp']}")
    return cfg


def key_from_seed(seed: int):
    """A JAX key from any whole-number seed (64 bits and more)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def _tree_shapes(s: dict) -> dict:
    """(shape, fan_in) of every weight, in the program's layout: one
    stacked period of layers, norms as {"w": (d,)}."""
    L, d, H, KVH, hd, F, E = (s[x] for x in ("L", "d", "H", "KVH", "hd",
                                              "F", "E"))
    layer = {
        "norm1": {"w": ((L, d), 0)}, "norm2": {"w": ((L, d), 0)},
        "attn": {"wq": ((L, d, H * hd), d), "wk": ((L, d, KVH * hd), d),
                 "wv": ((L, d, KVH * hd), d),
                 "wo": ((L, H * hd, d), H * hd)},
    }
    if E:
        layer["moe"] = {"router": ((L, d, E), d), "wg": ((L, E, d, F), d),
                        "wu": ((L, E, d, F), d), "wd": ((L, E, F, d), F)}
    else:
        layer["ffn"] = {"wg": ((L, d, F), d), "wu": ((L, d, F), d),
                        "wd": ((L, F, d), F)}
    tree = {"embed": ((s["Vp"], d), d), "final_norm": {"w": ((d,), 0)},
            "layers": (layer,)}
    if not s["tied"]:
        tree["lm_head"] = ((d, s["Vp"]), d)
    return tree


def make_weights(conf: dict, seed: int, dtype=None):
    """Every weight the program is given, P, drawn from `seed` on the
    default device in one jitted call: matrices N(0, 1/fan_in), norm gains
    1 + N(0, 0.1²). The published model's are W = published_scale() · P.
    Drawn so, the residual branches outweigh the raw embedding and the
    logits spread by about 1 around no favourite: drawing W so instead
    would let granite's ×12 embedding dominate the tied head, and the
    model would repeat its input token by a margin no rounding upsets."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    shapes = _tree_shapes(sizes(conf))
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[1], int))

    @jax.jit
    def init(key):
        out = []
        for i, (shape, fan_in) in enumerate(leaves):
            r = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            w = r * fan_in ** -0.5 if fan_in else 1.0 + 0.1 * r
            out.append(w.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return init(key_from_seed(seed))
