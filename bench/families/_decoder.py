"""The decoder that `granitemoe` and `phi3` share: Llama-style GQA with
RoPE, a SwiGLU FFN or granite's sparse-expert FFN (softmax top-k routing
renormalised over the chosen experts), and granite's four scalar
multipliers: embeddings times `embedding_multiplier`, attention scores
times `attention_multiplier`, each residual branch times
`residual_multiplier`, logits divided by `logits_scaling` (1 where a
configuration has none).

Granite's multipliers are folded into the weights the program is given,
so that the program, which has none of them, computes the published
function exactly. Scaling the residual stream by 1/m_emb (norms see no
difference once their eps is divided by m_emb²), the published model with
weights W is the program with weights P where W = scale · P: q projection
hd^-0.5 / m_att, attention output and expert down projections
m_emb / m_res, final norm gain logits_scaling (`published_scale()`). The
reference computes the published function on W.

Sliding windows are not modelled: a cell's sequences stay within its
configuration's window (bench/cell.py checks it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.flops import causal_pairs
from bench.reference.plain import (_attention, _einsum, _moe, _rmsnorm,
                                   _rope, _swiglu)
from bench.weights import Leaf


def sizes(conf: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    hd = int(conf.get("head_dim") or d // H)
    V = int(conf["vocab_size"])
    return {
        "model_type": conf["model_type"],
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


def tree(s: dict) -> dict:
    """Every weight in the program's layout: one stacked period of
    layers, norms as {"w": (d,)}; matrices and gains draw by default."""
    L, d, H, KVH, hd, F, E = (s[x] for x in ("L", "d", "H", "KVH", "hd",
                                              "F", "E"))
    layer = {
        "norm1": {"w": Leaf((L, d))}, "norm2": {"w": Leaf((L, d))},
        "attn": {"wq": Leaf((L, d, H * hd), d),
                 "wk": Leaf((L, d, KVH * hd), d),
                 "wv": Leaf((L, d, KVH * hd), d),
                 "wo": Leaf((L, H * hd, d), H * hd)},
    }
    if E:
        layer["moe"] = {"router": Leaf((L, d, E), d),
                        "wg": Leaf((L, E, d, F), d),
                        "wu": Leaf((L, E, d, F), d),
                        "wd": Leaf((L, E, F, d), F)}
    else:
        layer["ffn"] = {"wg": Leaf((L, d, F), d), "wu": Leaf((L, d, F), d),
                        "wd": Leaf((L, F, d), F)}
    out = {"embed": Leaf((s["Vp"], d), d), "final_norm": {"w": Leaf((d,))},
           "layers": (layer,)}
    if not s["tied"]:
        out["lm_head"] = Leaf((d, s["Vp"]), d)
    return out


@functools.partial(jax.jit, static_argnames=("s", "fp8", "block"))
def logits(weights, tokens, *, s: tuple, fp8: bool = False,
           block: int = 512):
    """tokens (T,) int32, T a multiple of `block` → logits (T, Vp) float32,
    padding columns of the vocabulary at -inf. `s` is
    `plain.static_sizes(sizes)`; each weight is read times its
    `published_scale`."""
    s = dict(s)
    H, KVH, hd = s["H"], s["KVH"], s["hd"]
    sc = published_scale(s)
    T = tokens.shape[0]
    x = s["m_emb"] * weights["embed"][tokens].astype(jnp.float32)

    def w(leaf, name):
        return leaf.astype(jnp.float32) * sc.get(name, 1.0)

    def layer(x, p):
        h = _rmsnorm(x, p["norm1"]["w"], s["eps"])
        a = p["attn"]
        q = _rope(_einsum("td,dn->tn", h, w(a["wq"], "wq"), fp8)
                  .reshape(T, H, hd), s["theta"])
        k = _rope(_einsum("td,dn->tn", h, a["wk"], fp8).reshape(T, KVH, hd),
                  s["theta"])
        v = _einsum("td,dn->tn", h, a["wv"], fp8).reshape(T, KVH, hd)
        o = _attention(q, k, v, fp8, block, s["m_att"]).reshape(T, H * hd)
        x = x + s["m_res"] * _einsum("tn,nd->td", o, w(a["wo"], "wo"), fp8)
        h = _rmsnorm(x, p["norm2"]["w"], s["eps"])
        hb = h.reshape(T // block, block, -1)
        if "moe" in p:
            f = lax.map(lambda hi: _moe(hi, p["moe"], s["k"], fp8,
                                        sc.get("wd", 1.0)), hb)
        else:
            f = lax.map(lambda hi: _swiglu(hi, p["ffn"]["wg"], p["ffn"]["wu"],
                                           w(p["ffn"]["wd"], "wd"), fp8), hb)
        return x + s["m_res"] * f.reshape(T, -1), None

    x, _ = lax.scan(layer, x, weights["layers"][0])
    x = _rmsnorm(x, w(weights["final_norm"]["w"], "final_norm"), s["eps"])
    head = (weights["embed"].T if s["tied"] else weights["lm_head"])
    out = _einsum("td,dv->tv", x, head, fp8) / s["m_logit"]
    return jnp.where(jnp.arange(s["Vp"]) < s["V"], out, -jnp.inf)


def layer_weights(s: dict) -> int:
    """Weights one token multiplies in one layer."""
    d, H, KVH, hd, F = s["d"], s["H"], s["KVH"], s["hd"], s["F"]
    attn = d * H * hd + 2 * d * KVH * hd + H * hd * d
    if s["E"]:
        return attn + d * s["E"] + s["k"] * 3 * d * F
    return attn + 3 * d * F


def attention_flops(s: dict, pairs: int) -> int:
    """Score and value products over `pairs` (query, visible key) pairs,
    all layers."""
    return s["L"] * 4 * s["H"] * s["hd"] * pairs


def prefill_flops(s: dict, T: int) -> int:
    """A prompt of T tokens: every layer on every token, causal attention,
    the LM head on the last row only (the first generated token)."""
    return (2 * T * s["L"] * layer_weights(s) + 2 * s["d"] * s["V"]
            + attention_flops(s, causal_pairs(T)))


def decode_flops(s: dict, ctx: int) -> int:
    """One generated token whose input sits at position `ctx` (it attends
    to ctx + 1 keys)."""
    return (2 * (s["L"] * layer_weights(s) + s["d"] * s["V"])
            + attention_flops(s, ctx + 1))


def decode_kv_bytes(s: dict, ctx: int, kv_bytes: int) -> int:
    """K and V read by one decode step of one slot at position `ctx`."""
    return s["L"] * 2 * s["KVH"] * s["hd"] * kv_bytes * (ctx + 1)


def prefill_attention_bytes(s: dict, T: int, in_bytes: int = 2,
                            out_bytes: int = 4) -> int:
    """Least bytes of a causal prefill's attention, all layers: q, k, v
    read once, the output written once."""
    return s["L"] * T * s["hd"] * ((s["H"] + 2 * s["KVH"]) * in_bytes
                                   + s["H"] * out_bytes)


def tiny(conf: dict) -> dict:
    """The same code paths at d_model 64, two layers."""
    out = dict(conf, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=503,
               intermediate_size=32 if conf.get("num_local_experts") else 96)
    if conf.get("num_local_experts"):
        out.update(num_local_experts=8, num_experts_per_tok=2)
    return out
