"""granite-4.0-h (`model_type` granitemoehybrid): Mamba2 layers with a GQA
attention layer at the places `layer_types` gives, attention with no
position embedding (`position_embedding_type` "nope"), an expert FFN in
every layer (softmax top-k over the router's experts, renormalised over
the picks, plus a shared SwiGLU expert), and granite's four multipliers,
folded into the program's weights. Unlike `_decoder.py`'s fold, the
residual stream keeps its published scale, so every norm keeps the
published eps: the published model with weights W is the program with
weights P where W = scale · P (`published_scale()`): the embedding
1 / m_emb (the program's embedding rows are the stream's first value),
the q projection hd^-0.5 / m_att, every residual branch's last
projection (attention output, Mamba2 out projection, the experts' and
the shared expert's down projections) 1 / m_res, and the final norm gain
m_logit · m_emb (the tied head reads the embedding, m_emb times the
published one).

A configuration may hold only some of the router's experts: experts
0 .. `num_local_experts` − 1 of `published.num_local_experts`. The router
keeps its published width and top-k; the layer computes the part of the
result that its own experts give, in the program and in the reference
alike (model-configs §4). `layer_types` is copied whole from the published
config and its first `num_hidden_layers` entries are the layers run.

The reference's Mamba2 is the sequential recurrence, one token at a time:
h_t = exp(dt_t·A)·h_{t−1} + dt_t·(x_t ⊗ B_t), y_t = h_t·C_t + D·x_t, then
y·silu(z) through the gated RMSNorm; never the program's chunked SSD. The
float8 control rounds the operands of every projection as `plain.py`
says; the recurrence stays in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.families import _decoder
from bench.flops import causal_pairs
from bench.reference.plain import (HIGHEST, _attention, _einsum, _q8,
                                   _rmsnorm, _rope, _swiglu)
from bench.weights import Leaf, uniform

DT_RANGE = (1e-3, 0.1)      # Mamba2's dt_min, dt_max
A_RANGE = (1.0, 16.0)       # Mamba2's A_init_range


def _period(types: tuple) -> tuple:
    """The shortest prefix of `types` that repeats to make all of it."""
    n = len(types)
    return next(types[:p] for p in range(1, n + 1)
                if n % p == 0 and types == types[:p] * (n // p))


def sizes(conf: dict) -> dict:
    """The sizes of a configuration file, under short names."""
    s = _decoder.sizes(conf)
    if int(conf["mamba_n_groups"]) != 1 or int(conf["mamba_d_conv"]) != 4:
        raise ValueError("the program runs Mamba2 with one group of B and C "
                         "and a convolution of width 4")
    types = tuple(conf["layer_types"][:s["L"]])
    Hs, P = int(conf["mamba_n_heads"]), int(conf["mamba_d_head"])
    din = int(conf["mamba_expand"]) * s["d"]
    if Hs * P != din:
        raise ValueError(f"{Hs} heads of {P} do not make d_inner {din}")
    s.update(
        types=types, period=_period(types),
        E_router=int(conf.get("published", {}).get("num_local_experts",
                                                   s["E"])),
        Fs=int(conf.get("shared_intermediate_size") or 0),
        Hs=Hs, P=P, N=int(conf["mamba_d_state"]), din=din,
        KW=int(conf["mamba_d_conv"]), chunk=int(conf["mamba_chunk_size"]),
        conv_bias=bool(conf["mamba_conv_bias"]),
        rope=conf.get("position_embedding_type", "rope") != "nope")
    return s


def published_scale(s: dict) -> dict:
    """Scalar that turns each folded program weight into the published
    model's weight (the shared expert's down projection takes `wd`'s)."""
    res = 1 / s["m_res"]
    return {"embed": 1 / s["m_emb"], "wq": s["hd"] ** -0.5 / s["m_att"],
            "wo": res, "wd": res, "out_proj": res,
            "final_norm": s["m_logit"] * s["m_emb"]}


def arch_config(conf: dict, name: str):
    """The program's ArchConfig for a configuration file."""
    from repro.models.config import ArchConfig

    s = sizes(conf)
    cfg = ArchConfig(
        name=name, family="hybrid", num_layers=s["L"], d_model=s["d"],
        num_heads=s["H"], num_kv_heads=s["KVH"], head_dim=s["hd"],
        d_ff=s["F"], vocab_size=s["V"], tie_embeddings=s["tied"],
        position_embedding="rope" if s["rope"] else "nope",
        rope_theta=s["theta"], layer_types=s["period"],
        num_experts=s["E_router"], experts_per_token=s["k"],
        experts_held=(0, s["E"]) if s["E"] != s["E_router"] else (),
        shared_expert=bool(s["Fs"]), d_ff_shared=s["Fs"],
        norm_eps=s["eps"],
        ssm_state=s["N"], ssm_heads=s["Hs"], ssm_head_dim=s["P"],
        ssm_expand=s["din"] // s["d"], ssm_chunk=s["chunk"],
        ssm_conv_bias=s["conv_bias"], ssm_head_d=True)
    if cfg.padded_vocab != s["Vp"]:
        raise ValueError("the program pads the vocabulary to "
                         f"{cfg.padded_vocab} rows, the benchmark to {s['Vp']}")
    return cfg


def _dt_bias(key, shape):
    """softplus⁻¹ of dt drawn log-uniform in DT_RANGE."""
    lo, hi = (jnp.log(x) for x in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log(key, shape):
    """log A, A uniform in A_RANGE."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))


def tree(s: dict) -> dict:
    """Every weight in the program's layout: one period of layers, each
    position's leaves stacked over the L / period superblocks."""
    n = s["L"] // len(s["period"])
    d, H, KVH, hd, F, Fs, E = (s[x] for x in ("d", "H", "KVH", "hd", "F",
                                              "Fs", "E"))
    Hs, N, din, KW = s["Hs"], s["N"], s["din"], s["KW"]
    cd = din + 2 * N                    # the convolved [x | B | C]
    conv = uniform(-KW ** -0.5, KW ** -0.5)   # torch's Conv1d default

    def layer(kind):
        p = {"norm1": {"w": Leaf((n, d))}, "norm2": {"w": Leaf((n, d))},
             "moe": {"router": Leaf((n, d, s["E_router"]), d),
                     "wg": Leaf((n, E, d, F), d), "wu": Leaf((n, E, d, F), d),
                     "wd": Leaf((n, E, F, d), F)}}
        if Fs:
            p["moe"] |= {"shared_wg": Leaf((n, d, Fs), d),
                         "shared_wu": Leaf((n, d, Fs), d),
                         "shared_wd": Leaf((n, Fs, d), Fs)}
        if kind == "attention":
            p["attn"] = {"wq": Leaf((n, d, H * hd), d),
                         "wk": Leaf((n, d, KVH * hd), d),
                         "wv": Leaf((n, d, KVH * hd), d),
                         "wo": Leaf((n, H * hd, d), H * hd)}
        else:
            p["ssm"] = {"in_proj": Leaf((n, d, 2 * din + 2 * N + Hs), d),
                        "conv_w": Leaf((n, KW, cd), draw=conv),
                        "dt_bias": Leaf((n, Hs), draw=_dt_bias),
                        "A_log": Leaf((n, Hs), draw=_a_log),
                        "D": Leaf((n, Hs)),
                        "norm_w": Leaf((n, din)),
                        "out_proj": Leaf((n, din, d), din)}
            if s["conv_bias"]:
                p["ssm"]["conv_b"] = Leaf((n, cd), draw=conv)
        return p

    out = {"embed": Leaf((s["Vp"], d), d), "final_norm": {"w": Leaf((d,))},
           "layers": tuple(layer(kind) for kind in s["period"])}
    if not s["tied"]:
        out["lm_head"] = Leaf((d, s["Vp"]), d)
    return out


def _mamba(h, p, s: dict, fp8: bool, out_scale: float):
    """Mamba2 mixer over h (T, d), token by token."""
    f32 = lambda a: a.astype(jnp.float32)
    T = h.shape[0]
    Hs, P, N, din, KW = (s[x] for x in ("Hs", "P", "N", "din", "KW"))
    zxbcdt = _einsum("td,dn->tn", h, p["in_proj"], fp8)
    z, xbc, dt = zxbcdt[:, :din], zxbcdt[:, din:2 * din + 2 * N], \
        zxbcdt[:, 2 * din + 2 * N:]
    xp = jnp.pad(xbc, ((KW - 1, 0), (0, 0)))
    conv = sum(xp[i:i + T] * f32(p["conv_w"][i]) for i in range(KW))
    if "conv_b" in p:
        conv = conv + f32(p["conv_b"])
    xbc = jax.nn.silu(conv)
    x, B, C = xbc[:, :din], xbc[:, din:din + N], xbc[:, din + N:]
    dt = jax.nn.softplus(dt + f32(p["dt_bias"]))              # (T, Hs)
    A = -jnp.exp(f32(p["A_log"]))
    xh = x.reshape(T, Hs, P)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t)
        return state, jnp.einsum("hpn,n->hp", state, C_t, precision=HIGHEST)

    _, y = lax.scan(step, jnp.zeros((Hs, P, N), jnp.float32), (xh, dt, B, C))
    y = (y + f32(p["D"])[:, None] * xh).reshape(T, din)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm_w"], s["eps"])
    return _einsum("tn,nd->td", y, f32(p["out_proj"]) * out_scale, fp8)


def _held_moe(h, p, k: int, fp8: bool, wd_scale: float):
    """Softmax router over every expert, top-k renormalised; the held
    experts' terms (the first wg.shape[0]) and the shared expert."""
    n_router, E = p["router"].shape[-1], p["wg"].shape[0]
    probs = jax.nn.softmax(_einsum("td,de->te", h, p["router"], fp8), -1)
    topv, topi = lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(topi, n_router) * topv[..., None],
                      axis=-2)[:, :E]
    g = _einsum("td,edf->tef", h, p["wg"], fp8, b_axis=1)
    u = _einsum("td,edf->tef", h, p["wu"], fp8, b_axis=1)
    z = jax.nn.silu(g) * u
    wd = p["wd"].astype(jnp.float32) * wd_scale
    if fp8:
        z, wd = _q8(z, -1), _q8(wd, 1)
    out = jnp.einsum("tef,efd->td", z * combine[..., None], wd,
                     precision=HIGHEST)
    if "shared_wg" in p:
        out = out + _swiglu(h, p["shared_wg"], p["shared_wu"],
                            p["shared_wd"].astype(jnp.float32) * wd_scale,
                            fp8)
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
    def w(leaf, name):
        return leaf.astype(jnp.float32) * sc.get(name, 1.0)

    embed = w(weights["embed"], "embed")
    x = s["m_emb"] * embed[tokens]

    def attention(h, a):
        q = _einsum("td,dn->tn", h, w(a["wq"], "wq"), fp8).reshape(T, H, hd)
        k = _einsum("td,dn->tn", h, a["wk"], fp8).reshape(T, KVH, hd)
        v = _einsum("td,dn->tn", h, a["wv"], fp8).reshape(T, KVH, hd)
        if s["rope"]:
            q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
        o = _attention(q, k, v, fp8, block, s["m_att"]).reshape(T, H * hd)
        return _einsum("tn,nd->td", o, w(a["wo"], "wo"), fp8)

    def superblock(x, layers):
        for kind, p in zip(s["period"], layers):
            h = _rmsnorm(x, p["norm1"]["w"], s["eps"])
            mix = (attention(h, p["attn"]) if kind == "attention" else
                   _mamba(h, p["ssm"], s, fp8, sc["out_proj"]))
            x = x + s["m_res"] * mix
            h = _rmsnorm(x, p["norm2"]["w"], s["eps"])
            f = lax.map(lambda hi: _held_moe(hi, p["moe"], s["k"], fp8,
                                             sc["wd"]),
                        h.reshape(T // block, block, -1))
            x = x + s["m_res"] * f.reshape(T, -1)
        return x, None

    x, _ = lax.scan(superblock, x, weights["layers"])
    x = _rmsnorm(x, w(weights["final_norm"]["w"], "final_norm"), s["eps"])
    head = embed.T if s["tied"] else weights["lm_head"]
    out = _einsum("td,dv->tv", x, head, fp8) / s["m_logit"]
    return jnp.where(jnp.arange(s["Vp"]) < s["V"], out, -jnp.inf)


def _attention_layers(s: dict) -> int:
    return s["types"].count("attention")


def _ssd_flops(s: dict) -> int:
    """One token through one Mamba2 layer's convolution and recurrence:
    the decay, the input's outer product and the sum (3 per state value),
    and the read-out (2 per state value)."""
    return 2 * s["KW"] * (s["din"] + 2 * s["N"]) + 5 * s["Hs"] * s["P"] * s["N"]


def layer_weights(s: dict, kind: str) -> int:
    """Weights one token multiplies in one layer: the mixer, the router,
    the shared expert, and of the k experts the router picks the share
    that falls on the held ones (k · E / E_router on average)."""
    d, H, KVH, hd, F = s["d"], s["H"], s["KVH"], s["hd"], s["F"]
    if kind == "attention":
        mix = d * H * hd + 2 * d * KVH * hd + H * hd * d
    else:
        mix = d * (2 * s["din"] + 2 * s["N"] + s["Hs"]) + s["din"] * d
    return (mix + d * s["E_router"] + 3 * d * s["Fs"]
            + s["k"] * s["E"] * 3 * d * F // s["E_router"])


def _token_flops(s: dict) -> int:
    """One token through every layer, attention scores left out."""
    return sum(2 * layer_weights(s, kind)
               + (_ssd_flops(s) if kind != "attention" else 0)
               for kind in s["types"])


def attention_flops(s: dict, pairs: int) -> int:
    """Score and value products over `pairs` (query, visible key) pairs,
    every attention layer."""
    return _attention_layers(s) * 4 * s["H"] * s["hd"] * pairs


def prefill_flops(s: dict, T: int) -> int:
    """A prompt of T tokens: every layer on every token, causal attention,
    the LM head on the last row only (the first generated token)."""
    return (T * _token_flops(s) + 2 * s["d"] * s["V"]
            + attention_flops(s, causal_pairs(T)))


def decode_flops(s: dict, ctx: int) -> int:
    """One generated token whose input sits at position `ctx` (it attends
    to ctx + 1 keys)."""
    return (_token_flops(s) + 2 * s["d"] * s["V"]
            + attention_flops(s, ctx + 1))


def decode_kv_bytes(s: dict, ctx: int, kv_bytes: int) -> int:
    """K and V read by one decode step of one slot at position `ctx`: the
    attention layers only."""
    return (_attention_layers(s) * 2 * s["KVH"] * s["hd"] * kv_bytes
            * (ctx + 1))


def prefill_attention_bytes(s: dict, T: int, in_bytes: int = 2,
                            out_bytes: int = 4) -> int:
    """Least bytes of a causal prefill's attention, every attention layer:
    q, k, v read once, the output written once."""
    return _attention_layers(s) * T * s["hd"] * (
        (s["H"] + 2 * s["KVH"]) * in_bytes + s["H"] * out_bytes)


def tiny(conf: dict) -> dict:
    """The same code paths at d_model 64: the first period of layers
    (nine Mamba2, one attention), 8 Mamba2 heads of 16 with state 16 and
    chunks of 8, 2 of 16 router experts held, top-4."""
    return dict(conf, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2,
                num_hidden_layers=len(_period(tuple(conf["layer_types"]))),
                vocab_size=503, intermediate_size=32,
                shared_intermediate_size=48, num_local_experts=2,
                num_experts_per_tok=4, mamba_n_heads=8, mamba_d_head=16,
                mamba_d_state=16, mamba_chunk_size=8,
                published=dict(conf.get("published", {}),
                               num_local_experts=16))
