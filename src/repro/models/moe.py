"""Mixture-of-Experts FFN: top-k routing, capacity + dropless dispatch, EP.

Two dispatches share one router:

* **Capacity (training)** — GShard-style static shapes: each expert
  processes its top-C tokens (C = ceil(k·T·capacity_factor / E)), gathered
  into a dense (B, E, C, D) buffer, run through batched expert GEMMs with
  the expert dim sharded over the `model` mesh axis (expert parallelism),
  and scatter-added back with the router combine weights. Compute scales
  with k·T (not E·T) but overflow tokens are *dropped*.

* **Dropless (serving)** — dense per-token expert compute: every expert runs
  every token and the k-sparse combine weights zero the non-routed pairs, so
  the result is the exact top-k router semantics with no capacity drops.
  Costs E/k× the capacity path's FLOPs — the right trade at decode shapes
  (T ∈ {1..8} per step), where dropping a user's token is unacceptable and
  the GEMMs are latency- not throughput-bound. Selected by the serving path
  (`model._sublayer` under a cache, optflag ``moe_dropless_serve``) or
  arch-wide via ``cfg.moe_dropless``.

Both take the layer's held experts (`cfg.experts_held`): the router scores
all `num_experts` at the published top-k, and the layer computes the part
of the result that its own experts give — what one chip of an
expert-parallel deployment computes before the exchange.

Every contraction in both paths is a GEMM under the SA precision contract,
and all shapes are static — no ragged collectives, dry-run friendly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.precision import sa_dot, sa_einsum
from repro.parallel import sharding as S_
from .layers import act_fn, ffn_swiglu


def router(x, w_router, k: int):
    """x: (B, T, D) → combine weights (B, T, E) (zero outside top-k,
    renormalized over the top-k) + aux losses."""
    B, T, D = x.shape
    logits = sa_dot(x.reshape(B * T, D), w_router).astype(jnp.float32)
    logits = logits.reshape(B, T, -1)
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(topi, E, dtype=probs.dtype)
                      * topv[..., None], axis=-2)
    density = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32),
                       axis=(0, 1, 2))
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux = {
        "load_balance": E * jnp.sum(density * mean_probs),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return combine, aux


def held_combine(x, p, cfg):
    """Router over all `cfg.num_experts`, then the combine weights of the
    held experts only: (B, T, n_held) + aux losses."""
    combine, aux = router(x, p["router"], cfg.experts_per_token)
    lo, hi = cfg.held_experts
    return combine[..., lo:hi], aux


def capacity(T: int, E: int, k: int, factor: float = 1.25) -> int:
    return max(1, min(T, math.ceil(T * k * factor / E)))


# dropless buffers are (B, E, Tc, F): chunking T bounds them during long
# serving prefills (decode steps are single-chunk). Per-token math is
# row-independent, so chunking never changes results.
DROPLESS_CHUNK_T = 128


def moe_ffn_dropless(x, p, cfg, act: str = "silu"):
    """Dropless dispatch (serving): exact top-k routing, no capacity drops.

    Dense per-token expert compute — every expert's activation for every
    token, with the k-sparse combine weights (zero outside the token's
    top-k) selecting and mixing. Exactly equals per-token
    ``Σ_{e∈topk(t)} w_e·E_e(x_t)`` at any T, so prefill+decode ≡ full
    forward for MoE archs. T is processed in chunks of `DROPLESS_CHUNK_T`
    so the (B, E, Tc, F) activations stay bounded on long prefills. See
    the module docstring for the FLOPs trade.
    """
    from jax import lax
    B, T, D = x.shape
    combine, aux = held_combine(x, p, cfg)                # (B, T, E held)
    E = combine.shape[-1]
    tp = max(S_.axis_count("model"), 1)
    ep_axis = "model" if E % tp == 0 else None
    Tc = min(T, DROPLESS_CHUNK_T)
    pad = (-T) % Tc
    xp_ = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    cp = jnp.pad(combine, ((0, 0), (0, pad), (0, 0)))
    nc = (T + pad) // Tc
    xb = xp_.reshape(B, nc, Tc, D).swapaxes(0, 1)         # (nc, B, Tc, D)
    cb = cp.reshape(B, nc, Tc, E).swapaxes(0, 1)

    def chunk(_, xc_cc):
        xc, cc = xc_cc
        g = sa_einsum("btd,edf->betf", xc, p["wg"])
        u = sa_einsum("btd,edf->betf", xc, p["wu"])
        y = sa_einsum("betf,efd->betd", act_fn(g, act) * u, p["wd"])
        y = S_.constrain(y, "batch", ep_axis, None, None)
        return None, jnp.sum(
            y * cc.swapaxes(1, 2)[..., None].astype(y.dtype), axis=1)

    _, outs = lax.scan(chunk, None, (xb, cb))             # (nc, B, Tc, D)
    out = outs.swapaxes(0, 1).reshape(B, T + pad, D)[:, :T]
    if "shared_wg" in p:
        out = out + ffn_swiglu(x, {"wg": p["shared_wg"], "wu": p["shared_wu"],
                                   "wd": p["shared_wd"]}, act)
    return out.astype(x.dtype), aux


def moe_ffn(x, p, cfg, act: str = "silu", capacity_factor: float = 1.25,
            dropless: bool = False):
    """x: (B, T, D); p: router (D, E), wg/wu (E, D, F), wd (E, F, D),
    optional shared expert (shared_wg/wu/wd). ``dropless=True`` selects the
    exact serving dispatch (see `moe_ffn_dropless`)."""
    from repro.core import optflags
    if dropless:
        return moe_ffn_dropless(x, p, cfg, act)
    B, T, D = x.shape
    # tokens per expert as the whole router spreads them
    C = capacity(T, cfg.num_experts, cfg.experts_per_token, capacity_factor)
    combine, aux = held_combine(x, p, cfg)                # (B, T, E held)
    E = combine.shape[-1]

    tp = max(S_.axis_count("model"), 1)
    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    if E % tp and optflags.enabled("pad_experts"):
        # pad experts to the TP axis: dummy experts receive zero combine
        # weight (never routed), so outputs are exact; the win is EP dispatch
        # instead of TP-inside-expert (granite: −60 % MoE collectives).
        E_pad = -(-E // tp) * tp
        combine = jnp.pad(combine, ((0, 0), (0, 0), (0, E_pad - E)))
        wg = jnp.pad(wg, ((0, E_pad - E), (0, 0), (0, 0)))
        wu = jnp.pad(wu, ((0, E_pad - E), (0, 0), (0, 0)))
        wd = jnp.pad(wd, ((0, E_pad - E), (0, 0), (0, 0)))
        E = E_pad

    # dispatch: per expert, its C highest-weight tokens (static shapes)
    gate, token_idx = jax.lax.top_k(combine.swapaxes(1, 2), C)  # (B, E, C)
    xe = jnp.take_along_axis(x[:, None], token_idx[..., None], axis=2)
    # expert GEMMs — E is the EP axis (sharded over `model` when divisible).
    # The explicit constraint keeps the dispatch buffer sharded like the
    # expert weights; without it the partitioner all-gathers the full expert
    # stack per device (observed: 160 GiB/dev on llama4 before this).
    ep_axis = "model" if E % tp == 0 else None
    xe = S_.constrain(xe, "batch", ep_axis, None, None)
    if ep_axis and E != cfg.n_held_experts:   # padded: pin EP layout
        wg = S_.constrain(wg, ep_axis, None, None)
        wu = S_.constrain(wu, ep_axis, None, None)
        wd = S_.constrain(wd, ep_axis, None, None)
    g = sa_einsum("becd,edf->becf", xe, wg)
    u = sa_einsum("becd,edf->becf", xe, wu)
    y = sa_einsum("becf,efd->becd", act_fn(g, act) * u, wd)
    y = S_.constrain(y, "batch", ep_axis, None, None)
    y = y * gate[..., None].astype(y.dtype)
    # combine: scatter-add expert outputs back to token positions
    out = jnp.zeros((B, T, D), y.dtype)
    bidx = jnp.arange(B)[:, None, None]
    out = out.at[bidx, token_idx].add(y)
    if "shared_wg" in p:
        out = out + ffn_swiglu(x, {"wg": p["shared_wg"], "wu": p["shared_wu"],
                                   "wd": p["shared_wd"]}, act)
    return out.astype(x.dtype), aux
