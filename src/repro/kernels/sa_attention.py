"""Pallas TPU kernel: flash attention with the SA arithmetic contract.

The framework's jnp-level blockwise attention (models/layers.py) materializes
per-tile probabilities in HBM; this kernel keeps the entire online-softmax
state — running max, normalizer, and the **unnormalized** output accumulator —
in VMEM scratch across the KV grid dimension, normalizing exactly once at the
end. That is the paper's skewed-column principle applied to attention:
unnormalized accumulation across the chain, deferred normalization, one
rounding at the end (DESIGN.md §2b).

Forward-only (training uses the custom-VJP jnp path; serving/prefill are
forward). GQA via the kv-head index map (query head h reads kv head h//g).
Causal/sliding-window masks and logit softcap supported statically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 n_kv: int, bq: int, bkv: int, scale: float, causal: bool,
                 window: int, cap: float, q_offset: int, kv_len: int):
    jkv = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(jkv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                # (bq, hd)
    k = k_ref[0, 0]                                # (bkv, hd)
    v = v_ref[0, 0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                          (bq, bkv), 0)
    kv_pos = jkv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    # kv rows past kv_len are the wrapper's block padding
    ok = kv_pos < kv_len
    if causal:
        ok &= q_pos >= kv_pos
    if window:
        ok &= q_pos - kv_pos < window
    s = jnp.where(ok, s, -jnp.inf)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[:, None])
    corr = jnp.exp(m_prev - m_safe)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    # unnormalized accumulate (the skewed-column contract): fp32 scratch,
    # no per-step normalization
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(jkv == n_kv - 1)
    def _normalize_once():
        o_ref[0, 0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def _blocks(n: int, cap: int, unit: int) -> tuple[int, int]:
    """(block, padded length) for an axis of length n: the fewest blocks of
    at most ~cap rows, each rounded up to `unit` (the Mosaic tile)."""
    nb = -(-n // max(1, cap))
    b = -(-n // nb)
    b = -(-b // unit) * unit
    return b, nb * b


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "cap", "q_offset", "bq",
                              "bkv", "interpret"))
def sa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool = True, window: int = 0, cap: float = 0.0,
                 q_offset: int = 0, bq: int = 512, bkv: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: (B, H, T, hd); k, v: (B, KVH, S, hd) → (B, H, T, hd) fp32.

    `bq`/`bkv` cap the block rows. Any T and S are served: the axes are
    split into the fewest blocks of at most that size and zero-padded to a
    whole number of blocks (on TPU each block rounded to the Mosaic tile: 16
    query rows, 128 kv rows, the kv rows being the score tile's lanes).
    Padded kv rows are masked out; padded query rows are sliced off."""
    B, H, T, hd = q.shape
    KVH, S = k.shape[1], k.shape[2]
    g = H // KVH
    scale = hd ** -0.5
    bq, Tp = _blocks(T, bq, 1 if interpret else 16)
    bkv, Sp = _blocks(S, bkv, 1 if interpret else 128)
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    grid = (B, H, Tp // bq, Sp // bkv)

    kernel = pl.pallas_call(
        functools.partial(_attn_kernel, n_kv=grid[3], bq=bq, bkv=bkv,
                          scale=scale, causal=causal, window=window, cap=cap,
                          q_offset=q_offset, kv_len=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    return kernel(q, k, v)[:, :, :T]
