"""Pallas flash-attention kernel vs naive oracle (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import sa_attention


def naive(q, k, v, causal=True, window=0, cap=0.0):
    B, H, T, hd = q.shape
    KVH, S = k.shape[1], k.shape[2]
    g = H // KVH
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * hd ** -0.5
    if cap:
        s = cap * jnp.tanh(s / cap)
    qp, kp = jnp.arange(T), jnp.arange(S)
    ok = jnp.ones((T, S), bool)
    if causal:
        ok &= qp[:, None] >= kp[None, :]
    if window:
        ok &= qp[:, None] - kp[None, :] < window
    s = jnp.where(ok[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv)


@pytest.mark.parametrize("kw", [
    dict(), dict(window=7), dict(cap=4.0), dict(causal=False),
    dict(window=5, cap=2.0)],
    ids=["causal", "window", "softcap", "bidir", "win+cap"])
@pytest.mark.parametrize("shape", [
    (1, 2, 2, 16, 16, 8),      # MHA
    (2, 4, 2, 32, 32, 16),     # GQA
    (1, 6, 3, 24, 48, 8),      # GQA, T != S, non-pow2
])
def test_sa_attention_vs_naive(kw, shape):
    B, H, KVH, T, S, hd = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, T, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KVH, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KVH, S, hd), jnp.float32)
    out = sa_attention(q, k, v, bq=8, bkv=8, **kw)
    ref = naive(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sa_attention_block_shape_invariance():
    B, H, KVH, T, hd = 1, 2, 1, 64, 8
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, H, T, hd))
    k = jax.random.normal(ks[1], (B, KVH, T, hd))
    v = jax.random.normal(ks[2], (B, KVH, T, hd))
    outs = [np.asarray(sa_attention(q, k, v, bq=bq, bkv=bkv))
            for bq, bkv in ((8, 8), (16, 32), (64, 64))]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-6, atol=2e-6)


def test_sa_attention_matches_model_blockwise():
    """Kernel ≡ the model's jnp blockwise attention (the path it replaces)."""
    from repro.core import PrecisionPolicy, use_policy
    from repro.models.layers import blockwise_attention
    with use_policy(PrecisionPolicy(input_format="fp32")):
        B, H, KVH, T, hd = 2, 4, 2, 32, 8
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (B, T, H, hd))
        k = jax.random.normal(ks[1], (B, T, KVH, hd))
        v = jax.random.normal(ks[2], (B, T, KVH, hd))
        jnp_out = blockwise_attention(q, k, v, causal=True, window=6,
                                      block_q=8, block_kv=8)
        krn_out = sa_attention(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3),
                               causal=True, window=6, bq=8, bkv=8)
        np.testing.assert_allclose(np.asarray(krn_out.transpose(0, 2, 1, 3)),
                                   np.asarray(jnp_out), rtol=3e-5, atol=3e-5)


def test_prefill_via_kernel_matches_jnp_path():
    """Flag-gated serving prefill through the Pallas kernel ≡ jnp path."""
    import dataclasses
    from repro.configs import reduced_config
    from repro.core import PrecisionPolicy, use_policy, optflags
    from repro.models import model as M

    cfg = dataclasses.replace(reduced_config("gemma2-9b"), remat=False)
    with use_policy(PrecisionPolicy(input_format="fp32")):
        params = M.init_params(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                  cfg.vocab_size)
        cache_a = M.init_cache(cfg, 2, 16, dtype=jnp.float32)
        logits_a, cache_a, _ = M.forward(params, cfg, toks, cache=cache_a)
        old = optflags.FLAGS["pallas_attention"]
        try:
            optflags.set_flag("pallas_attention", True)
            cache_b = M.init_cache(cfg, 2, 16, dtype=jnp.float32)
            logits_b, cache_b, _ = M.forward(params, cfg, toks, cache=cache_b)
        finally:
            optflags.set_flag("pallas_attention", old)
        np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b),
                                   rtol=2e-4, atol=2e-4)
        for a, b in zip(jax.tree.leaves(cache_a), jax.tree.leaves(cache_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def test_tpu_blocks_tile_aligned_for_every_length():
    """On TPU every prompt length gets Mosaic-legal blocks: query blocks of
    whole 16-row tiles, kv blocks of whole 128-lane score tiles, at most
    one block's worth of padding, and no block above the 512-row cap by
    more than one tile."""
    from repro.kernels.sa_attention import _blocks
    for n in range(1, 4097):
        for cap, unit in ((512, 16), (512, 128)):
            b, padded = _blocks(n, cap, unit)
            assert b % unit == 0 and padded % b == 0
            assert n <= padded < n + padded // b * unit
            assert b <= cap + unit - 1 or b <= unit
