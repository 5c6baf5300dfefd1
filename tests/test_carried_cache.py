"""The decode forward carries the stacked cache through its layer scan.

`model.forward` threads the cache through `lax.scan` as part of the carry
and each layer writes its rows in place at [layer, ...] of the stacked
leaves. These tests pin that to a per-layer reference — the scan slices
each superblock's cache off as an input and restacks the written layers as
its output — bit for bit, in the tokens of a multi-step decode chunk and in
every value of the cache it leaves behind."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import reduced_config
from repro.core import PrecisionPolicy, optflags, use_policy
from repro.core.precision import sa_dot
from repro.models import layers as L
from repro.models import model as M
from repro.train.step import make_verify_step

FP32 = PrecisionPolicy(input_format="fp32")
B, PSZ, N_PAGES, SEQ, STEPS = 3, 4, 12, 24, 4


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(reduced_config("qwen2.5-14b",
                                             layers_per_period=3),
                              remat=False)
    with use_policy(FP32):
        params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _per_layer_forward(params, cfg, tokens, cache, pos,
                       decode_multi=False):
    """`model.forward`'s decode arithmetic with the cache as the layer
    scan's xs and ys: each superblock gets its own one-layer stack, and the
    written layers are restacked into a fresh cache."""
    T = tokens.shape[1]
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)

    def superblock(x, xs):
        p_sb, c_sb = xs
        if optflags.enabled("bf16_params_in_layers"):
            p_sb = jax.tree.map(
                lambda w: w.astype(jnp.bfloat16)
                if w.ndim >= 2 and w.dtype == jnp.float32 else w, p_sb)
        new = []
        for j in range(cfg.stack_period):
            one = jax.tree.map(lambda a: a[None], c_sb[j])
            x, c = M._sublayer(x, p_sb[j], cfg, cfg.layer_kind(j), positions,
                               one, pos, None, decode_multi=decode_multi,
                               layer=0)
            new.append(jax.tree.map(lambda a: a[0], c))
        return x, tuple(new)

    x, cache = lax.scan(superblock, x, (params["layers"], cache))
    x = L.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    head = params.get("lm_head", params["embed"].T)
    logits = sa_dot(x.reshape(-1, cfg.d_model), head).reshape(
        x.shape[0], T, cfg.padded_vocab).astype(jnp.float32)
    valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(valid, logits, -jnp.inf), cache


def _carried_forward(params, cfg, tokens, cache, pos, decode_multi=False):
    logits, cache, _ = M.forward(params, cfg, tokens, cache=cache, pos=pos,
                                 decode_multi=decode_multi)
    return logits, cache


def _chunk(forward, params, cfg, tok, cache, pos):
    """STEPS greedy decode steps in one scan (the serve engine's chunk)."""
    def body(carry, _):
        tok, cache, pos = carry
        logits, cache = forward(params, cfg, tok[:, None], cache, pos)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        return (nxt, cache, pos + 1), nxt

    (_, cache, _), toks = lax.scan(body, (tok, cache, pos), length=STEPS)
    return toks, cache


def _filled(cache, rng, depth, block_rows=None):
    """Random K/V everywhere; positions (and block tables) for slots at
    `depth[b]` tokens — a depth of 0 is a free slot."""
    def fill(leaf):
        if not isinstance(leaf, (L.KVCache, L.PagedKVCache)):
            return leaf
        n = leaf.k.shape[0]
        k = rng.standard_normal(leaf.k.shape).astype(np.float32)
        v = rng.standard_normal(leaf.v.shape).astype(np.float32)
        if isinstance(leaf, L.KVCache):
            S = leaf.positions.shape[-1]
            s = np.arange(S)[None]
            pp = np.where(s < np.asarray(depth)[:, None], s, -1)
            return L.KVCache(jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(np.broadcast_to(pp, (n, B, S))))
        bt = np.full(leaf.block_table.shape[1:], -1, np.int32)
        pp = np.full(leaf.positions.shape[1:], -1, np.int32)
        for b, pages in enumerate(block_rows):
            bt[b, :len(pages)] = pages
            for i, page in enumerate(pages):
                t = i * PSZ + np.arange(PSZ)
                pp[page] = np.where(t < depth[b], t, -1)
        return L.PagedKVCache(
            jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(np.broadcast_to(pp, (n, *pp.shape))),
            jnp.asarray(np.broadcast_to(bt, (n, *bt.shape))))

    return jax.tree.map(fill, cache, is_leaf=lambda x: isinstance(
        x, (L.KVCache, L.PagedKVCache)))


def _pool_leaf(cache) -> L.PagedKVCache:
    return next(x for x in jax.tree.leaves(
        cache, is_leaf=lambda x: isinstance(x, L.PagedKVCache))
        if isinstance(x, L.PagedKVCache))


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# slot 0 at 9 tokens, slot 1 at 14 (both grow across a page boundary
# inside the chunk), slot 2 free: its block table is unmapped, so each of
# its writes lands on the trash page 0
DEPTH = [9, 14, 0]
ROWS = [[3, 7, 1, 5], [2, 9, 4, 11], []]


@pytest.mark.parametrize("layout,impl", [("paged", "fused"),
                                         ("paged", "gather"),
                                         ("ring", None)])
def test_carried_chunk_matches_per_layer(setup, monkeypatch, layout, impl):
    cfg, params = setup
    if impl:
        monkeypatch.setenv("REPRO_DECODE_ATTN", impl)
    paged = (N_PAGES, PSZ) if layout == "paged" else None
    rng = np.random.default_rng(7)
    cache = _filled(M.init_cache(cfg, B, SEQ, dtype=jnp.float32,
                                 paged=paged), rng, DEPTH, ROWS)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    pos = jnp.asarray(DEPTH, jnp.int32)
    with use_policy(FP32):
        got = jax.jit(lambda p, t, c, q: _chunk(
            _carried_forward, p, cfg, t, c, q))(params, tok, cache, pos)
        want = jax.jit(lambda p, t, c, q: _chunk(
            _per_layer_forward, p, cfg, t, c, q))(params, tok, cache, pos)
    _assert_same(got, want)
    if layout == "paged":
        # the free slot's rows reached the trash page, in every layer
        trash = np.asarray(_pool_leaf(got[1]).positions)[:, 0]
        assert (trash >= 0).any(axis=-1).all()


def test_carried_verify_matches_per_layer(setup):
    """Speculative verify (decode_multi): a (B, 3)-token block writes and
    attends at pos..pos+2 of every slot, the free slot on the trash page."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    cache = _filled(M.init_cache(cfg, B, SEQ, dtype=jnp.float32,
                                 paged=(N_PAGES, PSZ)), rng, DEPTH, ROWS)
    block = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 3)), jnp.int32)
    pos = jnp.asarray(DEPTH, jnp.int32)
    with use_policy(FP32):
        got = jax.jit(make_verify_step(cfg))(params, block, cache, pos)
        want = jax.jit(lambda p, t, c, q: _per_layer_forward(
            p, cfg, t, c, q, decode_multi=True))(params, block, cache, pos)
    _assert_same(got, want)
