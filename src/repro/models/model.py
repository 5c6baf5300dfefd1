"""Model assembly: decoder-only LM, hybrid (attn∥SSM), MoE, and enc-dec.

Layers are grouped into repeating *superblocks* (`cfg.stack_period` layers —
e.g. gemma3's 5 local + 1 global, llama4's dense+MoE pair) and scanned with
stacked parameters: HLO size is O(superblock), independent of depth — the
production pattern that keeps 48-layer × 512-device compiles fast.

Parameters are plain nested dicts (fp32 masters; forward casts via the SA
precision policy). `abstract_params` builds ShapeDtypeStructs via
`jax.eval_shape` so the dry-run never allocates.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import sa_dot
from repro.parallel import sharding as S_
from .config import ArchConfig
from . import layers as L
from .layers import KVCache
from .moe import moe_ffn
from .ssm import mamba2_block


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _dense(rng, fan_in, shape, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * (fan_in ** -0.5)


def _init_attn(rng, cfg: ArchConfig):
    d, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    p = {
        "wq": _dense(ks[0], d, (d, H * hd)),
        "wk": _dense(ks[1], d, (d, KVH * hd)),
        "wv": _dense(ks[2], d, (d, KVH * hd)),
        "wo": _dense(ks[3], H * hd, (H * hd, d)),
    }
    if cfg.qkv_bias:
        p |= {"bq": jnp.zeros((H * hd,)), "bk": jnp.zeros((KVH * hd,)),
              "bv": jnp.zeros((KVH * hd,))}
    return p


def _init_ffn(rng, cfg: ArchConfig, d_ff: int):
    d = cfg.d_model
    ks = jax.random.split(rng, 3)
    if cfg.family == "audio":   # classic 2-layer MLP (whisper)
        return {"w1": _dense(ks[0], d, (d, d_ff)),
                "w2": _dense(ks[1], d_ff, (d_ff, d))}
    return {"wg": _dense(ks[0], d, (d, d_ff)),
            "wu": _dense(ks[1], d, (d, d_ff)),
            "wd": _dense(ks[2], d_ff, (d_ff, d))}


def _init_moe(rng, cfg: ArchConfig):
    d, E, F = cfg.d_model, cfg.n_held_experts, cfg.d_ff
    ks = jax.random.split(rng, 5)
    p = {
        "router": _dense(ks[0], d, (d, cfg.num_experts)),
        "wg": _dense(ks[1], d, (E, d, F)),
        "wu": _dense(ks[2], d, (E, d, F)),
        "wd": _dense(ks[3], F, (E, F, d)),
    }
    if cfg.shared_expert:
        sk = jax.random.split(ks[4], 3)
        Fs = cfg.d_ff_shared or F
        p |= {"shared_wg": _dense(sk[0], d, (d, Fs)),
              "shared_wu": _dense(sk[1], d, (d, Fs)),
              "shared_wd": _dense(sk[2], Fs, (Fs, d))}
    return p


def _init_ssm(rng, cfg: ArchConfig):
    d, din, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    ks = jax.random.split(rng, 3)
    # mamba2 convolves the [x|B|C] concat (width din + 2N); z and dt skip it
    conv_dim = din + 2 * N
    p = {
        "in_proj": _dense(ks[0], d, (d, 2 * din + 2 * N + H)),
        "conv_w": jax.random.normal(ks[1], (4, conv_dim)) * 0.1,
        "dt_bias": jnp.zeros((H,)),
        "A_log": jnp.zeros((H,)),
        "norm_w": jnp.ones((din,)),
        "out_proj": _dense(ks[2], din, (din, d)),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((conv_dim,))
    if cfg.ssm_head_d:
        p["D"] = jnp.ones((H,))
    else:
        p["D_skip"] = jnp.ones((din,))
    return p


def _norm_p(cfg):
    p = {"w": jnp.ones((cfg.d_model,))}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((cfg.d_model,))
    return p


def init_layer(rng, cfg: ArchConfig, meta: dict, cross: bool = False):
    ks = jax.random.split(rng, 6)
    p: dict[str, Any] = {"norm1": _norm_p(cfg), "norm2": _norm_p(cfg)}
    if cfg.family == "ssm":
        p["ssm"] = _init_ssm(ks[0], cfg)
        p.pop("norm2")
        return p
    mixer = meta.get("mixer", "attention")
    if mixer == "hybrid":
        p["attn"] = _init_attn(ks[0], cfg)
        p["ssm"] = _init_ssm(ks[1], cfg)
        p["attn_norm"] = {"w": jnp.ones((cfg.d_model,))}
        p["ssm_norm"] = {"w": jnp.ones((cfg.d_model,))}
    elif mixer == "mamba":
        p["ssm"] = _init_ssm(ks[1], cfg)
    else:
        p["attn"] = _init_attn(ks[0], cfg)
    if cross:
        p["cross"] = _init_attn(ks[2], cfg)
        p["norm_cross"] = _norm_p(cfg)
    if meta["moe"]:
        p["moe"] = _init_moe(ks[3], cfg)
    else:
        p["ffn"] = _init_ffn(ks[4], cfg, cfg.d_ff_dense or cfg.d_ff)
    return p


def init_params(rng, cfg: ArchConfig, dtype=jnp.float32):
    """Full parameter tree; repeated superblocks stacked on axis 0."""
    period = cfg.stack_period
    n_super = cfg.num_layers // period
    assert n_super * period == cfg.num_layers, (cfg.num_layers, period)
    k_emb, k_out, k_layers, k_enc = jax.random.split(rng, 4)

    def one_superblock(k):
        ks = jax.random.split(k, period)
        return tuple(init_layer(ks[j], cfg, cfg.layer_kind(j),
                                cross=cfg.is_encdec) for j in range(period))

    blocks = jax.vmap(one_superblock)(jax.random.split(k_layers, n_super))
    params: dict[str, Any] = {
        "embed": jax.random.normal(
            k_emb, (cfg.padded_vocab, cfg.d_model)) * 0.02,
        "final_norm": _norm_p(cfg),
        "layers": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(k_out, cfg.d_model,
                                   (cfg.d_model, cfg.padded_vocab))
    if cfg.is_encdec:
        def enc_block(k):
            return init_layer(k, cfg, {"attn": "global", "moe": False})
        params["encoder"] = {
            "layers": jax.vmap(enc_block)(
                jax.random.split(k_enc, cfg.encoder_layers)),
            "final_norm": _norm_p(cfg),
        }
    return jax.tree.map(lambda x: x.astype(dtype), params)


def abstract_params(cfg: ArchConfig, dtype=jnp.float32):
    return jax.eval_shape(
        functools.partial(init_params, cfg=cfg, dtype=dtype),
        jax.random.key(0))


# ---------------------------------------------------------------------------
# Caches (decode)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16, abstract: bool = False,
               kv_pad_to: int = 1,
               paged: tuple[int, int] | None = None):
    """Stacked per-layer cache. Local layers get ring buffers of `window`.

    Every leaf carries the batch dimension at axis 1 (after the stacked
    superblock axis) — including the per-slot KV `positions` — so the serve
    engine can splice one request's cache fragment into batch row `slot` of
    every leaf with a single dynamic-update-slice (continuous batching).

    `kv_pad_to`: TP axis size — KV heads padded up so the cache shards over
    the model axis without per-step resharding (optflags: pad_kv_heads).

    `paged`: `(n_pages, page_size)` — global-attention layers become a
    shared `PagedKVCache` page pool plus a per-slot block table instead of
    per-slot rings (DESIGN.md §5). `seq_len` then caps a single request
    (`max_pages = ceil(seq_len / page_size)` block-table columns) while
    total capacity is the pool's `n_pages · page_size` tokens, shared
    across slots. Local-window layers keep their dense rings (already
    bounded by `window`, they never strand capacity) and SSM/conv state
    stays per-slot, so the engine's fragment splice handles mixed leaves."""
    from repro.models.layers import PagedKVCache, padded_kvh
    period = cfg.stack_period
    n_super = cfg.num_layers // period
    kvh = padded_kvh(cfg.num_kv_heads, kv_pad_to)

    def mk(shape, dt=dtype, fill=0):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.full(shape, fill, dt)

    def layer_cache(j):
        meta = cfg.layer_kind(j)
        c = {}
        if meta["mixer"] != "mamba":
            S = min(cfg.window, seq_len) if meta["attn"] == "local" else seq_len
            if paged is not None and not (meta["attn"] == "local"
                                          and cfg.window
                                          and cfg.window < seq_len):
                n_pages, psz = paged
                max_pages = -(-seq_len // psz)
                c["kv"] = PagedKVCache(
                    k=mk((n_super, n_pages, psz, kvh, cfg.hd)),
                    v=mk((n_super, n_pages, psz, kvh, cfg.hd)),
                    positions=mk((n_super, n_pages, psz), jnp.int32, -1),
                    block_table=mk((n_super, batch, max_pages),
                                   jnp.int32, -1))
            else:
                c["kv"] = KVCache(
                    k=mk((n_super, batch, S, kvh, cfg.hd)),
                    v=mk((n_super, batch, S, kvh, cfg.hd)),
                    positions=mk((n_super, batch, S), jnp.int32, -1))
        if meta["mixer"] != "attention":
            c["ssm"] = (
                mk((n_super, batch, cfg.n_ssm_heads, cfg.ssm_head_dim,
                    cfg.ssm_state), jnp.float32),
                mk((n_super, batch, 3, cfg.d_inner + 2 * cfg.ssm_state)))
        if cfg.is_encdec:
            c["cross"] = KVCache(
                k=mk((n_super, batch, cfg.frontend_tokens, cfg.num_kv_heads,
                      cfg.hd)),
                v=mk((n_super, batch, cfg.frontend_tokens, cfg.num_kv_heads,
                      cfg.hd)),
                positions=mk((n_super, batch, cfg.frontend_tokens),
                             jnp.int32, -1))
        return c

    return tuple(layer_cache(j) for j in range(period))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _sublayer(x, p, cfg, meta, positions, cache, pos, encoder_out,
              prefix_len: int = 0, decode_multi: bool = False, layer=None,
              n_valid=None):
    """One transformer layer. Returns (x, new_cache).

    `cache` holds the whole stack's leaves for this sublayer (leading
    superblock axis) and `layer` is the superblock's index: every write
    lands at [layer, ...] of the stacked leaves, and leaves the layer does
    not write pass through. `n_valid` (traced) counts the real rows of a
    right-padded prefill block: padding leaves recurrent state as it is."""
    new_cache: dict[str, Any] = dict(cache) if cache is not None else {}
    h = L.norm_apply(x, p["norm1"], cfg.norm, cfg.norm_eps)
    mixer = meta.get("mixer", "attention")
    if cfg.family == "ssm":
        mix, new_cache["ssm"] = _ssm_mix(h, p["ssm"], cfg, cache, layer,
                                         n_valid)
        return x + mix.astype(x.dtype), (new_cache if cache is not None else None)
    kv_cache = cache.get("kv") if cache else None
    attend = functools.partial(
        L.attention_block, cfg=cfg, meta=meta, positions=positions,
        cache=kv_cache, pos=pos, rope=cfg.position_embedding == "rope",
        prefix_len=prefix_len, decode_multi=decode_multi, layer=layer)
    if mixer == "hybrid":
        a, kv = attend(h, p["attn"])
        s, new_cache["ssm"] = _ssm_mix(h, p["ssm"], cfg, cache, layer,
                                       n_valid)
        mix = 0.5 * (L.rmsnorm(a, p["attn_norm"]["w"], cfg.norm_eps)
                     + L.rmsnorm(s, p["ssm_norm"]["w"], cfg.norm_eps))
    elif mixer == "mamba":
        mix, new_cache["ssm"] = _ssm_mix(h, p["ssm"], cfg, cache, layer,
                                         n_valid)
    else:
        mix, kv = attend(h, p["attn"])
    if cache is not None and mixer != "mamba":
        new_cache["kv"] = kv
    x = x + mix.astype(x.dtype)
    if cfg.is_encdec and encoder_out is not None:
        h = L.norm_apply(x, p["norm_cross"], cfg.norm, cfg.norm_eps)
        ca, cross_kv = L.attention_block(
            h, p["cross"], cfg, {"attn": "global"}, positions,
            cache=None, rope=False, causal=False,
            kv_override=_encoder_kv(p["cross"], cfg, encoder_out))
        x = x + ca.astype(x.dtype)
    h = L.norm_apply(x, p["norm2"], cfg.norm, cfg.norm_eps)
    aux = None
    if meta["moe"]:
        from repro.core import optflags
        # serving (cache threaded) routes MoE through the dropless dispatch:
        # capacity-drop is a training-time approximation that breaks
        # prefill+decode ≡ full-forward exactness (and drops user tokens)
        dropless = cfg.moe_dropless or (
            cache is not None and optflags.enabled("moe_dropless_serve"))
        f, aux = moe_ffn(h, p["moe"], cfg, cfg.act, dropless=dropless)
    elif cfg.family == "audio":
        f = L.ffn_mlp(h, p["ffn"], "gelu")
    else:
        f = L.ffn_swiglu(h, p["ffn"], cfg.act)
    return x + f.astype(x.dtype), (new_cache if cache is not None else aux)


def _ssm_mix(h, p, cfg, cache, layer, n_valid=None):
    """Mamba2 mixer on superblock `layer` of a stacked ssm/conv state.
    Returns (mix, the updated stacked state, or None without a cache)."""
    ssm = cache.get("ssm") if cache else None
    if ssm is None:
        return mamba2_block(h, p, cfg, n_valid=n_valid)[0], None
    mix, st = mamba2_block(h, p, cfg, *(L.at_layer(a, layer) for a in ssm),
                           n_valid=n_valid)
    return mix, tuple(L.set_layer(a, new, layer) for a, new in zip(ssm, st))


def _encoder_kv(p, cfg, encoder_out):
    B, S, _ = encoder_out.shape
    k = sa_dot(encoder_out.reshape(B * S, -1),
               p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.hd)
    v = sa_dot(encoder_out.reshape(B * S, -1),
               p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.hd)
    return k, v


def _sinusoid(T, d):
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None]
    ang = pos / (10000.0 ** (dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)[None]


def encode(params, cfg: ArchConfig, frontend_embeds):
    """Encoder stack over stub frontend embeddings (B, S, d_model)."""
    x = frontend_embeds + _sinusoid(frontend_embeds.shape[1],
                                    cfg.d_model).astype(frontend_embeds.dtype)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                 (x.shape[0], x.shape[1]))

    def body(h, p):
        h2 = L.norm_apply(h, p["norm1"], cfg.norm, cfg.norm_eps)
        a, _ = L.attention_block(h2, p["attn"], cfg, {"attn": "global"},
                                 positions, rope=False, causal=False)
        h = h + a.astype(h.dtype)
        h2 = L.norm_apply(h, p["norm2"], cfg.norm, cfg.norm_eps)
        h = h + L.ffn_mlp(h2, p["ffn"], "gelu").astype(h.dtype)
        return h, None

    x, _ = lax.scan(body, x, params["encoder"]["layers"])
    return L.norm_apply(x, params["encoder"]["final_norm"], cfg.norm,
                        cfg.norm_eps)


def forward(params, cfg: ArchConfig, tokens, *, positions=None, cache=None,
            pos=None, frontend_embeds=None, last_only: bool = False,
            last_index=None, prefix_len: int = 0, decode_multi: bool = False,
            valid_len=None):
    """Token ids (B, T) → logits. Returns (logits, new_cache, aux).

    `cache`/`pos` engage the decode path; `pos` is a (B,) int32 vector of
    per-sequence positions (each batch row — serving *slot* — may be at its
    own depth; a scalar is broadcast for single-sequence callers).
    `frontend_embeds` feeds the modality stub (vlm: prepended to the text
    sequence; audio: encoder input for cross-attention). `prefix_len`
    (static) is the continued-prefill offset: `tokens` holds only a
    prompt's uncached suffix and the dense cache's first `prefix_len` rows
    hold pre-loaded KV (serve prefix-cache hits; see layers.attention_block).
    `decode_multi` (static) marks the T tokens as T consecutive *decode*
    steps per slot (speculative verify, DESIGN.md §9) instead of a prefill
    fragment — row t writes and attends at position pos+t. `last_index`
    (traced, used with `last_only`) selects WHICH row feeds the lm_head
    instead of the static -1: bucketed prefill (serve prompt-length
    bucketing) right-pads the token block, so the real prompt's logits
    live at row `last_index`, not the padded block's end. `valid_len`
    (traced) is such a prefill's real absolute length: recurrent layers
    leave their state as it stands at that position (model._sublayer).
    """
    B, T = tokens.shape
    if decode_multi and cfg.recurrent:
        # a rejected draft would leave the recurrent state advanced past
        # the rollback point; attention caches roll back by position,
        # ssm states cannot — the serve engine gates spec decoding off
        # for these families (ServeEngine.spec_decoding_on)
        raise ValueError("decode_multi needs rollback-by-position; "
                         "ssm/hybrid recurrent state cannot roll back")
    compute_dtype = jnp.bfloat16
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    x = S_.constrain(x, "batch", None, None)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
    encoder_out = None
    if cfg.family == "vlm" and frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(compute_dtype), x], axis=1)
        T = x.shape[1]
    elif cfg.is_encdec and frontend_embeds is not None:
        encoder_out = encode(params, cfg, frontend_embeds.astype(compute_dtype))
    if pos is None and prefix_len:
        # continued prefill: positions (and a 1-token suffix's decode-path
        # write) start at the first uncached token
        pos = prefix_len
    if pos is not None:
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if positions is None:
        if pos is not None:
            positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        else:
            positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    period = cfg.stack_period
    n_super = jax.tree.leaves(params["layers"])[0].shape[0]
    n_valid = None if valid_len is None else valid_len - prefix_len

    def superblock(carry, xs):
        # the cache rides in the carry, not in xs/ys: each layer writes its
        # rows in place at [i, ...] of the stacked leaves (a decode step's
        # B rows scatter into the donated pool) instead of slicing its
        # layer out and restacking a fresh copy of the whole cache
        x, cache = carry
        p_sb, i = xs
        x = S_.constrain(x, "batch", None, None)  # pin the residual stream
        from repro.core import optflags
        if optflags.enabled("bf16_params_in_layers"):
            # cast matrices to bf16 *before* use so FSDP all-gathers move
            # bf16 payloads (2× ICI saving; numerically identical — sa_dot
            # quantizes to bf16 at consumption anyway). 1-D leaves (norms,
            # dt_bias, A_log) stay fp32.
            p_sb = jax.tree.map(
                lambda w: w.astype(jnp.bfloat16)
                if (hasattr(w, "ndim") and w.ndim >= 2
                    and w.dtype == jnp.float32) else w, p_sb)
        new_caches = []
        aux_acc = jnp.zeros((2,), jnp.float32)
        for j in range(period):
            c_j = None if cache is None else cache[j]
            x, extra = _sublayer(x, p_sb[j], cfg, cfg.layer_kind(j),
                                 positions, c_j, pos, encoder_out,
                                 prefix_len, decode_multi, layer=i,
                                 n_valid=n_valid)
            if cache is not None:
                new_caches.append(extra)
            elif isinstance(extra, dict):   # moe aux losses
                aux_acc = aux_acc + jnp.stack(
                    [extra["load_balance"], extra["router_z"]])
        return (x, tuple(new_caches) if cache is not None else None), aux_acc

    if cfg.remat and cache is None:   # remat for training only
        superblock = jax.checkpoint(
            superblock, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    # the scan runs the superblocks of `params` — a prefix of the cache's
    # stack when a caller passes fewer layers (the early-exit draft)
    (x, new_cache), aux_sb = lax.scan(
        superblock, (x, cache),
        (params["layers"], jnp.arange(n_super, dtype=jnp.int32)))
    aux = {"load_balance": jnp.sum(aux_sb[:, 0]),
           "router_z": jnp.sum(aux_sb[:, 1])}
    x = L.norm_apply(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if last_only:
        if last_index is not None:
            x = lax.dynamic_slice_in_dim(
                x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
        else:
            x = x[:, -1:]
    head = params.get("lm_head", None)
    if head is None:
        head = params["embed"].T
    logits = sa_dot(x.reshape(-1, cfg.d_model),
                    head).reshape(x.shape[0], x.shape[1], cfg.padded_vocab)
    logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if cfg.padded_vocab != cfg.vocab_size:   # mask padding logits (no reshard)
        valid = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(valid, logits, -jnp.inf)
    return logits, new_cache, aux


def lm_loss(params, cfg: ArchConfig, tokens, labels, *, frontend_embeds=None,
            aux_weight: float = 0.01):
    """Next-token cross-entropy (fp32 logsumexp) + MoE aux losses."""
    logits, _, aux = forward(params, cfg, tokens,
                             frontend_embeds=frontend_embeds)
    if cfg.family == "vlm" and frontend_embeds is not None:
        logits = logits[:, frontend_embeds.shape[1]:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - gold)
    return nll + aux_weight * (aux["load_balance"] + aux["router_z"]), nll
