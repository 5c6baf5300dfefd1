"""Model layers. Every GEMM routes through the SA precision policy
(`repro.core.precision.sa_dot` / `sa_einsum`) — the paper's reduced-precision
chained-accumulate contract is the framework's arithmetic everywhere.

Attention is flash-style blockwise (two-level `lax.scan`, online softmax in
fp32): O(T·block) memory, compiles at 32k/500k sequence lengths, and maps the
"never materialize the unnormalized chain" idea to the softmax accumulator.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import sa_dot, sa_einsum

# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = ((x32 - mu) * lax.rsqrt(var + eps) * w.astype(jnp.float32)
         + b.astype(jnp.float32))
    return y.astype(x.dtype)


def norm_apply(x, p, kind="rmsnorm", eps=1e-6):
    if kind == "layernorm":
        return layernorm(x, p["w"], p["b"], eps)
    return rmsnorm(x, p["w"], eps)


def act_fn(x, kind="silu"):
    # one activation table for fused and unfused paths: drift between the
    # two would break the fused_epilogue flag's numerics-preserving A/B
    from repro.kernels.sa_matmul import EPILOGUES, apply_act
    if kind not in EPILOGUES or kind == "none":
        raise ValueError(f"unknown activation {kind!r}")
    return apply_act(x, kind)


def _fuse_epilogue() -> bool:
    from repro.core import optflags
    return optflags.enabled("fused_epilogue")


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap else x


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)


def apply_rope(x, positions, theta: float):
    """x: (..., T, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise attention (training / prefill)
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, kv_pos, causal: bool, window: int):
    """(bq, bkv) additive bias: 0 where visible, -inf where masked."""
    ok = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= kv_pos[None, :]
    if window:
        ok &= q_pos[:, None] - kv_pos[None, :] < window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def _div_block(n, target):  # largest divisor of n that is <= target
    b = min(target, n)
    while n % b:
        b -= 1
    return b


def _scores(q_i, k_j, q_pos, kv_pos, causal, window, cap, scale):
    """Raw + masked-capped scores for one (q-block, kv-block) tile."""
    s_raw = sa_einsum("bqhgd,bkhd->bhgqk", q_i, k_j).astype(jnp.float32)
    s = softcap(s_raw * scale, cap)
    s = s + _mask_bias(q_pos, kv_pos, causal, window)[None, None, None]
    return s_raw, s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, cap, q_offset, bq, bkv, scale):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, cap, q_offset, bq, bkv,
                             scale)
    return out


def _flash_fwd_impl(q, k, v, causal, window, cap, q_offset, bq, bkv, scale):
    """Online-softmax forward. Returns (out (B,KVH,g,T,hd), lse)."""
    B, T, KVH, g, hd = q.shape
    S = k.shape[1]
    nq, nkv = T // bq, S // bkv
    qb = q.reshape(B, nq, bq, KVH, g, hd)
    kb, vb = (x.reshape(B, nkv, bkv, KVH, hd) for x in (k, v))

    def q_step(_, qi):
        q_i, iq = qi
        q_pos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, kvj):
            acc, m, l = carry
            k_j, v_j, jk = kvj
            kv_pos = jk * bkv + jnp.arange(bkv)
            _, s = _scores(q_i, k_j, q_pos, kv_pos, causal, window, cap, scale)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # fully-masked tiles (sliding windows) leave m_new = -inf; the
            # guard keeps exp() at exactly 0 instead of NaN
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            # online softmax: the running (unnormalized) accumulator is
            # normalized once at the end — the softmax analogue of the
            # round-once-per-column reduction.
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(m - m_safe)
            l = l * corr + jnp.sum(p, axis=-1)
            pv = sa_einsum("bhgqk,bkhd->bhgqd", p.astype(q.dtype), v_j)
            acc = acc * corr[..., None] + pv.astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, KVH, g, bq, hd), jnp.float32)
        m0 = jnp.full((B, KVH, g, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KVH, g, bq), jnp.float32)
        (acc, m, l), _ = lax.scan(
            kv_step, (acc0, m0, l0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nkv)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(q.dtype), lse)

    _, (blocks, lses) = lax.scan(q_step, None,
                                 (qb.swapaxes(0, 1), jnp.arange(nq)))
    # blocks: (nq, B, KVH, g, bq, hd) → (B, KVH, g, T, hd)
    out = blocks.transpose(1, 2, 3, 0, 4, 5).reshape(B, KVH, g, T, hd)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, KVH, g, T)
    return out, lse


def _flash_fwd(q, k, v, causal, window, cap, q_offset, bq, bkv, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, cap, q_offset, bq,
                               bkv, scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, cap, q_offset, bq, bkv, scale, res, dout):
    """Flash backward: recompute p per tile; O(block²) memory.

    dq pass scans q blocks (kv inner); dk/dv pass scans kv blocks (q inner).
    """
    q, k, v, out, lse = res
    B, T, KVH, g, hd = q.shape
    S = k.shape[1]
    nq, nkv = T // bq, S // bkv
    qb = q.reshape(B, nq, bq, KVH, g, hd)
    kb, vb = (x.reshape(B, nkv, bkv, KVH, hd) for x in (k, v))
    doutb = dout.reshape(B, KVH, g, nq, bq, hd)
    lseb = lse.reshape(B, KVH, g, nq, bq)
    # delta = rowsum(dout ⊙ out)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)
    deltab = delta.reshape(B, KVH, g, nq, bq)

    def p_and_ds(q_i, k_j, v_j, do_i, lse_i, dl_i, iq, jk):
        q_pos = q_offset + iq * bq + jnp.arange(bq)
        kv_pos = jk * bkv + jnp.arange(bkv)
        s_raw, s = _scores(q_i, k_j, q_pos, kv_pos, causal, window, cap, scale)
        p = jnp.exp(s - lse_i[..., None])                      # (B,h,g,bq,bkv)
        dp = sa_einsum("bhgqd,bkhd->bhgqk", do_i, v_j).astype(jnp.float32)
        ds = p * (dp - dl_i[..., None])
        if cap:   # softcap jacobian: d tanh = 1 - tanh²
            ds = ds * (1.0 - (softcap(s_raw * scale, cap) / cap) ** 2)
        return p, ds * scale

    def dq_step(_, xs):
        q_i, do_i, lse_i, dl_i, iq = xs

        def inner(dq_acc, kvj):
            k_j, v_j, jk = kvj
            _, ds = p_and_ds(q_i, k_j, v_j, do_i, lse_i, dl_i, iq, jk)
            dq_acc += sa_einsum("bhgqk,bkhd->bqhgd", ds.astype(q.dtype), k_j
                                ).astype(jnp.float32)
            return dq_acc, None

        dq0 = jnp.zeros((B, bq, KVH, g, hd), jnp.float32)
        dq_i, _ = lax.scan(inner, dq0, (kb.swapaxes(0, 1),
                                        vb.swapaxes(0, 1), jnp.arange(nkv)))
        return None, dq_i

    _, dq_blocks = lax.scan(
        dq_step, None,
        (qb.swapaxes(0, 1), doutb.transpose(3, 0, 1, 2, 4, 5),
         lseb.transpose(3, 0, 1, 2, 4), deltab.transpose(3, 0, 1, 2, 4),
         jnp.arange(nq)))
    dq = dq_blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, KVH, g, hd)

    def dkv_step(_, xs):
        k_j, v_j, jk = xs

        def inner(carry, qs):
            dk_acc, dv_acc = carry
            q_i, do_i, lse_i, dl_i, iq = qs
            p, ds = p_and_ds(q_i, k_j, v_j, do_i, lse_i, dl_i, iq, jk)
            dv_acc += sa_einsum("bhgqk,bhgqd->bkhd", p.astype(q.dtype), do_i
                                ).astype(jnp.float32)
            dk_acc += sa_einsum("bhgqk,bqhgd->bkhd", ds.astype(q.dtype), q_i
                                ).astype(jnp.float32)
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, bkv, KVH, hd), jnp.float32)
        (dk_j, dv_j), _ = lax.scan(
            inner, (z, z),
            (qb.swapaxes(0, 1), doutb.transpose(3, 0, 1, 2, 4, 5),
             lseb.transpose(3, 0, 1, 2, 4), deltab.transpose(3, 0, 1, 2, 4),
             jnp.arange(nq)))
        return None, (dk_j, dv_j)

    _, (dk_blocks, dv_blocks) = lax.scan(
        dkv_step, None, (kb.swapaxes(0, 1), vb.swapaxes(0, 1),
                         jnp.arange(nkv)))
    dk = dk_blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, KVH, hd)
    dv = dv_blocks.transpose(1, 0, 2, 3, 4).reshape(B, S, KVH, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def blockwise_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                        q_offset=0, block_q=1024, block_kv=1024, scale=None):
    """q: (B, T, H, hd); k, v: (B, S, KVH, hd) → (B, T, H, hd).

    Flash-style attention with a custom VJP: forward keeps only (out, lse);
    backward recomputes probabilities tile-by-tile — O(T·block) memory in
    both passes at any sequence length. GQA via grouped query heads; all
    contractions under the SA contract (bf16 in, fp32 accumulate).
    """
    B, T, H, hd = q.shape
    S, KVH = k.shape[1], k.shape[2]
    g = H // KVH
    scale = scale or hd ** -0.5
    bq, bkv = _div_block(T, block_q), _div_block(S, block_kv)
    qg = q.reshape(B, T, KVH, g, hd)
    out = _flash(qg, k, v, causal, window, cap, q_offset, bq, bkv, scale)
    # (B, KVH, g, T, hd) → (B, T, H, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)


def decode_attention(q, k_cache, v_cache, kv_positions, pos, *, window=0,
                     cap=0.0, scale=None):
    """Single-token attention against a (possibly ring-buffer) cache.

    q: (B, 1, H, hd); caches: (B, S, KVH, hd); kv_positions: (B, S) original
    token position per cache slot (-1 = empty); pos: (B,) per-sequence
    current position — rows of the batch may sit at different depths
    (continuous batching: each slot serves an independent request).
    """
    B, _, H, hd = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    g = H // KVH
    scale = scale or hd ** -0.5
    qg = q.reshape(B, KVH, g, hd)
    s = sa_einsum("bhgd,bkhd->bhgk", qg, k_cache).astype(jnp.float32)
    # softcap with the constants folded on the host: a mul→div→tanh chain
    # is NOT fusion-stable on XLA CPU (eager vs jit codegen round the
    # intermediate differently), while single-mul→tanh is — and the fused
    # paged kernel computes this exact expression, so the bit-parity pin
    # (tests/test_decode_kernel.py) holds in every execution regime
    s = cap * jnp.tanh(s * (scale / cap)) if cap else s * scale
    ok = (kv_positions >= 0) & (kv_positions <= pos[:, None])
    if window:
        ok &= kv_positions > pos[:, None] - window
    s = jnp.where(ok[:, None, None, :], s, -jnp.inf)
    # safe-row softmax: a slot with zero valid cache entries (freshly freed
    # slot, wholly-unmapped block table) is a row of -inf, which
    # jax.nn.softmax turns into NaNs. Guarding the max keeps exp() at
    # exactly 0 and the floor on the normalizer yields an all-zero row;
    # non-empty rows have l >= 1 (the max element contributes exp(0) = 1),
    # so the maximum() never engages and the result is bit-identical to
    # jax.nn.softmax. The fused paged kernel carries the same guard.
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = sa_einsum("bhgk,bkhd->bhgd", p.astype(q.dtype), v_cache)
    return out.reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + attention)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # (B, S_cache, KVH, hd)
    v: jax.Array
    positions: jax.Array  # (B, S_cache) int32 per-slot positions, -1 = empty


class PagedKVCache(NamedTuple):
    """Paged KV layout: a global page pool shared by every batch slot.

    Slots own whole pages via `block_table`; a short request maps few pages
    while a long neighbour maps many — capacity is pooled instead of each
    slot owning a full fixed-length ring (DESIGN.md §5). Page 0 is the
    reserved *trash* page: decode writes from slots with no mapped page for
    their current position (free / just-retired slots keep decoding until
    the next scheduler tick) land there, and it is never handed out by the
    allocator, so a stale write can never corrupt a live request.
    """
    k: jax.Array            # (n_pages, page_size, KVH, hd)
    v: jax.Array
    positions: jax.Array    # (n_pages, page_size) int32, -1 = empty
    block_table: jax.Array  # (B, max_pages) int32 page ids, -1 = unmapped


def at_layer(buf, layer):
    """Superblock `layer` of a stacked cache leaf (leading axis: the
    superblocks of `model.init_cache`)."""
    return lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def set_layer(buf, value, layer):
    """`buf` with superblock `layer` replaced by `value`."""
    return lax.dynamic_update_index_in_dim(buf, value.astype(buf.dtype),
                                           layer, 0)


def _write_rows(cache, layer, idx, k, v, positions):
    """Decode write: K/V/position rows at `idx` of superblock `layer`,
    scattered straight into the stacked leaves (in place on a carried or
    donated pool — no layer-sized copy is rebuilt around the write).
    Returns (the updated stacked cache, its superblock-`layer` view)."""
    at = (layer, *idx)
    new = cache._replace(
        k=cache.k.at[at].set(k.astype(cache.k.dtype)),
        v=cache.v.at[at].set(v.astype(cache.v.dtype)),
        positions=cache.positions.at[at].set(positions.astype(jnp.int32)))
    return new, jax.tree.map(lambda a: at_layer(a, layer), new)


def gather_pages(cache: PagedKVCache):
    """Gather each slot's mapped pages into a virtually-contiguous view.

    Returns (k, v, positions) shaped like a dense per-slot cache of length
    S = max_pages·page_size, so `decode_attention` runs unchanged on top.
    Unmapped block-table entries gather the trash page; their k/v are
    zeroed (a free slot's garbage row can carry NaNs — and 0·NaN = NaN
    would leak through the masked softmax) and positions forced to -1, so
    they are masked out exactly like empty dense-ring entries. An explicit
    page-0 entry is treated as unmapped too: id 0 is the reserved trash
    page the allocator never hands out, and the fused decode kernel masks
    it the same way — the two paths must agree on every block table.
    """
    B, P = cache.block_table.shape
    psz = cache.k.shape[1]
    safe = jnp.maximum(cache.block_table, 0)              # (B, P)
    mapped = (cache.block_table > 0)[:, :, None]          # (B, P, 1)
    kvhd = cache.k.shape[2:]
    k = jnp.where(mapped[..., None, None], cache.k[safe], 0)
    v = jnp.where(mapped[..., None, None], cache.v[safe], 0)
    pos = jnp.where(mapped, cache.positions[safe], -1)
    return (k.reshape(B, P * psz, *kvhd), v.reshape(B, P * psz, *kvhd),
            pos.reshape(B, P * psz))


def qkv_project(x, p, cfg, meta):
    """x: (B, T, D) → q (B,T,H,hd), k/v (B,T,KVH,hd)."""
    B, T, _ = x.shape
    xf = x.reshape(B * T, -1)
    # fused: bias rides the GEMM epilogue — added to the fp32 chain before
    # the single output rounding instead of to the already-rounded output
    fused = cfg.qkv_bias and _fuse_epilogue()
    q = sa_dot(xf, p["wq"], bias=p["bq"] if fused else None
               ).reshape(B, T, cfg.num_heads, cfg.hd)
    k = sa_dot(xf, p["wk"], bias=p["bk"] if fused else None
               ).reshape(B, T, cfg.num_kv_heads, cfg.hd)
    v = sa_dot(xf, p["wv"], bias=p["bv"] if fused else None
               ).reshape(B, T, cfg.num_kv_heads, cfg.hd)
    if cfg.qkv_bias and not fused:
        q = q + p["bq"].reshape(cfg.num_heads, cfg.hd)
        k = k + p["bk"].reshape(cfg.num_kv_heads, cfg.hd)
        v = v + p["bv"].reshape(cfg.num_kv_heads, cfg.hd)
    return q, k, v


def attn_out(x_attn, p):
    B, T, H, hd = x_attn.shape
    return sa_dot(x_attn.reshape(B * T, H * hd), p["wo"]).reshape(B, T, -1)


def padded_kvh(kvh: int, tp: int) -> int:
    """KV head count after TP padding (optflags: pad_kv_heads)."""
    from repro.core import optflags
    if tp <= 1 or kvh % tp == 0 or not optflags.enabled("pad_kv_heads"):
        return kvh
    return -(-kvh // tp) * tp


def _pad_heads(q, k, v, kvh_target: int):
    """Zero-pad KV heads (and the kv-major grouped Q heads) to kvh_target.

    Without this, KVH that doesn't divide the 16-way model axis makes the
    SPMD partitioner REPLICATE every attention einsum across the axis
    (16× FLOPs on phi3; full cache reshards per decode step). Zero k/v heads
    produce garbage outputs only in the padded q-head slots, which are
    sliced away (EXPERIMENTS.md §Perf, hillclimb 1).
    """
    B, T, KVH, hd = k.shape
    H = q.shape[2]
    g = H // KVH
    pad = kvh_target - KVH
    if pad <= 0:
        return q, k, v, H
    k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad * g), (0, 0)))
    return q, k, v, H


def attention_block(x, p, cfg, meta, positions, cache: KVCache | None = None,
                    pos=None, rope: bool = True, causal: bool = True,
                    kv_override=None, prefix_len: int = 0,
                    decode_multi: bool = False, layer=None):
    """Full attention sub-layer. Returns (out, new_cache).

    meta: layer descriptor {"attn": "global"|"local"}. If `cache` is given and
    x is a single token, runs the decode path (ring-buffer update for local
    layers). `cache` holds the whole stack (every leaf leads with the
    superblock axis of `model.init_cache`) and `layer` is this superblock's
    index: writes land at [layer, ...] of the stacked leaves and attention
    reads the superblock-`layer` view, so a forward that carries the stack
    through its layer scan updates it in place. `kv_override` supplies
    cross-attention K/V source outputs.
    `prefix_len` (static) engages continued prefill: the dense cache's first
    `prefix_len` rows already hold KV for positions [0, prefix_len) — the
    serve engine's prefix-cache hits load them from shared pool pages — and
    x carries only the uncached suffix, whose KV is written at offset
    `prefix_len` and whose queries attend over [prefix ‖ suffix].
    `decode_multi` (static) treats the T tokens of x as T *consecutive
    decode steps* per slot (speculative verify, DESIGN.md §9) rather than a
    prefill fragment: row t writes KV at position pos+t and attends like a
    single-token decode at that position.
    """
    from repro.parallel import sharding as S_
    window = cfg.window if meta.get("attn") == "local" else 0
    theta = cfg.rope_theta
    q, k, v = qkv_project(x, p, cfg, meta)
    rope_kv = kv_override is None
    if kv_override is not None:          # cross-attention: kv from encoder
        k, v = kv_override
    if rope:                             # positions: (B, T)
        q = apply_rope(q.transpose(0, 2, 1, 3), positions[:, None, :],
                       theta).transpose(0, 2, 1, 3)
        if rope_kv:
            k = apply_rope(k.transpose(0, 2, 1, 3), positions[:, None, :],
                           theta).transpose(0, 2, 1, 3)
    # TP head padding: cache layout wins if present; the pure-training path
    # pads only where the arch opts in (cfg.pad_attn_train — see config.py)
    H_orig = q.shape[2]
    if cache is not None:
        kvh_target = cache.k.shape[-2]
    elif cfg.pad_attn_train:
        kvh_target = padded_kvh(k.shape[2], S_.axis_count("model"))
    else:
        kvh_target = k.shape[2]
    padding_active = kvh_target != k.shape[2] or cache is not None
    q, k, v, H_orig = _pad_heads(q, k, v, kvh_target)
    if padding_active:
        # pin the head-sharded layout (cache-matching / replication fix);
        # un-padded training paths keep XLA's own layout choice — forcing
        # head sharding there only adds reshards (measured, §Perf)
        q = S_.constrain(q, "batch", None, "model", None)
        k = S_.constrain(k, "batch", None, "model", None)
        v = S_.constrain(v, "batch", None, "model", None)
    new_cache = None
    if cache is not None and decode_multi:
        # multi-token decode (speculative verify): row t of the T-token
        # block is the decode step for position pos+t — it writes KV at
        # its own position, then the T query rows are folded into the
        # batch axis so every row runs the *single-token* decode
        # arithmetic (same reduction shapes, same kernel dispatch, with
        # kv_positions <= pos+t standing in for the causal mask). XLA's
        # row arithmetic is batch-fold stable, so under greedy decoding
        # row t is bit-identical to the sequential decode step it
        # replaces — that is the whole exactness argument for acceptance.
        B, T = x.shape[0], x.shape[1]
        H, hd = q.shape[2], q.shape[3]
        tpos = positions.astype(jnp.int32)              # (B, T) = pos + t
        qf = q.reshape(B * T, 1, H, hd)
        posf = tpos.reshape(B * T)
        from repro.core import optflags
        from repro.core.precision import current_policy
        from repro.kernels import ops as K
        b = jnp.arange(B)[:, None]
        if isinstance(cache, PagedKVCache):
            psz = cache.k.shape[-3]
            P = cache.block_table.shape[-1]
            page_i = tpos // psz                        # (B, T)
            off = tpos % psz
            pid = at_layer(cache.block_table,
                           layer)[b, jnp.clip(page_i, 0, P - 1)]
            pid = jnp.where((page_i < P) & (pid >= 0), pid, 0)
            new_cache, lc = _write_rows(cache, layer, (pid, off), k, v, tpos)
            impl = optflags.decode_attn_impl()
            if impl == "fused" and K.fused_decode_supported(current_policy()):
                btf = jnp.repeat(lc.block_table, T, axis=0)
                o = K.paged_decode_attention(
                    qf, lc.k, lc.v, lc.positions,
                    btf, posf, window=window, cap=cfg.attn_softcap)
            else:
                k_g, v_g, pos_g = gather_pages(lc)
                o = decode_attention(
                    qf, jnp.repeat(k_g, T, axis=0),
                    jnp.repeat(v_g, T, axis=0),
                    jnp.repeat(pos_g, T, axis=0), posf, window=window,
                    cap=cfg.attn_softcap)
        else:
            slot = tpos % cache.k.shape[-3]             # (B, T)
            new_cache, lc = _write_rows(cache, layer, (b, slot), k, v, tpos)
            o = decode_attention(
                qf, jnp.repeat(lc.k, T, axis=0), jnp.repeat(lc.v, T, axis=0),
                jnp.repeat(lc.positions, T, axis=0), posf, window=window,
                cap=cfg.attn_softcap)
        o = o.reshape(B, T, H, hd)
    elif (cache is not None and x.shape[1] == 1
            and isinstance(cache, PagedKVCache)):
        # paged write: position p of slot b lives at offset p % page_size of
        # page block_table[b, p // page_size]. Rows whose position falls
        # outside their mapped pages (free slots, post-retirement steps
        # inside a chunk) write to the reserved trash page 0 instead —
        # never to a page another request owns.
        B = x.shape[0]
        psz = cache.k.shape[-3]
        P = cache.block_table.shape[-1]
        page_i = (pos // psz).astype(jnp.int32)         # (B,)
        off = (pos % psz).astype(jnp.int32)
        b = jnp.arange(B)
        pid = at_layer(cache.block_table, layer)[b, jnp.clip(page_i, 0, P - 1)]
        pid = jnp.where((page_i < P) & (pid >= 0), pid, 0)
        new_cache, lc = _write_rows(cache, layer, (pid, off), k[:, 0],
                                    v[:, 0], pos)
        # attention over the slot's mapped pages only; page order in the
        # block table is allocation order == sequence order, so the paged
        # view is position-sorted exactly like a non-wrapped ring. Default
        # impl is the fused Pallas kernel walking the block table in-kernel
        # (no dense gathered view in HBM); REPRO_DECODE_ATTN=gather keeps
        # the materializing path as the bit-identical A/B fallback, and
        # policies the kernel can't reproduce (FP8 in, non-fp32 out) fall
        # back automatically.
        from repro.core import optflags
        from repro.core.precision import current_policy
        from repro.kernels import ops as K
        impl = optflags.decode_attn_impl()
        if impl == "fused" and K.fused_decode_supported(current_policy()):
            o = K.paged_decode_attention(
                q, lc.k, lc.v, lc.positions, lc.block_table, pos,
                window=window, cap=cfg.attn_softcap)
        else:
            k_g, v_g, pos_g = gather_pages(lc)
            o = decode_attention(q, k_g, v_g, pos_g, pos, window=window,
                                 cap=cfg.attn_softcap)
    elif cache is not None and x.shape[1] == 1:
        # per-slot ring write: row b of the batch is an independent request
        # at its own depth, so each row scatters into its own ring slot
        slot = (pos % cache.k.shape[-3]).astype(jnp.int32)   # (B,)
        new_cache, lc = _write_rows(cache, layer, (jnp.arange(x.shape[0]),
                                                   slot), k[:, 0], v[:, 0],
                                    pos)
        o = decode_attention(q, lc.k, lc.v, lc.positions, pos,
                             window=window, cap=cfg.attn_softcap)
    else:
        from repro.core import optflags
        k_att, v_att = k, v
        if cache is not None and prefix_len:
            # continued prefill (serve prefix-cache hit): suffix queries
            # attend over [cached prefix ‖ fragment] at their absolute
            # offset. The concat keeps the kv length — and therefore the
            # flash kv tiling and online-softmax accumulation order —
            # identical to a full prefill of the whole prompt, and the
            # cache round-trip is value-preserving (fp32 cache, or a bf16
            # cache of values the SA contract re-quantizes to bf16 anyway),
            # so the suffix rows come out bit-identical to full prefill.
            k_att = jnp.concatenate(
                [at_layer(cache.k, layer)[:, :prefix_len].astype(k.dtype),
                 k], axis=1)
            v_att = jnp.concatenate(
                [at_layer(cache.v, layer)[:, :prefix_len].astype(v.dtype),
                 v], axis=1)
        if cache is not None and optflags.enabled("pallas_attention"):
            # serving prefill (full or continued) is forward-only: use the
            # Pallas flash kernel (VMEM-resident softmax state;
            # kernels/sa_attention.py) on SA-contract inputs
            from repro.core.precision import current_policy
            from repro.kernels.ops import sa_attention
            pol = current_policy()
            o = sa_attention(*(pol.cast_in(x).transpose(0, 2, 1, 3)
                               for x in (q, k_att, v_att)),
                             causal=causal, window=window,
                             cap=cfg.attn_softcap,
                             q_offset=prefix_len).transpose(0, 2, 1, 3)
        else:
            o = blockwise_attention(q, k_att, v_att, causal=causal,
                                    window=window, cap=cfg.attn_softcap,
                                    q_offset=prefix_len)
        if cache is not None:            # prefill: fill the cache
            if isinstance(cache, PagedKVCache):
                # the serve engine prefills a dense batch-1 fragment and
                # page-scatters it into the pool (engine._insert); a direct
                # multi-token forward over the pool has no defined slot
                raise ValueError(
                    "paged KV caches take prefill via the engine's fragment "
                    "splice, not a multi-token forward")
            B, S = cache.positions.shape[-2:]
            T = k.shape[1]
            if T >= S:                   # keep last S positions (ring)
                assert not prefix_len, (
                    "continued prefill needs prefix_len + suffix <= cache "
                    "capacity (the engine sizes fragments to whole prompts)")
                bidx = jnp.arange(B)[:, None]
                pos_keep = positions[:, -S:].astype(jnp.int32)   # (B, S)
                # ring layout: slot = pos % S, per batch row
                slots = pos_keep % S
                lc = KVCache(
                    jnp.zeros(cache.k.shape[1:], cache.k.dtype)
                    .at[bidx, slots].set(k[:, -S:].astype(cache.k.dtype)),
                    jnp.zeros(cache.v.shape[1:], cache.v.dtype)
                    .at[bidx, slots].set(v[:, -S:].astype(cache.v.dtype)),
                    jnp.full((B, S), -1, jnp.int32)
                    .at[bidx, slots].set(pos_keep))
                new_cache = jax.tree.map(
                    lambda full, new: set_layer(full, new, layer), cache, lc)
            else:                        # suffix rows land after the prefix
                new_cache = KVCache(*(
                    lax.dynamic_update_slice(
                        full, new[None].astype(full.dtype),
                        (layer, 0, prefix_len) + (0,) * (full.ndim - 3))
                    for full, new in zip(cache, (k, v, positions))))
    o = o[:, :, :H_orig]   # drop padded q-head outputs before the projection
    return attn_out(o, p), new_cache


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def ffn_swiglu(x, p, act="silu"):
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    if _fuse_epilogue():
        h = sa_dot(xf, p["wg"], act=act) * sa_dot(xf, p["wu"])
    else:
        h = act_fn(sa_dot(xf, p["wg"]), act) * sa_dot(xf, p["wu"])
    return sa_dot(h, p["wd"]).reshape(B, T, D)


def ffn_mlp(x, p, act="gelu"):
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    if _fuse_epilogue():
        return sa_dot(sa_dot(xf, p["w1"], act=act), p["w2"]).reshape(B, T, D)
    return sa_dot(act_fn(sa_dot(xf, p["w1"]), act), p["w2"]).reshape(B, T, D)
