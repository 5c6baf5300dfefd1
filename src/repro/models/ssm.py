"""Mamba2 SSD (state-space duality) layer — chunked scan + O(1) decode.

Implements the SSD algorithm of Dao & Gu 2024 (arXiv:2405.21060): within a
chunk the recurrence is computed in its quadratic "attention" form (all
GEMMs — SA-contract friendly), states are passed between chunks with a
`lax.scan`. Per-token decode updates the (H, P, N) state in O(1).

Layer structure follows Mamba2: in_proj → [z | x | B | C | dt], causal
depthwise conv on (x, B, C), SSD core, gated RMSNorm, out_proj.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import sa_dot, sa_einsum
from .layers import rmsnorm


def _segsum(a):
    """Stable 'segment sum' → lower-triangular L[t, s] = Σ_{s<j<=t} a_j."""
    T = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    L = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, L, -jnp.inf)


def ssd_chunked(x, dt, A, B_, C_, chunk: int, initial_state=None):
    """SSD core.

    x:  (B, T, H, P)   inputs per head
    dt: (B, T, H)      positive step sizes (post-softplus)
    A:  (H,)           negative decay rates
    B_: (B, T, N)      input projection (single group, broadcast over heads)
    C_: (B, T, N)      output projection
    returns y (B, T, H, P), final_state (B, H, P, N)
    """
    Bsz, T, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, T)
    pad = (-T) % Q
    if pad:   # zero-pad tail: dt=0 ⇒ decay=1, no input ⇒ state unaffected
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
    T_pad, T_orig = T + pad, T
    T = T_pad
    nc = T // Q

    xb = x.reshape(Bsz, nc, Q, H, P)
    dtb = dt.reshape(Bsz, nc, Q, H)
    Bb = B_.reshape(Bsz, nc, Q, N)
    Cb = C_.reshape(Bsz, nc, Q, N)

    dA = dtb * A  # (B, nc, Q, H)  log-decay per step
    dA_cum = jnp.cumsum(dA, axis=2)                     # within-chunk cumsum
    dA_total = dA_cum[:, :, -1]                         # (B, nc, H)

    # intra-chunk (quadratic / attention form): all contractions are GEMMs
    Lmat = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))   # (B, nc, H, Q, Q)
    scores = sa_einsum("bcqn,bckn->bcqk", Cb, Bb)       # (B, nc, Q, Q)
    M = scores[:, :, None] * Lmat.transpose(0, 1, 2, 3, 4)  # (B,nc,H,Q,Q)
    xdt = xb * dtb[..., None]                           # (B, nc, Q, H, P)
    y_intra = sa_einsum("bchqk,bckhp->bcqhp",
                        M.astype(x.dtype), xdt.astype(x.dtype))

    # chunk states: S_c = Σ_s exp(dA_total − dA_cum[s]) · B_s ⊗ (x_s·dt_s)
    decay_to_end = jnp.exp(dA_total[:, :, None] - dA_cum)     # (B, nc, Q, H)
    Sx = xdt * decay_to_end[..., None]
    S_chunk = sa_einsum("bcqn,bcqhp->bchpn", Bb.astype(x.dtype),
                        Sx.astype(x.dtype))              # (B, nc, H, P, N)

    # inter-chunk scan: carry running state across chunks
    def chunk_step(S_prev, inputs):
        S_c, dA_tot_c, C_c, dA_cum_c = inputs
        # contribution of the carried state to this chunk's outputs
        decay_in = jnp.exp(dA_cum_c)                     # (B, Q, H)
        y_c = sa_einsum("bqn,bhpn->bqhp", C_c.astype(x.dtype),
                        S_prev.astype(x.dtype))
        y_c = y_c * decay_in.transpose(0, 1, 2)[..., None]
        S_new = S_prev * jnp.exp(dA_tot_c)[:, :, None, None] + S_c
        return S_new, y_c

    S0 = (initial_state if initial_state is not None
          else jnp.zeros((Bsz, H, P, N), jnp.float32))
    S_final, y_inter = lax.scan(
        chunk_step, S0.astype(jnp.float32),
        (S_chunk.swapaxes(0, 1).astype(jnp.float32),
         dA_total.swapaxes(0, 1),
         Cb.swapaxes(0, 1),
         dA_cum.swapaxes(0, 1)))
    y = y_intra + y_inter.swapaxes(0, 1).reshape(Bsz, nc, Q, H, P).astype(y_intra.dtype)
    return y.reshape(Bsz, T, H, P)[:, :T_orig], S_final


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """O(1) recurrent update. state: (B, H, P, N); x_t: (B, H, P);
    dt_t: (B, H); B_t/C_t: (B, N)."""
    dA = jnp.exp(dt_t * A)                                    # (B, H)
    dBx = (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
    state = state * dA[..., None, None] + dBx
    y = sa_einsum("bn,bhpn->bhp", C_t.astype(x_t.dtype),
                  state.astype(x_t.dtype))
    return state, y


def _causal_conv(x, w, cache=None, b=None, n_valid=None):
    """Depthwise causal conv1d. x: (B, T, D); w: (KW, D); optional bias
    b: (D,). Returns (y, tail) where tail is the last KW-1 inputs (decode
    cache) — the last KW-1 before row `n_valid` where rows from there on
    are padding."""
    KW = w.shape[0]
    T = x.shape[1]
    if cache is not None:
        xp = jnp.concatenate([cache, x], axis=1)
    else:
        xp = jnp.pad(x, ((0, 0), (KW - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + T] * w[i] for i in range(KW))
    if b is not None:
        y = y + b
    tail = None
    if KW > 1:
        # input row t sits at xp row t + KW - 1
        tail = (xp[:, -(KW - 1):] if n_valid is None else
                lax.dynamic_slice_in_dim(xp, n_valid, KW - 1, axis=1))
    return jax.nn.silu(y), tail


def mamba2_block(x, p, cfg, state=None, conv_cache=None, n_valid=None):
    """Full Mamba2 mixer. x: (B, T, D). If `state` is given and T == 1 runs
    the recurrent decode path. `n_valid` (traced) marks rows from there on
    as padding (bucketed prefill): their dt is 0, which leaves the state as
    it is (decay 1, no input), and the conv tail is taken before them.
    Returns (y, (state, conv_cache))."""
    B, T, D = x.shape
    H, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    din = cfg.d_inner
    zxbcdt = sa_dot(x.reshape(B * T, D), p["in_proj"]).reshape(B, T, -1)
    z, xin, B_, C_, dt = jnp.split(
        zxbcdt, [din, 2 * din, 2 * din + N, 2 * din + 2 * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, T, H)
    if n_valid is not None:
        dt = jnp.where((jnp.arange(T) < n_valid)[None, :, None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                 # (H,)

    conv_in = jnp.concatenate([xin, B_, C_], axis=-1)
    conv_out, conv_tail = _causal_conv(conv_in, p["conv_w"], conv_cache,
                                       p.get("conv_b"), n_valid)
    xin, B_, C_ = jnp.split(conv_out, [din, din + N], axis=-1)
    xh = xin.reshape(B, T, H, P)

    if state is not None and T == 1:
        state, y = ssd_decode_step(state, xh[:, 0], dt[:, 0], A,
                                   B_[:, 0], C_[:, 0])
        y = y[:, None]                                           # (B, 1, H, P)
        new_state = state
    else:
        y, new_state = ssd_chunked(xh, dt, A, B_, C_, cfg.ssm_chunk,
                                   initial_state=state)
    if "D" in p:        # one skip weight per head (Mamba2 as published)
        y = (y + xh * p["D"][:, None]).reshape(B, T, din)
    else:
        y = y.reshape(B, T, din) + xin * p["D_skip"]
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["norm_w"], cfg.norm_eps)
    out = sa_dot(y.reshape(B * T, din), p["out_proj"]).reshape(B, T, D)
    return out, (new_state, conv_tail)
