"""The plain reference: a model's forward pass in float32 jax.numpy.

Each family file (`bench/families/<model_type>.py`) gives the published
function of its architecture as `logits()`, built from the blocks here:
RMSNorm, RoPE, causal GQA softmax attention, SwiGLU and a softmax top-k
expert FFN renormalised over the chosen experts. `logits()` here picks the
family by the sizes' `model_type`. None of it imports the program: it
reads the benchmark's own weights (`bench/weights.py`) and the
configuration file.

No cache, no kernels, no batching: one sequence at a time, every matrix
product at `Precision.HIGHEST` on float32 values. Layers run one after
another in a scan, attention in blocks of query rows and the FFN in blocks
of tokens, so the largest temporary is a block's and the reference fits
beside the weights on one chip.

`fp8=True` is the control: every matrix product takes its operands
rounded to float8 e4m3, scaled per row of the left operand and per column
of the right one over the contracted axis, still accumulating in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec, a, b, fp8: bool, a_axis=-1, b_axis=0):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x (T, heads, hd): rotate the two halves of each head (Llama's
    rotate_half form) by position · theta^(-2i/hd)."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, fp8: bool, block: int, scale: float):
    """Causal GQA softmax attention, `block` query rows at a time, scores
    times `scale`. q (T, H, hd); k, v (T, KVH, hd) → (T, H, hd)."""
    T, H, hd = q.shape
    KVH = k.shape[1]
    g = H // KVH
    # query head h reads KV head h // g
    kx = jnp.repeat(k, g, axis=1)
    vx = jnp.repeat(v, g, axis=1)
    nb = T // block
    qb = q.reshape(nb, block, H, hd)

    def one(args):
        qi, i = args
        s = _einsum("qhd,khd->hqk", qi, kx, fp8, b_axis=-1) * scale
        rows = i * block + jnp.arange(block)
        ok = rows[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _einsum("hqk,khd->qhd", p, vx, fp8)

    return lax.map(one, (qb, jnp.arange(nb))).reshape(T, H, hd)


def _swiglu(h, wg, wu, wd, fp8):
    a = jax.nn.silu(_einsum("td,df->tf", h, wg, fp8))
    return _einsum("tf,fd->td", a * _einsum("td,df->tf", h, wu, fp8), wd,
                   fp8)


def _moe(h, p, k: int, fp8: bool, wd_scale: float):
    """Softmax router, top-k experts renormalised, every expert computed
    and weighted by its (zero outside the top k) combine weight."""
    E = p["router"].shape[-1]
    probs = jax.nn.softmax(_einsum("td,de->te", h, p["router"], fp8), -1)
    topv, topi = lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(topi, E) * topv[..., None], axis=-2)
    g = _einsum("td,edf->tef", h, p["wg"], fp8, b_axis=1)
    u = _einsum("td,edf->tef", h, p["wu"], fp8, b_axis=1)
    z = jax.nn.silu(g) * u
    wd = p["wd"].astype(jnp.float32) * wd_scale
    if fp8:
        z, wd = _q8(z, -1), _q8(wd, 1)
    return jnp.einsum("tef,efd->td", z * combine[..., None], wd,
                      precision=HIGHEST)


def static_sizes(sz: dict) -> tuple:
    """`weights.sizes()`, hashable, for `logits(s=...)`."""
    return tuple(sorted(sz.items()))


def logits(weights, tokens, *, s: tuple, fp8: bool = False,
           block: int = 512):
    """tokens (T,) int32, T a multiple of `block` → logits (T, Vp) float32,
    padding columns of the vocabulary at -inf, by the family of `s`
    (`static_sizes(sizes)`)."""
    from bench.families import family

    return family(dict(s)["model_type"]).logits(weights, tokens, s=s,
                                                fp8=fp8, block=block)


def served_gaps(weights, sz: dict, prompt, served, pad_to: int,
                control: bool = False, block: int = 512):
    """For one request: at each served token, the gap by which its
    reference logit lies below the reference's best logit.

    `prompt` and `served` are int lists; the reference reads
    prompt + served[:-1] (teacher-forced on what the program served), and
    row prompt_len - 1 + i predicts served[i]. The sequence is padded with
    token 0 to `pad_to` (causal: padding never reaches earlier rows), so
    one compiled program serves every request of a cell.

    Returns (gaps, control_gaps) as float32 numpy arrays, one entry per
    served token. With `control`, control_gaps holds the control's
    reading: at each of those rows, the reference's gap of the token the
    float8 reference ranks first; otherwise it is None."""
    import numpy as np

    seq = list(prompt) + list(served[:-1])
    T = -(-max(pad_to, len(seq)) // block) * block
    toks = jnp.asarray(np.pad(np.asarray(seq, np.int32), (0, T - len(seq))))
    key = static_sizes(sz)
    rows = np.arange(len(prompt) - 1, len(seq))

    def gap(r, pick):
        return np.asarray(jnp.max(r, axis=-1) - jnp.take_along_axis(
            r, pick[:, None], axis=-1)[:, 0], np.float32)

    with jax.default_matmul_precision("highest"):
        ref = logits(weights, toks, s=key, block=block)[rows]
        got = gap(ref, jnp.asarray(np.asarray(served, np.int32)))
        ctl = None
        if control:
            c = logits(weights, toks, s=key, fp8=True, block=block)[rows]
            ctl = gap(ref, jnp.argmax(c, axis=-1))
    return got, ctl
