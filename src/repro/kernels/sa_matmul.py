"""Pallas TPU kernel: the SA column's arithmetic contract as an MXU GEMM.

TPU-native restatement of the paper's skewed pipeline (DESIGN.md §2b):

  * the K-grid dimension is the **column of PEs** — each step fuses one
    (bm×bk)·(bk×bn) product into the running block result;
  * the accumulator lives **unnormalized in fp32 VMEM scratch across all K
    steps** — the chain is never rounded/materialized between steps (the
    paper's "no per-PE normalization, double-width reduction");
  * the Pallas grid pipelines the *next* K-tile's HBM→VMEM DMA under the
    *current* tile's MXU work — the software analogue of the skew's
    stage-overlap between consecutive PEs;
  * rounding to the output format happens exactly once, in the final K step
    (the paper's single rounder at the column south end).

Fused epilogue (DESIGN.md §2c): the final K step can apply, *before* the
single rounding, ``y = act(acc · scale + bias)`` — output descale for the
FP8 path, bias add, and a pointwise activation. This keeps the paper's
round-once contract while eliminating the separate elementwise passes the
model layers would otherwise run on the already-rounded output.

The op carries a `jax.custom_vjp`: both backward GEMMs (dA = dY·Wᵀ and
dW = Aᵀ·dY) run through the same round-once kernel, so the pallas backend
works under `jax.grad` (training on the paper's datapath).

Block shapes default to MXU-aligned (multiples of 128 in M/N, 512 in K) and
are swept/cached by `repro.kernels.autotune`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


EPILOGUES = ("none", "relu", "gelu", "silu")
MODES = ("exact", "approx")

# The MXU-path model of the approximate-normalization datapath ("bulk"
# serving tier): chained_fma.approx_chain bounds the coarse-LZA truncation
# debt to GUARD bits of the wide accumulator, so on the production fp32
# chain the same information loss is the low GUARD mantissa bits of the
# accumulator — dropped (round-to-zero) before the single output rounding.
APPROX_DROP_BITS = 3


def truncate_mantissa(y: jax.Array, bits: int = APPROX_DROP_BITS) -> jax.Array:
    """Zero the low `bits` mantissa bits of an fp32 array (RTZ truncation).

    Shared by every backend's mode="approx" path (pallas epilogue, xla
    fallback in core/precision.py) so the tier arithmetic is
    backend-independent."""
    b = jax.lax.bitcast_convert_type(y.astype(jnp.float32), jnp.uint32)
    b = b & ~jnp.uint32((1 << bits) - 1)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def apply_act(y: jax.Array, act: str) -> jax.Array:
    """Pointwise epilogue activation (shared by all backends for parity)."""
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "gelu":
        return jax.nn.gelu(y)
    if act == "silu":
        return jax.nn.silu(y)
    return y


# minimum hardware tile: 16 sublanes (bf16) × 128 lanes. bm is sublane-only;
# bk is a lane dim in the A block AND a sublane dim in the W block, so it
# takes the stricter 128; bn is lane-only.
_SUBLANE, _LANE = 16, 128


def _round_up(d: int, unit: int) -> int:
    return -(-d // unit) * unit


def clip_blocks(bm: int, bn: int, bk: int, m: int, n: int, k: int
                ) -> tuple[int, int, int]:
    """Clip requested block dims to the problem — but never below the
    hardware tile: small/ragged dims clip to the *tile-rounded* size (the
    input is zero-padded to a block multiple anyway), so Mosaic always sees
    (16, 128)-aligned blocks. A caller-pinned block smaller than the tile is
    honored as-is (interpret-mode tests sweep tiny blocks)."""
    return (min(bm, _round_up(m, _SUBLANE)),
            min(bn, _round_up(n, _LANE)),
            min(bk, _round_up(k, _LANE)))


def default_blocks(m: int, n: int, k: int) -> tuple[int, int, int]:
    """Heuristic MXU-aligned block shapes (autotune's fallback)."""
    return clip_blocks(256, 256, 512, m, n, k)


def _matmul_kernel(a_ref, w_ref, scale_ref, *refs, n_k: int, out_dtype,
                   act: str, has_bias: bool, save_raw: bool, approx: bool):
    """One (i, j, k) grid step: psum_k = psum_{k-1} + A_ik · W_kj."""
    if has_bias:
        bias_ref, refs = refs[0], refs[1:]
    o_ref = refs[0]
    raw_ref = refs[1] if save_raw else None
    acc_ref = refs[-1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The chained multiply-add: MXU product accumulated into the persistent
    # fp32 scratch (never normalized/rounded mid-chain).
    acc_ref[...] += jnp.dot(a_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue_and_round_once():
        # epilogue on the unnormalized fp32 chain, then the single rounding
        # at the end of the K chain (column south end)
        raw = acc_ref[...]
        if approx:
            # bulk-tier arithmetic: drop the accumulator's guard-band low
            # bits (the information a coarse-LZA datapath loses) before the
            # epilogue and the single rounding
            raw = truncate_mantissa(raw)
        if save_raw:
            raw_ref[...] = raw
        y = raw * scale_ref[0, 0]
        if has_bias:
            y = y + bias_ref[...].astype(jnp.float32)   # (1, bn) broadcast
        y = apply_act(y, act)
        o_ref[...] = y.astype(out_dtype)


def _pallas_fused(a, w, bias, scale, *, act, bm, bn, bk, out_dtype,
                  save_raw, interpret, mode="exact"):
    """pallas_call plumbing: padding, specs, optional raw-accumulator output."""
    m, k = a.shape
    k2, n = w.shape
    assert k == k2, (a.shape, w.shape)
    bm, bn, bk = clip_blocks(bm, bn, bk, m, n, k)
    # pad to block multiples (zero products are exact under the contract)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        # scalar epilogue scale: (1, 1) in SMEM (Mosaic cannot deref ANY)
        pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0),
                     memory_space=pltpu.SMEM),
    ]
    operands = [a, w, jnp.asarray(scale, jnp.float32).reshape(1, 1)]
    if bias is not None:
        if pn:
            bias = jnp.pad(bias, ((0, pn),))
        # 2-D (1, bn) block: 1-D blocks don't tile cleanly on Mosaic lanes
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, -1))

    out_block = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    out_shape = [jax.ShapeDtypeStruct((m + pm, n + pn), out_dtype)]
    out_specs = [out_block]
    if save_raw:
        out_shape.append(jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32))
        out_specs.append(out_block)

    kernel = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2], out_dtype=out_dtype,
                          act=act, has_bias=bias is not None,
                          save_raw=save_raw, approx=(mode == "approx")),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    outs = kernel(*operands)
    if pm or pn:
        outs = [o[:m, :n] for o in outs]
    return outs if save_raw else outs[0]


@dataclasses.dataclass(frozen=True)
class _GemmCfg:
    """Static configuration of one fused GEMM (nondiff arg of the vjp)."""
    act: str
    bm: int
    bn: int
    bk: int
    out_dtype: object
    interpret: bool
    has_scale: bool = False   # caller passed a real scale (vs synthesized 1)
    mode: str = "exact"       # "approx" = bulk-tier truncated accumulator

    @property
    def needs_raw(self) -> bool:
        # the backward pass needs the unnormalized accumulator only for the
        # activation jacobian or a real dscale; plain GEMMs (the majority of
        # training projections) skip the second (M, N) fp32 output entirely
        return self.act != "none" or self.has_scale


def _bwd_blocks(m: int, n: int, k: int) -> tuple[int, int, int]:
    """Block shapes for the backward GEMMs: autotune cache else heuristic.

    The import is function-level because autotune imports this module at
    load time (it times the kernel); by backward-execution time it is
    always importable."""
    from .autotune import lookup
    return lookup(m, n, k, dtype="float32", epilogue="none", sweep=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sa_matmul_vjp(cfg: _GemmCfg, a, w, bias, scale):
    return _pallas_fused(a, w, bias, scale, act=cfg.act, bm=cfg.bm, bn=cfg.bn,
                         bk=cfg.bk, out_dtype=cfg.out_dtype, save_raw=False,
                         interpret=cfg.interpret, mode=cfg.mode)


def _sa_matmul_fwd(cfg: _GemmCfg, a, w, bias, scale):
    # when the epilogue is nontrivial, the kernel emits the unnormalized
    # fp32 accumulator alongside the epilogued output, so the backward pass
    # can form the activation jacobian / dscale without a recompute GEMM
    out = _pallas_fused(a, w, bias, scale, act=cfg.act, bm=cfg.bm,
                        bn=cfg.bn, bk=cfg.bk, out_dtype=cfg.out_dtype,
                        save_raw=cfg.needs_raw, interpret=cfg.interpret,
                        mode=cfg.mode)
    y, raw = out if cfg.needs_raw else (out, None)
    return y, (a, w, bias, scale, raw)


def _sa_matmul_bwd(cfg: _GemmCfg, res, dy):
    a, w, bias, scale, raw = res
    dy = dy.astype(jnp.float32)
    scale32 = jnp.asarray(scale, jnp.float32)
    if raw is None:       # act == "none" and scale synthesized: linear vjp
        du = dy
        dscale = jnp.zeros((), scale.dtype)
    else:
        u = raw * scale32
        if bias is not None:
            u = u + bias.astype(jnp.float32)
        if cfg.act == "none":
            du = dy
        else:
            _, act_vjp = jax.vjp(lambda t: apply_act(t, cfg.act), u)
            (du,) = act_vjp(dy)
        dscale = jnp.sum(du * raw).astype(scale.dtype)
    dbias = jnp.sum(du, axis=0).astype(bias.dtype) if bias is not None else None
    dus = du * scale32
    # both backward GEMMs run through the same round-once kernel (fp32
    # operands: every reduced-format value is exact in fp32, so upcasting
    # the saved a/w changes nothing)
    one = jnp.float32(1.0)
    m, k = a.shape
    n = w.shape[1]
    da_b = _bwd_blocks(m, k, n)
    da = _pallas_fused(dus, w.astype(jnp.float32).T, None, one, act="none",
                       bm=da_b[0], bn=da_b[1], bk=da_b[2],
                       out_dtype=jnp.float32, save_raw=False,
                       interpret=cfg.interpret)
    dw_b = _bwd_blocks(k, n, m)
    dw = _pallas_fused(a.astype(jnp.float32).T, dus, None, one, act="none",
                       bm=dw_b[0], bn=dw_b[1], bk=dw_b[2],
                       out_dtype=jnp.float32, save_raw=False,
                       interpret=cfg.interpret)
    return da.astype(a.dtype), dw.astype(w.dtype), dbias, dscale


_sa_matmul_vjp.defvjp(_sa_matmul_fwd, _sa_matmul_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("act", "bm", "bn", "bk", "out_dtype", "interpret",
                     "mode"))
def sa_matmul_pallas(a: jax.Array, w: jax.Array, bias: jax.Array | None = None,
                     scale: jax.Array | float | None = None, *,
                     act: str = "none", bm: int = 256, bn: int = 256,
                     bk: int = 512, out_dtype=jnp.float32,
                     interpret: bool = False, mode: str = "exact"):
    """(M, K) @ (K, N) with SA-contract arithmetic. Inputs bf16 (or fp8
    values carried in bf16/f32 containers); fused epilogue
    ``act(acc·scale + bias)`` applied before the single rounding to
    `out_dtype`. Differentiable (custom VJP; backward GEMMs use the same
    kernel).

    ``mode="approx"`` is the bulk serving tier: the accumulator's low
    APPROX_DROP_BITS mantissa bits are truncated before the epilogue and
    the single rounding (forward only — backward GEMMs stay exact)."""
    if act not in EPILOGUES:
        raise ValueError(f"unknown epilogue act {act!r}; have {EPILOGUES}")
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; have {MODES}")
    if bias is not None and bias.ndim != 1:
        # the kernel's (1, bn) block broadcasts a single bias row per output
        # column tile — anything but a (N,) vector would be silently wrong
        raise ValueError(f"bias must be a (N,) vector, got {bias.shape}")
    scale_arr = jnp.asarray(1.0 if scale is None else scale, jnp.float32)
    cfg = _GemmCfg(act=act, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                   interpret=interpret, has_scale=scale is not None,
                   mode=mode)
    return _sa_matmul_vjp(cfg, a, w, bias, scale_arr)
