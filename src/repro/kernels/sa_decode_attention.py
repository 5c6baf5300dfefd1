"""Pallas TPU kernel: fused paged-attention decode (flash-decoding style).

The paged serving layout (DESIGN.md §5) stores KV in a global page pool with
per-slot block tables. The jnp decode path gathers every slot's mapped pages
into a dense ``(B, max_pages·page_size, KVH, hd)`` view in HBM each token,
each layer — a bandwidth tax proportional to the block-table capacity, not to
the tokens actually attended. This kernel walks ``block_table[b]`` directly:
the grid runs over (batch-slot, KV-head block, page block), and a
scalar-prefetch-driven index map fetches each step's pages straight from the
pool into VMEM, so the dense gathered view never exists in HBM — the paged
gather happens inside the kernel's memory hierarchy (the Gemmini
scratchpad/mvin idiom restated in Pallas).

Numerics are pinned **bit-for-bit** to ``gather_pages`` + ``decode_attention``
(tests/test_decode_kernel.py): scores run under the SA contract
(``PrecisionPolicy.cast_in`` per operand — elementwise, so quantizing page
blocks in VMEM ≡ quantizing the gathered view — fp32 accumulate, same
softcap/window/GQA semantics), and the exponential/normalize/PV reduction is
deferred to the final grid step over the whole (hb, g, S) score scratch —
the softmax analogue of the paper's round-once column: unnormalized state
across the chain, one normalization at the end. Unmapped block-table entries
and the reserved trash page (id 0) are masked inside the kernel: their score
lanes are -inf (their kv positions arrive as -1) and their K/V rows are
zeroed without arithmetic on the pool (a free slot's garbage rows can hold
NaNs — 0·NaN would poison the PV dot), and ``pl.when`` skips a grid step's
score work entirely when none of its pages is mapped, which is why sparse
block tables get cheaper while the dense gather path keeps paying for full
capacity.

Layout on TPU (Mosaic): each grid step stages its pages' K rows per head
into a (hb, ppb·psz, hd) scratch at sublane offsets, scores them with one
(g, hd)·(ppb·psz, hd)ᵀ dot per head, and stores the (g, ppb·psz) score tile
at a lane offset that is a multiple of 128; the per-slot kv positions come
in lane-dense as one (1, ppb·psz) block per step. Hence the legal grids
(`tpu_blocks`): heads_per_block ∈ {KVH} ∪ 8ℤ, ppb·psz ∈ 128ℤ or the whole
block table, psz ∈ 16ℤ.

VMEM grows with the block-table width S = max_pages·psz: the score scratch
is (hb, g, S) fp32 and the V staging (hb, S, hd). At granite-moe-3b's decode
shape (KVH = hb = 8, g = 3, hd = 64, page 16, bf16 containers, fp32 pool)
`vmem_bytes` estimates ~8 MiB at max_seq_len 2048 and ~90 MiB at 32768 —
both compile for a v5e (128 MiB VMEM), the latter with a raised
`vmem_limit_bytes`. Past `VMEM_CAP_BYTES` (100 MiB, about 36k tokens per
slot at that shape) the kernel raises instead of compiling.

Grid/block shapes are autotuned (`kernels/autotune.py`): ``pages_per_block``
(how many pages one grid step fetches — one BlockSpec per page offset, all
indexed through the prefetched block table) and ``heads_per_block`` (KV-head
tiling). Requests are clipped to divisors in interpret mode and snapped to
the legal grids above on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sa_matmul import truncate_mantissa

_SUPPORTED_INPUT_FORMATS = ("fp32", "bf16", "fp16")
_TPU_INPUT_FORMATS = ("fp32", "bf16")
_INPUT_DTYPE = {"bf16": jnp.bfloat16, "fp16": jnp.float16}


def fused_decode_supported(policy) -> bool:
    """True when the fused kernel reproduces the jnp path for `policy`.

    FP8 inputs quantize through `fpformats.quantize` (grid snapping, not a
    dtype cast) and non-fp32 output formats round through the same machinery
    — both stay on the gather+dense path rather than re-implementing them
    in-kernel. fp16 containers do not lower through Mosaic on TPU v5e, so
    fp16 inputs take the gather path there too. `models/layers.py` consults
    this before dispatching, and the serve summary reports the path taken.
    """
    formats = (_SUPPORTED_INPUT_FORMATS if _exact_containers()
               else _TPU_INPUT_FORMATS)
    return (policy.input_format in formats
            and policy.output_format == "fp32")


def _exact_containers() -> bool:
    # read at trace time: the dry-run flips precision.EXACT_CPU_CONTAINERS
    # off in-process to lower the TPU-true bf16 program
    from repro.core import precision
    return precision.EXACT_CPU_CONTAINERS


def _cast_in(x, fmt: str):
    """`PrecisionPolicy.cast_in` restated for in-kernel use (fp32/bf16/fp16
    only — see `fused_decode_supported`). Elementwise, so casting each page
    block in VMEM is bit-identical to casting the gathered dense view."""
    if fmt == "fp32":
        return x.astype(jnp.float32)
    q = x.astype(_INPUT_DTYPE[fmt])
    return q.astype(jnp.float32) if _exact_containers() else q


def _container_dtype(fmt: str):
    """Dtype the cast-in operands (and the V scratch) actually carry."""
    if fmt == "fp32" or _exact_containers():
        return jnp.float32
    return _INPUT_DTYPE[fmt]


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of `n` that is <= cap (>= 1)."""
    d = max(1, min(int(cap), int(n)))
    while n % d:
        d -= 1
    return d


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _pick(legal: list[int], want: int) -> int:
    """Largest legal value <= want, else the smallest legal one."""
    below = [d for d in legal if d <= want]
    return max(below) if below else min(legal)


def tpu_blocks(max_pages: int, kvh: int, psz: int, ppb: int, hb: int
               ) -> tuple[int, int]:
    """Snap a requested (pages_per_block, heads_per_block) onto a grid that
    Mosaic accepts, or raise if none exists.

    Block rules (last two dims divisible by (8, 128) or equal to the
    array's): the K/V page blocks are (1, psz, hb, hd), so hb is KVH or a
    multiple of 8; the lane-dense kv-position block is (1, 1, ppb·psz), so
    ppb·psz is a multiple of 128 or covers the whole block table. Pages
    land in the V/K staging scratch at sublane offsets that are multiples
    of psz, so psz must fill whole (16-row) bf16 sublane tiles."""
    if psz % 16:
        raise ValueError(
            f"fused paged decode on TPU needs page_size % 16 == 0 (bf16 "
            f"sublane tile); got page_size={psz}")
    heads = [d for d in range(1, kvh + 1)
             if kvh % d == 0 and (d == kvh or d % 8 == 0)]
    pages = [d for d in range(1, max_pages + 1)
             if max_pages % d == 0 and (d == max_pages or d * psz % 128 == 0)]
    return _pick(pages, ppb), _pick(heads, hb)


# Mosaic's scoped-VMEM ceiling used for this kernel on a TPU v5e (128 MiB
# physical VMEM per core; headroom left for Mosaic's own temporaries).
VMEM_CAP_BYTES = 100 * 2 ** 20
_VMEM_DEFAULT_BYTES = 16 * 2 ** 20
_VMEM_MAX_LIMIT_BYTES = 120 * 2 ** 20


def vmem_bytes(hb: int, g: int, hd: int, psz: int, max_pages: int,
               ppb: int, container, pool_dtype) -> int:
    """Tile-padded VMEM the kernel holds: the (hb, g, S) fp32 score scratch
    twice over (the final step's exp/normalize temporaries are as large),
    the (hb, S, hd) V staging, the per-step K staging, and the
    double-buffered input/output blocks."""
    S = max_pages * psz
    cb = jnp.dtype(container).itemsize
    pb = jnp.dtype(pool_dtype).itemsize
    lanes_hd = _round_up(hd, 128)
    score = hb * _round_up(g, 8) * _round_up(S, 128) * 4
    v_buf = hb * _round_up(S, 16) * lanes_hd * cb
    k_stage = hb * _round_up(ppb * psz, 16) * lanes_hd * cb
    pages_in = 2 * ppb * psz * _round_up(hb, 8) * lanes_hd * pb
    kvp = 8 * _round_up(ppb * psz, 128) * 4
    q_out = 2 * hb * _round_up(g, 8) * lanes_hd * 4
    return 3 * score + v_buf + k_stage + 2 * (pages_in + kvp + q_out)


def _vmem_limit(hb, g, hd, psz, max_pages, ppb, container, pool_dtype):
    need = vmem_bytes(hb, g, hd, psz, max_pages, ppb, container, pool_dtype)
    return min(_VMEM_MAX_LIMIT_BYTES,
               max(_VMEM_DEFAULT_BYTES, _round_up(need + need // 4, 2 ** 20)))


def _decode_kernel(bt_ref, pos_ref, q_ref, *refs, ppb: int, hb: int,
                   psz: int, n_steps: int, scale: float, window: int,
                   cap: float, fmt: str, approx: bool):
    """One grid step: stage `ppb` pages of `hb` KV heads, score them in one
    dot per head into the (hb, g, S) score scratch, stage their cast-in V
    rows; the final step runs the deferred softmax + PV dot. refs layout
    (positional, after the two scalar-prefetch refs and the q ref): k×ppb,
    v×ppb, kv-positions, out, score scratch, V scratch, K staging."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    kvp_ref, o_ref, s_buf, v_buf, k_stage = refs[2 * ppb:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    my_pos = pos_ref[b]
    blk = ppb * psz
    # lane offset of this step's score block: 0 in a single-step walk, else
    # a multiple of 128 by construction of `tpu_blocks`
    base = 0 if n_steps == 1 else pl.multiple_of(j * blk, blk)
    # id 0 is the reserved trash page: stale decode writes land there, so
    # an explicit 0 entry is as dead as an unmapped (-1) one
    mapped = [bt_ref[b, j * ppb + i] > 0 for i in range(ppb)]
    live = functools.reduce(jnp.logical_or, mapped)

    @pl.when(live)
    def _score():
        for i in range(ppb):
            rows = pl.ds(pl.multiple_of(base + i * psz, psz), psz)
            for t in range(hb):
                # unmapped pages are zeroed without arithmetic on the pool
                # (a trash page can hold NaNs — 0·NaN would poison the PV
                # dot); their score lanes are masked below via kvp = -1
                k = jnp.where(mapped[i], _cast_in(k_refs[i][0, :, t, :], fmt),
                              0)                      # (psz, hd)
                k_stage[t, i * psz:(i + 1) * psz, :] = k.astype(
                    k_stage.dtype)
                v_buf[t, rows, :] = jnp.where(
                    mapped[i], _cast_in(v_refs[i][0, :, t, :], fmt),
                    0).astype(v_buf.dtype)
        q = _cast_in(q_ref[0], fmt)                   # (hb, g, hd)
        kvp = kvp_ref[0]                              # (1, blk)
        ok = (kvp >= 0) & (kvp <= my_pos)
        if window:
            ok &= kvp > my_pos - window
        for t in range(hb):
            # per-head 2-D dot: contraction over hd in fp32, exactly the
            # per-(b, h) slice of the dense path's batched einsum
            s = jax.lax.dot_general(
                q[t], k_stage[t], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # (g, blk)
            if approx:
                s = truncate_mantissa(s)
            # constants folded on the host: single-mul→tanh is the only
            # fusion-stable form (see decode_attention's softcap note)
            s = cap * jnp.tanh(s * (scale / cap)) if cap else s * scale
            s_buf[t, :, pl.ds(base, blk)] = jnp.where(ok, s, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _mask_out():
        # no pool read at all: score lanes -inf, V lanes 0 (the dense path
        # zeroes gathered trash-page rows for the same reason)
        s_buf[:, :, pl.ds(base, blk)] = jnp.full(
            (*s_buf.shape[:2], blk), -jnp.inf, s_buf.dtype)
        v_buf[:, pl.ds(base, blk), :] = jnp.zeros(
            (v_buf.shape[0], blk, v_buf.shape[2]), v_buf.dtype)

    @pl.when(j == n_steps - 1)
    def _normalize_once():
        s = s_buf[...]                                # (hb, g, S)
        m = jnp.max(s, axis=-1)
        # all-masked rows (slot with zero live entries) keep m = -inf; the
        # guard makes them exp(-inf - 0) = 0 instead of exp(nan)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        for t in range(hb):
            pq = _cast_in(p[t].astype(q_ref.dtype), fmt)
            out = jax.lax.dot_general(
                pq, v_buf[t], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if approx:
                out = truncate_mantissa(out)
            o_ref[0, t] = out


@functools.partial(
    jax.jit, static_argnames=("window", "cap", "scale", "ppb", "hb", "fmt",
                              "approx", "interpret"))
def _paged_decode(qg, k_pool, v_pool, page_positions, block_table, pos, *,
                  window: int, cap: float, scale: float, ppb: int, hb: int,
                  fmt: str, approx: bool, interpret: bool):
    B, KVH, g, hd = qg.shape
    psz = k_pool.shape[1]
    P = block_table.shape[1]
    # per-slot token positions, -1 where unmapped: B·S int32 — 1/(2·KVH·hd)
    # of the K/V bytes the kernel walks in place — arrive lane-dense, one
    # (1, ppb·psz) block per grid step
    kvpos = jnp.where((block_table > 0)[..., None],
                      page_positions[jnp.maximum(block_table, 0)], -1)
    kvpos = kvpos.reshape(B, 1, P * psz).astype(jnp.int32)

    def page_idx(i):
        # the prefetched block table drives the pool index: unmapped (-1)
        # entries clamp to the trash page, whose block the kernel zeroes
        return lambda b, h, j, bt, ps: (jnp.maximum(bt[b, j * ppb + i], 0),
                                        0, h, 0)

    def run(qb, btb, posb, kvpb):
        bb = qb.shape[0]
        grid = (bb, KVH // hb, P // ppb)
        in_specs = [pl.BlockSpec((1, hb, g, hd),
                                 lambda b, h, j, bt, ps: (b, h, 0, 0))]
        in_specs += [pl.BlockSpec((1, psz, hb, hd), page_idx(i))
                     for i in range(ppb)]
        in_specs += [pl.BlockSpec((1, psz, hb, hd), page_idx(i))
                     for i in range(ppb)]
        in_specs += [pl.BlockSpec((1, 1, ppb * psz),
                                  lambda b, h, j, bt, ps: (b, 0, j))]
        cdt = _container_dtype(fmt)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hb, g, hd),
                                   lambda b, h, j, bt, ps: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hb, g, P * psz), jnp.float32),
                pltpu.VMEM((hb, P * psz, hd), cdt),
                pltpu.VMEM((hb, ppb * psz, hd), cdt),
            ],
        )
        kernel = functools.partial(_decode_kernel, ppb=ppb, hb=hb, psz=psz,
                                   n_steps=grid[2], scale=scale,
                                   window=window, cap=cap, fmt=fmt,
                                   approx=approx)
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bb, KVH, g, hd), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(hb, g, hd, psz, P, ppb, cdt,
                                             k_pool.dtype)),
            interpret=interpret,
        )(btb.astype(jnp.int32), posb.astype(jnp.int32), qb,
          *([k_pool] * ppb), *([v_pool] * ppb), kvpb)

    if interpret and B > 1:
        # Interpret-mode lowering runs the grid as an XLA while loop whose
        # carry holds EVERY operand — each step past the first re-writes
        # all 2·ppb pool-sized carries (measured ~3 ms/step at a 4 MB
        # pool), while a single-step grid folds the loop away entirely.
        # The batch axis would force >= B steps, so on CPU we unroll it
        # into B independent single-slot calls instead; each one can then
        # collapse to one grid step when (ppb, hb) = (P, KVH). Numerics
        # are per-(b, h) slices either way — bit-identical. On TPU the
        # batched grid stands: steps are real parallel work there, and
        # the pools are never in any carry.
        return jnp.concatenate(
            [run(qg[b:b + 1], block_table[b:b + 1], pos[b:b + 1],
                 kvpos[b:b + 1]) for b in range(B)], axis=0)
    return run(qg, block_table, pos, kvpos)


def sa_paged_decode_attention(q, k_pool, v_pool, page_positions, block_table,
                              pos, *, window: int = 0, cap: float = 0.0,
                              scale: float | None = None,
                              ppb: int | None = None, hb: int | None = None,
                              policy=None, interpret: bool = False):
    """Fused paged decode attention.

    q: (B, 1, H, hd); pools: (n_pages, psz, KVH, hd);
    page_positions: (n_pages, psz) int32 (-1 = empty);
    block_table: (B, max_pages) int32 page ids (-1 = unmapped, 0 = trash);
    pos: (B,) per-slot current position. → (B, 1, H, hd) fp32.

    Bit-identical to ``decode_attention(q, *gather_pages(cache), pos)`` for
    every supported policy (`fused_decode_supported`). `ppb`/`hb` default to
    the autotuned `pages_per_block` / KV-head tiling for this workload
    (`autotune.lookup_decode_attn`); explicit values are clipped to
    divisors (interpret) or snapped to a Mosaic-legal grid (`tpu_blocks`),
    so any (ppb, hb) is safe to pin. Raises ValueError where no legal grid
    exists or the VMEM estimate exceeds `VMEM_CAP_BYTES`.
    """
    from repro.core.precision import current_policy
    policy = policy or current_policy()
    if not fused_decode_supported(policy):
        raise ValueError(
            f"fused paged decode does not support input_format="
            f"{policy.input_format!r} / output_format="
            f"{policy.output_format!r}; use the gather path")
    B, _, H, hd = q.shape
    psz, KVH = k_pool.shape[1], k_pool.shape[2]
    P = block_table.shape[1]
    g = H // KVH
    scale = scale or hd ** -0.5
    if ppb is None or hb is None:
        from . import autotune
        tppb, thb = autotune.lookup_decode_attn(B, KVH, g, hd, psz, P)
        ppb, hb = ppb or tppb, hb or thb
    if interpret:
        ppb, hb = largest_divisor(P, ppb), largest_divisor(KVH, hb)
    else:
        ppb, hb = tpu_blocks(P, KVH, psz, ppb, hb)
        need = vmem_bytes(hb, g, hd, psz, P, ppb, _container_dtype(
            policy.input_format), k_pool.dtype)
        if need > VMEM_CAP_BYTES:
            raise ValueError(
                f"fused paged decode needs ~{need / 2 ** 20:.0f} MiB of VMEM "
                f"at max_pages={P}, page_size={psz}, heads_per_block={hb} "
                f"(cap {VMEM_CAP_BYTES / 2 ** 20:.0f} MiB); lower "
                f"max_seq_len or use REPRO_DECODE_ATTN=gather")
    out = _paged_decode(q.reshape(B, KVH, g, hd), k_pool, v_pool,
                        page_positions, block_table, pos,
                        window=int(window), cap=float(cap or 0.0),
                        scale=float(scale), ppb=ppb, hb=hb,
                        fmt=policy.input_format,
                        approx=policy.mode == "approx", interpret=interpret)
    return out.reshape(B, 1, H, hd)
