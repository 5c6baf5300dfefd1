"""Fused paged decode-attention kernel: bit-for-bit parity with the
gather+dense path (kernels/sa_decode_attention.py vs gather_pages +
decode_attention), across GQA ratios, window/softcap, precision formats,
grid-shape (ppb, hb) pins, staggered per-slot positions, partial block
tables, NaN-poisoned trash pages, and fully-empty slots. Parity is u32
equality, not allclose — the kernel is a data-movement change, and the knob
(REPRO_DECODE_ATTN) must A/B only the movement, never the numbers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import given, settings, st
from repro.core import PrecisionPolicy, use_policy
from repro.kernels import ops
from repro.kernels.sa_decode_attention import (fused_decode_supported,
                                               largest_divisor)
from repro.models.layers import PagedKVCache, decode_attention, gather_pages

FP32 = PrecisionPolicy(input_format="fp32")


def _workload(seed, batch, kvh, g, hd, psz, max_pages, mapped,
              poison_trash=True, pos=None):
    """Synthetic pool + block tables; `mapped` is pages-per-slot (int or
    per-slot list). Trash page (id 0) NaN-poisoned by default so a masking
    bug in either path turns into a non-finite output, not a tiny error."""
    rng = np.random.default_rng(seed)
    mapped = [mapped] * batch if isinstance(mapped, int) else list(mapped)
    n_pages = batch * max_pages + 1
    q = jnp.asarray(rng.standard_normal((batch, 1, kvh * g, hd)),
                    jnp.float32)
    k = rng.standard_normal((n_pages, psz, kvh, hd)).astype(np.float32)
    v = rng.standard_normal((n_pages, psz, kvh, hd)).astype(np.float32)
    if poison_trash:
        k[0] = v[0] = np.nan
    pp = np.full((n_pages, psz), -1, np.int32)
    bt = np.full((batch, max_pages), -1, np.int32)
    for b in range(batch):
        pids = 1 + b * max_pages + np.arange(mapped[b])
        bt[b, :mapped[b]] = pids
        pp[pids] = np.arange(mapped[b] * psz, dtype=np.int32).reshape(
            mapped[b], psz)
    if pos is None:
        pos = [max(m * psz - 1, 0) for m in mapped]
    pos = jnp.asarray(pos, jnp.int32)
    return (q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(pp),
            jnp.asarray(bt), pos)


def _gather_ref(q, k, v, pp, bt, pos, **kw):
    return decode_attention(q, *gather_pages(PagedKVCache(k, v, pp, bt)),
                            pos, **kw)


def _assert_bit_equal(a, b, msg=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype == np.float32
    if not np.array_equal(a.view(np.uint32), b.view(np.uint32)):
        diff = np.abs(np.where(np.isnan(a), np.inf, a)
                      - np.where(np.isnan(b), np.inf, b))
        raise AssertionError(f"fused != gather {msg}: "
                             f"max abs diff {np.nanmax(diff)}")


# ---------------------------------------------------------------------------
# parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvh,g", [(2, 4), (4, 1), (1, 4)])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (5, 0.0), (0, 3.0),
                                        (7, 2.0)])
def test_bit_parity_gqa_window_softcap(kvh, g, window, cap):
    """GQA ratios (grouped / MHA / single-KV-head) × window × softcap: the
    kernel replicates decode_attention's masking and score epilogue under
    the SA contract exactly."""
    with use_policy(FP32):
        q, k, v, pp, bt, pos = _workload(0, 2, kvh, g, 16, 4, 4,
                                         mapped=[3, 1])
        ref = _gather_ref(q, k, v, pp, bt, pos, window=window, cap=cap)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos,
                                         window=window, cap=cap)
    assert np.isfinite(np.asarray(out)).all()
    _assert_bit_equal(ref, out, f"kvh={kvh} g={g} w={window} cap={cap}")


@pytest.mark.parametrize("fmt,mode", [("fp32", "exact"), ("bf16", "exact"),
                                      ("fp16", "exact"), ("fp32", "approx"),
                                      ("bf16", "approx")])
def test_bit_parity_formats_and_modes(fmt, mode):
    """Reduced-precision input formats and the approximate-normalization
    (bulk-tier) mode: cast_in per page block in VMEM ≡ cast_in on the dense
    gathered view, and the guard-bit truncation lands at the same two spots
    as the dense sa_einsum."""
    pol = PrecisionPolicy(input_format=fmt, mode=mode)
    with use_policy(pol):
        q, k, v, pp, bt, pos = _workload(1, 2, 2, 2, 16, 4, 4,
                                         mapped=[4, 2])
        ref = _gather_ref(q, k, v, pp, bt, pos)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos)
    _assert_bit_equal(ref, out, f"fmt={fmt} mode={mode}")


@pytest.mark.parametrize("ppb", [1, 2, 4])
@pytest.mark.parametrize("hb", [1, 2])
def test_bit_parity_all_grid_shapes(ppb, hb):
    """Every (pages_per_block, heads_per_block) grid shape is numerics-
    invariant — autotuning can never change the answer. (Non-divisor pins
    are clipped; ppb=4 with P=4 is the single-step walk.)"""
    with use_policy(FP32):
        q, k, v, pp, bt, pos = _workload(2, 2, 2, 2, 8, 4, 4,
                                         mapped=[2, 4])
        ref = _gather_ref(q, k, v, pp, bt, pos)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos, ppb=ppb,
                                         hb=hb)
    _assert_bit_equal(ref, out, f"ppb={ppb} hb={hb}")


def test_bit_parity_staggered_positions_partial_page():
    """Slots at unrelated decode depths (continuous batching) with the last
    page only partially written (tail positions -1): position masking in
    the kernel must match the gathered view's row for row."""
    with use_policy(FP32):
        q, k, v, pp, bt, pos = _workload(3, 3, 2, 2, 16, 4, 4,
                                         mapped=[3, 1, 4],
                                         pos=[9, 2, 14])
        # slot 0's third page is half-empty: positions beyond 9 never
        # written; mark them -1 like a real mid-page decode state
        pp = np.array(pp)
        pp[3, 2:] = -1
        pp = jnp.asarray(pp)
        ref = _gather_ref(q, k, v, pp, bt, pos)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos)
    assert np.isfinite(np.asarray(out)).all()
    _assert_bit_equal(ref, out, "staggered")


def test_trash_page_nan_and_explicit_zero_entry():
    """A block table carrying an explicit 0 (the reserved trash page id)
    must be treated as unmapped by both paths even while the trash page is
    NaN everywhere — neither 0·NaN nor a gathered NaN row may leak."""
    with use_policy(FP32):
        q, k, v, pp, bt, pos = _workload(4, 2, 2, 2, 16, 4, 4,
                                         mapped=[2, 2])
        bt = np.asarray(bt).copy()
        bt[0, 2] = 0                    # explicit trash-page entry
        bt = jnp.asarray(bt)
        ref = _gather_ref(q, k, v, pp, bt, pos)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(ref)).all()
    _assert_bit_equal(ref, out, "explicit page-0")


def test_empty_slot_yields_zeros_both_paths():
    """A slot with zero mapped pages (admitted but nothing written yet) has
    every score lane masked: the safe-softmax guard turns the would-be
    NaN row into exact zeros — in the kernel and in decode_attention."""
    with use_policy(FP32):
        q, k, v, pp, bt, pos = _workload(5, 2, 2, 2, 16, 4, 4,
                                         mapped=[3, 0], pos=[11, 0])
        ref = _gather_ref(q, k, v, pp, bt, pos)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos)
    assert np.isfinite(np.asarray(out)).all()
    assert (np.asarray(out)[1] == 0.0).all()
    assert (np.asarray(ref)[1] == 0.0).all()
    _assert_bit_equal(ref, out, "empty slot")


def test_decode_attention_all_masked_rows_guarded():
    """Unit guard test on the dense path itself: a fully-empty cache slot
    (all kv_positions -1) must produce zeros, not NaN — the pre-guard
    softmax returned exp(-inf - -inf)/0."""
    B, S, kvh, g, hd = 2, 8, 2, 2, 4
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((B, 1, kvh * g, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, kvh, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, kvh, hd)), jnp.float32)
    kv_pos = jnp.asarray(
        np.stack([np.arange(S), np.full(S, -1)]), jnp.int32)
    with use_policy(FP32):
        o = decode_attention(q, kc, vc, kv_pos, jnp.asarray([7, 0],
                                                            jnp.int32))
    o = np.asarray(o)
    assert np.isfinite(o).all()
    assert (o[1] == 0.0).all() and not (o[0] == 0.0).all()


def test_fused_unsupported_policies_raise_and_report():
    """FP8 inputs / non-fp32 output formats are the gather path's job:
    `fused_decode_supported` says so and the kernel refuses loudly rather
    than silently diverging from the quantization machinery."""
    assert fused_decode_supported(FP32)
    assert fused_decode_supported(PrecisionPolicy(input_format="bf16"))
    fp8 = PrecisionPolicy(input_format="fp8_e4m3")
    assert not fused_decode_supported(fp8)
    out_rounded = PrecisionPolicy(input_format="bf16", output_format="bf16")
    assert not fused_decode_supported(out_rounded)
    q, k, v, pp, bt, pos = _workload(7, 1, 2, 2, 8, 4, 4, mapped=2)
    with pytest.raises(ValueError, match="fused paged decode"):
        ops.paged_decode_attention(q, k, v, pp, bt, pos, policy=fp8)


def test_largest_divisor():
    assert largest_divisor(8, 8) == 8
    assert largest_divisor(8, 5) == 4
    assert largest_divisor(7, 2) == 1
    assert largest_divisor(12, 9) == 6
    assert largest_divisor(3, 100) == 3


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 4), st.integers(0, 4),
       st.integers(0, 4))
def test_random_block_tables_property(seed, m0, m1, m2):
    """Property: for any random block-table occupancy (including empty and
    full slots) the fused walk and the dense gather agree bit-for-bit."""
    with use_policy(FP32):
        q, k, v, pp, bt, pos = _workload(seed % 1000, 3, 2, 2, 8, 4, 4,
                                         mapped=[m0, m1, m2])
        ref = _gather_ref(q, k, v, pp, bt, pos)
        out = ops.paged_decode_attention(q, k, v, pp, bt, pos)
    assert np.isfinite(np.asarray(out)).all()
    _assert_bit_equal(ref, out, f"mapped=({m0},{m1},{m2})")


# ---------------------------------------------------------------------------
# serve-level A/B: the knob changes nothing but the data movement
# ---------------------------------------------------------------------------

def test_serve_fused_equals_gather_tokens(monkeypatch):
    """End-to-end: a paged engine decoding with the fused kernel (default)
    and one decoding with REPRO_DECODE_ATTN=gather produce identical token
    streams through refills. Fresh engines per setting — the knob is read
    at trace time, so each engine's chunk fn lowers its own path."""
    import dataclasses

    from repro.configs import reduced_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import SlotScheduler

    cfg = dataclasses.replace(reduced_config("qwen2.5-14b"), remat=False)
    with use_policy(FP32):
        params = M.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in
               (5, 9, 7)]
    budgets = [6, 3, 4]

    def run(impl):
        monkeypatch.setenv("REPRO_DECODE_ATTN", impl)
        with use_policy(FP32):
            eng = ServeEngine(cfg, params, batch=2, cache_len=32,
                              eos_id=-1, sync_every=2, kv_layout="paged",
                              page_size=8)
            sched = SlotScheduler(2, eos_id=-1)
            for p, n in zip(prompts, budgets):
                sched.submit(p, max_new_tokens=n)
            summary = eng.serve(sched)
        assert summary["decode_attn"] == impl
        return {r.rid: r.tokens for r in sched.finished}

    fused, gather = run("fused"), run("gather")
    assert fused == gather
    assert all(len(v) for v in fused.values())


# ---------------------------------------------------------------------------
# TPU grid legality (Mosaic block rules) — checked without a chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvh,P,psz,want,got", [
    # granite decode shape: head tiles must be KVH or 8ℤ, page blocks fill
    # 128 lanes
    (8, 128, 16, (8, 1), (8, 8)),
    (8, 128, 16, (128, 8), (128, 8)),
    (16, 128, 16, (4, 2), (8, 8)),
    # a block table narrower than 128 lanes walks in one step
    (8, 4, 16, (1, 1), (4, 8)),
    (8, 2048, 32, (2, 8), (4, 8)),
])
def test_tpu_blocks_snap_to_legal_grid(kvh, P, psz, want, got):
    from repro.kernels.sa_decode_attention import tpu_blocks
    ppb, hb = tpu_blocks(P, kvh, psz, *want)
    assert (ppb, hb) == got
    assert P % ppb == 0 and kvh % hb == 0
    assert hb == kvh or hb % 8 == 0
    assert ppb == P or ppb * psz % 128 == 0


def test_tpu_autotune_candidates_are_legal(monkeypatch):
    """Every (ppb, hb) the decode-attention autotuner can sweep or fall
    back to on TPU is a grid Mosaic accepts."""
    from repro.kernels import autotune
    from repro.kernels.sa_decode_attention import tpu_blocks
    monkeypatch.setattr(autotune, "backend_key", lambda: "tpu")
    for kvh, P, psz in ((8, 128, 16), (8, 2048, 16), (2, 4, 16), (32, 64, 32)):
        cands = autotune.decode_attn_candidates(kvh, P, psz)
        cands.append(autotune.default_decode_attn_blocks(kvh, P, psz))
        for c in cands:
            assert tpu_blocks(P, kvh, psz, *c) == c, (kvh, P, psz, c)


def test_tpu_unservable_shapes_raise():
    """No legal grid (page size off the bf16 sublane tile) or a VMEM need
    past the cap is a clear error on the chip path, never a silent fallback
    to interpret mode or the gather path."""
    q, k, v, pp, bt, pos = _workload(0, 1, 2, 2, 16, 8, 4, mapped=2)
    with pytest.raises(ValueError, match="page_size % 16"):
        ops.paged_decode_attention(q, k, v, pp, bt, pos, interpret=False)
    B, kvh, g, hd, psz, P = 1, 8, 3, 64, 16, 4096
    q = jnp.zeros((B, 1, kvh * g, hd), jnp.float32)
    pool = jnp.zeros((2, psz, kvh, hd), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        ops.paged_decode_attention(
            q, pool, pool, jnp.zeros((2, psz), jnp.int32),
            jnp.zeros((B, P), jnp.int32), jnp.zeros((B,), jnp.int32),
            interpret=False)
