"""Beyond-paper performance optimizations (EXPERIMENTS.md §Perf).

Each flag gates one hillclimb change so baseline/optimized lowerings can be
A/B'd from the same tree. `REPRO_OPT=0` disables all.

  pad_kv_heads        — pad KV heads (and the grouped Q heads) up to the TP
                        axis size when KVH doesn't divide it. Without this
                        the SPMD partitioner REPLICATES all attention einsums
                        across the model axis (observed: 16× attention FLOPs
                        on phi3 40H/10KVH, full KV-cache reshard per decode
                        step on gemma). Padding costs ≤2× score FLOPs but
                        shards 16×.
  bf16_params_in_layers — cast layer params to bf16 at superblock entry, so
                        FSDP all-gathers move bf16, not fp32 (2× ICI saving
                        on llama4-maverick). Numerically identical: sa_dot
                        quantizes to bf16 at every use anyway.
  pallas_attention    — route forward-only attention (serving prefill) through
                        the Pallas flash kernel (kernels/sa_attention.py):
                        softmax state stays in VMEM instead of materializing
                        probability tiles in HBM. Default on for TPU only
                        (interpret mode on CPU is correctness-grade, not
                        speed-grade); training keeps the custom-VJP jnp path.
  moe_dropless_serve  — route MoE through the dropless dense dispatch
                        (models/moe.py moe_ffn_dropless) whenever a decode
                        cache is threaded through the forward. Capacity-drop
                        dispatch silently drops overflow tokens — fine as a
                        training approximation, unacceptable when serving a
                        user's prompt, and it breaks prefill+decode ≡ full
                        forward exactness. Costs E/k× MoE FLOPs at decode
                        shapes (T ∈ {1..8}), where the GEMMs are latency-
                        not throughput-bound. Unlike the perf flags this is
                        a correctness switch, so REPRO_OPT=0 does NOT
                        disable it.
  fused_epilogue      — fuse bias add / activation into the GEMM epilogue
                        (models/layers.py passes bias=/act= to sa_dot). On the
                        pallas backend this runs inside the kernel's final K
                        step before the single output rounding; on xla it is
                        the same fp32 math before cast_out — so the flag is
                        numerics-preserving under the default fp32 output
                        format and A/B-s only the fusion, not the result.

The GEMM backend itself (xla | pallas | emulate) is a string knob, not a
bool flag: `gemm_backend()` reads REPRO_GEMM_BACKEND (default "xla") and
seeds `core.precision.DEFAULT_POLICY`, so the whole stack — layers, train
step, benchmarks — is A/B-able end-to-end from one environment variable.
`decode_attn_impl()` (REPRO_DECODE_ATTN, default "fused") is the same
pattern for the paged decode-attention path: fused Pallas page walk vs the
gather+dense fallback.
"""
from __future__ import annotations

import os

import jax

_ENABLED = os.environ.get("REPRO_OPT", "1") not in ("0", "false", "off")

FLAGS = {
    "pad_kv_heads": _ENABLED,
    "bf16_params_in_layers": _ENABLED,
    "pallas_attention": _ENABLED and jax.default_backend() == "tpu",
    "fused_epilogue": _ENABLED,
    # NOT gated on REPRO_OPT: serving exactness is a correctness property,
    # not a perf optimization — the kill-switch must never silently revert
    # to token-dropping dispatch. A/B via set_flag / moe_ffn(dropless=).
    "moe_dropless_serve": True,
    # REFUTED (kept for the record, default off): padding the expert dim at
    # trace time (granite 40→48) forces a per-layer-per-µstep reshard of the
    # F-sharded stored weights into the E-sharded compute layout — measured
    # +104 % collectives (10.9 s→22.3 s) instead of the predicted win. The
    # correct version stores params E-padded (checkpoint-shape change);
    # documented in EXPERIMENTS.md §Perf.
    "pad_experts": False,
}


def enabled(name: str) -> bool:
    return FLAGS.get(name, False)


def set_flag(name: str, value: bool):
    FLAGS[name] = value


_GEMM_BACKENDS = ("xla", "pallas", "emulate")


def gemm_backend() -> str:
    """Process-default GEMM backend for `PrecisionPolicy` (reads
    REPRO_GEMM_BACKEND at call time; `core.precision.current_policy`
    consults this on every un-scoped call, so late env changes are
    honored). Scoped overrides go through `core.precision.use_policy`."""
    backend = os.environ.get("REPRO_GEMM_BACKEND", "xla")
    if backend not in _GEMM_BACKENDS:
        raise ValueError(
            f"REPRO_GEMM_BACKEND={backend!r}; want one of {_GEMM_BACKENDS}")
    return backend


_DECODE_ATTN_IMPLS = ("fused", "gather")


def decode_attn_impl() -> str:
    """Paged decode-attention implementation (reads REPRO_DECODE_ATTN at
    call time, same contract as `gemm_backend`). "fused" (default) walks
    the block table inside the Pallas kernel
    (kernels/sa_decode_attention.py); "gather" is the A/B fallback that
    materializes the dense gathered view and runs jnp `decode_attention`
    on top — kept exactly like REPRO_KV=ring keeps the dense ring. The two
    are pinned bit-identical (tests/test_decode_kernel.py), so the knob
    A/Bs only the data movement. Consulted at trace time in
    models/layers.py; policies the kernel can't reproduce (FP8 inputs,
    non-fp32 output rounding) fall back to "gather" regardless."""
    impl = os.environ.get("REPRO_DECODE_ATTN", "fused")
    if impl not in _DECODE_ATTN_IMPLS:
        raise ValueError(
            f"REPRO_DECODE_ATTN={impl!r}; want one of {_DECODE_ATTN_IMPLS}")
    return impl


def prefix_cache_enabled() -> bool:
    """Prefix sharing + copy-on-write on the paged KV (reads
    REPRO_PREFIX_CACHE at call time, default on). When on, paged serve
    engines key whole-page prompt-prefix runs by
    (config fingerprint, tier, token ids) and map cache hits into new
    block tables with refcount bumps instead of re-prefilling
    (serve/scheduler.PageAllocator). "0" falls back to the allocate-and-
    prefill-everything path — kept as an A/B exactly like
    REPRO_DECODE_ATTN=gather; the two are pinned token-identical in
    tests/test_paged_kv.py. Engines additionally auto-disable sharing for
    layouts where a page is not a pure function of the prompt (local-
    window dense rings, ssm/hybrid states)."""
    return os.environ.get("REPRO_PREFIX_CACHE", "1") not in (
        "0", "false", "off")


def disagg_enabled() -> bool:
    """Disaggregated prefill/decode serving (reads REPRO_DISAGG at call
    time, default off — opt-in, same contract as `prefix_cache_enabled`).
    When on, paged serve engines split into a prefill pool and a decode
    pool with an explicit KV-page handoff (DESIGN.md §10): prefill workers
    run dense batch-1 prefill into a staging fragment, the finished pages
    are scattered whole into the shared pool, and decode admissions drain
    a ready queue of already-prefilled requests between chunks — decode
    never waits on prefill compute, only on the handoff splice. "1" and
    "0" are pinned token-identical on the greedy stream digest (CI
    serve-smoke), so the knob trades scheduling only, never tokens.
    Engines auto-disable the split where pages are not a pure function of
    the prompt (ring layout, local-window rings, ssm/hybrid state) — the
    same gate family as prefix sharing."""
    return os.environ.get("REPRO_DISAGG", "0") not in ("0", "false", "off")


def prefill_bucket_enabled() -> bool:
    """Prompt-length bucketing in the serve prefill path (reads
    REPRO_PREFILL_BUCKET at call time, default off). When on, engines
    pad each prefill's token block up to a powers-of-two-ish
    bucket length, so mixed --prompt-lens streams reuse a handful of jit
    traces instead of retracing per distinct length (the summary's
    `prefill_compiles` counts distinct traces). Padded rows are masked
    after the fact: their cache positions are forced to -1 (invisible to
    the attention mask, exactly like empty ring entries) and the logits
    are taken at the real last token via `last_index`, so real rows come
    out of the same causal arithmetic; recurrent (ssm/conv) layers give
    padded rows dt = 0 and take the conv tail at the real length, so
    padding leaves their state as it stands. Engines auto-disable
    bucketing where padded writes could touch live state (local-window
    rings)."""
    return os.environ.get("REPRO_PREFILL_BUCKET", "0") not in (
        "0", "false", "off")


def spec_decode_enabled() -> bool:
    """Self-speculative decoding kill-switch (reads REPRO_SPEC_DECODE at
    call time, default on — same contract as `prefix_cache_enabled`).
    The flag only *arms* the path: a serve engine actually drafts when its
    `spec_k >= 1` (constructor arg or REPRO_SPEC_K / --spec-k), so default
    environments never speculate. "0" is the A/B: under greedy decoding
    the spec path is pinned token-identical to plain chunked decode
    (tests/test_serve.py, CI serve-smoke), so the switch trades wall time
    only, never tokens. Engines additionally auto-disable drafting where
    rollback-by-position is unsound (ssm/hybrid recurrent state, single-
    superblock stacks with nothing to early-exit from)."""
    return os.environ.get("REPRO_SPEC_DECODE", "1") not in (
        "0", "false", "off")


def spec_k(default: int = 0) -> int:
    """Default draft length for self-speculative decoding (reads
    REPRO_SPEC_K at call time; 0 = off). Each serve iteration drafts
    `spec_k` tokens with the early-exit forward and verifies them in one
    batched M = spec_k+1 forward; `ServeEngine(spec_k=...)` and the
    driver's --spec-k override this per engine."""
    k = int(os.environ.get("REPRO_SPEC_K", default))
    if k < 0:
        raise ValueError(f"REPRO_SPEC_K={k}; want >= 0")
    return k


_SA_MODES = ("exact", "approx")


def sa_mode() -> str:
    """Process-default SA arithmetic mode for `PrecisionPolicy` (reads
    REPRO_SA_MODE at call time, same contract as `gemm_backend`).
    "exact" is the paper's round-once datapath; "approx" is the
    approximate-normalization variant (coarse LZA, arxiv 2408.11997) that
    backs the serve engine's "bulk" quality tier."""
    mode = os.environ.get("REPRO_SA_MODE", "exact")
    if mode not in _SA_MODES:
        raise ValueError(f"REPRO_SA_MODE={mode!r}; want one of {_SA_MODES}")
    return mode
