"""Continuous-batching serving engine: jitted prefill + chunked decode.

Architecture (DESIGN.md §Serving):

* **Slot table** — batch row == slot. The host-side `SlotScheduler`
  (serve/scheduler.py) admits queued requests into free slots and retires
  finished ones between jitted decode chunks, so the batch never blocks on
  its slowest member (the old engine's static batch did).
* **Per-slot positions** — the decode step takes a (B,) position vector;
  each KV cache row keys/masks on its own per-slot positions
  (models/layers.py), so sequences at different depths coexist in one
  decode GEMM batch. M = batch rows per GEMM is exactly the small-M
  latency regime the SA skewed pipeline targets.
* **Batched host syncs** — decode runs `sync_every` steps device-side in a
  single `lax.scan` before the one tokens fetch + scheduler tick per
  chunk; no per-token `bool(done.all())` blocking the dispatch queue.
* **Single-slot prefill** — an admission prefills (1, T_prompt) and the
  resulting cache fragment is dynamic-update-sliced into batch row `slot`
  of every cache leaf (they all carry batch at axis 1 — see
  model.init_cache). Prefill retraces per distinct prompt length unless
  prompt-length bucketing (below) is on.
* **Paged KV (default)** — under ``REPRO_KV=paged`` (the default; ``ring``
  is the A/B fallback) `serve()` replaces the per-slot fixed rings with a
  global page pool + per-slot block tables (DESIGN.md §5): the scheduler's
  `PageAllocator` hands pages out at admission and takes them back at
  retirement, so a long prompt can map many pages while short neighbours
  map few, and admission is gated on free *pages*, not free slots. The
  prefill fragment stays dense; `_insert` page-scatters it into the pool.
  `generate()` (static batches, frontend archs) always uses dense rings.
* **Disaggregated two-pool mode** (``REPRO_DISAGG=1`` / ``disagg=True``,
  DESIGN.md §10) — prefill and decode become separately-scheduled pools:
  prefill workers run dense batch-1 prefill into a staging fragment, the
  handoff scatters the finished pages whole into the shared pool
  (`_scatter` — ownership moves, not per-token copies), and the prefilled
  request waits on the scheduler's READY queue until a decode slot frees;
  binding then costs only the block-table splice (`_bind`). Decode chunks
  never wait on prefill compute — only on the handoff splice. The unified
  path's `_insert` is exactly `_scatter` + `_bind` composed in one jitted
  program, so the split cannot change tokens: ``REPRO_DISAGG=1|0`` is
  pinned token-identical on the stream digest (CI serve-smoke).
* **Prompt-length bucketing** (``REPRO_PREFILL_BUCKET=1`` /
  ``bucket_prompts=True``) — engines without local-window rings pad each
  prefill suffix up to a powers-of-two-ish bucket so mixed prompt-length
  streams share O(log) jit traces instead of one per distinct length;
  padded rows get positions -1 (invisible to the attention mask, like
  empty ring entries), leave recurrent (ssm/conv) state as it stands at
  the real length (dt = 0 there; the conv tail is taken before them), and
  the first token reads the real last row via `last_index`.
  The summary's `prefill_compiles` counts distinct prefill traces either
  way.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import optflags
from repro.core.precision import current_policy, use_policy
from repro.kernels.ops import fused_decode_supported
from repro.models.config import ArchConfig
from repro.models import model as M
from repro.models.layers import KVCache, PagedKVCache
from repro.parallel import sharding as shardlib
from repro.train.step import (make_bucketed_prefill_step, make_draft_step,
                              make_prefill_step, make_serve_step,
                              make_verify_step)
from .scheduler import PageAllocator, SlotScheduler
from .trace import Recorder


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _jit(phase: str, fn, **kw):
    """`jax.jit` of `fn` traced under `jax.named_scope(phase)`: the
    program's device ops carry the engine phase in their sources."""
    return jax.jit(jax.named_scope(phase)(fn), **kw)


def _bucket_len(n: int) -> int:
    """Smallest powers-of-two-ish length (8, 12, 16, 24, 32, 48, 64, …)
    ≥ n: neighbours are ≤ 1.5× apart, so bucketed prefill pads ≤ 50 % in
    the worst case while a mixed-length stream shares O(log) jit traces."""
    b = 8
    while b < n:
        b = b * 3 // 2 if (b & (b - 1)) == 0 else b * 4 // 3
    return b


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params: Any, batch: int,
                 cache_len: int, eos_id: int = 2, cache_dtype=jnp.float32,
                 sync_every: int = 8, kv_layout: str | None = None,
                 page_size: int = 16, pool_pages: int | None = None,
                 max_seq_len: int | None = None, spec_k: int | None = None,
                 spec_draft_layers: int | None = None,
                 disagg: bool | None = None, prefill_workers: int = 1,
                 bucket_prompts: bool | None = None):
        """`cache_len` is the per-request capacity of the ring layout and
        the pool-sizing reference of the paged one: by default the pool
        holds the same `batch · cache_len` tokens (plus the trash page) a
        dense ring allocation would, while `max_seq_len` (default
        `cache_len`, rounded up to a page) caps a single request and
        `pool_pages` overrides total pool size — so a paged engine can
        admit one long request beyond `cache_len` without paying dense
        rings of that length in every slot.

        `spec_k` (default: REPRO_SPEC_K, 0 = off) is the self-speculative
        draft length (DESIGN.md §9): each serve iteration drafts spec_k
        tokens with an early-exit forward over the first
        `spec_draft_layers` superblocks (default: half the stack) and
        verifies them in one batched M = spec_k+1 forward.

        `disagg` (default: REPRO_DISAGG) selects the two-pool serve loop
        (DESIGN.md §10); `prefill_workers` is how many prefills the
        prefill pool runs per decode chunk. `bucket_prompts` (default:
        REPRO_PREFILL_BUCKET) pads prefill suffixes to bucket lengths —
        see `bucketing_on` for the soundness gate."""
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.cache_len = cache_len
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        self.sync_every = max(1, int(sync_every))
        kv_layout = kv_layout or os.environ.get("REPRO_KV", "paged")
        if kv_layout not in ("ring", "paged"):
            raise ValueError(
                f"REPRO_KV/kv_layout={kv_layout!r}; want 'ring' or 'paged'")
        if cfg.family == "ssm":
            kv_layout = "ring"   # no KV to page; SSM state is O(1) per slot
        self.kv_layout = kv_layout
        self.page_size = int(page_size)
        self.max_seq_len = _round_up(max_seq_len or cache_len, self.page_size)
        self.max_pages = self.max_seq_len // self.page_size
        self.pool_pages = int(
            pool_pages
            or _round_up(batch * cache_len, self.page_size) // self.page_size
            + 1)                 # +1: the reserved trash page
        # local-window rings survive in the paged layout (bounded by
        # `window`, they never strand capacity); the dense prefill fragment
        # must carry rings of the same length, so fragments are floored at
        # `window` tokens (and page allocations cover that floor)
        has_local = cfg.family != "ssm" and any(
            cfg.layer_kind(j).get("attn") == "local"
            for j in range(cfg.stack_period))
        self._frag_floor = (cfg.window if has_local and cfg.window
                            and cfg.window < self.max_seq_len else 1)
        # prefills take a fresh fragment (argument 2) and write it in
        # place: the layer scan carries the cache (model.forward), and an
        # undonated fragment would be copied into the carry first
        self._prefill = _jit("prefill", make_prefill_step(cfg),
                             donate_argnums=(2,))
        # continued-prefill variants, one jitted closure per shared-prefix
        # length (prefix_len is trace-time state like the arithmetic mode);
        # drivers already quantize prompt lengths, and shared spans are
        # page-quantized, so the population stays small
        self._prefills: dict[int, Any] = {0: self._prefill}
        self._bucketed_prefills: dict[int, Any] = {}
        # distinct prefill trace shapes seen: (prefix_len, T, bucketed) —
        # the summary's `prefill_compiles`, the quantity bucketing exists
        # to shrink
        self._prefill_shapes: set[tuple[int, int, bool]] = set()
        self._disagg_arg = disagg
        self.prefill_workers = max(1, int(prefill_workers))
        self._bucket_arg = bucket_prompts
        self._serve_step = make_serve_step(cfg)
        self.spec_k = (optflags.spec_k() if spec_k is None
                       else max(0, int(spec_k)))
        n_super = cfg.num_layers // cfg.stack_period
        self.spec_draft_layers = (
            min(max(1, int(spec_draft_layers)), n_super)
            if spec_draft_layers else max(1, n_super // 2))
        # jit-key closure cache for decode chunks. spec_k is part of the
        # key (0 = the plain chunk): the spec chunk is a different traced
        # program over the same (steps, greedy, mode) tuple, and a shared
        # entry would silently serve whichever variant traced first — the
        # same aliasing the divergence probe hit with shared mode traces.
        self._chunks: dict[tuple[int, bool, str, int], Any] = {}
        self._insert = _jit("insert", self._insert_impl, donate_argnums=(0,))
        self._scatter = _jit("scatter", self._scatter_impl,
                             donate_argnums=(0,))
        self._bind = _jit("bind", self._bind_impl, donate_argnums=(0,))
        self._clear_slot = _jit("clear_slot", self._clear_slot_impl,
                                donate_argnums=(0,))
        self._load_prefix = _jit("load_prefix", self._load_prefix_impl,
                                 static_argnums=(3,), donate_argnums=(0,))
        self.last_stats: dict[str, float] = {}
        # the spans and counters of the last serve() (serve/trace.py)
        self.recorder = Recorder()

    def new_cache(self, batch: int | None = None):
        return M.init_cache(self.cfg, batch or self.batch, self.cache_len,
                            dtype=self.cache_dtype)

    def new_pool(self):
        """Paged serve cache: global page pools + per-slot block tables."""
        return M.init_cache(self.cfg, self.batch, self.max_seq_len,
                            dtype=self.cache_dtype,
                            paged=(self.pool_pages, self.page_size))

    def new_frag(self, prompt_len: int):
        """Dense batch-1 prefill fragment sized for one paged admission:
        the prompt rounded up to whole pages (and floored at `window` so
        local-ring leaves match the pool's)."""
        cap = _round_up(max(prompt_len, self._frag_floor), self.page_size)
        return M.init_cache(self.cfg, 1, cap, dtype=self.cache_dtype)

    def prefix_caching_on(self) -> bool:
        """Prefix sharing is sound only when every prompt page is a pure
        function of the prompt tokens (+ engine config): paged layout, no
        local-window dense rings (their fragment floor couples neighbours),
        no per-slot recurrent state (ssm/hybrid). REPRO_PREFIX_CACHE=0
        forces the allocate-and-prefill-everything fallback."""
        return (optflags.prefix_cache_enabled()
                and self.kv_layout == "paged"
                and self._frag_floor == 1
                and not self.cfg.recurrent)

    def disagg_on(self) -> bool:
        """The two-pool split is sound exactly where prefix sharing is:
        a handed-off page run must mean the same thing to whichever decode
        slot eventually binds it, i.e. pages must be a pure function of
        the prompt — paged layout, no local-window dense rings, no
        per-slot recurrent state. Opt-in (REPRO_DISAGG / constructor
        `disagg`); ineligible engines silently serve unified, same
        convention as `spec_decoding_on`."""
        on = (self._disagg_arg if self._disagg_arg is not None
              else optflags.disagg_enabled())
        return (on and self.kv_layout == "paged"
                and self._frag_floor == 1
                and not self.cfg.recurrent)

    def bucketing_on(self) -> bool:
        """Prompt-length bucketing is sound wherever padded rows stay
        inert: attention masks their positions, and recurrent (ssm/conv)
        layers take dt = 0 and the conv tail at the real length
        (train.step.make_bucketed_prefill_step). Local-window ring writes
        past the real length could wrap onto live rows, so local-window
        stacks stay exact-length. Opt-in (REPRO_PREFILL_BUCKET /
        constructor `bucket_prompts`)."""
        on = (self._bucket_arg if self._bucket_arg is not None
              else optflags.prefill_bucket_enabled())
        return on and self._frag_floor == 1

    def spec_decoding_on(self) -> bool:
        """Self-speculative decoding is armed (spec_k >= 1 and the
        REPRO_SPEC_DECODE kill-switch is up) *and* sound for this engine:
        rollback is a per-slot position non-advance, which only attention
        caches support — ssm/hybrid recurrent state advances on every
        forward and cannot un-see a rejected draft. A single-superblock
        stack has no depth to early-exit from (the draft would BE the
        model), and dense ring leaves (local windows; the ring layout)
        need spec_k+1 distinct ring slots or the verify block's writes
        would collide."""
        n_super = self.cfg.num_layers // self.cfg.stack_period
        min_ring = (self._frag_floor if self._frag_floor > 1
                    else (self.cache_len if self.kv_layout == "ring"
                          else None))
        return (optflags.spec_decode_enabled() and self.spec_k >= 1
                and n_super >= 2
                and not self.cfg.recurrent
                and (min_ring is None or self.spec_k + 1 <= min_ring))

    def _fingerprint(self) -> str:
        """Cache-key component isolating engines whose pages would not be
        interchangeable: arch/config, cache dtype, GEMM backend. The
        arithmetic *mode* (premium-exact vs bulk-approx) is keyed per
        request tier by the allocator, not here."""
        import hashlib
        raw = f"{self.cfg!r}|{jnp.dtype(self.cache_dtype).name}|" \
              f"{optflags.gemm_backend()}"
        return hashlib.sha1(raw.encode()).hexdigest()[:16]

    def new_allocator(self) -> PageAllocator:
        return PageAllocator(
            self.pool_pages, self.page_size,
            max_request_pages=self.max_pages,
            min_request_tokens=self._frag_floor,
            prefix_caching=self.prefix_caching_on(),
            fingerprint=self._fingerprint())

    # ------------------------------------------------------------------
    # jitted building blocks
    # ------------------------------------------------------------------

    @staticmethod
    def _scatter_impl(cache, frag, block_row, keep=0):
        """Pool half of the fragment splice: write a batch-1 fragment's
        rows into the global page pool WITHOUT touching any slot's block
        table. This is the disaggregated handoff (DESIGN.md §10) — the
        request may sit on the ready queue for many chunks before
        `_bind_impl` maps its pages into a decode slot, and until then no
        block table references them, so the writes race with nothing.

        The fragment's rows land at flat offsets
        `block_row[t // psz] · psz + t % psz`, after wiping the positions
        of *every* page in `block_row` to -1 — recycled pages still hold
        the previous owner's positions, which would otherwise be visible
        to the attention mask. `block_row` is the request's (max_pages,)
        page run, -1-padded.

        `keep` (prefix sharing) is the count of leading block-row pages
        that are cache-hit SHARED pages: they already hold the right KV,
        other readers may be attending to them concurrently, and this
        request must never write them — both the wipe and the scatter
        redirect those pages to the reserved trash page 0 (writes there
        are harmless by the same convention unmapped decode writes rely
        on). A COW'd tail page is NOT kept: its rows ride in the fragment
        (pre-loaded from the donor) and the scatter into the request's own
        page IS the copy-on-write. Dense leaves (local rings, ssm/conv
        state) pass through — they have no pool; `_insert_impl` row-splices
        them."""
        def splice(full, one):
            if not isinstance(full, PagedKVCache):
                return full
            psz = full.k.shape[2]
            s_frag = one.k.shape[2]
            npp = s_frag // psz
            dest_row = jnp.where(jnp.arange(npp) < keep, 0,
                                 block_row[:npp])
            # bucketed prefill fragments can round up past the allocated
            # run (-1 tail in block_row): those pages hold pure padding
            # (positions already -1), redirect them to the trash page
            dest_row = jnp.where(dest_row >= 0, dest_row, 0)
            wipe_row = jnp.where(block_row >= 0, block_row, 0)
            wipe_row = jnp.where(
                jnp.arange(block_row.shape[0]) < keep, 0,
                wipe_row)                       # page 0 wipe: harmless

            def pages(x):           # (n_super, 1, s_frag, ...) fragment
                return x[:, 0].reshape(x.shape[0], npp, psz, *x.shape[3:])

            # whole pages go to [:, page] of the pool as it is laid out: a
            # flattened (page · offset) view makes XLA relayout the whole
            # pool around the scatter, on the way in and out
            return PagedKVCache(
                full.k.at[:, dest_row].set(pages(one.k).astype(full.k.dtype)),
                full.v.at[:, dest_row].set(pages(one.v).astype(full.v.dtype)),
                full.positions.at[:, wipe_row].set(-1)
                .at[:, dest_row].set(pages(one.positions)),
                full.block_table)

        return jax.tree.map(
            splice, cache, frag,
            is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache)))

    @staticmethod
    def _bind_impl(cache, block_row, slot):
        """Block-table half of the fragment splice: map an
        already-scattered page run into batch row `slot`. This is the ONLY
        device work a two-pool decode admission pays (admit_ready) — the
        KV itself was handed off at prefill completion."""
        def bind(leaf):
            if not isinstance(leaf, PagedKVCache):
                return leaf
            n_super = leaf.block_table.shape[0]
            bt = lax.dynamic_update_slice_in_dim(
                leaf.block_table,
                jnp.broadcast_to(block_row,
                                 (n_super, 1, block_row.shape[0])),
                slot, axis=1)
            return leaf._replace(block_table=bt)

        return jax.tree.map(
            bind, cache,
            is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache)))

    @staticmethod
    def _insert_impl(cache, frag, slot, block_row=None, keep=0):
        """Splice a batch-1 cache fragment into batch row `slot`.

        Dense leaves (rings, SSM/conv state, per-slot positions) carry
        batch at axis 1 (model.init_cache) and take a dynamic-update-slice.
        Paged pool leaves take `_scatter_impl`'s page scatter plus
        `_bind_impl`'s block-table splice — the unified path runs both
        halves in this one jitted program, the two-pool path runs them
        separately (scatter at handoff, bind at decode admission); either
        way the lowered writes are identical, which is why REPRO_DISAGG
        can never change tokens."""
        if block_row is not None:
            cache = ServeEngine._scatter_impl(cache, frag, block_row, keep)
            cache = ServeEngine._bind_impl(cache, block_row, slot)

        def splice(full, one):
            if isinstance(full, PagedKVCache):
                return full          # handled above
            if isinstance(full, KVCache):
                return KVCache(*(lax.dynamic_update_slice_in_dim(
                    f, o.astype(f.dtype), slot, axis=1)
                    for f, o in zip(full, one)))
            return lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=1)

        return jax.tree.map(
            splice, cache, frag,
            is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache)))

    @staticmethod
    def _load_prefix_impl(frag, cache, src_row, prefix_len: int):
        """Load a shared prompt prefix from pool pages into a dense
        prefill fragment's first `prefix_len` rows (the continued prefill
        attends over them; layers.attention_block).

        `src_row` holds the ceil(prefix_len / psz) source page ids in
        sequence order: the cache-hit whole pages, plus — on a tail hit —
        the DONOR's partial page as the last entry (its rows are gathered
        here and later scattered into the request's own page by `_insert`,
        which completes the copy-on-write without a separate device pass).
        Positions are rebuilt as arange(prefix_len): by construction row t
        of a registered prompt run holds position t, and the donor's rows
        past the shared span (its own decode tokens) are cropped by the
        `[:prefix_len]` slice."""
        def load(one, full):
            if not isinstance(full, PagedKVCache):
                return one
            def rows(x):            # whole source pages, then the rows
                x = x[:, src_row]
                return x.reshape(x.shape[0], -1,
                                 *x.shape[3:])[:, :prefix_len]

            return KVCache(
                one.k.at[:, 0, :prefix_len].set(
                    rows(full.k).astype(one.k.dtype)),
                one.v.at[:, 0, :prefix_len].set(
                    rows(full.v).astype(one.v.dtype)),
                one.positions.at[:, 0, :prefix_len].set(
                    jnp.arange(prefix_len, dtype=jnp.int32)))

        return jax.tree.map(
            load, frag, cache,
            is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache)))

    def _prefill_for(self, prefix_len: int):
        """Jitted prefill closure for one static shared-prefix length."""
        fn = self._prefills.get(prefix_len)
        if fn is None:
            fn = _jit("prefill", make_prefill_step(self.cfg, prefix_len),
                      donate_argnums=(2,))
            self._prefills[prefix_len] = fn
        return fn

    def _bucketed_prefill_for(self, prefix_len: int):
        """Jitted bucketed-prefill closure (train.step
        make_bucketed_prefill_step); the padded token length is part of
        jit's shape key, so one closure serves every bucket."""
        fn = self._bucketed_prefills.get(prefix_len)
        if fn is None:
            fn = _jit("bucketed_prefill",
                      make_bucketed_prefill_step(self.cfg, prefix_len),
                      donate_argnums=(2,))
            self._bucketed_prefills[prefix_len] = fn
        return fn

    def _prefill_request(self, scheduler, req, cache, greedy: bool, rng):
        """Shared prefill body for the unified and two-pool paths: build
        the dense fragment (prefix-cache load + COW fork included), run
        the suffix prefill — bucketed when `bucketing_on()` — and pick the
        first token. Returns (frag, first, row, keep, rng) where `row` is
        the -1-padded (max_pages,) page run and `keep` the shared leading
        page count (both None for ring engines).

        Everything after the bucket choice is the `prefill` span, which
        ends with the first token's fetch; the counters take the real
        prompt tokens prefilled (`prefill_tokens`) and the positions
        computed for them after bucketing (`prefill_positions`)."""
        paged = self.kv_layout == "paged"
        shared = req.shared_tokens if paged else 0
        suffix = req.prompt_len - shared
        Tb = suffix
        if self.bucketing_on():
            cap = self.max_seq_len if paged else self.cache_len
            b = _bucket_len(suffix)
            if shared + b <= cap:
                Tb = b
        rec = self.recorder
        rec.count("prefill_tokens", suffix)
        rec.count("prefill_positions", Tb)
        with rec.span("prefill", req.rid, tokens=suffix, positions=Tb,
                      shared=shared):
            frag = (self.new_frag(shared + Tb) if paged
                    else self.new_cache(batch=1))
            if shared:
                # prefix-cache hit: pre-load the shared span's KV from the
                # hit pages (plus the COW donor's partial tail) and prefill
                # only the uncached suffix — TTFT stays honest, it times
                # the load + suffix prefill actually paid
                src = list(req.pages[:shared // self.page_size])
                if req.cow_src is not None:
                    src.append(req.cow_src)
                frag = self._load_prefix(
                    frag, cache, jnp.asarray(src, jnp.int32), shared)
                if req.cow_src is not None:
                    # the donor's rows are in the fragment now; the
                    # scatter writes them into the request's own tail page
                    # (the copy), so the donor's copy-window lease can drop
                    scheduler.cow_done(req)
            tokens = np.asarray(req.prompt[shared:], np.int32)
            if Tb != suffix or self.bucketing_on():
                tokens = np.pad(tokens, (0, Tb - suffix))
                self._prefill_shapes.add((shared, Tb, True))
                logits, frag = self._bucketed_prefill_for(shared)(
                    self.params, jnp.asarray(tokens)[None], frag,
                    jnp.asarray(suffix - 1, jnp.int32),
                    jnp.asarray(req.prompt_len, jnp.int32))
            else:
                self._prefill_shapes.add((shared, suffix, False))
                logits, frag = self._prefill_for(shared)(
                    self.params, jnp.asarray(tokens)[None], frag, None)
            if greedy:
                first = int(np.asarray(jnp.argmax(logits[0, -1])))
            else:
                rng, k = jax.random.split(rng)
                first = int(np.asarray(
                    jax.random.categorical(k, logits[0, -1])))
            row = keep = None
            if paged:
                r = np.full((self.max_pages,), -1, np.int32)
                r[:len(req.pages)] = req.pages
                row = jnp.asarray(r)
                keep = jnp.asarray(shared // self.page_size, jnp.int32)
        return frag, first, row, keep, rng

    @staticmethod
    def _clear_slot_impl(cache, slot):
        """Unmap a freed slot's block-table rows (set to -1) so its decode
        writes fall to the trash page before the pages are reallocated."""
        def clear(leaf):
            if not isinstance(leaf, PagedKVCache):
                return leaf
            bt = leaf.block_table
            row = jnp.full((bt.shape[0], 1, bt.shape[2]), -1, bt.dtype)
            return leaf._replace(block_table=lax.dynamic_update_slice_in_dim(
                bt, row, slot, axis=1))
        return jax.tree.map(
            clear, cache,
            is_leaf=lambda x: isinstance(x, (KVCache, PagedKVCache)))

    def _must_reject(self, req) -> bool:
        """A just-admitted request the engine cannot serve.

        Paged: the allocator marked it unallocatable (more pages than the
        pool or the per-request block table holds). Ring: prompt + budget
        would wrap a global-attention ring (local windows and SSM state
        are the only wrap-safe caches)."""
        if self.kv_layout == "paged":
            return req.pages is None
        return (self.cfg.family != "ssm"
                and req.prompt_len + req.max_new_tokens > self.cache_len)

    def _chunk_fn(self, steps: int, greedy: bool, mode: str = "exact"):
        """steps decode iterations in one device-side lax.scan.

        Returns (tok, cache, pos, rng, toks (steps, B)); the caller fetches
        `toks` once per chunk — the only host sync on the decode path.

        `mode` selects the SA datapath for the chunk ("exact" | "approx" —
        the bulk serving tier). The precision policy is consulted at TRACE
        time, so mode is part of the jit-cache key and each variant is
        traced under its own `use_policy` scope — a shared traced callable
        would silently keep the mode it first saw."""
        key = (steps, greedy, mode, 0)   # 0: the non-speculative chunk
        if key not in self._chunks:
            serve_step = self._serve_step

            def chunk(params, tok, cache, pos, frontend, rng):
                def body(carry, _):
                    tok, cache, pos, rng = carry
                    logits, cache = serve_step(params, tok[:, None], cache,
                                               pos, frontend)
                    if greedy:
                        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                    else:
                        rng, k = jax.random.split(rng)
                        nxt = jax.random.categorical(
                            k, logits[:, -1]).astype(jnp.int32)
                    return (nxt, cache, pos + 1, rng), nxt

                (tok, cache, pos, rng), toks = lax.scan(
                    body, (tok, cache, pos, rng), length=steps)
                return tok, cache, pos, rng, toks

            jitted = _jit("decode_chunk", chunk, donate_argnums=(2,))

            def run(*args, _jitted=jitted, _mode=mode):
                pol = dataclasses.replace(current_policy(), mode=_mode)
                with use_policy(pol):
                    return _jitted(*args)

            self._chunks[key] = run
        return self._chunks[key]

    def _spec_chunk_fn(self, iters: int, greedy: bool, mode: str, k: int):
        """`iters` draft-then-verify iterations in one device-side scan
        (DESIGN.md §9). Each iteration drafts k tokens with the early-exit
        step, scores them with one batched M=k+1 verify forward, and
        advances every slot by its accepted-prefix length + 1:

        * verify column t's target (argmax, or the sampled token) is the
          token the plain decode path would emit at position pos+t, so the
          longest prefix where draft == target is exactly correct output;
        * column `acc` rides free — its context is fully verified even
          when the draft at that column missed — so a reject-all
          iteration still emits one normal token;
        * rollback is the position non-advance itself: stale verify
          writes past the new position stay masked (kv_positions <= pos)
          and are overwritten in place by the next iteration's writes
          (positions only re-cover ground, never skip it).

        Returns (tok, cache, pos, rng, toks (iters, B, k+1),
        accs (iters, B)); the scheduler's `observe_spec` keeps
        toks[i, b, :accs[i, b] + 1] per iteration.
        """
        key = (iters, greedy, mode, k)
        if key not in self._chunks:
            draft_step = make_draft_step(self.cfg, self.spec_draft_layers)
            verify_step = make_verify_step(self.cfg)

            def chunk(params, tok, cache, pos, frontend, rng):
                del frontend             # serve() is text-only

                def body(carry, _):
                    tok, cache, pos, rng = carry

                    def draft_body(c, _):
                        dtok, dcache, dpos = c
                        dlogits, dcache = draft_step(params, dtok[:, None],
                                                     dcache, dpos)
                        nxt = jnp.argmax(dlogits[:, -1],
                                         -1).astype(jnp.int32)
                        return (nxt, dcache, dpos + 1), nxt

                    # the draft threads the shared cache: step i attends
                    # over step i-1's early-layer keys; the verify below
                    # rewrites every row the draft wrote (all layers ⊇
                    # early layers, pos..pos+k ⊇ pos..pos+k-1), so
                    # rejected drafts leave no live state
                    (_, cache, _), drafts = lax.scan(
                        draft_body, (tok, cache, pos), length=k)
                    drafts = drafts.T                         # (B, k)
                    block = jnp.concatenate([tok[:, None], drafts], axis=1)
                    logits, cache = verify_step(params, block, cache, pos)
                    if greedy:
                        out = jnp.argmax(logits, -1).astype(jnp.int32)
                    else:
                        rng, s = jax.random.split(rng)
                        out = jax.random.categorical(
                            s, logits).astype(jnp.int32)      # (B, k+1)
                    match = (drafts == out[:, :-1]).astype(jnp.int32)
                    acc = jnp.cumprod(match, axis=1).sum(axis=1)   # (B,)
                    tok = jnp.take_along_axis(out, acc[:, None],
                                              axis=1)[:, 0]
                    return (tok, cache, pos + acc + 1, rng), (out, acc)

                (tok, cache, pos, rng), (toks, accs) = lax.scan(
                    body, (tok, cache, pos, rng), length=iters)
                return tok, cache, pos, rng, toks, accs

            jitted = _jit("spec_chunk", chunk, donate_argnums=(2,))

            def run(*args, _jitted=jitted, _mode=mode):
                pol = dataclasses.replace(current_policy(), mode=_mode)
                with use_policy(pol):
                    return _jitted(*args)

            self._chunks[key] = run
        return self._chunks[key]

    def spec_timing_probe(self, reps: int = 3) -> dict:
        """Per-iteration draft/verify wall split at this engine's serving
        shapes. serve() cannot time the two phases individually — they
        live inside one jitted scan, and a host sync between them would
        serialize the dispatch queue — so the driver's honest accounting
        (launch/serve.py) runs the same two device programs standalone on
        a fresh cache (identical shapes and tracing; an empty pool only
        changes data, not the op graph) and scales the measured costs by
        the spec iteration count. Returns {"draft_s", "verify_s"} per
        iteration."""
        k = self.spec_k
        draft_step = make_draft_step(self.cfg, self.spec_draft_layers)
        verify_step = make_verify_step(self.cfg)

        def draft_scan(params, tok, cache, pos):
            def body(c, _):
                dtok, dcache, dpos = c
                dlogits, dcache = draft_step(params, dtok[:, None], dcache,
                                             dpos)
                nxt = jnp.argmax(dlogits[:, -1], -1).astype(jnp.int32)
                return (nxt, dcache, dpos + 1), ()

            (tok, cache, _), _ = lax.scan(body, (tok, cache, pos), length=k)
            return tok, cache

        cache = (self.new_pool() if self.kv_layout == "paged"
                 else self.new_cache())
        tok = jnp.zeros((self.batch,), jnp.int32)
        pos = jnp.zeros((self.batch,), jnp.int32)
        block = jnp.zeros((self.batch, k + 1), jnp.int32)
        out = {}
        for name, fn, args in (
                ("draft_s", jax.jit(draft_scan),
                 (self.params, tok, cache, pos)),
                ("verify_s", jax.jit(verify_step),
                 (self.params, block, cache, pos))):
            jax.block_until_ready(fn(*args))     # compile + warm
            t = time.monotonic()
            r = None
            for _ in range(reps):
                r = fn(*args)
            jax.block_until_ready(r)
            out[name] = (time.monotonic() - t) / reps
        return out

    # ------------------------------------------------------------------
    # quality-tier instrumentation
    # ------------------------------------------------------------------

    def macs_per_token(self) -> int:
        """Model MACs per generated token ≈ total parameter count (every
        dense weight element contributes one MAC per token at decode;
        attention-score MACs are a small correction at decode depths).
        Feeds the per-tier energy model (core/energy.py)."""
        return int(sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(self.params)))

    def divergence_probe(self, prompt, steps: int = 16) -> dict:
        """Measure the bulk tier's output divergence against the exact
        datapath on this engine's weights.

        Teacher-forced A/B: prefill once on the exact path (prefill is
        always exact in `serve()` too), then feed the exact path's greedy
        tokens to BOTH datapaths from the same cache state and compare the
        per-step next-token logits. Each mode jits a *fresh closure* over
        the step — the precision policy is trace-time state and jit's
        trace cache keys on the wrapped function object, so re-jitting
        `self._serve_step` itself would reuse the first mode's trace.

        Returns {"steps", "max_ulp", "kl_mean", "max_abs_diff"}: max-ulp is
        the largest per-logit distance in units-in-the-last-place (ordered
        int32 mapping), kl_mean the mean per-step KL(exact ‖ approx) of the
        next-token distributions.
        """
        prompt = list(map(int, prompt))
        T = len(prompt)
        if T + steps > self.cache_len:
            raise ValueError(f"probe needs {T + steps} cache slots; "
                             f"engine has {self.cache_len}")
        exact_pol = dataclasses.replace(current_policy(), mode="exact")
        cache0 = self.new_cache(batch=1)
        with use_policy(exact_pol):
            prefill = jax.jit(make_prefill_step(self.cfg))
            logits, cache0 = prefill(
                self.params, jnp.asarray(prompt, jnp.int32)[None], cache0,
                None)
        first = int(np.asarray(jnp.argmax(logits[0, -1])))

        def fresh_step():
            # a new function object per call: jit must not share traces
            # across modes (see docstring)
            def step(params, tok, cache, pos, frontend,
                     _inner=self._serve_step):
                return _inner(params, tok, cache, pos, frontend)
            return jax.jit(step)

        def run_mode(mode, tokens):
            """Decode `steps` tokens under `mode`. `tokens[s]` (if set)
            teacher-forces step s's input; else greedy from step s-1."""
            pol = dataclasses.replace(current_policy(), mode=mode)
            step = fresh_step()
            out = []
            cache, tok = cache0, first
            with use_policy(pol):
                for s in range(steps):
                    if tokens is not None:
                        tok = tokens[s]
                    logits, cache = step(
                        self.params, jnp.asarray([[tok]], jnp.int32), cache,
                        jnp.asarray([T + s], jnp.int32), None)
                    row = np.asarray(logits[0, -1], np.float32)
                    out.append(row)
                    tok = int(row.argmax())
            return np.stack(out)

        le = run_mode("exact", None)
        # teacher-forced approx pass: replay the exact tokens so both modes
        # see identical inputs at every step (divergence is per-step, not
        # compounded through token choices)
        teacher = [first] + [int(r.argmax()) for r in le[:-1]]
        la = run_mode("approx", teacher)

        def ordered(x):
            b = x.view(np.int32).astype(np.int64)
            return np.where(b < 0, -(b & 0x7FFFFFFF), b)

        max_ulp = int(np.max(np.abs(ordered(le) - ordered(la))))
        pe = jax.nn.log_softmax(jnp.asarray(le), axis=-1)
        pa = jax.nn.log_softmax(jnp.asarray(la), axis=-1)
        kl = jnp.sum(jnp.exp(pe) * (pe - pa), axis=-1)
        return {"steps": int(steps), "max_ulp": max_ulp,
                "kl_mean": float(jnp.mean(kl)),
                "max_abs_diff": float(np.max(np.abs(le - la)))}

    # ------------------------------------------------------------------
    # static-batch generation (convenience / frontend archs)
    # ------------------------------------------------------------------

    def generate(self, prompts: jax.Array, max_new_tokens: int,
                 frontend=None, greedy: bool = True, rng=None):
        """prompts: (B, T_prompt) int32 → (B, ≤max_new_tokens) int32.

        Static batch: all B sequences prefill together and decode in
        lock-step. Decode runs in device-side chunks of `sync_every` steps;
        EOS is checked once per chunk on the fetched token block (the old
        per-token `bool(done.all())` blocked the dispatch queue every
        step), so an early-finishing batch stops at chunk granularity.
        `last_stats` records the prefill/decode wall split."""
        B, T = prompts.shape
        assert B == self.batch
        rng = rng if rng is not None else jax.random.key(0)
        t0 = time.monotonic()
        cache = self.new_cache()
        logits, cache = self._prefill(self.params, prompts, cache, frontend)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        first = np.asarray(tok)              # sync: prefill boundary (TTFT)
        t_prefill = time.monotonic() - t0
        pos = jnp.full((B,), T, jnp.int32)
        cols = [first]
        done = first == self.eos_id
        while len(cols) < max_new_tokens and not done.all():
            steps = min(self.sync_every, max_new_tokens - len(cols))
            tok, cache, pos, rng, toks = self._chunk_fn(steps, greedy)(
                self.params, tok, cache, pos, frontend, rng)
            t_np = np.asarray(toks)          # one sync per chunk
            cols.extend(t_np)
            done |= (t_np == self.eos_id).any(axis=0)
        self.last_stats = {"prefill_s": t_prefill,
                           "decode_s": time.monotonic() - t0 - t_prefill,
                           "decode_tokens": (len(cols) - 1) * B}
        return jnp.asarray(np.stack(cols, axis=1))

    # ------------------------------------------------------------------
    # continuous batching
    # ------------------------------------------------------------------

    def serve(self, scheduler: SlotScheduler, greedy: bool = True, rng=None,
              clock=time.monotonic) -> dict:
        """Run the continuous-batching loop until the scheduler drains.

        Per-request results/metrics live on the `Request` objects
        (`scheduler.finished`); returns `scheduler.summary()` merged with
        the engine's prefill/decode wall-time split, which sums the spans
        this call recorded (`self.recorder`, reset here; per-name totals
        under `spans`, the counters under `counters`). Text-only for now:
        per-slot frontends would need fragment caches of their own.
        """
        assert scheduler.n_slots == self.batch, (
            scheduler.n_slots, self.batch)
        if self.cfg.family == "vlm" or self.cfg.is_encdec:
            # prefill/decode below run frontend=None: a vlm/enc-dec arch
            # would silently skip its encoder and generate garbage
            raise ValueError(
                "continuous serving is text-only (per-slot frontends are a "
                "ROADMAP item); use ServeEngine.generate for frontend archs")
        B = self.batch
        paged = self.kv_layout == "paged"
        if paged and scheduler.pages is None:
            scheduler.pages = self.new_allocator()
        rng = rng if rng is not None else jax.random.key(0)
        t0 = clock()
        skew = 0.0          # engine-time fast-forward for frozen clocks

        def now():
            return clock() - t0 + skew
        rec = self.recorder
        rec.reset(now)
        span = rec.span
        cache = self.new_pool() if paged else self.new_cache()
        # one slot's recurrent state: ssm and conv leaves of every layer
        state_bytes = sum(a.size * a.dtype.itemsize for c in cache
                          for a in c.get("ssm", ())) // B
        tok = jnp.zeros((B,), jnp.int32)
        pos = jnp.zeros((B,), jnp.int32)
        disagg = self.disagg_on()
        mesh = shardlib.active_mesh()
        chunk_modes = {"exact": 0, "approx": 0}
        spec = self.spec_decoding_on()
        # a spec iteration emits 1..spec_k+1 tokens; size the chunk so its
        # *emission capacity* matches the plain chunk's sync_every tokens,
        # keeping admission latency (scheduler ticks happen at chunk
        # boundaries) comparable between the two paths
        spec_iters = (max(1, -(-self.sync_every // (self.spec_k + 1)))
                      if spec else 0)
        # token positions one chunk can emit per slot (decode_steps)
        chunk_steps = (spec_iters * (self.spec_k + 1) if spec
                       else self.sync_every)
        spec_chunks = 0

        # pre-compile the decode chunk before the timed loop: the first
        # call otherwise charges multi-second XLA compilation to decode_s
        # and drowns the steady-state rate the summary reports (the spec
        # chunk's draft-scan + verify graph compiles several times longer
        # than the plain chunk — exactly the A/B the accounting must not
        # skew). Safe on the fresh cache: block tables are unmapped (paged
        # writes fall to the trash page) and admission overwrites a ring/
        # ssm slot row wholesale; tok/pos/rng results are discarded, so
        # the token stream is byte-identical with or without the warmup.
        t_c = clock()
        warm = (self._spec_chunk_fn(spec_iters, greedy, "exact", self.spec_k)
                if spec else self._chunk_fn(self.sync_every, greedy))
        cache = warm(self.params, tok, cache, pos, None, rng)[1]
        jax.block_until_ready(cache)
        compile_s = clock() - t_c

        def clear_freed():
            # retirement freed the slot's pages; unmap its block-table rows
            # *before* the pages can be handed to a new admission, or the
            # stale slot's decode writes would corrupt the new owner (they
            # fall to the trash page once unmapped). Runs before admissions
            # (observe-retired slots) and again after them (a request whose
            # first token already finished it frees pages mid-admission;
            # its slot cannot be refilled within the same pass, so clearing
            # here never wipes a live row).
            nonlocal cache
            for freed in scheduler.drain_freed():
                with span("clear_slot", slot=freed):
                    cache = self._clear_slot(cache, freed)

        while not scheduler.drained():
            if paged:
                clear_freed()
            if disagg:
                # decode-pool admissions: bind already-prefilled requests
                # off the ready queue — a block-table splice, never
                # prefill compute — so free slots refill between chunks
                # at handoff cost only
                for slot in scheduler.free_slots():
                    req = scheduler.admit_ready(slot, now())
                    if req is None:
                        break
                    with span("bind", req.rid):
                        r = np.full((self.max_pages,), -1, np.int32)
                        r[:len(req.pages)] = req.pages
                        cache = self._bind(cache, jnp.asarray(r), slot)
                        # first token came from finish_prefill; resume
                        # after it
                        tok = tok.at[slot].set(req.tokens[0])
                        pos = pos.at[slot].set(req.prompt_len)
                # prefill pool: up to `prefill_workers` prefills per chunk
                # interval, stopping once the ready queue could refill
                # every slot (prefilling further ahead only pins pages
                # earlier for no latency win)
                n_pf = 0
                while (n_pf < self.prefill_workers
                       and scheduler.ready_depth() < B):
                    req = scheduler.begin_prefill(now())
                    if req is None:
                        break
                    if self._must_reject(req):
                        # the allocator found the request can never fit
                        # the pool / block table — retire it as rejected
                        scheduler.reject_prefill(req, now())
                        continue
                    with span("admission", req.rid):
                        frag, first, row, keep, rng = self._prefill_request(
                            scheduler, req, cache, greedy, rng)
                        # the handoff: reshard the staged fragment onto the
                        # pool's layout (page dim sharded over data axes —
                        # whole pages move, no per-token traffic), scatter
                        # it in, and sync — the one wall decode can block
                        # on, a stall while any decode slot is live
                        with span("scatter", req.rid,
                                  stall=scheduler.num_active() > 0):
                            if mesh is not None:
                                frag = shardlib.reshard_handoff(frag, mesh,
                                                                self.cfg)
                            cache = self._scatter(cache, frag, row, keep)
                            jax.block_until_ready(cache)
                        # register BEFORE the scheduler sees the first
                        # token: a first-token EOS retires the request
                        # immediately, and the registered pages must park
                        # as cached, not return to the free list
                        scheduler.pages.prefix_register(req.prompt,
                                                        req.pages, req.tier)
                        scheduler.finish_prefill(
                            req, first, now(),
                            prefill_s=rec.last("prefill").dur)
                    n_pf += 1
            else:
                for slot in scheduler.free_slots():
                    req = scheduler.admit(slot, now())
                    if req is None:
                        break
                    if self._must_reject(req):
                        # ring: a global-attention KV ring must never wrap
                        # (the write would overwrite live prompt keys and
                        # silently corrupt the request). Paged: the
                        # allocator found the request can never fit the
                        # pool / block table. Retire it as rejected —
                        # in-flight slots keep decoding.
                        scheduler.reject(slot, now())
                        continue
                    # every other live slot sits idle through this
                    # admission — the stall disaggregation exists to remove
                    with span("admission", req.rid,
                              stall=scheduler.num_active() > 1) as adm:
                        frag, first, row, keep, rng = self._prefill_request(
                            scheduler, req, cache, greedy, rng)
                        # dispatch-only: the unified splice overlaps the
                        # next admission, unlike the two-pool synced handoff
                        with span("insert", req.rid):
                            if paged:
                                cache = self._insert(cache, frag, slot, row,
                                                     keep)
                                # register this prompt's pages for reuse
                                # BEFORE the scheduler sees the first token:
                                # a first-token EOS retires the request
                                # immediately, and the registered pages must
                                # park as cached, not return to the free list
                                scheduler.pages.prefix_register(
                                    req.prompt, req.pages, req.tier)
                            else:
                                cache = self._insert(cache, frag, slot)
                        tok = tok.at[slot].set(first)
                        pos = pos.at[slot].set(req.prompt_len)
                    scheduler.start(slot, first, now(), prefill_s=adm.dur)
            if paged:
                clear_freed()

            if scheduler.num_active() == 0:
                if scheduler.ready_depth() > 0:
                    # staged-but-unbound work: the next pass binds it
                    continue
                # queue non-empty but nothing has arrived yet: wait for the
                # next arrival instead of spinning
                with span("wait_arrival"):
                    nxt = scheduler.next_arrival()
                    if nxt is None:
                        break
                    wait = nxt - now()
                    if wait > 0:
                        before = clock()
                        time.sleep(min(wait, 0.05))
                        if clock() == before:
                            # injected/frozen clock: real sleeps cannot
                            # advance it — fast-forward engine time to the
                            # arrival
                            skew += wait
                continue

            # chunk datapath: approximate only when EVERY active slot is a
            # bulk request — premium never decodes on the approx path; bulk
            # slots sharing a chunk with premium get exact arithmetic (the
            # tier is a quality floor). Tier-affine admission (scheduler)
            # phase-separates mixed streams so all-bulk chunks do occur.
            live = [s.req for s in scheduler.slots if s.req is not None]
            mode = ("approx" if {r.tier for r in live} == {"bulk"}
                    else "exact")
            chunk_modes[mode] += 1
            with span("decode_chunk", steps=chunk_steps, live=len(live),
                      mode=mode):
                if spec:
                    tok, cache, pos, rng, toks, accs = self._spec_chunk_fn(
                        spec_iters, greedy, mode, self.spec_k)(
                        self.params, tok, cache, pos, None, rng)
                else:
                    tok, cache, pos, rng, toks = self._chunk_fn(
                        self.sync_every, greedy, mode)(
                        self.params, tok, cache, pos, None, rng)
                with span("chunk_wait"):
                    toks_np = np.asarray(toks)   # the chunk's single sync
                    if spec:
                        accs_np = np.asarray(accs)
            rec.count("decode_steps", chunk_steps)
            rec.count("decode_slot_steps", chunk_steps * B)
            if state_bytes:
                # each step reads and writes every live slot's state
                rec.count("decode_state_bytes",
                          2 * state_bytes * len(live) * chunk_steps)
            before = sum(r.n_generated for r in live)
            with span("observe"):
                if spec:
                    spec_chunks += 1
                    scheduler.observe_spec(toks_np, accs_np, now(),
                                           mode=mode)
                else:
                    scheduler.observe(toks_np, now(), mode=mode)
            rec.count("decode_tokens_kept",
                      sum(r.n_generated for r in live) - before)

        # the wall split, summed over spans (honest accounting, DESIGN.md
        # §10): handoff = the page scatter / block-table splice walls;
        # decode_stall = the admission wall spent while ≥1 OTHER slot sat
        # idle waiting — the decode-blocking component. Unified mode
        # charges the whole admission (prefill + insert) to the stall (the
        # slots genuinely wait on it); two-pool mode charges only the
        # handoff sync, the part a real two-pool deployment (prefill on its
        # own devices) would retain. Single-host caveat: both pools share
        # this process's device, so the stall split is the modeled
        # decode-blocking time, while wall_s/ITL remain real measurements.
        if disagg:
            prefill_s = rec.total("prefill")
            handoff_s = rec.total("scatter") + rec.total("bind")
            decode_stall_s = rec.total("scatter", stall=True)
        else:
            prefill_s = rec.total("admission")
            handoff_s = rec.total("insert")
            decode_stall_s = rec.total("admission", stall=True)
        decode_s = rec.total("decode_chunk")
        summary = scheduler.summary()
        if chunk_modes["approx"]:
            summary |= {"chunks_exact": chunk_modes["exact"],
                        "chunks_approx": chunk_modes["approx"]}
        summary |= {"prefill_s": round(prefill_s, 4),
                    "decode_s": round(decode_s, 4),
                    "compile_s": round(compile_s, 4),
                    "wall_s": round(now(), 4),
                    "handoff_s": round(handoff_s, 4),
                    "decode_stall_s": round(decode_stall_s, 4),
                    "prefill_compiles": len(self._prefill_shapes),
                    "disagg": disagg}
        summary |= rec.summary()
        if spec_chunks:
            summary |= {"spec_k": self.spec_k,
                        "spec_draft_layers": self.spec_draft_layers,
                        "spec_iters": spec_chunks * spec_iters}
        if paged:
            # which decode-attention path actually lowered into the chunk fn
            # (the knob is read at trace time; FP8 / non-fp32-out policies
            # fall back to gather regardless of the env setting)
            impl = optflags.decode_attn_impl()
            if impl == "fused" and not fused_decode_supported(current_policy()):
                impl = "gather"
            summary["decode_attn"] = impl
        served = summary["requests"] - summary["rejected"]
        if decode_s > 0 and served:
            # each *served* request's first token came from prefill, not
            # the decode chunks (rejected ones produced nothing at all)
            decode_tokens = summary["generated_tokens"] - served
            summary["decode_tok_s"] = round(decode_tokens / decode_s, 2)
        self.last_stats = summary
        return summary
