"""Request-stream serving driver: Poisson arrivals through the
continuous-batching engine (scheduler + slot table + chunked decode).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --reduced \
        --batch 4 --requests 16 --rate 8 --prompt-lens 8,16,32 --max-new 32

`--rate` is the mean arrival rate in requests/s (exponential inter-arrival
times); 0 queues everything up-front. Prompt lengths cycle through the
`--prompt-lens` set (each distinct length costs one prefill retrace).
Frontend archs (vlm / enc-dec) fall back to static-batch `generate` — the
continuous engine is text-only for now — with the same honest accounting:
tok/s counts real generated tokens (nothing past EOS), and prefill vs
decode wall time are reported separately.

`--prefix-mix p` prepends one fixed `--prefix-len`-token system prompt to
a fraction p of the requests: with the prefix cache on (REPRO_PREFIX_CACHE,
default 1, paged layout) those requests prefill the shared span once and
later admissions map the cached pages with refcount bumps — the summary's
`prefix_hits` / `prefix_tokens_saved` / `pages_cached` fields and the lower
`pages_peak_in_use` / `prefill_s` quantify the win, and TTFT stays honest
(it times the suffix prefill a hit actually pays, not the full prefill it
skipped). The same seed with `REPRO_PREFIX_CACHE=0` serves the identical
stream without sharing — outputs are pinned token-identical.

`--tier-mix p` marks each request "bulk" with probability p (seeded):
bulk requests may decode on the approximate-normalization datapath (the
coarse-LZA design of arxiv 2408.11997 — see core/chained_fma.approx_*)
whenever a whole chunk is bulk; premium requests always get the exact
round-once datapath. With a mix, the driver also runs the engine's
divergence probe (teacher-forced exact-vs-approx logits on one prompt;
max-ulp is bounded by the dropped guard bits — see DESIGN.md §6) and the
per-tier modeled energy summary (core/energy.py tier_energy_summary).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import SlotScheduler


def build_requests(sched: SlotScheduler, cfg, n: int, rate: float,
                   prompt_lens: list[int], max_new: int, seed: int,
                   tier_mix: float = 0.0, prefix_mix: float = 0.0,
                   prefix_len: int = 32):
    """Queue `n` synthetic requests. `prefix_mix p` prepends one fixed
    `prefix_len`-token system prompt (drawn once per run) to a fraction p
    of the requests — the shared-system-prompt fleet the prefix cache
    multiplies: under REPRO_PREFIX_CACHE=1 those prompts prefill the shared
    span once and later admissions map the cached pages. The request
    stream is a pure function of `seed`, so A/B runs with the cache on and
    off serve the identical workload."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, prefix_len)
    t = 0.0
    for i in range(n):
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        plen = prompt_lens[i % len(prompt_lens)]
        prompt = rng.integers(0, cfg.vocab_size, plen)
        if rng.random() < prefix_mix:
            prompt = np.concatenate([system, prompt])
        tier = "bulk" if rng.random() < tier_mix else "premium"
        sched.submit(prompt, max_new_tokens=max_new, arrival_time=t,
                     tier=tier)


def spec_warmup_train(cfg, params, steps: int, seed: int):
    """Seed-pure warm-up training for the speculative-decoding demo.

    Random init weights are random rotations layer to layer — the early-
    exit draft's argmax agrees with the full model's ~10% of the time, so
    speculation can only lose. Real deployments speculate on *trained*
    models; this stands in for that with a few hundred AdamW steps on an
    order-1 Markov corpus (each token has a dominant successor drawn once
    from `seed`, taken with p=0.9), which is learnable by the early layers
    alone — exactly the regime where a shallow draft agrees with the full
    stack. Pure function of (cfg, seed): the REPRO_SPEC_DECODE=1|0 A/B
    trains identical weights on both sides.
    """
    import dataclasses

    from repro.train.optimizer import AdamW, constant_lr
    from repro.train.step import make_train_step
    from repro.train.train_state import TrainState

    rng = np.random.default_rng(seed + 11)
    succ = rng.integers(0, cfg.vocab_size, size=cfg.vocab_size)

    def markov_batch(bsz=8, T=32):
        toks = np.empty((bsz, T + 1), np.int64)
        toks[:, 0] = rng.integers(0, cfg.vocab_size, size=bsz)
        for t in range(T):
            toks[:, t + 1] = np.where(
                rng.random(bsz) < 0.9, succ[toks[:, t]],
                rng.integers(0, cfg.vocab_size, size=bsz))
        return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                "labels": jnp.asarray(toks[:, 1:], jnp.int32)}

    opt = AdamW(constant_lr(3e-3), weight_decay=0.0)
    # remat trades compute for memory — pointless at warm-up scale
    step = jax.jit(make_train_step(dataclasses.replace(cfg, remat=False),
                                   opt))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt.init(params))
    metrics = {}
    for _ in range(steps):
        state, metrics = step(state, markov_batch())
    print(f"[spec-warmup] steps={steps} "
          f"final_loss={float(metrics.get('loss', float('nan'))):.3f}")
    return state.params


def preseed_decode_blocks(cfg, batch: int, page_size: int | None = None,
                          max_pages: int | None = None,
                          spec_k: int = 0):
    """Sweep decode-shape GEMV blocks before serving starts.

    The jitted decode step cannot sweep mid-trace (autotune.lookup falls
    back to the heuristic there), so winners must be in the cache before
    the first chunk compiles. Seeds the (N, K) pairs the decode step's
    projections actually look up — QKV (d→heads), out-proj (heads→d),
    FFN up/down, lm head — at M = batch (the decode GEMMs flatten
    (B, 1, D) to (B, D), so batch IS the GEMM M; other Ms would never be
    consulted). Epilogue-fused keys (e.g. the silu'd gate) fall back to
    these bare-GEMM entries (autotune.lookup's documented fallback).

    When the engine serves the paged KV layout (`page_size`/`max_pages`
    given), also sweeps the fused decode-attention grid shapes
    (pages_per_block, heads_per_block) for the exact workload the chunk fn
    will lower — same cannot-sweep-mid-trace constraint, same cache.

    With `spec_k > 0`, also pre-seeds the speculative verify forward's
    GEMM shapes at M = batch·(spec_k+1) (autotune.tune_spec_verify) — the
    batched verify is the one decode-path GEMM that doesn't run at
    M = batch."""
    from repro.kernels import autotune

    dtype = autotune.production_dtype()
    d, hd = cfg.d_model, cfg.hd
    shapes = {(cfg.num_heads * hd, d), (cfg.num_kv_heads * hd, d),
              (d, cfg.num_heads * hd), (cfg.padded_vocab, d)}
    ff = cfg.d_ff_dense or cfg.d_ff
    if ff:
        shapes |= {(ff, d), (d, ff)}
    for n, k in sorted(shapes):
        if spec_k:
            autotune.tune_spec_verify(n, k, batch, spec_k, dtype=dtype,
                                      reps=2)
        else:
            autotune.tune_decode(n, k, ms=(batch,), dtype=dtype, reps=2)
    if page_size and max_pages:
        kvh = cfg.num_kv_heads
        autotune.tune_decode_attn(batch, kvh, cfg.num_heads // kvh, hd,
                                  page_size, max_pages, reps=2)


def _itl_p50_ms(finished) -> float | None:
    """Median per-request inter-token latency (ms): decode wall after the
    first token / tokens after the first. The disagg acceptance metric —
    it must stay flat while decode stalls drop."""
    itls = [(r.t_done - r.t_first_token) / (r.n_generated - 1)
            for r in finished
            if r.t_first_token is not None and r.t_done is not None
            and r.n_generated >= 2]
    if not itls:
        return None
    return round(float(np.percentile(itls, 50)) * 1000, 3)


def _print_phases(summary) -> None:
    """Honest per-phase wall split (engine.serve accounting comment):
    prefill/decode walls are real measurements in both modes;
    decode_stall is the decode-blocking component — the whole admission
    prefill in unified mode, only the synced handoff in two-pool mode.
    The line ends with every span's total seconds and count."""
    spans = " ".join(f"{k}={v['s']:.4f}s/{v['n']}"
                     for k, v in sorted(summary.get("spans", {}).items()))
    print(f"[phases] disagg={summary.get('disagg')} "
          f"prefill={summary.get('prefill_s')}s "
          f"decode={summary.get('decode_s')}s "
          f"handoff={summary.get('handoff_s')}s "
          f"decode_stall={summary.get('decode_stall_s')}s "
          f"itl_p50={summary.get('decode_itl_p50_ms')}ms "
          f"ready_p50={summary.get('ready_depth_p50')} "
          f"prefill_compiles={summary.get('prefill_compiles')} "
          f"spans: {spans}")


def make_engine(args, cfg, params) -> ServeEngine:
    return ServeEngine(cfg, params, args.batch, args.cache_len,
                       eos_id=args.eos_id, sync_every=args.sync_every,
                       kv_layout=args.kv, page_size=args.page_size,
                       pool_pages=args.pool_pages,
                       max_seq_len=args.max_seq_len, spec_k=args.spec_k,
                       spec_draft_layers=args.spec_draft_layers or None,
                       disagg=args.disagg or None,
                       prefill_workers=args.prefill_workers,
                       bucket_prompts=args.bucket_prompts or None)


def stream_digest(finished) -> str:
    """Digest of the full rid-ordered token streams: the spec-decode CI leg
    pins REPRO_SPEC_DECODE=1|0 byte-identical through this one field
    without dumping every token into the summary line."""
    import hashlib
    streams = ",".join(
        f"{r.rid}:{'-'.join(map(str, r.tokens))}"
        for r in sorted(finished, key=lambda r: r.rid))
    return hashlib.sha1(streams.encode()).hexdigest()[:16]


def serve_stream(args, cfg, params, plens):
    """One continuous-batching run: engine, seeded request stream, serve.
    Returns (engine, scheduler, summary); the finished requests and their
    tokens are on `scheduler.finished`."""
    if args.autotune_decode:
        import os as _os
        paged = (args.kv or _os.environ.get("REPRO_KV", "paged")) == "paged"
        seq = args.max_seq_len or args.cache_len
        max_pages = -(-seq // args.page_size) if paged else None
        preseed_decode_blocks(cfg, args.batch,
                              page_size=args.page_size if paged else None,
                              max_pages=max_pages, spec_k=args.spec_k)
    engine = make_engine(args, cfg, params)
    sched = SlotScheduler(args.batch, eos_id=args.eos_id)
    build_requests(sched, cfg, args.requests, args.rate, plens,
                   args.max_new, args.seed, tier_mix=args.tier_mix,
                   prefix_mix=args.prefix_mix, prefix_len=args.prefix_len)
    summary = engine.serve(sched, greedy=True)
    summary["stream_digest"] = stream_digest(sched.finished)
    itl = _itl_p50_ms(sched.finished)
    if itl is not None:
        summary["decode_itl_p50_ms"] = itl
    return engine, sched, summary


def serve_continuous(args, cfg, params, plens) -> dict:
    engine, sched, summary = serve_stream(args, cfg, params, plens)
    _print_phases(summary)
    if engine.spec_decoding_on() and summary.get("spec_iters"):
        # honest accounting: decode_tok_s above already counts only
        # accepted tokens (rejected drafts never reach a Request); the
        # draft/verify split is measured standalone at serving shapes
        # (spec_timing_probe — the two phases share one jitted scan in
        # serve(), so they cannot be timed in situ) and scaled by the
        # iteration count actually run
        split = engine.spec_timing_probe()
        iters = summary["spec_iters"]
        summary["spec_draft_s"] = round(split["draft_s"] * iters, 4)
        summary["spec_verify_s"] = round(split["verify_s"] * iters, 4)
        print(f"[spec] k={engine.spec_k} "
              f"draft_layers={engine.spec_draft_layers}/"
              f"{cfg.num_layers // cfg.stack_period} "
              f"accept_rate={summary.get('spec_accept_rate', 0.0)} "
              f"accepted={summary.get('spec_accepted', 0)}/"
              f"drafted={summary.get('spec_drafted', 0)} "
              f"draft_s~{summary['spec_draft_s']} "
              f"verify_s~{summary['spec_verify_s']}")
    for r in sorted(sched.finished, key=lambda r: r.rid):
        # rejected requests never started: no TTFT / rate to report
        ttft = float("nan") if r.ttft is None else r.ttft
        print(f"req {r.rid:3d} slot {r.slot} {r.tier:7s} "
              f"prompt {r.prompt_len:4d} "
              f"gen {r.n_generated:4d} ({r.finish_reason or 'n/a':8s}) "
              f"ttft {ttft:.3f}s "
              f"decode {r.decode_tok_s or float('nan'):.1f} tok/s")
    if args.tier_mix > 0:
        from repro.core.energy import tier_energy_summary

        energy = tier_energy_summary(sched.tier_mode_tokens,
                                     engine.macs_per_token())
        summary |= {f"energy_{k}": v for k, v in energy.items()}
        rng = np.random.default_rng(args.seed + 7)
        probe = engine.divergence_probe(
            rng.integers(0, cfg.vocab_size, plens[0]),
            steps=min(16, args.max_new))
        print(f"[divergence] steps={probe['steps']} "
              f"max_ulp={probe['max_ulp']} kl_mean={probe['kl_mean']:.3e} "
              f"max_abs_diff={probe['max_abs_diff']:.3e}")
        summary |= {f"divergence_{k}": v for k, v in probe.items()}
    return summary


def serve_replicas(args, cfg, params, plens) -> dict:
    """`--decode-replicas N`: N data-parallel engine replicas behind one
    shared arrival stream (DESIGN.md §10). The stream is built once, then
    each request is routed up-front in arrival order by pick-least-loaded
    (scheduler.ReplicaRouter — a pure function of the submitted stream, so
    the aggregate digest is reproducible and replica-count-independent
    routing ties go to the lowest index). Single-host emulation: replicas
    share `params` and serve SEQUENTIALLY on this process's devices, so
    per-replica walls and ITL are real; the aggregate reports the modeled
    parallel wall = max(replica walls) next to the serial wall actually
    paid. Requests keep their global rids across replicas, so the
    aggregate `stream_digest` is comparable with a 1-replica run of the
    same stream."""
    from repro.serve.scheduler import ReplicaRouter

    n = args.decode_replicas
    master = SlotScheduler(args.batch, eos_id=args.eos_id)
    build_requests(master, cfg, args.requests, args.rate, plens,
                   args.max_new, args.seed, tier_mix=args.tier_mix,
                   prefix_mix=args.prefix_mix, prefix_len=args.prefix_len)
    router = ReplicaRouter(n)
    scheds = [SlotScheduler(args.batch, eos_id=args.eos_id)
              for _ in range(n)]
    for req in master.pending:     # already arrival-sorted
        i = router.route(req.prompt_len, req.max_new_tokens)
        r2 = scheds[i].submit(req.prompt, req.max_new_tokens,
                              arrival_time=req.arrival_time, tier=req.tier)
        r2.rid = req.rid           # global rid: aggregate digest key

    summaries = []
    finished = []
    for i, sched in enumerate(scheds):
        engine = make_engine(args, cfg, params)
        s = engine.serve(sched, greedy=True)
        s["decode_itl_p50_ms"] = _itl_p50_ms(sched.finished)
        summaries.append(s)
        finished.extend(sched.finished)
        print(f"[replica {i}] requests={s['requests']} "
              f"wall_s={s['wall_s']} decode_tok_s={s['decode_tok_s']} "
              f"itl_p50={s['decode_itl_p50_ms']}ms "
              f"pages_leaked={s.get('pages_leaked')} "
              f"decode_stall={s.get('decode_stall_s')}s")

    def total(key):
        return round(sum(s.get(key) or 0 for s in summaries), 4)

    spans: dict[str, dict] = {}
    for s in summaries:
        for k, v in s["spans"].items():
            t = spans.setdefault(k, {"n": 0, "s": 0.0})
            t["n"] += v["n"]
            t["s"] += v["s"]

    summary = {
        "replicas": n,
        "requests": sum(s["requests"] for s in summaries),
        "generated_tokens": sum(s["generated_tokens"] for s in summaries),
        "rejected": sum(s.get("rejected", 0) for s in summaries),
        "pages_leaked": total("pages_leaked"),
        "prefill_s": total("prefill_s"),
        "decode_s": total("decode_s"),
        "handoff_s": total("handoff_s"),
        "decode_stall_s": total("decode_stall_s"),
        "spans": spans,
        "prefill_compiles": sum(s.get("prefill_compiles", 0)
                                for s in summaries),
        # serial = what this single-host emulation paid; parallel = the
        # deployment model (replicas run concurrently, wall = slowest)
        "wall_s_serial": total("wall_s"),
        "wall_s_parallel": round(max(s["wall_s"] for s in summaries), 4),
        "disagg": summaries[0].get("disagg"),
        "ready_depth_p50": summaries[0].get("ready_depth_p50"),
        "stream_digest": stream_digest(finished),
    }
    itl = _itl_p50_ms(finished)
    if itl is not None:
        summary["decode_itl_p50_ms"] = itl
    _print_phases(summary)
    return summary


def serve_static(args, cfg, params, plens) -> dict:
    """Static-batch fallback (frontend archs): `--requests` prompts served
    in waves of `--batch` (arrivals/`--rate` don't apply — each wave blocks
    on its slowest member; that gap is exactly the continuous engine's
    point), same honest accounting as the continuous path. Prompt lengths
    cycle per *wave* (a wave's batch prefill is rectangular)."""
    engine = ServeEngine(cfg, params, args.batch, args.cache_len,
                         eos_id=args.eos_id, sync_every=args.sync_every)
    served = n_real = 0
    prefill_s = decode_s = 0.0
    waves = max(1, -(-args.requests // args.batch))
    for w in range(waves):
        plen = plens[w % len(plens)]
        rng = jax.random.key(args.seed + 1 + w)
        prompts = jax.random.randint(rng, (args.batch, plen), 0,
                                     cfg.vocab_size, dtype=jnp.int32)
        frontend = None
        if cfg.family == "vlm" or cfg.is_encdec:
            frontend = jax.random.normal(
                rng, (args.batch, cfg.frontend_tokens, cfg.d_model))
        out = np.asarray(engine.generate(prompts, args.max_new,
                                         frontend=frontend))
        # only this wave's real requests count (the last wave may be ragged)
        n_rows = min(args.batch, args.requests - served)
        # real generated tokens: through the first EOS per row, no further
        for row in out[:n_rows]:
            eos = np.nonzero(row == args.eos_id)[0]
            n_real += int(eos[0]) + 1 if eos.size else row.shape[0]
        served += n_rows
        prefill_s += engine.last_stats["prefill_s"]
        decode_s += engine.last_stats["decode_s"]
    return {"requests": served, "generated_tokens": n_real,
            "waves": waves,
            "prefill_s": round(prefill_s, 4),
            "decode_s": round(decode_s, 4),
            "decode_tok_s": round(max(n_real - served, 0) / decode_s, 2)
            if decode_s > 0 else 0.0}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (continuous) / wave size (static)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, req/s (0 = all at t=0)")
    ap.add_argument("--prompt-lens", default="8,16,32",
                    help="comma-set of prompt lengths, cycled per request")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=None)
    ap.add_argument("--kv", default=None, choices=(None, "ring", "paged"),
                    help="KV layout (default: $REPRO_KV or 'paged'): "
                         "'paged' pools pages across slots with per-slot "
                         "block tables; 'ring' is the per-slot dense "
                         "fallback (DESIGN.md §5)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="total pages in the pool (default: dense-ring-"
                         "equivalent batch*cache_len tokens + trash page)")
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="per-request token cap = block-table width "
                         "(default: cache-len) — raise it to admit one "
                         "long request without growing every slot")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode steps per host sync / scheduler tick")
    ap.add_argument("--prefix-mix", type=float, default=0.0,
                    help="fraction of requests sharing one fixed system "
                         "prompt — the prefix-cache workload (REPRO_PREFIX_"
                         "CACHE=1|0 A/Bs sharing on the same stream; TTFT "
                         "stays honest, timing only the suffix prefill a "
                         "cache hit actually pays)")
    ap.add_argument("--prefix-len", type=int, default=32,
                    help="shared system-prompt length for --prefix-mix")
    ap.add_argument("--tier-mix", type=float, default=0.0,
                    help="fraction of requests submitted as the 'bulk' "
                         "quality tier (approximate-normalization decode "
                         "when a whole chunk is bulk); 0 = all premium")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative draft length (0 = off): draft "
                         "spec-k tokens per slot with the early-exit "
                         "forward, verify them in one batched M=spec-k+1 "
                         "forward, keep the longest agreeing prefix "
                         "(DESIGN.md §9). Greedy output is token-identical "
                         "to spec-k 0; REPRO_SPEC_DECODE=0 kill-switches")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="superblocks the draft forward runs "
                         "(0 = half the stack)")
    ap.add_argument("--spec-warmup", type=int, default=0,
                    help="seed-pure AdamW warm-up steps on a synthetic "
                         "Markov corpus before serving — stands in for "
                         "trained weights so the draft's acceptance rate "
                         "is meaningful (random init accepts ~10%)")
    ap.add_argument("--layers-per-period", type=int, default=1,
                    help="depth multiplier for --reduced configs (the "
                         "early-exit draft needs >= 2 superblocks)")
    ap.add_argument("--width", type=int, default=1,
                    help="width multiplier for --reduced configs "
                         "(d_model/d_ff × width) — width >= 4 leaves the "
                         "dispatch-bound floor so depth-proportional "
                         "speedups (--spec-k) are measurable")
    ap.add_argument("--disagg", action="store_true",
                    help="two-pool disaggregated serving (DESIGN.md §10): "
                         "prefill workers stage finished prompts' KV pages "
                         "and a ready queue feeds decode admissions, so "
                         "decode chunks never block on a prefill. Paged "
                         "layout only; token-identical to unified "
                         "(REPRO_DISAGG=1 is the env equivalent)")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="prefill-pool width under --disagg: prompts staged "
                         "per scheduler tick before decode resumes")
    ap.add_argument("--decode-replicas", type=int, default=1,
                    help="N data-parallel engine replicas behind the shared "
                         "arrival queue, routed pick-least-loaded "
                         "(scheduler.ReplicaRouter); served sequentially "
                         "on this host, parallel wall modeled as "
                         "max(replica walls)")
    ap.add_argument("--bucket-prompts", action="store_true",
                    help="prompt-length bucketing for attention-only archs: "
                         "pad prefill to ~1.5x-spaced buckets to cut jit "
                         "retraces (summary: prefill_compiles); "
                         "token-identical (REPRO_PREFILL_BUCKET=1 is the "
                         "env equivalent)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="EOS token id (-1: never fires on synthetic vocab)")
    ap.add_argument("--autotune-decode", action="store_true",
                    help="pre-seed decode-shape GEMV blocks (autotune."
                         "tune_decode) before the first chunk compiles")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def load_model(args):
    """(cfg, params) for `args`: the registry config (or its reduced
    variant) with seeded random weights. Published widths store bf16
    weights: every GEMM quantizes its inputs to bf16 on the TPU anyway
    (PrecisionPolicy.cast_in), and fp32 would double the weight bytes
    (12.6 GiB for granite-moe-3b on a 16 GB chip). The init is jitted so
    the fp32 draws never coexist with the bf16 tree on the device."""
    import functools

    cfg = (reduced_config(args.arch,
                          layers_per_period=args.layers_per_period,
                          width=args.width)
           if args.reduced else get_config(args.arch))
    dtype = jnp.float32 if args.reduced else jnp.bfloat16
    params = jax.jit(functools.partial(M.init_params, cfg=cfg,
                                       dtype=dtype))(
        jax.random.key(args.seed))
    if args.spec_warmup > 0:
        params = spec_warmup_train(cfg, params, args.spec_warmup, args.seed)
    return cfg, params


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    cfg, params = load_model(args)
    plens = [int(x) for x in args.prompt_lens.split(",")]
    # prefix-mix prompts grow by the shared system prompt; size the default
    # per-request capacity to still fit them
    extra = args.prefix_len if args.prefix_mix > 0 else 0
    args.cache_len = args.cache_len or (max(plens) + extra + args.max_new)

    if cfg.family == "vlm" or cfg.is_encdec:
        summary = serve_static(args, cfg, params, plens)
        mode = "static"
    elif args.decode_replicas > 1:
        summary = serve_replicas(args, cfg, params, plens)
        mode = f"replicas x{args.decode_replicas}"
    else:
        summary = serve_continuous(args, cfg, params, plens)
        mode = "continuous"
    print(f"[{mode}] " + " ".join(f"{k}={v}" for k, v in summary.items()))
    return summary


if __name__ == "__main__":
    main()
