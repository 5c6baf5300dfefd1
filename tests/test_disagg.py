"""Disaggregated two-pool serving (DESIGN.md §10): the prefill pool stages
KV pages and a ready queue feeds decode admissions. Tokens must be
identical to the unified engine (the handoff runs the same scatter+bind
writes `_insert_impl` fuses), no page may leak through the
prefill→ready→retirement lease, and the two-pool scheduler / replica
router / prompt-length bucketing each keep their contracts."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import PrecisionPolicy, use_policy
from repro.configs import reduced_config
from repro.models import model as M
from repro.serve.engine import ServeEngine, _bucket_len
from repro.serve.scheduler import ReplicaRouter, SlotScheduler

FP32 = PrecisionPolicy(input_format="fp32")


def _cfg(name="qwen2.5-14b", **kw):
    return dataclasses.replace(reduced_config(name, **kw), remat=False)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    with use_policy(FP32):
        params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]


def _serve(cfg, params, prompts, budgets, arrivals=None, *, batch=2,
           cache_len=64, page_size=8, sync_every=4, **engine_kw):
    with use_policy(FP32):
        engine = ServeEngine(cfg, params, batch=batch, cache_len=cache_len,
                             eos_id=-1, sync_every=sync_every,
                             kv_layout="paged", page_size=page_size,
                             **engine_kw)
        sched = SlotScheduler(batch, eos_id=-1)
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            sched.submit(p, max_new_tokens=n,
                         arrival_time=arrivals[i] if arrivals else 0.0)
        summary = engine.serve(sched, greedy=True)
    return sched, summary


def _tokens_by_rid(sched):
    return {r.rid: r.tokens for r in sched.finished}


def test_disagg_matches_unified(setup):
    """The acceptance gate: the two-pool engine's token streams are
    bit-identical to the unified engine's on a staggered mixed-length
    stream, no pages leak through the handoff, and only the two-pool run
    reports ready-queue depth."""
    cfg, params = setup
    prompts = _prompts(cfg, [5, 9, 13, 6, 11, 7])
    budgets = [12, 6, 9, 5, 10, 8]
    arrivals = [0.0, 0.0, 0.1, 0.1, 0.2, 0.2]
    on_sched, on = _serve(cfg, params, prompts, budgets, arrivals,
                          disagg=True, prefill_workers=2)
    off_sched, off = _serve(cfg, params, prompts, budgets, arrivals,
                            disagg=False)
    assert _tokens_by_rid(on_sched) == _tokens_by_rid(off_sched)
    assert len(on_sched.finished) == len(prompts)
    assert on["disagg"] is True and off["disagg"] is False
    assert on["pages_leaked"] == 0 and off["pages_leaked"] == 0
    assert "ready_depth_p50" in on and "ready_depth_p50" not in off
    assert {"prefill_s", "decode_s", "handoff_s",
            "decode_stall_s", "spans"} <= set(on) & set(off)


def test_disagg_prefix_cache_parity(setup):
    """Prefix-cache hits survive the two-pool split: the prefill worker
    maps cached pages (COW tail included) before staging, registers the
    fresh run before the request reaches the ready queue, and tokens stay
    identical to unified with the same hit pattern."""
    cfg, params = setup
    rng = np.random.default_rng(9)
    system = rng.integers(0, cfg.vocab_size, 16).tolist()
    tails = _prompts(cfg, [5, 7, 9], seed=10)
    prompts = [system + t for t in tails]
    budgets = [6, 6, 6]
    on_sched, on = _serve(cfg, params, prompts, budgets,
                          disagg=True, cache_len=96)
    off_sched, off = _serve(cfg, params, prompts, budgets,
                            disagg=False, cache_len=96)
    assert _tokens_by_rid(on_sched) == _tokens_by_rid(off_sched)
    assert on["prefix_hits"] >= 1
    assert on["prefix_hits"] == off["prefix_hits"]
    assert on["pages_leaked"] == 0


def test_disagg_first_token_finishes_at_prefill(setup):
    """A max_new=1 request retires inside finish_prefill — it never enters
    the ready queue or a decode slot — while its neighbours decode
    normally; the leased pages still come home."""
    cfg, params = setup
    prompts = _prompts(cfg, [6, 8, 7], seed=4)
    budgets = [1, 8, 1]
    sched, summary = _serve(cfg, params, prompts, budgets, disagg=True)
    by_rid = {r.rid: r for r in sched.finished}
    assert len(by_rid) == 3
    for rid in (0, 2):
        assert by_rid[rid].n_generated == 1
        assert by_rid[rid].slot == -1          # never bound to a slot
        assert by_rid[rid].finish_reason == "length"
    assert by_rid[1].n_generated == 8
    assert summary["pages_leaked"] == 0


def test_two_pool_scheduler_unit():
    """begin_prefill / finish_prefill / admit_ready semantics without an
    engine: arrival gating, ready staging, slot binding, the reject path,
    and drained() counting staged-but-unbound work."""
    sched = SlotScheduler(2, eos_id=-1)
    r0 = sched.submit([1, 2, 3], max_new_tokens=4, arrival_time=0.0)
    r1 = sched.submit([4, 5], max_new_tokens=3, arrival_time=5.0)
    r2 = sched.submit([6, 7], max_new_tokens=1, arrival_time=5.0)

    assert sched.begin_prefill(0.0) is r0
    assert sched.begin_prefill(0.0) is None      # r1 hasn't arrived yet
    assert sched.ready_depth() == 0
    assert sched.finish_prefill(r0, 42, 0.1) is True
    assert sched.ready_depth() == 1 and not sched.drained()
    assert r0.tokens == [42] and r0.t_first_token is not None

    got = sched.admit_ready(0, 0.2)
    assert got is r0 and r0.slot == 0
    assert sched.ready_depth() == 0 and sched.num_active() == 1
    assert sched.admit_ready(1, 0.2) is None     # nothing staged

    # arrival-sorted FIFO: ties at t=5.0 pop in submit order (r1 before r2)
    assert sched.begin_prefill(6.0) is r1
    assert sched.begin_prefill(6.0) is r2

    # a single-token budget retires inside finish_prefill: never queued
    sched2 = SlotScheduler(2, eos_id=-1)
    short = sched2.submit([6, 7], max_new_tokens=1, arrival_time=0.0)
    assert sched2.begin_prefill(0.0) is short
    assert sched2.finish_prefill(short, 9, 0.1) is False
    assert short.finish_reason == "length" and short.t_done is not None
    assert sched2.ready_depth() == 0 and sched2.drained()

    sched3 = SlotScheduler(2, eos_id=-1)
    doomed = sched3.submit([1] * 8, max_new_tokens=4, arrival_time=0.0)
    assert sched3.begin_prefill(0.0) is doomed
    sched3.reject_prefill(doomed, 0.0)
    assert doomed.finish_reason == "rejected" and doomed.t_done is not None
    assert sched3.drained() and doomed in sched3.finished

    # summary reports ready-depth percentiles only once two-pool mode ran
    assert "ready_depth_p50" in sched.summary()
    assert "ready_depth_p50" not in SlotScheduler(2, eos_id=-1).summary()


def test_replica_router():
    """Pick-least-loaded by outstanding token estimate, ties to the lowest
    index — a pure function of the routed stream."""
    with pytest.raises(ValueError):
        ReplicaRouter(0)
    r = ReplicaRouter(3)
    assert r.route(4, 4) == 0          # all empty: lowest index
    assert r.route(2, 2) == 1
    assert r.route(1, 1) == 2
    assert r.route(1, 1) == 2          # replica 2 lightest (2 < 8, 4)
    assert r.outstanding == [8, 4, 4]
    r.complete(0, 4, 4)
    assert r.outstanding == [0, 4, 4]
    assert r.route(1, 1) == 0
    assert r.routed == [2, 1, 2]
    with pytest.raises(AssertionError):
        r.complete(1, 100, 100)        # over-completion is a bug


def test_bucket_len_sequence():
    """Buckets step 8 → 12 → 16 → 24 → 32 → 48 → 64 → 96: alternating
    x1.5 / x1.33, so padding waste stays under 50% at every length."""
    got = [_bucket_len(n)
           for n in (1, 8, 9, 12, 13, 16, 17, 24, 25, 32, 33, 48, 49, 65)]
    assert got == [8, 8, 12, 12, 16, 16, 24, 24, 32, 32, 48, 48, 64, 96]
    for n in range(1, 200):
        b = _bucket_len(n)
        assert b >= n and b < 2 * max(n, 8)


def test_bucketed_serve_identical_fewer_compiles(setup):
    """Prompt-length bucketing pads prefill to the bucket grid: token
    streams stay bit-identical (padded rows carry position -1, the last
    real row feeds the lm head) while distinct prefill traces drop."""
    cfg, params = setup
    prompts = _prompts(cfg, [5, 6, 7, 9], seed=6)
    budgets = [6, 6, 6, 6]
    on_sched, on = _serve(cfg, params, prompts, budgets,
                          bucket_prompts=True)
    off_sched, off = _serve(cfg, params, prompts, budgets,
                            bucket_prompts=False)
    assert _tokens_by_rid(on_sched) == _tokens_by_rid(off_sched)
    assert on["prefill_compiles"] < off["prefill_compiles"]


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "granite-4.0-h-small"])
def test_bucketed_serve_identical_for_recurrent_stacks(arch):
    """Stacks with recurrent state bucket too: padded rows leave the SSM
    state and the conv tail as they stand at the real prompt length, so
    token streams match exact-length prefill (Mamba2 on its rings,
    granite-4.0-h's one attention layer in ten on pages)."""
    cfg = _cfg(arch)
    with use_policy(FP32):
        params = M.init_params(jax.random.key(2), cfg)
    prompts = _prompts(cfg, [5, 6, 7, 9, 13], seed=9)
    budgets = [6, 5, 6, 4, 6]
    on_sched, on = _serve(cfg, params, prompts, budgets,
                          bucket_prompts=True)
    off_sched, off = _serve(cfg, params, prompts, budgets,
                            bucket_prompts=False)
    assert _tokens_by_rid(on_sched) == _tokens_by_rid(off_sched)
    assert on["prefill_compiles"] < off["prefill_compiles"]


def test_disagg_composes_with_bucketing(setup):
    """Both knobs on at once still reproduce the plain engine's streams —
    the staged fragment is bucket-padded, overflow pages land in the trash
    page, and the handoff binds only the allocated run."""
    cfg, params = setup
    prompts = _prompts(cfg, [5, 9, 13, 7], seed=8)
    budgets = [8, 6, 7, 5]
    on_sched, on = _serve(cfg, params, prompts, budgets,
                          disagg=True, bucket_prompts=True)
    off_sched, off = _serve(cfg, params, prompts, budgets)
    assert _tokens_by_rid(on_sched) == _tokens_by_rid(off_sched)
    assert on["pages_leaked"] == 0
