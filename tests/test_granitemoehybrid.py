"""granite-4.0-h (bench family `granitemoehybrid`) through the program's
serving path, at d_model 64 on the CPU with seeded weights: nine Mamba2
layers and one attention layer with no position embedding, an expert FFN
holding 2 of the router's 16 experts plus a shared expert in every layer.

The reference is the family's published function in float32 (the Mamba2
recurrence token by token, bench/families/granitemoehybrid.py). The
program keeps its residual stream in bfloat16, so against the reference
it agrees to bfloat16's rounding, amplified over the layers; serving
(paged prefill, then decode through the cache) is a re-association of the
program's own forward, so against that it agrees to float32 rounding."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.families import granitemoehybrid as G  # noqa: E402
from bench.reference import plain  # noqa: E402
from bench.tests import tiny  # noqa: E402
from repro.core import PrecisionPolicy, use_policy  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.moe import moe_ffn  # noqa: E402
from repro.serve.engine import ServeEngine, _bucket_len  # noqa: E402
from repro.serve.scheduler import SlotScheduler  # noqa: E402
from repro.train.step import (make_bucketed_prefill_step,  # noqa: E402
                              make_prefill_step, make_serve_step)

CONFIG = "granite-4.0-h-small-10l"
CELL = "granite-h-10l-longdoc"
FP32 = PrecisionPolicy(input_format="fp32")
SEED = 2**35 + 1
NEW = 24                                   # tokens served per request


def _setup():
    conf = tiny.conf(CONFIG)
    sz = W.sizes(conf)
    cfg = dataclasses.replace(W.arch_config(conf, "tiny"), remat=False)
    return conf, sz, cfg, W.make_weights(conf, SEED, jnp.float32)


@pytest.fixture(scope="module")
def served():
    """Two requests (prompts of 13 and 21 tokens, both bucketed) served by
    a paged engine; then the logits of the same path replayed from the
    engine's own blocks: bucketed prefill into a fragment, `_insert` into
    the page pool, decode steps through the pool teacher-forced on what
    was served."""
    conf, sz, cfg, params = _setup()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, sz["V"], n).astype(np.int32) for n in (13, 21)]
    with use_policy(FP32):
        eng = ServeEngine(cfg, params, batch=2, cache_len=64, eos_id=-1,
                          sync_every=4, kv_layout="paged", page_size=8,
                          bucket_prompts=True)
        sched = SlotScheduler(2, eos_id=-1)
        reqs = [sched.submit(p, NEW) for p in prompts]
        summary = eng.serve(sched)
        tokens = [list(r.tokens) for r in reqs]
        pool = eng.new_pool()
        logits = []
        for slot, p in enumerate(prompts):
            Tb = _bucket_len(len(p))
            lg, frag = eng._bucketed_prefill_for(0)(
                params, jnp.asarray(np.pad(p, (0, Tb - len(p))))[None],
                eng.new_frag(Tb), jnp.int32(len(p) - 1), jnp.int32(len(p)))
            logits.append([np.asarray(lg[0, -1])])
            row = np.full((eng.max_pages,), -1, np.int32)
            row[:8] = np.arange(1 + 8 * slot, 9 + 8 * slot)
            pool = eng._insert(pool, frag, slot, jnp.asarray(row),
                               jnp.int32(0))
        step = jax.jit(make_serve_step(cfg))
        pos = np.array([len(p) for p in prompts], np.int32)
        for t in range(NEW - 1):
            tok = np.array([[s[t]] for s in tokens], np.int32)
            lg, pool = step(params, jnp.asarray(tok), pool,
                            jnp.asarray(pos + t))
            for b in range(2):
                logits[b].append(np.asarray(lg[b, 0]))
    return dict(conf=conf, sz=sz, cfg=cfg, params=params, eng=eng,
                prompts=prompts, tokens=tokens, summary=summary,
                logits=[np.stack(x)[:, :sz["V"]] for x in logits])


def _rows(got, want):
    """Each row's widest logit gap, over the widest reference logit."""
    return np.max(np.abs(got - want), axis=1) / np.max(np.abs(want))


def test_serving_matches_the_reference_and_the_programs_forward(served):
    """Prefill and decode through the paged cache against (a) the
    family's float32 reference over prompt + served tokens and (b) the
    program's own full forward over the same tokens.

    (a) Rows agree to the bfloat16 residual stream's rounding: 1.5–2 % of
    the widest logit on this model; a router near-tie can flip a top-4
    pick on a row and the recurrence carries it a few rows on, so the
    bound is on the median and on nine rows in ten. Dropping Mamba2's D
    term moves every row by about the widest logit.
    (b) Serving re-associates the forward's float32 arithmetic (chunked
    SSD over the prompt, then the one-token recurrence): observed ≤ 2e-7.
    A bfloat16 SSM state in the cache rounds the state at every decode
    step and moves rows by ~1e-2, so the bound 1e-4 lies between."""
    sz, cfg, params = served["sz"], served["cfg"], served["params"]
    fwd = jax.jit(lambda p, t: M.forward(
        p, dataclasses.replace(cfg, moe_dropless=True), t)[0])
    for p, toks, got in zip(served["prompts"], served["tokens"],
                            served["logits"]):
        assert (got.argmax(1) == np.asarray(toks)).all()   # what was served
        seq = np.asarray(list(p) + toks[:-1], np.int32)
        T = -(-len(seq) // 32) * 32
        ref = np.asarray(plain.logits(
            params, jnp.asarray(np.pad(seq, (0, T - len(seq)))),
            s=plain.static_sizes(sz), block=32))[len(p) - 1:len(seq), :sz["V"]]
        rows = _rows(got, ref)
        assert np.median(rows) < 3e-2, rows
        assert np.mean(rows < 5e-2) >= 0.9, rows
        assert np.mean(got.argmax(1) == ref.argmax(1)) >= 0.8
        with use_policy(FP32):
            full = np.asarray(fwd(params, jnp.asarray(seq)[None])[0])
        assert _rows(got, full[len(p) - 1:, :sz["V"]]).max() < 1e-4


def test_decode_counts_the_recurrent_state_it_moves(served):
    """`decode_state_bytes`: each decode step reads and writes every live
    slot's ssm and conv state, over the nine Mamba2 layers."""
    cfg, summary = served["cfg"], served["summary"]
    per_slot = 9 * 4 * (cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
                        + 3 * (cfg.d_inner + 2 * cfg.ssm_state))
    c = summary["counters"]
    assert c["decode_state_bytes"] > 0
    assert c["decode_state_bytes"] % (2 * per_slot) == 0
    assert c["decode_state_bytes"] <= 2 * per_slot * c["decode_slot_steps"]


def test_recurrent_stack_buckets_but_shares_nothing(served):
    eng = served["eng"]
    assert eng.cfg.recurrent and eng.kv_layout == "paged"
    assert eng.bucketing_on()
    assert not (eng.prefix_caching_on() or eng.disagg_on()
                or eng.spec_decoding_on())


@pytest.mark.parametrize("n", [5, 13, 19])
def test_bucketed_prefill_equals_exact_length(n):
    """A prompt right-padded to its bucket: the padded rows get dt = 0 and
    the conv tail is taken at the real length, so every Mamba2 layer's
    final SSM state and conv tail, and the first token's logits, equal an
    exact-length prefill's (float32 policy; the chunked scan sees the
    same chunks, so observed equal to 2e-8). Without the padding mask the
    states move by ~0.3 and the tails by ~5."""
    _, sz, cfg, params = _setup()
    p = np.random.default_rng(n).integers(0, sz["V"], n).astype(np.int32)
    Tb = _bucket_len(n)
    with use_policy(FP32):
        want_l, want = jax.jit(make_prefill_step(cfg))(
            params, jnp.asarray(p)[None],
            M.init_cache(cfg, 1, 32, dtype=jnp.float32))
        got_l, got = jax.jit(make_bucketed_prefill_step(cfg))(
            params, jnp.asarray(np.pad(p, (0, Tb - n)))[None],
            M.init_cache(cfg, 1, 32, dtype=jnp.float32),
            jnp.int32(n - 1), jnp.int32(n))
    np.testing.assert_allclose(got_l, want_l, rtol=0, atol=1e-6)
    states = [(a, b) for lw, lg in zip(want, got) if "ssm" in lw
              for a, b in zip(lw["ssm"], lg["ssm"])]
    assert len(states) == 2 * 9
    for a, b in states:
        np.testing.assert_allclose(b, a, rtol=0, atol=1e-6)


@pytest.mark.parametrize("dispatch", ["dropless", "capacity"])
def test_expert_shares_add_up_to_the_uncut_layer(dispatch):
    """Eight chips each hold 2 of the router's 16 experts: each computes
    its own experts' part of the FFN (the program's expert layer told which
    experts it holds), the shared expert counted once (on chip 0). The
    parts add up to the reference's whole layer with all 16 held. Float32
    policy: the parts differ from the whole by float32 re-association
    only. The capacity dispatch is given room for every token (C = T)."""
    conf = tiny.conf(CONFIG)
    sz = W.sizes(dict(conf, num_local_experts=16))
    whole = W.make_weights(dict(conf, num_local_experts=16), SEED,
                           jnp.float32)["layers"][0]["moe"]
    whole = jax.tree.map(lambda a: a[0], whole)
    cfg = W.arch_config(conf, "tiny")
    h = jax.random.normal(jax.random.key(4), (1, 32, sz["d"]))
    want = G._held_moe(h[0], whole, sz["k"], False, 1.0)
    total = 0.0
    with use_policy(FP32):
        for chip in range(8):
            lo, hi = 2 * chip, 2 * chip + 2
            part = {"router": whole["router"], "wg": whole["wg"][lo:hi],
                    "wu": whole["wu"][lo:hi], "wd": whole["wd"][lo:hi]}
            if chip == 0:
                part |= {k: v for k, v in whole.items()
                         if k.startswith("shared_")}
            c = dataclasses.replace(cfg, experts_held=(lo, hi))
            out, _ = moe_ffn(h, part, c, dropless=dispatch == "dropless",
                             capacity_factor=16 / sz["k"])
            total = total + out[0]
    np.testing.assert_allclose(total, want, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_nope_attention_differs_from_rope():
    """With no position embedding the attention layer sees only content:
    row 0 (RoPE rotates position 0 by nothing) agrees with a RoPE stack's,
    every later row differs."""
    _, sz, cfg, params = _setup()
    toks = jnp.asarray(np.random.default_rng(1).integers(0, sz["V"], 16),
                       jnp.int32)[None]
    out = {}
    with use_policy(FP32):
        for pe in ("nope", "rope"):
            c = dataclasses.replace(cfg, position_embedding=pe)
            out[pe] = np.asarray(M.forward(params, c, toks)[0][0, :,
                                                               :sz["V"]])
    np.testing.assert_allclose(out["nope"][0], out["rope"][0], atol=1e-6)
    assert (np.max(np.abs(out["nope"][1:] - out["rope"][1:]), axis=1)
            > 1e-3).all()


def test_counts_read_the_layer_pattern():
    """At the configuration's sizes: one attention layer in ten carries
    the KV bytes and the attention FLOPs; a token's FLOPs count the
    Mamba2 projections, the router, the shared expert and 10 · 9 / 72 of
    an expert."""
    s = W.sizes(W.load_config(CONFIG))
    assert s["types"].count("attention") == 1 and s["period"] == s["types"]
    assert G.decode_kv_bytes(s, 99, 4) == 2 * 8 * 128 * 4 * 100
    assert G.attention_flops(s, 7) == 4 * 32 * 128 * 7
    d = 4096
    mamba = d * (2 * 8192 + 2 * 128 + 128) + 8192 * d
    attn = 2 * d * 4096 + 2 * d * 1024
    ffn = d * 72 + 3 * d * 1536 + 10 * 9 * 3 * d * 768 // 72
    ssd = 2 * 4 * (8192 + 256) + 5 * 128 * 64 * 128
    assert G.decode_flops(s, 0) == (2 * (9 * mamba + attn + 10 * ffn)
                                    + 9 * ssd + 2 * d * 100352
                                    + G.attention_flops(s, 1))


def test_the_cell_runs_whole_on_a_tiny_stand_in():
    """The cell as `bench/run.py` runs it, at d_model 64 with prompts cut
    by 16: served tokens pass the reference check, and a traced run gives
    every per-layer metric of the cell that the program's spans and
    counters carry (a CPU trace has no device plane for idle_share)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {c["name"]: c for c in spec["workloads"]}[CELL]
    mine = [m for m in spec["per_layer"] if CELL in m.get("workloads", [])]
    assert len(mine) == 8
    mix = tiny.mix(w["traffic"], 0.05)
    mix["engine"].update(max_seq_len=544, pool_pages=137)
    mix["trace_from"] = 0.0
    out = cell.run(tiny.conf(w["config"]), mix, name="tiny", seed=2**33 + 7,
                   seconds=6.0, trace=True, e2e=[], per_layer=mine,
                   peaks=tiny.PEAKS)
    assert out["correct"], out["checks"]
    for m in mine:
        if m["source"] == "device_trace":
            continue
        v = out["metrics"][m["name"]]["value"]
        assert v > 0 and (m["unit"] != "%" or v <= 100), (m["name"], v)


def test_state_reader_is_silent_without_the_counter():
    """A program that keeps no `decode_state_bytes` counter gives no
    value, and no error."""
    ctx = {"summary": {"prefill_s": 1.0, "decode_s": 2.0, "counters": {}},
           "peaks": tiny.PEAKS}
    assert cell.reader("state_bw_share.hybrid")(ctx) is None
