"""The serve loop's spans and counters (serve/trace.py): the recorder's
bookkeeping, the summary's wall split as sums over spans in each serve
path, the counters against what the scheduler saw, and the spans on the
host plane of a profile."""
import dataclasses
import glob
import itertools

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import PrecisionPolicy, use_policy
from repro.models import model as M
from repro.serve.engine import ServeEngine, _bucket_len
from repro.serve.scheduler import SlotScheduler
from repro.serve.trace import Recorder

FP32 = PrecisionPolicy(input_format="fp32")
LENS = [5, 9, 13, 6]
BUDGETS = [12, 5, 9, 6]


def test_recorder_nesting_parent_rid_self_time_and_counters():
    ticks = itertools.count()
    rec = Recorder(clock=lambda: float(next(ticks)))
    with rec.span("admission", 7, stall=True) as adm:     # 0 .. 5
        with rec.span("prefill", 7, tokens=3):            # 1 .. 2
            pass
        with rec.span("insert", 7):                       # 3 .. 4
            pass
    with rec.span("decode_chunk", steps=8):               # 6 .. 7
        pass
    rec.count("prefill_tokens", 3)
    rec.count("prefill_tokens")
    names = [s.name for s in rec.spans]
    assert names == ["admission", "prefill", "insert", "decode_chunk"]
    assert [s.parent for s in rec.spans] == [None, 0, 0, None]
    assert [s.rid for s in rec.spans] == [7, 7, 7, None]
    assert adm.dur == 5.0 and rec.spans[1].dur == 1.0
    assert rec.self_s(0) == 3.0                 # 5 less two children of 1
    assert rec.self_s(3) == 1.0
    assert rec.spans[1].attrs == {"tokens": 3}
    assert rec.total("admission", stall=True) == 5.0
    assert rec.total("admission", stall=False) == 0.0
    assert rec.last("insert") is rec.spans[2]
    assert rec.last("observe") is None
    assert rec.summary() == {
        "spans": {"admission": {"n": 1, "s": 5.0},
                  "prefill": {"n": 1, "s": 1.0},
                  "insert": {"n": 1, "s": 1.0},
                  "decode_chunk": {"n": 1, "s": 1.0}},
        "counters": {"prefill_tokens": 4}}
    rec.reset()
    assert rec.spans == [] and rec.counters == {}
    with rec.span("observe"):
        pass
    assert rec.spans[0].parent is None


def test_span_closes_when_its_body_raises():
    rec = Recorder()
    with pytest.raises(ValueError):
        with rec.span("admission", 1):
            with rec.span("prefill", 1):
                raise ValueError
    assert all(s.end is not None for s in rec.spans)
    with rec.span("observe"):
        pass
    assert rec.spans[-1].parent is None


@pytest.fixture(scope="module")
def setup():
    """Two superblocks: the least stack the speculative path runs on."""
    cfg = dataclasses.replace(
        reduced_config("qwen2.5-14b", layers_per_period=2), remat=False)
    with use_policy(FP32):
        params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _serve(cfg, params, path, engine=None):
    kw = {"unified": dict(bucket_prompts=True),
          "disagg": dict(disagg=True, prefill_workers=2),
          "spec": dict(spec_k=3)}[path]
    rng = np.random.default_rng(1)
    with use_policy(FP32):
        engine = engine or ServeEngine(
            cfg, params, batch=2, cache_len=64, eos_id=-1, sync_every=4,
            kv_layout="paged", page_size=8, **kw)
        sched = SlotScheduler(2, eos_id=-1)
        for n, b in zip(LENS, BUDGETS):
            sched.submit(rng.integers(0, cfg.vocab_size, n).tolist(), b)
        summary = engine.serve(sched)
    return engine, sched, summary


@pytest.mark.parametrize("path", ["unified", "disagg", "spec"])
def test_wall_split_is_the_sum_of_its_spans(setup, path):
    cfg, params = setup
    engine, sched, summary = _serve(cfg, params, path)
    rec = engine.recorder
    assert summary["disagg"] is (path == "disagg")
    assert ("spec_iters" in summary) is (path == "spec")

    def spans(name, **where):
        return [s for s in rec.spans if s.name == name
                and all(s.attrs.get(k) == v for k, v in where.items())]

    def total(name, **where):
        return round(sum(s.dur for s in spans(name, **where)), 4)

    if path == "disagg":
        assert summary["prefill_s"] == total("prefill")
        assert summary["handoff_s"] == round(
            sum(s.dur for s in spans("scatter"))
            + sum(s.dur for s in spans("bind")), 4)
        assert summary["decode_stall_s"] == total("scatter", stall=True)
        assert len(spans("bind")) == len(LENS)
        children = {"prefill", "scatter"}
    else:
        assert summary["prefill_s"] == total("admission")
        assert summary["handoff_s"] == total("insert")
        assert summary["decode_stall_s"] == total("admission", stall=True)
        children = {"prefill", "insert"}
    assert summary["decode_s"] == total("decode_chunk")
    # a slot freed while the other decoded: some admission stalled it
    assert summary["decode_stall_s"] > 0
    # every admission holds its request's prefill and splice
    adm = [i for i, s in enumerate(rec.spans) if s.name == "admission"]
    assert sorted(rec.spans[i].rid for i in adm) == sorted(
        r.rid for r in sched.finished)
    for i in adm:
        kids = [s for s in rec.spans if s.parent == i]
        assert {s.name for s in kids} == children
        assert {s.rid for s in kids} == {rec.spans[i].rid}
        assert rec.self_s(i) >= 0
    chunks = [i for i, s in enumerate(rec.spans) if s.name == "decode_chunk"]
    assert len(chunks) == summary["spans"]["decode_chunk"]["n"] > 0
    for i in chunks:
        assert [s.name for s in rec.spans if s.parent == i] == ["chunk_wait"]
    assert len(spans("observe")) == len(chunks)

    c = summary["counters"]
    assert c["prefill_tokens"] == sum(LENS)
    assert c["prefill_positions"] == (
        sum(map(_bucket_len, LENS)) if path == "unified" else sum(LENS))
    # sync_every steps, or one speculative iteration of k + 1 positions
    assert c["decode_steps"] == 4 * len(chunks)
    assert c["decode_slot_steps"] == 2 * c["decode_steps"]
    # every generated token past each request's first came from a chunk
    assert c["decode_tokens_kept"] == (summary["generated_tokens"]
                                       - len(LENS))
    assert summary["generated_tokens"] == sum(BUDGETS)


def test_spans_are_reset_by_each_serve(setup):
    cfg, params = setup
    engine, _, first = _serve(cfg, params, "unified")
    _, _, second = _serve(cfg, params, "unified", engine=engine)
    assert second["counters"] == first["counters"]
    assert second["spans"]["admission"]["n"] == len(LENS)
    assert sum(s.name == "admission"
               for s in engine.recorder.spans) == len(LENS)


def test_profile_holds_one_host_event_per_chunk_span(setup, tmp_path):
    """The spans sit on the profile's host plane: one `repro:decode_chunk`
    event for each decode_chunk span, of the span's duration, and the
    admissions carry their request ids."""
    from jax.profiler import ProfileData

    cfg, params = setup
    engine, _, _ = _serve(cfg, params, "unified")        # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine, sched, _ = _serve(cfg, params, "unified", engine=engine)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro:"):
                        events.setdefault(ev.name, []).append(ev)
    chunks = sorted(events["repro:decode_chunk"], key=lambda e: e.start_ns)
    spans = [s for s in engine.recorder.spans if s.name == "decode_chunk"]
    assert len(chunks) == len(spans) > 0
    for ev, s in zip(chunks, spans):
        # the annotation wraps the span's two clock reads
        assert abs(ev.duration_ns / 1e9 - s.dur) < 5e-3
    rids = sorted(dict(ev.stats)["rid"] for ev in events["repro:admission"])
    assert rids == sorted(r.rid for r in sched.finished)
