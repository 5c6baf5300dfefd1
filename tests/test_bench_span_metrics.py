"""The readers of the program's spans and counters: on a whole traced
tiny run of each cell, against counts taken independently from the
scheduler's records, and silent on a program that records no spans.
The benchmark harness (`bench/`) lives beside `src/` at the repo root."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell, traffic  # noqa: E402
from bench import serve as S  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.tests import tiny  # noqa: E402
from repro.serve.engine import _bucket_len  # noqa: E402

SPEC = json.loads((cell.ROOT.parent / "BENCHMARK.json").read_text())
NEW = {"granite-chat": ["admit_stall_share.chat"],
       "phi3-10l-longdoc": ["prefill_pad_share.longdoc",
                            "decode_occupancy.longdoc"]}


def per_layer(name):
    return [m for m in SPEC["per_layer"]
            if name in m.get("workloads", [name])]


@pytest.mark.parametrize("name", ["granite-chat", "phi3-10l-longdoc"])
def test_readers_on_a_traced_tiny_run(name):
    w = {c["name"]: c for c in SPEC["workloads"]}[name]
    mix = tiny.mix(w["traffic"], 0.05)
    mix["trace_from"] = 0.0      # the tiny batch may drain before 40 %
    out = cell.run(tiny.conf(w["config"]), mix, name="tiny", seed=2**33 + 5,
                   seconds=4.0, trace=True, e2e=[], per_layer=per_layer(name),
                   peaks=tiny.PEAKS)
    assert out["correct"], out["checks"]
    for m in NEW[name]:
        assert 0.0 <= out["metrics"][m]["value"] <= 100.0, m
        assert out["metrics"][m]["unit"] == "%"


def _window(cell_name):
    w = {c["name"]: c for c in SPEC["workloads"]}[cell_name]
    conf, mix = tiny.conf(w["config"]), tiny.mix(w["traffic"], 0.05)
    sz = W.sizes(conf)
    params = W.make_weights(conf, 11)
    specs = traffic.generate(mix, sz["V"], 11, 3.0)
    engine = S.make_engine(W.arch_config(conf, "tiny"), params, mix["engine"])
    S.warm_up(engine, S.warm_lengths([len(x.prompt) for x in specs]),
              sz["V"], mix["engine"]["sync_every"])
    sched, _, summary, _ = S.run_window(engine, specs, 3.0, None)
    return {"sched": sched, "summary": summary}, mix["engine"]["slots"]


def test_longdoc_readers_match_the_scheduler_records():
    """Padding from the admissions' prompt lengths and the engine's
    buckets; occupancy from the tokens each chunk delivered."""
    ctx, slots = _window("phi3-10l-longdoc")
    adm = ctx["sched"].admissions
    pos = sum(_bucket_len(a.prompt_len) for a in adm)
    pad = 100.0 * (pos - sum(a.prompt_len for a in adm)) / pos
    assert pad > 0
    assert cell.reader("prefill_pad_share.longdoc")(ctx) == pytest.approx(
        pad, rel=1e-12)
    chunks = ctx["sched"].chunks
    kept = sum(sum(c.delivered) for c in chunks)
    rows = sum(c.steps for c in chunks) * slots
    assert cell.reader("decode_occupancy.longdoc")(ctx) == pytest.approx(
        100.0 * kept / rows, rel=1e-12)


def test_admit_stall_share_reads_the_stall_and_chunk_spans():
    ctx, _ = _window("granite-chat")
    s = ctx["summary"]
    assert s["decode_stall_s"] > 0
    want = 100.0 * s["decode_stall_s"] / (
        s["decode_stall_s"] + s["spans"]["decode_chunk"]["s"])
    assert cell.reader("admit_stall_share.chat")(ctx) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("metric", sorted(sum(NEW.values(), [])))
def test_readers_are_silent_without_spans(metric):
    """A program that records no spans or counters (the summary keys
    before they existed) gives no value, and no error."""
    ctx = {"summary": {"prefill_s": 1.0, "decode_s": 2.0,
                       "decode_stall_s": 0.5}}
    assert cell.reader(metric)(ctx) is None
