"""bench/trace_reduce.py on traces kept in bench/testdata/: a synthetic one
whose numbers are worked out by hand, and a slice recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as R

DATA = Path(__file__).resolve().parents[1] / "testdata"


def load(name):
    return json.loads((DATA / name).read_text())


def test_synthetic_by_hand():
    tr = load("synthetic_trace.json")
    ops = tr["devices"]["/device:TPU:0"]
    assert R.window(tr) == (0, 1000)
    # union of [100,180], [300,400], [500,600]; the op at 1100 is outside
    assert R.busy_ns(ops, 0, 1000) == 280
    assert R.busy_s(tr) == pytest.approx(280e-9)
    assert R.op_ns(ops, "decode_kernel", 0, 1000) == 60
    assert R.op_ns(ops, "prefill", 0, 1000) == 100     # by JAX source
    assert R.collective_ns(ops, 0, 1000) == 100
    assert R.idle_gaps(ops, 0, 1000) == [(0, 100), (180, 300), (400, 500),
                                         (600, 1000)]
    b = R.breakdown(tr)
    assert b["idle_gaps"] == [["trace_off", 400e-9], ["admit", 120e-9],
                              ["trace_on", 100e-9], ["chunk_sync", 100e-9]]
    assert b["device_ops"][0] == ["all-gather.3", 100e-9]
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(310e-9)



def test_recorded_v5e_slice():
    """45 ms of a granite-chat trace from a TPU v5e: the events are named
    by their HLO instruction's text, the decode kernel's reader finds its
    custom call, and busy time plus idle gaps make up the window."""
    import importlib.util

    tr = load("v5e_granite_chat.json")
    lo, hi = R.window(tr)
    ops = next(iter(tr["devices"].values()))
    path = DATA.parent / "metrics" / "sa_decode_attention_roofline.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert R.op_ns(ops, reader.KERNEL, lo, hi) > 0
    gaps = R.idle_gaps(ops, lo, hi)
    assert R.busy_ns(ops, lo, hi) + sum(e - s for s, e in gaps) == hi - lo
    assert 0 < R.busy_s(tr) <= (hi - lo) / 1e9
    b = R.breakdown(tr)
    assert b["device_ops"] and all(" = " not in n for n, _ in b["device_ops"])
    assert {g for g, _ in b["idle_gaps"]} <= {"trace_on", "chunk_sync"}
