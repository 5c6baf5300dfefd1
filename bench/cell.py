"""One run of one serving cell: set-up, the measured window, the check.

`run()` is everything `run.py` does after it has found the chip: it
makes the weights from the seed, builds the engine, warms up every shape
the traffic will use, serves the traffic for the window, reads the
metrics, frees the program's state and compares what was served with the
plain reference.
"""
from __future__ import annotations

import gc
import importlib.util
import shutil
import sys
import time
from pathlib import Path

import jax

from . import endtoend, traffic
from . import flops as F
from . import weights as W

ROOT = Path(__file__).resolve().parent
TRACE_DIR = ROOT.parent / ".bench_trace"


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def reader(name: str):
    """The per-layer metric reader bench/metrics/<name>.py."""
    path = ROOT / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Programs lowered while `on` (each is a compile or a cache load)."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_, **__):
        if self.on and name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def device_label(devs, chips: int) -> dict:
    d = devs[0]
    peak = max(((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for x in devs[:chips]), default=0)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": int(peak)}


def run(conf: dict, mix: dict, *, name: str, seed: int, seconds: float,
        trace: bool, e2e: list[str], per_layer: list[str], chips: int = 1,
        t_start: float | None = None, peaks: dict | None = None,
        control: bool = False) -> dict:
    from . import serve as S
    from .correct import sample, served_gap

    t_start = t_start or time.monotonic()
    devs = jax.devices()
    peaks = peaks or F.peaks(devs[0].device_kind)
    sz = W.sizes(conf)
    cfg = W.arch_config(conf, name)
    eng = mix["engine"]
    if traffic.longest(mix) > eng["max_seq_len"]:
        raise ValueError("the mix asks for more than max_seq_len tokens")
    if sz["window"] and traffic.longest(mix) - 1 > sz["window"]:
        # the program and the reference attend over the whole sequence,
        # which is the published model only while it fits the window
        raise ValueError("the mix's sequences outgrow the sliding window")
    params = W.make_weights(conf, seed)
    specs = traffic.generate(mix, sz["V"], seed, seconds)
    engine = S.make_engine(cfg, params, eng)
    kvb = S.kv_bytes(engine)
    S.warm_up(engine, S.warm_lengths([len(s.prompt) for s in specs]),
              sz["V"], eng["sync_every"])
    jax.block_until_ready(params)
    counter = CompileCounter()
    tracer = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        t0 = mix.get("trace_from", 0.4) * seconds
        tracer = S.Tracer(str(TRACE_DIR), t0,
                          t0 + mix.get("trace_seconds", 3.0))
    setup_s = time.monotonic() - t_start
    counter.on = True
    sched, reqs, summary, wall = S.run_window(engine, specs, seconds, tracer)
    counter.on = False
    device = device_label(devs, chips)

    ctx = {"sizes": sz, "peaks": peaks, "kv_bytes": kvb, "seconds": seconds,
           "sched": sched, "reqs": reqs, "summary": summary}
    due = [r for r in reqs if r.arrival_time < seconds]
    started = [r for r in reqs if r.t_first_token is not None]
    rejected = [r for r in reqs if r.finish_reason == "rejected"]
    log(f"[window] seconds={seconds} due={len(due)} admitted="
        f"{sum(r.t_admitted is not None for r in reqs)} started="
        f"{len(started)} finished="
        f"{sum(r.finish_reason == 'length' for r in reqs)} rejected="
        f"{len(rejected)} backlog_at_close="
        f"{len(due) - len(started) - len(rejected)} "
        f"tokens_in_window={sum(sched.in_window.values())} "
        f"serve_wall_s={wall:.3f} compiles_in_window={counter.n} "
        f"prefill_s={summary['prefill_s']} decode_s={summary['decode_s']} "
        f"decode_chunks={len(sched.chunks)} kv_bytes={kvb} "
        f"setup_s={setup_s:.3f}")

    metrics: dict = {}
    breakdown = None
    if trace:
        from . import trace_reduce as R

        t_r = time.monotonic()
        tr = R.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx["trace"] = tr
        ctx["trace_span"] = (tracer.t_on, tracer.t_off)
        lo, hi = R.window(tr)
        device["busy_s"] = R.busy_s(tr)
        device["window_s"] = (hi - lo) / 1e9
        breakdown = R.breakdown(tr)
        ops = [o for v in tr["devices"].values() for o in v]
        log(f"[trace] span_s={tracer.t_on:.3f}..{tracer.t_off:.3f} "
            f"devices={sorted(tr['devices'])} ops={len(ops)} "
            f"window_ns={lo}..{hi} ops_ns="
            f"{min((o[1] for o in ops), default=None)}.."
            f"{max((o[1] + o[2] for o in ops), default=None)} "
            f"busy_s={device['busy_s']:.6f} window_s="
            f"{device['window_s']:.6f} read_s={time.monotonic() - t_r:.3f}")
        for m in per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
                continue
            v = endtoend.METRICS[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state goes before the reference runs: the pool went
    # with serve(); the engine holds only compiled programs and params
    del engine, ctx
    gc.collect()
    chk = mix["check"]
    picked = sample(reqs, seed, chk["sample_tokens"], chk["sample_requests"])
    t_c = time.monotonic()
    gap, ctl, n_tok = served_gap(params, sz, picked, traffic.longest(mix),
                                 control=control)
    limit = chk["served_gap_limit"]
    # the control stands in the program's place: its tokens are judged
    # by the same comparison and the same limit
    value = ctl if control else gap
    correct = bool(picked) and value <= limit
    log(f"[check] requests={[r.rid for r in picked]} served_tokens={n_tok} "
        f"reference_s={time.monotonic() - t_c:.3f}"
        + (f" control=float8 program_gap={gap}" if control else ""))
    checks = {"served_gap": {"value": value, "limit": limit}}
    for k, c in checks.items():
        log(f"check {k} value={c['value']} limit={c['limit']}")
    out = {"correct": correct, "attempted": len(due) if mix["arrivals"][
        "process"] == "poisson" else len(started) + len(rejected),
        "failed": len(rejected), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["program_gap"] = gap
    out["checks"] = checks
    return out
