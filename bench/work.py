"""The work a serving run did, counted from the scheduler's records
(serve.WindowScheduler) and the published sizes (flops.py)."""
from __future__ import annotations

from . import flops as F


def _in(t: float, span) -> bool:
    return span is None or span[0] < t <= span[1]


def prefill_flops(ctx, span=None, attention_only: bool = False) -> int:
    s = ctx["sizes"]
    return sum(F.attention_flops(s, F.causal_pairs(a.prompt_len))
               if attention_only else F.prefill_flops(s, a.prompt_len)
               for a in ctx["sched"].admissions
               if span is None or (a.t_admit >= span[0]
                                   and a.t_first <= span[1]))


def prefill_bytes(ctx, span) -> int:
    return sum(F.prefill_attention_bytes(ctx["sizes"], a.prompt_len)
               for a in ctx["sched"].admissions
               if a.t_admit >= span[0] and a.t_first <= span[1])


def decode_steps(ctx, span=None):
    """(position, ) of every token a live slot kept, chunk by chunk."""
    for c in ctx["sched"].chunks:
        if _in(c.t, span):
            for p, n in zip(c.ctx, c.delivered):
                yield from range(p, p + n)


def decode_flops(ctx, span=None) -> int:
    s = ctx["sizes"]
    return sum(F.decode_flops(s, p) for p in decode_steps(ctx, span))


def decode_kv_bytes(ctx, span=None) -> int:
    s, kb = ctx["sizes"], ctx["kv_bytes"]
    return sum(F.decode_kv_bytes(s, p, kb) for p in decode_steps(ctx, span))


def decode_attention_flops(ctx, span=None) -> int:
    s = ctx["sizes"]
    return sum(F.attention_flops(s, p + 1) for p in decode_steps(ctx, span))


def mfu(ctx) -> float | None:
    """Model FLOPs of the prefills and decode tokens run, over the
    engine's own prefill + decode wall time, over the chip's peak (%)."""
    t = ctx["summary"]["prefill_s"] + ctx["summary"]["decode_s"]
    if t <= 0:
        return None
    work = prefill_flops(ctx) + decode_flops(ctx)
    return 100.0 * work / t / ctx["peaks"]["bf16_flops"]


def roofline(ctx, pattern: str, flops: int, nbytes: int) -> float | None:
    """Least time for (flops, nbytes) over the kernel's device time (%)."""
    from . import trace_reduce as R

    tr = ctx.get("trace")
    if tr is None or not tr["devices"] or not (flops or nbytes):
        return None
    lo, hi = R.window(tr)
    ops = next(iter(tr["devices"].values()))
    ns = R.op_ns(ops, pattern, lo, hi)
    if ns <= 0:
        return None
    p = ctx["peaks"]
    least = max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
