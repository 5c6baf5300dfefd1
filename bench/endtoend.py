"""End-to-end metrics of a serving window, from the scheduler's stamps.

Every time is the engine's clock (seconds since serve() began), which is
the clock the due times are laid out on.
"""
from __future__ import annotations

import math


def p90(values: list[float]) -> float | None:
    """Nearest-rank 90th percentile."""
    if not values:
        return None
    v = sorted(values)
    return v[math.ceil(0.9 * len(v)) - 1]


def out_tok_s(ctx) -> float:
    """Generated tokens delivered inside the window, per second of it."""
    return sum(ctx["sched"].in_window.values()) / ctx["seconds"]


def ttft_p90_ms(ctx) -> float | None:
    """p90 over every request due in the window of first token − due time.
    A request refused, or still without a first token when the window
    closed, counts at least the time from its due time to the close."""
    end = ctx["seconds"]
    out = []
    for r in ctx["reqs"]:
        if r.arrival_time >= end:
            continue
        if r.t_first_token is not None and r.finish_reason != "rejected":
            out.append(r.t_first_token - r.arrival_time)
        else:
            out.append(end - r.arrival_time)
    v = p90(out)
    return None if v is None else 1e3 * v


def tpot_p90_ms(ctx) -> float | None:
    """p90 over requests with two or more tokens delivered in the window
    of (last − first token time) / (tokens − 1)."""
    s = ctx["sched"]
    v = p90([(s.last_t[rid] - s.first_t[rid]) / (n - 1)
             for rid, n in s.in_window.items() if n >= 2])
    return None if v is None else 1e3 * v


METRICS = {"out_tok_s": out_tok_s, "ttft_p90_ms": ttft_p90_ms,
           "tpot_p90_ms": tpot_p90_ms}
