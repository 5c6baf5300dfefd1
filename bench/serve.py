"""Serving cells: the program's `ServeEngine` under one traffic mix.

What the benchmark takes from the program is listed in PERF.md as its
contract with it: `ArchConfig`, `ServeEngine(...)`, `engine.serve`,
`engine.new_pool` (shapes only, through `jax.eval_shape`; its
`PagedKVCache` leaves `k` and `v`), the engine's prompt buckets
(`_bucket_len`),
`SlotScheduler` (`submit`, `admit`, `start`, `observe`, `next_arrival`,
`drained`, `slots`) and the `Request` fields (`rid`, `prompt`, `tokens`,
`arrival_time`, `t_admitted`, `t_first_token`, `finish_reason`).

`WindowScheduler` is the thin subclass that measures. It stops admitting
once the window has closed and then reports itself drained, so `serve()`
returns after the chunk in flight; it stamps every token with the host
time of the scheduler call that delivered it (tokens after the close do
not count); and it keeps, for each decode chunk, the position of every
live slot, from which the benchmark counts the work the chunk did.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.serve.engine import _bucket_len
from repro.serve.scheduler import SlotScheduler

MARK = "bench:"


@dataclasses.dataclass
class Chunk:
    t: float                       # host time the chunk's tokens arrived
    steps: int
    ctx: list[int]                 # each live slot's position at the start
    delivered: list[int]           # tokens each of those slots kept


@dataclasses.dataclass
class Admission:
    t_admit: float
    t_first: float
    prompt_len: int


class Tracer:
    """Profiles [start_s, stop_s) of the window, switching only at a
    scheduler call (the device is idle there: the chunk's tokens were just
    fetched, or nothing runs yet)."""

    def __init__(self, out_dir: str | None, start_s: float, stop_s: float):
        self.out_dir, self.start_s, self.stop_s = out_dir, start_s, stop_s
        self.on = False
        self.t_on = self.t_off = None

    def mark(self, name: str):
        if self.on:
            with jax.profiler.TraceAnnotation(MARK + name):
                pass

    def tick(self, now: float):
        if self.out_dir is None:
            return
        if not self.on and self.t_on is None and now >= self.start_s:
            jax.profiler.start_trace(self.out_dir)
            self.on, self.t_on = True, now
            self.mark("trace_on")
        elif self.on and now >= self.stop_s:
            self.stop(now)

    def stop(self, now: float):
        if self.on:
            self.mark("trace_off")
            jax.profiler.stop_trace()
            self.on, self.t_off = False, now


class WindowScheduler(SlotScheduler):
    def __init__(self, n_slots: int, eos_id: int, end_s: float,
                 tracer: Tracer | None = None):
        super().__init__(n_slots, eos_id=eos_id)
        self.end_s = end_s
        self.closed = False
        self.tracer = tracer or Tracer(None, 0.0, 0.0)
        self.chunks: list[Chunk] = []
        self.admissions: list[Admission] = []
        self.first_t: dict[int, float] = {}
        self.last_t: dict[int, float] = {}
        self.in_window: dict[int, int] = {}
        self._t_admit: dict[int, float] = {}

    def _deliver(self, rid: int, n: int, now: float):
        if n and now <= self.end_s:
            self.in_window[rid] = self.in_window.get(rid, 0) + n
            self.first_t.setdefault(rid, now)
            self.last_t[rid] = now

    def admit(self, slot_idx: int, now: float):
        if now >= self.end_s:
            self.closed = True
            return None
        self.tracer.tick(now)
        req = super().admit(slot_idx, now)
        if req is not None:
            self.tracer.mark("admit")
            self._t_admit[req.rid] = now
        return req

    def start(self, slot_idx: int, first_token: int, now: float,
              prefill_s: float = 0.0):
        req = self.slots[slot_idx].req
        super().start(slot_idx, first_token, now, prefill_s=prefill_s)
        self.tracer.mark("first_token")
        self.admissions.append(Admission(self._t_admit.pop(req.rid), now,
                                         req.prompt_len))
        self._deliver(req.rid, 1, now)

    def observe(self, chunk_tokens, now: float, mode: str = "exact"):
        live = [s.req for s in self.slots if s.req is not None]
        before = [(r, r.n_generated) for r in live]
        super().observe(chunk_tokens, now, mode=mode)
        self.tracer.mark("chunk_sync")
        got = [r.n_generated - n for r, n in before]
        self.chunks.append(Chunk(
            now, int(chunk_tokens.shape[0]),
            [r.prompt_len + n - 1 for r, n in before], got))
        for (r, _), n in zip(before, got):
            self._deliver(r.rid, n, now)
        if now >= self.end_s:
            self.closed = True
        self.tracer.tick(now)

    def next_arrival(self):
        self.tracer.mark("wait_arrival")
        return super().next_arrival()

    def drained(self) -> bool:
        return self.closed or super().drained()


def make_engine(cfg, params, eng: dict):
    from repro.serve.engine import ServeEngine

    return ServeEngine(
        cfg, params, batch=eng["slots"], cache_len=eng["max_seq_len"],
        eos_id=-1, sync_every=eng["sync_every"], kv_layout="paged",
        page_size=eng["page_size"], pool_pages=eng["pool_pages"],
        max_seq_len=eng["max_seq_len"], spec_k=0, disagg=False,
        bucket_prompts=True)


def kv_bytes(engine) -> int:
    """Bytes of one K or V value in the page pool this engine builds: its
    `PagedKVCache` K and V pages, whatever state sits beside them."""
    from repro.models.layers import PagedKVCache

    pages = [x for x in jax.tree.leaves(
        jax.eval_shape(engine.new_pool),
        is_leaf=lambda x: isinstance(x, PagedKVCache))
        if isinstance(x, PagedKVCache)]
    sizes = {jax.numpy.dtype(a.dtype).itemsize for p in pages
             for a in (p.k, p.v)}
    if len(sizes) != 1:
        raise ValueError("the page pool's K and V pages have dtypes of "
                         f"{sizes} bytes; want one")
    return sizes.pop()


def warm_lengths(prompt_lens) -> list[int]:
    """One prompt length for each prefill shape the traffic can reach:
    one for each of the engine's prompt buckets."""
    by_bucket: dict[int, int] = {}
    for n in sorted(set(prompt_lens)):
        by_bucket.setdefault(_bucket_len(n), n)
    return sorted(by_bucket.values())


def warm_up(engine, lengths, vocab: int, sync_every: int):
    """One serve() on the engine: a prompt of each length (compiling
    every prefill and splice shape) and one request long enough to run
    decode chunks; everything it compiles lands in the compile cache."""
    sched = SlotScheduler(engine.batch, eos_id=-1)
    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        new = 2 * sync_every + 1 if i == 0 else 1
        sched.submit(rng.integers(0, vocab, n, dtype=np.int32), new)
    engine.serve(sched)
    return sched


def run_window(engine, specs, seconds: float, tracer: Tracer | None):
    """Serve `specs` (traffic.Spec) with due times relative to the start
    of serve(); the window closes `seconds` later."""
    sched = WindowScheduler(engine.batch, -1, seconds, tracer)
    reqs = [sched.submit(s.prompt, s.max_new_tokens, arrival_time=s.due_s)
            for s in specs]
    t0 = time.monotonic()
    summary = engine.serve(sched)
    wall = time.monotonic() - t0
    if tracer is not None:
        tracer.stop(seconds)
    return sched, reqs, summary, wall
