"""From a profiler trace to device busy time, kernel time and idle gaps.

`load()` reads the `.xplane.pb` the JAX profiler wrote into a small dict
that the rest of this module (and the tests, from `bench/testdata/`) read:

  {"devices": {plane: [[op, start_ns, dur_ns, source], ...]},
   "host":    [[marker, t_ns], ...]}

`op` is the device operation's name on the plane's "XLA Ops" line and
`source` the JAX operation it came from (the `tf_op` stat, or ""). Host
markers are the benchmark's own zero-length `TraceAnnotation`s named
`bench:<what>` (serve.py): `trace_on`/`trace_off` bound the window, the
others say what the host was doing. Device and host events share the
profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re

MARK = "bench:"
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|all-to-all", re.I)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            # the line of XLA operations ("XLA Ops"; any "... Ops" line
            # where a profiler names it otherwise)
            lines = sorted((ln for ln in plane.lines if "Ops" in ln.name),
                           key=lambda ln: ln.name != "XLA Ops")
            if lines:
                devices[plane.name] = [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns),
                     str(_stats(ev).get("tf_op", ""))]
                    for ev in lines[0].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK):
                        host.append([ev.name[len(MARK):],
                                     int(ev.start_ns)])
    host.sort(key=lambda m: m[1])
    return {"devices": devices, "host": host}


def window(trace: dict) -> tuple[int, int]:
    """[trace_on, trace_off] in the profiler's clock."""
    on = [t for m, t in trace["host"] if m == "trace_on"]
    off = [t for m, t in trace["host"] if m == "trace_off"]
    if not on or not off:
        raise ValueError("the trace has no trace_on/trace_off markers")
    return on[0], off[-1]


def _merged(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d, _ in ops
                   if s < hi and s + d > lo)
    out: list[list[int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi] during which some operation ran on the device."""
    return sum(e - s for s, e in _merged(ops, lo, hi))


def busy_s(trace: dict) -> float:
    """Busy seconds in the window, averaged over the traced devices."""
    lo, hi = window(trace)
    devs = trace["devices"].values()
    if not devs:
        return 0.0
    return sum(busy_ns(ops, lo, hi) for ops in devs) / len(devs) / 1e9


def op_ns(ops, pattern: str, lo: int, hi: int) -> int:
    """Summed device time of the operations whose name or source
    matches `pattern`, clipped to [lo, hi]."""
    rx = re.compile(pattern)
    return sum(min(s + d, hi) - max(s, lo) for n, s, d, src in ops
               if (rx.search(n) or rx.search(src)) and s < hi and s + d > lo)


def collective_ns(ops, lo: int, hi: int) -> int:
    return sum(min(s + d, hi) - max(s, lo) for n, s, d, _ in ops
               if COLLECTIVE.search(n) and s < hi and s + d > lo)


def idle_gaps(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    gaps, t = [], lo
    for s, e in _merged(ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _label(host, t_end: int) -> str:
    """What the host last did before the device resumed at `t_end`."""
    last = "trace_on"
    for m, t in host:
        if t > t_end:
            break
        last = m
    return last


def _op_group(name: str, src: str) -> str:
    """The JAX source's program and op, or else the HLO instruction's
    name (a TPU trace names each event by the instruction's whole text,
    "%fusion.168 = bf16[16,1536]{...} fusion(...)")."""
    if src:
        parts = [p for p in src.split("/") if p]
        return "/".join(parts[:1] + parts[-1:])
    return name.split(" = ")[0].lstrip("%")


def breakdown(trace: dict, top: int = 10) -> dict:
    """The operations that took the most device time, grouped by their
    JAX source, and the longest idle gaps, each labelled with the host
    marker that preceded its end; seconds, first device."""
    lo, hi = window(trace)
    ops = next(iter(trace["devices"].values()), [])
    by: dict[str, int] = {}
    for n, s, d, src in ops:
        if s < hi and s + d > lo:
            k = _op_group(n, src)
            by[k] = by.get(k, 0) + min(s + d, hi) - max(s, lo)
    dev = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in dev],
            "idle_gaps": [[_label(trace["host"], e), (e - s) / 1e9]
                          for s, e in gaps]}
