"""Block-shape autotuner for the SA GEMM (ArrayFlex-style configurability).

Sweeps (bm, bn, bk) per (M, N, K, dtype, epilogue) workload and remembers the
winner in two layers:

  * an **in-process dict** (`_MEM`) consulted on every `lookup`, and
  * an **on-disk JSON cache** so tuning results persist across processes
    (default ``~/.cache/repro_sa/autotune.json``; override with
    ``REPRO_AUTOTUNE_CACHE``).

Entries are keyed by backend (``cpu-interpret`` on this container, ``tpu``
on hardware) — interpret-mode timings never pollute hardware decisions.

`lookup` is the cheap path used by `repro.kernels.ops.sa_matmul` on every
call: memory cache → disk cache → MXU-aligned heuristic. It only *sweeps*
when asked (``sweep=True`` or ``REPRO_AUTOTUNE=1``), so test/serving paths
never pay tuning latency by surprise. A corrupt or unreadable cache file is
ignored, never fatal.

Cache format (DESIGN.md §2d)::

    {"version": 1,
     "entries": {"cpu-interpret|256x256x512|bfloat16|none":
                 {"blocks": [256, 256, 512], "us": 812.4}}}
"""
from __future__ import annotations

import contextlib
import json
import os
import time

try:
    import fcntl
except ImportError:  # pragma: no cover - non-posix
    fcntl = None

import jax
import jax.numpy as jnp
import numpy as np

from .sa_matmul import clip_blocks, default_blocks, sa_matmul_pallas

_VERSION = 1
# entry values are block tuples: (bm, bn, bk) for GEMM keys, (ppb, hb) for
# the paged decode-attention keys ("|dattn|" — pages_per_block, head tiling)
_MEM: dict[str, tuple[int, ...]] = {}
_DISK_LOADED = False

# candidate (bm, bn, bk) shapes; clipped to the problem and deduped per
# shape. All tile-aligned by construction (bm % 16, bn/bk % 128 == 0), so
# the tile-rounded clip in candidates_for keeps every swept shape aligned.
CANDIDATES = (
    (64, 128, 128),
    (128, 128, 256),
    (128, 256, 512),
    (256, 128, 512),
    (256, 256, 512),
    (512, 256, 512),
    (256, 512, 1024),
)

# decode / GEMV shapes: per-token serving GEMMs have M = B·T ∈ {1..16}
# (clip_blocks rounds any smaller M up to one 16-sublane tile), so bm
# collapses and the sweep is really over the (bn, bk) tiling — which is
# what differentiates latency when the whole M side fits in one tile pass
# and the K-chain (the SA column) dominates. Only swept when M fits one
# candidate block (m <= bm): at training M these shapes are never
# competitive and would just add compiles to every sweep.
DECODE_CANDIDATES = (
    (16, 128, 512),
    (16, 256, 1024),
    (16, 512, 512),
    (32, 256, 512),
)


def backend_key() -> str:
    """Cache namespace: platform, plus '-interpret' off-TPU (interpret-mode
    timings must never steer hardware block choices)."""
    plat = jax.default_backend()
    return plat if plat == "tpu" else f"{plat}-interpret"


def _decode_attn_snap(kvh: int, max_pages: int, psz: int, ppb: int,
                      hb: int) -> tuple[int, int]:
    """The (ppb, hb) the decode kernel will actually run for a request:
    Mosaic-legal blocks on TPU (`sa_decode_attention.tpu_blocks`), plain
    divisor clipping in interpret mode."""
    from .sa_decode_attention import largest_divisor, tpu_blocks
    if backend_key() == "tpu":
        return tpu_blocks(max_pages, kvh, psz, ppb, hb)
    return largest_divisor(max_pages, ppb), largest_divisor(kvh, hb)


def production_dtype() -> str:
    """The dtype `sa_dot` actually hands the kernel on this backend: f32
    containers on CPU (`precision.EXACT_CPU_CONTAINERS`), bf16 on TPU.
    Sweeps (bench / pre-seeders) must tune under this dtype — entries swept
    under any other are cache keys the production path never reads."""
    from repro.core.precision import EXACT_CPU_CONTAINERS
    return "float32" if EXACT_CPU_CONTAINERS else "bfloat16"


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro_sa",
                     "autotune.json"))


def _key(m: int, n: int, k: int, dtype: str, epilogue: str) -> str:
    return f"{backend_key()}|{m}x{n}x{k}|{dtype}|{epilogue}"


def _read_disk() -> dict:
    """Parse the on-disk cache; corrupt/missing files are just empty."""
    try:
        with open(cache_path()) as f:
            data = json.load(f)
        entries = data.get("entries", {})
        if data.get("version") != _VERSION or not isinstance(entries, dict):
            return {}
        return entries
    except (OSError, ValueError):
        return {}


def _sweep_requested() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "0") not in ("0", "false", "off")


def _load_disk_once():
    """Merge the on-disk cache into `_MEM` once per process — only when
    tuning was asked for (REPRO_AUTOTUNE) or a cache file was named
    (REPRO_AUTOTUNE_CACHE). By default nothing is read from disk and an
    unseen shape takes the MXU heuristic, so a run's blocks never depend on
    a cache file left behind by an earlier sweep."""
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    if not (_sweep_requested() or "REPRO_AUTOTUNE_CACHE" in os.environ):
        return
    for key, ent in _read_disk().items():
        try:
            blocks = tuple(int(x) for x in ent["blocks"])
        except (KeyError, TypeError, ValueError):
            continue
        if blocks:
            _MEM.setdefault(key, blocks)


@contextlib.contextmanager
def _file_lock(path: str):
    """flock-serialized critical section so concurrent tuners don't drop
    each other's entries in the read-merge-write below (best-effort: no-op
    where flock is unavailable)."""
    if fcntl is None:
        yield
        return
    with open(f"{path}.lock", "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


def _write_disk(key: str, blocks: tuple[int, int, int], us: float):
    """Merge one entry into the JSON cache (flock + tmp-rename atomic)."""
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with _file_lock(path):
            entries = _read_disk()
            entries[key] = {"blocks": list(blocks), "us": round(float(us), 2)}
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"version": _VERSION, "entries": entries}, f,
                          indent=1)
            os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc. — in-process cache still works


def reset():
    """Forget the in-process cache (tests: simulates a fresh process)."""
    global _DISK_LOADED
    _MEM.clear()
    _DISK_LOADED = False


def candidates_for(m: int, n: int, k: int) -> list[tuple[int, int, int]]:
    decode = tuple(c for c in DECODE_CANDIDATES if m <= c[0])
    seen, out = set(), []
    for bm, bn, bk in CANDIDATES + decode + (default_blocks(m, n, k),):
        # same tile-aligned clipping the kernel applies, so cached entries
        # record the blocks that actually run
        c = clip_blocks(bm, bn, bk, m, n, k)
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def _time_blocks(m, n, k, dtype, epilogue, blocks, reps=3) -> float:
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    a = jnp.asarray(rng.standard_normal((m, k)), dt)
    w = jnp.asarray(rng.standard_normal((k, n)), dt)
    bias = jnp.zeros((n,), jnp.float32) if epilogue != "none" else None
    interpret = jax.default_backend() != "tpu"
    bm, bn, bk = blocks

    def run():
        return sa_matmul_pallas(a, w, bias, act=epilogue, bm=bm, bn=bn,
                                bk=bk, interpret=interpret)

    run().block_until_ready()          # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run()
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def tune(m: int, n: int, k: int, *, dtype: str = "bfloat16",
         epilogue: str = "none", reps: int = 3
         ) -> tuple[tuple[int, int, int], list[dict]]:
    """Sweep candidate block shapes; cache and return the winner.

    Returns (best_blocks, table) where table rows are
    {"blocks": (bm,bn,bk), "us": float} sorted by time.
    """
    table = [{"blocks": c, "us": _time_blocks(m, n, k, dtype, epilogue, c,
                                              reps=reps)}
             for c in candidates_for(m, n, k)]
    table.sort(key=lambda r: r["us"])
    best = tuple(table[0]["blocks"])
    key = _key(m, n, k, dtype, epilogue)
    _MEM[key] = best
    _write_disk(key, best, table[0]["us"])
    return best, table


def _trace_state_clean() -> bool:
    """True when no jit trace is in flight (a sweep must execute eagerly).
    jax >= 0.6 drops `trace_state_clean` from the public `jax.core`."""
    try:
        return jax.core.trace_state_clean()
    except AttributeError:     # pragma: no cover - newer jax
        from jax._src.core import trace_state_clean
        return trace_state_clean()


def tune_decode(n: int, k: int, ms: tuple[int, ...] = (1, 4, 8), *,
                dtype: str = "bfloat16", reps: int = 3
                ) -> dict[int, tuple[int, int, int]]:
    """Pre-seed the cache with decode-shape winners: M ∈ `ms` GEMVs against
    one (K, N) weight. Serving engines can call this once at startup so the
    jitted decode step gets tuned blocks (lookup cannot sweep mid-trace)."""
    return {m: tune(m, n, k, dtype=dtype, reps=reps)[0] for m in ms}


def tune_spec_verify(n: int, k: int, batch: int, spec_k: int, *,
                     dtype: str = "bfloat16", reps: int = 3
                     ) -> dict[int, tuple[int, int, int]]:
    """Pre-seed the speculative-decode GEMM shapes (DESIGN.md §9): the
    draft/plain decode rows at M = batch and the batched verify forward at
    M = batch·(spec_k+1) — the verify folds each slot's k+1 draft rows
    into the batch axis, so its GEMMs run at that one M. Same startup
    contract as `tune_decode` (lookup cannot sweep inside the jitted spec
    chunk)."""
    return tune_decode(n, k, ms=(batch, batch * (spec_k + 1)),
                       dtype=dtype, reps=reps)


def lookup(m: int, n: int, k: int, *, dtype: str = "bfloat16",
           epilogue: str = "none", sweep: bool | None = None
           ) -> tuple[int, int, int]:
    """Best-known (bm, bn, bk): memory → disk → (optional sweep) → heuristic.

    `sweep=None` defers to the ``REPRO_AUTOTUNE`` env var (default off), so
    production callers hit at most one JSON read per process. A sweep
    cannot run while an outer `jit` is tracing (the timing calls would
    trace into the caller's computation instead of executing), so mid-trace
    misses fall back to the heuristic — pre-seed the cache eagerly
    (`tune()` / `benchmarks/kernel_bench.py`) to get tuned blocks inside
    jitted steps.

    A miss on an epilogue-specific key falls back to the bare-GEMM entry
    for the same shape: the epilogue is O(M·N) elementwise against the
    O(M·N·K) GEMM, so tuned blocks transfer — and the fused-activation FFN
    paths benefit from a cache swept with ``epilogue="none"``.
    """
    _load_disk_once()
    key = _key(m, n, k, dtype, epilogue)
    hit = _MEM.get(key)
    if hit is None and epilogue != "none":
        hit = _MEM.get(_key(m, n, k, dtype, "none"))
    if hit is not None:
        return hit
    if sweep is None:
        sweep = _sweep_requested()
    if sweep and _trace_state_clean():
        return tune(m, n, k, dtype=dtype, epilogue=epilogue)[0]
    # heuristic fallback — deliberately NOT memoized, so a later in-process
    # sweep can still take over this key (the disk cache is only read once
    # per process, so cross-process updates need a restart to be seen)
    return default_blocks(m, n, k)


# ---------------------------------------------------------------------------
# Paged decode-attention grid shapes (kernels/sa_decode_attention.py)
# ---------------------------------------------------------------------------

# (pages_per_block, kv_heads_per_block) candidates; clipped to divisors of
# (max_pages, KVH) per workload and deduped. More pages per grid step
# amortizes per-step overhead; head tiling trades grid steps for VMEM.
DECODE_ATTN_CANDIDATES = (
    (1, 1),
    (2, 1),
    (4, 1),
    (8, 1),
    (2, 2),
    (4, 2),
)


def decode_attn_key(batch: int, kvh: int, g: int, hd: int, psz: int,
                    max_pages: int, dtype: str) -> str:
    return (f"{backend_key()}|dattn|{batch}x{kvh}x{g}x{hd}|"
            f"{psz}x{max_pages}|{dtype}")


def default_decode_attn_blocks(kvh: int, max_pages: int,
                               psz: int) -> tuple[int, int]:
    """Heuristic: walk up to 8 pages per grid step, one KV head (snapped to
    the smallest legal tiling on TPU)."""
    return _decode_attn_snap(kvh, max_pages, psz, 8, 1)


def decode_attn_candidates(kvh: int, max_pages: int, psz: int
                           ) -> list[tuple[int, int]]:
    # (max_pages, kvh) collapses the page/head axes into a single grid
    # step — the interpret-mode winner (no while-loop carry copies) and a
    # legitimate TPU shape for small pools
    pool = DECODE_ATTN_CANDIDATES + ((max_pages, 1), (max_pages, kvh))
    seen, out = set(), []
    for ppb, hb in pool + (default_decode_attn_blocks(kvh, max_pages, psz),):
        c = _decode_attn_snap(kvh, max_pages, psz, ppb, hb)
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def tune_decode_attn(batch: int, kvh: int, g: int, hd: int, psz: int,
                     max_pages: int, *, dtype: str | None = None,
                     mapped_pages: int | None = None, reps: int = 2
                     ) -> tuple[tuple[int, int], list[dict]]:
    """Sweep (pages_per_block, head tiling) for one paged decode-attention
    workload; cache and return the winner, `tune()`-style.

    Timed on a synthetic pool with `mapped_pages` pages mapped per slot
    (default: half the block table — the mid-sparsity regime serving
    actually sits in). Serving engines call this once at startup
    (`launch/serve.py --autotune-decode`): the jitted decode chunk cannot
    sweep mid-trace, so winners must be on disk/in memory before the first
    chunk compiles.
    """
    from .sa_decode_attention import sa_paged_decode_attention
    dtype = dtype or production_dtype()
    mapped = mapped_pages or max(1, max_pages // 2)
    mapped = min(mapped, max_pages)
    rng = np.random.default_rng(0)
    n_pages = batch * max_pages + 1
    dt = jnp.dtype(dtype)
    k_pool = jnp.asarray(rng.standard_normal((n_pages, psz, kvh, hd)), dt)
    v_pool = jnp.asarray(rng.standard_normal((n_pages, psz, kvh, hd)), dt)
    bt = np.full((batch, max_pages), -1, np.int32)
    for b in range(batch):
        bt[b, :mapped] = 1 + b * max_pages + np.arange(mapped)
    page_pos = np.full((n_pages, psz), -1, np.int32)
    for b in range(batch):
        page_pos[bt[b, :mapped].reshape(-1)] = np.arange(
            mapped * psz, dtype=np.int32).reshape(mapped, psz)
    bt, page_pos = jnp.asarray(bt), jnp.asarray(page_pos)
    pos = jnp.full((batch,), mapped * psz - 1, jnp.int32)
    q = jnp.asarray(rng.standard_normal((batch, 1, kvh * g, hd)), dt)
    interpret = jax.default_backend() != "tpu"

    def time_one(ppb, hb):
        def run():
            return sa_paged_decode_attention(
                q, k_pool, v_pool, page_pos, bt, pos, ppb=ppb, hb=hb,
                interpret=interpret)
        run().block_until_ready()      # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = run()
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    table = [{"blocks": c, "us": time_one(*c)}
             for c in decode_attn_candidates(kvh, max_pages, psz)]
    table.sort(key=lambda r: r["us"])
    best = tuple(table[0]["blocks"])
    key = decode_attn_key(batch, kvh, g, hd, psz, max_pages, dtype)
    _MEM[key] = best
    _write_disk(key, best, table[0]["us"])
    return best, table


def lookup_decode_attn(batch: int, kvh: int, g: int, hd: int, psz: int,
                       max_pages: int, *, dtype: str | None = None,
                       sweep: bool | None = None) -> tuple[int, int]:
    """Best-known (pages_per_block, head tiling): memory → disk →
    (optional sweep) → heuristic. Same contract as `lookup`: consulted at
    trace time by `sa_paged_decode_attention`, never sweeps mid-trace."""
    _load_disk_once()
    dtype = dtype or production_dtype()
    hit = _MEM.get(decode_attn_key(batch, kvh, g, hd, psz, max_pages, dtype))
    if hit is not None and len(hit) == 2:
        return hit
    if sweep is None:
        sweep = _sweep_requested()
    if sweep and _trace_state_clean():
        return tune_decode_attn(batch, kvh, g, hd, psz, max_pages,
                                dtype=dtype)[0]
    return default_decode_attn_blocks(kvh, max_pages, psz)
