"""Bring-up smoke: the serving path on one TPU chip, or sharded training on four.

    python chip_smoke.py                # one chip: continuous-batching serve
    python chip_smoke.py --four-chips   # four chips: 2x2-mesh train steps

Run it from the repo root on a machine whose JAX finds a TPU. It refuses any
other platform (no CPU or interpret-mode fallback), and it needs the package
under `src/` next to it. One process drives every phase: a parent that has
touched JAX holds the chip.

Default phase — granite-moe-3b-a800m at its published widths and full depth,
seeded random weights stored in bf16, through `launch/serve.py`'s own path
(`parse_args` → `load_model` → `build_requests` → `ServeEngine.serve`): 8
requests through 4 paged slots (page 16, max_seq_len 2048, 32 new tokens
each), prompts of 96, 600 and 1000 tokens, two of them behind one shared
64-token system prompt. It fails unless the fused paged decode kernel ran,
every request got its tokens, no page leaked, the prefix cache hit, and the
engine's prefill logits and one teacher-forced decode step agree with a plain
forward (no cache, no Pallas kernels, dropless MoE) within `LOGIT_RTOL`,
with the fused decode kernel bit-identical to the gather path. It then
serves the same stream with the prefix cache off: every token stream, the
shared-prefix pair's included, must match.

`--four-chips` — granite at published widths cut to 4 layers: a few AdamW
steps on a (data=2, model=2) mesh through `launch/train.py`'s placement
(`make_host_mesh` → `place_state`), and the same steps on a one-device mesh
in the same process. Losses and gradient norms must agree within
`TRAIN_RTOL`.

The times printed are set-up and wall times of a bring-up run, not benchmark
numbers. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

ARCH = "granite-moe-3b-a800m"
# 8 requests through 4 slots; prompt lengths cross the 512-row flash block
# and are not multiples of 128. Seed 6 gives two 600-token prompts behind
# the shared 64-token (4-page) system prompt.
SEED = 6
SERVE_ARGV = ["--arch", ARCH, "--batch", "4", "--requests", "8",
              "--prompt-lens", "96,600,1000", "--max-new", "32",
              "--kv", "paged", "--page-size", "16", "--max-seq-len", "2048",
              "--cache-len", "1096", "--prefix-mix", "0.3",
              "--prefix-len", "64", "--sync-every", "8",
              "--seed", str(SEED)]
# engine vs plain forward: max |Δlogit| / max |reference logit|. Both sides
# are the same math on a bf16 residual stream, compiled as different
# programs (cache threaded or not, remat, attention tiling), so bf16
# rounding points differ and 32 layers amplify them. On a v5e the gap was
# 1.4e-2 (prefill) and 4.5e-2 (decode), and the engine with its blockwise
# prefill instead of the flash kernel sits at 2.3e-2 / 4.0e-2 — a floor of
# program differences, not of the kernels. A wrong page or position at
# this prompt length moves the row by O(1).
LOGIT_RTOL = 1e-1
# 2x2 mesh vs one device, per step, for the loss and the gradient norm:
# |Δ| / |one-device value|. The first step is the same math in another
# reduction order (~1e-5); AdamW's normalized update then amplifies
# rounding gaps in near-zero gradients and flips top-k routing ties, so
# later steps drift further (1.4e-3 by step 3 in a CPU rehearsal at reduced
# width). A missing or doubled collective moves the gradient norm by tens
# of percent.
TRAIN_RTOL = 1e-2
TRAIN_LAYERS, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 4, 3, 8, 256


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


def _import_repo():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        fail(f"the repro package is not at {src}; run from a repo checkout")
    sys.path.insert(0, str(src))


def _device_or_fail():
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no usable backend: {e}")
    platform = devs[0].platform
    check(platform == "tpu",
          f"needs a TPU; JAX's platform is {platform!r} "
          f"({devs[0].device_kind}) — no CPU or interpret-mode fallback")
    from repro.core import precision
    from repro.kernels import ops
    check(not ops.INTERPRET, "kernels.ops.INTERPRET is on")
    check(not precision.EXACT_CPU_CONTAINERS,
          "precision.EXACT_CPU_CONTAINERS is on")
    return devs


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# one chip: serving
# ---------------------------------------------------------------------------

def _serve_once(S, args, cfg, params, plens, prefix_cache: bool):
    os.environ["REPRO_PREFIX_CACHE"] = "1" if prefix_cache else "0"
    t0 = time.monotonic()
    _, sched, summary = S.serve_stream(args, cfg, params, plens)
    wall = time.monotonic() - t0
    tag = f"prefix_cache={int(prefix_cache)}"
    print(f"[serve {tag}] bring-up wall_s={wall:.3f} (compile included) "
          f"engine compile_s={summary['compile_s']} "
          f"prefill_s={summary['prefill_s']} decode_s={summary['decode_s']} "
          f"decode_tok_s={summary.get('decode_tok_s')} "
          f"prefill_compiles={summary['prefill_compiles']}", flush=True)
    print(f"[serve {tag}] requests={summary['requests']} "
          f"rejected={summary['rejected']} "
          f"generated_tokens={summary['generated_tokens']} "
          f"decode_attn={summary.get('decode_attn')} "
          f"pages_leaked={summary.get('pages_leaked')} "
          f"prefix_hits={summary.get('prefix_hits')} "
          f"prefix_tokens_saved={summary.get('prefix_tokens_saved')} "
          f"stream_digest={summary['stream_digest']}", flush=True)
    check(summary.get("decode_attn") == "fused",
          f"{tag}: decode_attn={summary.get('decode_attn')!r}, want 'fused'")
    check(summary["requests"] == args.requests and summary["rejected"] == 0,
          f"{tag}: served {summary['requests']} with "
          f"{summary['rejected']} rejected, want {args.requests}")
    short = [r.rid for r in sched.finished if len(r.tokens) != args.max_new]
    check(not short, f"{tag}: requests {short} did not get "
                     f"{args.max_new} tokens")
    check(summary.get("pages_leaked") == 0,
          f"{tag}: pages_leaked={summary.get('pages_leaked')}")
    return sched, summary


def _prefix_pair(finished, prefix_len: int):
    by_prefix: dict[tuple, list] = {}
    for r in finished:
        by_prefix.setdefault(tuple(r.prompt[:prefix_len]), []).append(r.rid)
    pairs = [rids for rids in by_prefix.values() if len(rids) >= 2]
    return sorted(pairs[0]) if pairs else None


def _logits_check(S, args, cfg, params, prompt, tok: int):
    """Engine prefill logits and one teacher-forced paged decode step vs a
    plain forward of prompt + [tok] (no cache, Pallas attention off,
    dropless MoE like the serve path); the decode step's fused kernel also
    vs the gather path on the same page pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import optflags
    from repro.models import model as M
    from repro.train.step import make_prefill_step, make_serve_step

    engine = S.make_engine(args, cfg, params)
    T = len(prompt)
    frag = engine.new_frag(T)
    logits_p, frag = jax.jit(make_prefill_step(cfg))(
        params, jnp.asarray(prompt, jnp.int32)[None], frag, None)
    n_pages = frag[0]["kv"].k.shape[2] // engine.page_size
    row = np.full((engine.max_pages,), -1, np.int32)
    row[:n_pages] = np.arange(1, n_pages + 1)
    pool = engine._insert(engine.new_pool(), frag, jnp.int32(0),
                          jnp.asarray(row), jnp.int32(0))
    toks = np.zeros((engine.batch, 1), np.int32)
    toks[0, 0] = tok
    pos = np.zeros((engine.batch,), np.int32)
    pos[0] = T

    def decode(impl):
        # REPRO_DECODE_ATTN is read at trace time: a fresh jit per impl
        os.environ["REPRO_DECODE_ATTN"] = impl
        return jax.jit(make_serve_step(cfg))(
            params, jnp.asarray(toks), pool, jnp.asarray(pos))[0]

    old_env = os.environ.get("REPRO_DECODE_ATTN")
    try:
        logits_g = np.asarray(decode("gather"))
        logits_d = decode("fused")
    finally:
        if old_env is None:
            os.environ.pop("REPRO_DECODE_ATTN", None)
        else:
            os.environ["REPRO_DECODE_ATTN"] = old_env
    # the fused page walk is pinned bit-identical to gather + dense in
    # interpret mode; on the chip it must be too
    same = np.array_equal(np.asarray(logits_d), logits_g)
    print(f"[logits] decode fused kernel vs gather path on one pool: "
          f"bit-identical={same}", flush=True)
    check(same, "fused decode logits differ from the gather path")

    # the plain forward: no cache (so blockwise attention and no decode
    # path), Pallas attention off, dropless MoE as the serve path runs it
    ref_cfg = dataclasses.replace(cfg, moe_dropless=True)
    old_flag = optflags.enabled("pallas_attention")
    optflags.set_flag("pallas_attention", False)
    try:
        ref, _, _ = jax.jit(lambda p, t: M.forward(p, ref_cfg, t))(
            params, jnp.asarray(list(prompt) + [tok], jnp.int32)[None])
    finally:
        optflags.set_flag("pallas_attention", old_flag)
    V = cfg.vocab_size
    ref = np.asarray(ref[0, :, :V], np.float32)
    for name, got, want in (
            ("prefill", np.asarray(logits_p[0, -1, :V], np.float32),
             ref[T - 1]),
            ("decode", np.asarray(logits_d[0, 0, :V], np.float32),
             ref[T])):
        check(np.isfinite(got).all(), f"{name} logits are not finite")
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        print(f"[logits] {name} T={T}: max|Δ|/max|ref|={rel:.3e} "
              f"(tolerance {LOGIT_RTOL:g}) argmax engine={int(got.argmax())} "
              f"reference={int(want.argmax())}", flush=True)
        check(rel <= LOGIT_RTOL,
              f"{name} logits differ from the plain forward by {rel:.3e} "
              f"> {LOGIT_RTOL:g}")


def serve_phase(argv=SERVE_ARGV):
    import jax
    import numpy as np

    from repro.core import optflags
    from repro.launch import serve as S

    check(optflags.enabled("pallas_attention"),
          "the Pallas flash prefill kernel is off")
    args = S.parse_args(argv)
    plens = [int(x) for x in args.prompt_lens.split(",")]
    t0 = time.monotonic()
    cfg, params = S.load_model(args)
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)
                     if x.ndim >= 2})
    print(f"[model] {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"experts={cfg.num_experts} top{cfg.experts_per_token} "
          f"params={n_params} matrix dtypes={dtypes} "
          f"set-up init_s={time.monotonic() - t0:.3f}", flush=True)
    check(dtypes == ["bfloat16"], f"weights are stored as {dtypes}")

    sched_on, summary_on = _serve_once(S, args, cfg, params, plens, True)
    check((summary_on.get("prefix_hits") or 0) >= 1,
          "the shared-prefix pair never hit the prefix cache")
    pair = _prefix_pair(sched_on.finished, args.prefix_len)
    check(pair is not None, "no two requests share the system prompt")

    # the plain-forward check on the stream's longest prompt (1000 tokens:
    # two flash blocks, not a multiple of 128)
    prompt = max((r.prompt for r in sched_on.finished), key=len)
    tok = int(np.random.default_rng(SEED).integers(0, cfg.vocab_size))
    _logits_check(S, args, cfg, params, list(map(int, prompt)), tok)

    sched_off, _ = _serve_once(S, args, cfg, params, plens, False)
    on = {r.rid: r.tokens for r in sched_on.finished}
    off = {r.rid: r.tokens for r in sched_off.finished}
    print(f"[prefix] pair rids={pair} tokens match with REPRO_PREFIX_CACHE "
          f"on and off: {all(on[i] == off[i] for i in pair)}; all "
          f"{len(on)} streams match: {on == off}", flush=True)
    check(on == off, "token streams differ with the prefix cache on and off")


# ---------------------------------------------------------------------------
# four chips: sharded training
# ---------------------------------------------------------------------------

def _device_bytes(state) -> dict[int, int]:
    import jax
    per: dict[int, int] = {}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) + \
                shard.data.nbytes
    return dict(sorted(per.items()))


def train_phase(cfg=None, devices=None):
    import jax

    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import place_state
    from repro.train.optimizer import AdamW, constant_lr
    from repro.train.step import make_train_step
    from repro.train.train_state import init_state

    cfg = cfg or dataclasses.replace(get_config(ARCH),
                                     num_layers=TRAIN_LAYERS)
    devices = list(devices or jax.devices())
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    opt = AdamW(schedule=constant_lr(1e-4))
    step_fn = make_train_step(cfg, opt)
    data = SyntheticLM(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH, seed=SEED)
    batches = [data.batch_at(i) for i in range(TRAIN_STEPS)]

    def run(mesh, name):
        t0 = time.monotonic()
        state, _ = place_state(cfg, init_state(jax.random.key(SEED), cfg,
                                               opt), mesh)
        placed = _device_bytes(state)
        jstep = jax.jit(step_fn, donate_argnums=(0,))
        out = {"loss": [], "grad_norm": []}
        for batch in batches:
            state, metrics = jstep(state, batch)
            for k in out:
                out[k].append(float(metrics[k]))
        wall = time.monotonic() - t0
        print(f"[train {name}] mesh={dict(mesh.shape)} losses={out['loss']} "
              f"grad_norms={out['grad_norm']} state bytes per "
              f"device={placed} bring-up wall_s={wall:.3f} "
              f"(compile included)", flush=True)
        del state
        return out

    one = run(make_host_mesh(1, devices=devices[:1]), "1 device")
    four = run(make_host_mesh(2, devices=devices[:4]), "2x2")
    for k in one:
        check(all(map(math.isfinite, one[k] + four[k])), f"non-finite {k}")
        worst = max(abs(a - b) / abs(b) for a, b in zip(four[k], one[k]))
        print(f"[train] 2x2 vs 1 device: max |Δ{k}|/|{k}|={worst:.3e} "
              f"(tolerance {TRAIN_RTOL:g})", flush=True)
        check(worst <= TRAIN_RTOL, f"2x2-mesh {k} differs from one device "
                                   f"by {worst:.3e} > {TRAIN_RTOL:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh training phase and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    _import_repo()
    devs = _device_or_fail()
    import jax

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)
    t0 = time.monotonic()
    if args.four_chips:
        train_phase()
    else:
        serve_phase()
    peaks = [_peak_bytes(d) for d in devs[:4 if args.four_chips else 1]]
    print(f"[device] peak_bytes_in_use={peaks} "
          f"total bring-up wall_s={time.monotonic() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
