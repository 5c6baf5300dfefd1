"""Training driver: mesh setup, data, fault tolerance, checkpointing.

CPU-scale by default (reduced configs); the same code path drives pod-scale
runs — the mesh/shardings come from the same rules the dry-run validates.

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 20
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, reduced_config
from repro.data.pipeline import SyntheticLM, Prefetcher
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.parallel import sharding as S
from repro.train import checkpoint as CKPT
from repro.train.fault import PreemptionGuard, StragglerWatchdog
from repro.train.optimizer import AdamW, warmup_cosine
from repro.train.step import make_train_step
from repro.train.train_state import TrainState, init_state


def place_state(cfg, state: TrainState, mesh):
    """Shard a train state onto `mesh` by the parameter rules
    (parallel/sharding.py); returns (placed state, its shardings)."""
    pshard = S.param_shardings(cfg, state.params, mesh)
    state_shard = TrainState(step=NamedSharding(mesh, P()), params=pshard,
                             opt_state=type(state.opt_state)(
                                 count=NamedSharding(mesh, P()),
                                 mu=pshard, nu=pshard))
    return jax.device_put(state, state_shard), state_shard


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale smoke)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log", default=None, help="metrics JSONL path")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_host_mesh(args.model_parallel)
    opt = AdamW(schedule=warmup_cosine(args.lr, max(2, args.steps // 10),
                                       args.steps))
    step_fn = make_train_step(cfg, opt, accum_steps=args.accum)

    state = init_state(jax.random.key(args.seed), cfg, opt)
    state, state_shard = place_state(cfg, state, mesh)

    start = 0
    if args.resume and args.ckpt_dir and CKPT.latest_step(args.ckpt_dir) is not None:
        state, extra, start = CKPT.restore(args.ckpt_dir, state,
                                           shardings=state_shard)
        print(f"resumed from step {start}")

    jstep = jax.jit(step_fn, donate_argnums=(0,))
    data = Prefetcher(iter(SyntheticLM(cfg.vocab_size, args.seq, args.batch,
                                       seed=args.seed)))
    guard = PreemptionGuard()
    watchdog = StragglerWatchdog(
        on_straggler=lambda s, dt, med: print(
            f"[straggler] step {s}: {dt:.2f}s vs median {med:.2f}s"))
    saver = CKPT.AsyncSaver()
    logf = open(args.log, "a") if args.log else None

    t_start = time.time()
    for step in range(start, args.steps):
        watchdog.step_start()
        batch = next(data)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = jstep(state, batch)
        dt = watchdog.step_end(step)
        m = {k: float(v) for k, v in metrics.items()}
        m |= {"step": step + 1, "wall_s": round(dt, 4)}
        print(f"step {step+1:5d} loss={m['loss']:.4f} "
              f"gnorm={m['grad_norm']:.3f} {dt*1e3:.0f}ms", flush=True)
        if logf:
            logf.write(json.dumps(m) + "\n")
            logf.flush()
        want_ckpt = args.ckpt_dir and ((step + 1) % args.ckpt_every == 0
                                       or step + 1 == args.steps)
        if guard.should_stop:   # graceful preemption: checkpoint + exit
            if args.ckpt_dir:
                saver.wait()
                CKPT.save(args.ckpt_dir, step + 1, state)
            print(f"preempted at step {step+1}; checkpoint written")
            break
        if want_ckpt:
            saver.save_async(args.ckpt_dir, step + 1, state)
    saver.wait()
    data.close()
    if logf:
        logf.close()
    n = args.steps - start
    print(f"done: {n} steps in {time.time()-t_start:.1f}s; "
          f"{len(watchdog.events)} straggler events")
    return state


if __name__ == "__main__":
    main()
