"""Roofline analysis (deliverable g): three terms per (arch × shape × mesh),
derived from the dry-run artifacts (cost_analysis + HLO collective parse).

TPU v5e constants (per chip): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI. `cost_analysis()` on the SPMD-partitioned module reports **per-device**
FLOPs/bytes; the parsed collective payloads are per-device payload proxies
(max tensor per collective op ≈ ring payload). Terms are therefore computed
per device without re-dividing by chip count:

    compute_s    = flops_dev / 197e12
    memory_s     = bytes_dev / 819e9
    collective_s = coll_bytes_dev / 50e9

MODEL_FLOPS uses 6·N_active·tokens (train) / 2·N_active·tokens (serve);
the ratio MODEL_FLOPS / HLO_FLOPs exposes remat/dispatch/recompute waste.
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs import get_config
from repro.models.config import SHAPES_BY_NAME

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")

# Activation materialization passes per layer (audited against the per-layer
# op inventory of the compiled HLO: ~15 residual-width tensors fwd, ~22 bwd,
# ~8 remat re-forward).
ACT_PASSES = {"train": 45, "prefill": 15, "decode": 20}


def analytic_bytes_per_device(arch: str, shape_name: str, n_dev: int) -> float:
    """HBM traffic model (bytes/device/step).

    The CPU-compiled HLO cannot give TPU-faithful HBM traffic (different
    fusion granularity, hoisting artifacts inside scan bodies — see
    EXPERIMENTS.md §Roofline); this explicit model counts: optimizer state
    r/w (train), bf16 weight reads per pass, residual-width activation
    materializations, attention score/probability tiles (our flash attention
    is jnp-level: p tiles do hit HBM), and KV/state cache traffic (decode).
    """
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    P = cfg.param_count()
    L, d, T, GB = cfg.num_layers, cfg.d_model, shape.seq_len, shape.global_batch
    # layers whose mixer attends / keeps Mamba2 state (hybrid: both)
    n_attn = sum(cfg.mixer(i) != "mamba" for i in range(L))
    n_ssm = sum(cfg.mixer(i) != "attention" for i in range(L))
    H = max(cfg.num_heads, cfg.num_heads if n_attn else cfg.n_ssm_heads)
    # attention head sharding efficiency: replicated when KVH doesn't divide
    # the 16-way model axis (this is also visible as the FLOPs inflation)
    tp = 16
    heads_eff = H / tp if (cfg.num_kv_heads % tp == 0) else H
    tokens_dev = GB * T / n_dev

    if shape.kind == "train":
        opt = 28.0 * P / n_dev                       # 7 fp32 quantities r/w
        wts = 3.0 * 2.0 * P / n_dev * 1.0            # bf16 fwd+dgrad+wgrad
        act = ACT_PASSES["train"] * L * tokens_dev * d * 2.0
        attn_p = 5.0 * n_attn * (GB / n_dev) * heads_eff * T * T * 4.0
        return opt + wts + act + attn_p
    if shape.kind == "prefill":
        wts = 2.0 * P / n_dev
        act = ACT_PASSES["prefill"] * L * tokens_dev * d * 2.0
        attn_p = 1.0 * n_attn * (GB / n_dev) * heads_eff * T * T * 4.0
        return wts + act + attn_p
    # decode: weights (active experts only) + cache read + small activations
    wts = 2.0 * cfg.active_param_count() / n_dev
    cache = 0.0
    for i in range(L):
        kind = cfg.layer_kind(i)
        if kind["mixer"] == "mamba":
            continue
        S = min(cfg.window, T) if kind["attn"] == "local" else T
        cache += 2 * GB * S * cfg.num_kv_heads * cfg.hd * 2.0
    cache += (GB * cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
              * 4.0 * 2 * n_ssm)
    act = ACT_PASSES["decode"] * L * (GB / n_dev) * d * 2.0
    return wts + cache / n_dev + act


def model_flops_per_device(arch: str, shape_name: str, n_dev: int) -> float:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / n_dev
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / n_dev
    # decode: one token per sequence per step
    return 2.0 * n_active * shape.global_batch / n_dev


def analyze(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    n_dev = rec["n_devices"]
    flops = rec.get("flops", 0.0)          # trip-count-aware HLO dot count
    byts = analytic_bytes_per_device(rec["arch"], rec["shape"], n_dev)
    byts_hlo = rec.get("bytes_accessed", 0.0)   # CPU-fusion upper bound
    coll = rec.get("collectives", {}).get("total", 0.0)
    t_c = flops / PEAK_FLOPS
    t_m = byts / HBM_BW
    t_n = coll / ICI_BW
    dom = max((t_c, "compute"), (t_m, "memory"), (t_n, "collective"))[1]
    mf = model_flops_per_device(rec["arch"], rec["shape"], n_dev)
    t_total = max(t_c, t_m, t_n)
    # roofline fraction: useful-model-FLOPs time over the modeled step time
    frac = (mf / PEAK_FLOPS) / t_total if t_total > 0 else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "memory_s_hlo_upper": byts_hlo / HBM_BW,
        "bottleneck": dom,
        "model_flops_dev": mf,
        "useful_ratio": mf / flops if flops else 0.0,
        "roofline_frac": frac,
        "peak_gib": rec.get("memory", {}).get("peak_estimate_bytes", 0) / 2**30,
    }


def all_rows(mesh: str | None = "pod"):
    rows = []
    for f in sorted(glob.glob(os.path.join(ART, "*.json"))):
        rec = json.load(open(f))
        if mesh and rec.get("mesh") != mesh:
            continue
        r = analyze(rec)
        if r:
            rows.append(r)
    return rows


def main():
    rows = all_rows("pod")
    if not rows:
        print("# no dry-run artifacts found — run repro.launch.dryrun first")
        return
    rows.sort(key=lambda r: r["roofline_frac"])
    for r in rows:
        print(f"table=roofline,arch={r['arch']},shape={r['shape']},"
              f"compute_s={r['compute_s']:.2e},memory_s={r['memory_s']:.2e},"
              f"collective_s={r['collective_s']:.2e},"
              f"bottleneck={r['bottleneck']},"
              f"useful_ratio={r['useful_ratio']:.3f},"
              f"roofline_frac={r['roofline_frac']:.3f},"
              f"peak_gib={r['peak_gib']:.2f}")
    worst = rows[0]
    coll_bound = max(rows, key=lambda r: r["collective_s"]
                     / max(r["compute_s"], 1e-12))
    print(f"# worst roofline fraction: {worst['arch']}×{worst['shape']} "
          f"({worst['roofline_frac']:.3f})")
    print(f"# most collective-bound: {coll_bound['arch']}×{coll_bound['shape']}")


if __name__ == "__main__":
    main()
