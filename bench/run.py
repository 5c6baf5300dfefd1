"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
BENCHMARK.json at the root of the checkout. The run needs as many TPU
chips as the cell asks for: with no TPU, or too few chips, it exits with
code 3 and prints no result. `--trace 0` reports the cell's end-to-end
metrics, `--trace 1` its per-layer metrics from a profile of a few seconds
in the middle of the window. Each run compares what was served with the
plain reference (bench/correct.py) and prints the numbers compared, with
their limits, as the last lines on standard error and under "checks", the
last key of the result.

JAX's persistent compilation cache lives in <checkout>/.jax_cache, unless
JAX_COMPILATION_CACHE_DIR names another directory.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def cell_entry(name: str) -> tuple[dict, dict, list, list]:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")

    def mine(m):
        return name in m.get("workloads", [name])

    return (cells[name], spec, [m for m in spec["end_to_end"] if mine(m)],
            [m for m in spec["per_layer"] if mine(m)])


def chips_or_exit(n: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no backend: {e}", file=sys.stderr)
        raise SystemExit(3)
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s); JAX has {len(devs)} "
              f"{devs[0].platform} device(s) ({devs[0].device_kind})",
              file=sys.stderr)
        raise SystemExit(3)
    return devs


def use_compile_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    entry, _, e2e, per_layer = cell_entry(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not at {ROOT / 'src'}",
              file=sys.stderr)
        return 3
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    chips_or_exit(entry["chips"])
    use_compile_cache()
    from bench import cell, traffic, weights

    out = cell.run(weights.load_config(entry["config"]),
                   traffic.load_mix(entry["traffic"]),
                   name=entry["config"], seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace), e2e=e2e,
                   per_layer=per_layer, chips=entry["chips"],
                   t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
