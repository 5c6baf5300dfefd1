"""Readings that set a serving cell's limit: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds <s>

For each seed, one run of the cell as `run.py` makes it (set-up, window,
sample), then the widest served gap against the plain reference (the
program's reading) and, for the control seeds, the control's reading: at
the same rows, the reference's gap of the token a float8 reference ranks
first; such a run is judged on the control's reading (`correct` must come
out false). The limit lies between the largest program reading and the
smallest control reading (PERF.md gives both). The benchmark's own runs
never run the control. One process serves every seed; it needs the
cell's chips, like run.py.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as R  # bench/ is this script's directory


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    entry, _, e2e, _ = R.cell_entry(args.workload)
    sys.path[:0] = [str(R.ROOT / "src"), str(R.ROOT)]
    R.chips_or_exit(entry["chips"])
    R.use_compile_cache()
    from bench import cell, traffic, weights

    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = cell.run(weights.load_config(entry["config"]),
                       traffic.load_mix(entry["traffic"]),
                       name=entry["config"], seed=seed,
                       seconds=args.seconds, trace=False, e2e=e2e,
                       per_layer=[], chips=entry["chips"],
                       control=seed in ctl_seeds)
        c = seed in ctl_seeds
        row = {"seed": seed, "program": out["program_gap"] if c
               else out["checks"]["served_gap"]["value"],
               "control": out["checks"]["served_gap"]["value"] if c
               else None, "correct": out["correct"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    prog = [r["program"] for r in rows]
    ctl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"workload": args.workload, "lower": max(prog),
                      "upper": min(ctl) if ctl else None,
                      "program": prog, "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
