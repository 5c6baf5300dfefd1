"""The knee of an open-loop cell: one process, one set-up, the cell's mix
served at each of several rates in turn.

    python3 bench/sweep.py --workload granite-chat --seed <n> \\
        --seconds 30 --rates 2,3,4,5

For each rate it prints one JSON line: the requests due in the window,
how many had their first token by the close, the backlog (due and not
started) at the middle and at the close of the window, and every serving
metric of endtoend.py. The knee is the highest rate whose backlog does not
grow over the window. A cell's rate is set from it once (PERF.md); the
benchmark's own runs never sweep. Needs the cell's chips, like run.py.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

import run as R  # bench/ is this script's directory


def backlog(reqs, t: float) -> int:
    """Requests due by `t` without a first token by `t`."""
    return sum(r.arrival_time <= t and (r.t_first_token is None
                                        or r.t_first_token > t)
               for r in reqs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    entry = R.cell_entry(args.workload)[0]
    sys.path[:0] = [str(R.ROOT / "src"), str(R.ROOT)]
    R.chips_or_exit(entry["chips"])
    R.use_compile_cache()
    from bench import endtoend, traffic, weights
    from bench import serve as S

    conf = weights.load_config(entry["config"])
    mix = traffic.load_mix(entry["traffic"])
    if mix["arrivals"]["process"] != "poisson":
        raise SystemExit("a sweep needs an open-loop (poisson) mix")
    sz = weights.sizes(conf)
    eng = mix["engine"]
    mixes = {}
    for r in (float(x) for x in args.rates.split(",")):
        mixes[r] = copy.deepcopy(mix)
        mixes[r]["arrivals"]["rate_per_s"] = r
    specs = {r: traffic.generate(m, sz["V"], args.seed, args.seconds)
             for r, m in mixes.items()}
    params = weights.make_weights(conf, args.seed)
    engine = S.make_engine(weights.arch_config(conf, entry["config"]),
                           params, eng)
    S.warm_up(engine, S.warm_lengths([len(s.prompt) for v in specs.values()
                                      for s in v]), sz["V"],
              eng["sync_every"])
    for r, sp in specs.items():
        sched, reqs, summary, wall = S.run_window(engine, sp, args.seconds,
                                                  None)
        ctx = {"seconds": args.seconds, "sched": sched, "reqs": reqs}
        due = [q for q in reqs if q.arrival_time < args.seconds]
        row = {"rate_per_s": r, "due": len(due),
               "started": sum(q.t_first_token is not None for q in due),
               "backlog_mid": backlog(reqs, args.seconds / 2),
               "backlog_close": backlog(reqs, args.seconds),
               "serve_wall_s": wall, "prefill_s": summary["prefill_s"],
               "decode_s": summary["decode_s"]}
        for name, metric in endtoend.METRICS.items():
            row[name] = metric(ctx)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
