#!/usr/bin/env python3
"""Find the highest rate a served cell sustains, by a sweep in one process.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates 50 100 200 ...

Runs the cell's open loop at each offered rate in turn (the traffic mix
as committed, with only ``rate`` replaced) and prints one JSON line per
rate: the rate offered, ``served_rate``, the p50 and p95 latency, the
median latency of the last quarter of the requests against the first
(a backlog that grows through the window shows as a ratio well above 1),
the sender's p95 lag and whether the answers were correct.  A rate is
sustained when the completions keep pace with the arrivals (served rate at
least 90 % of the offered rate; the window's last answers come a few
dispatches after its close) and no backlog grows (last-quarter median
latency at most 1.2 times the first quarter's); ``--stop`` ends the sweep
at the first rate that is not.  Needs the chip.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as bench_run  # noqa: E402
from bench.harness import Context, nearest_rank, passed  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--stop", action="store_true")
    args = ap.parse_args(argv)
    _bench, _cell, config, traffic, devices, _peak, _cache = \
        bench_run.open_cell(args.workload)
    for rate in args.rates:
        ctx = Context(workload=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=False, config=config,
                      traffic=dict(traffic, rate=rate),
                      t_start=time.perf_counter(),
                      devices=devices)
        out = bench_run.measure(ctx, traffic["loop"])
        lat = np.asarray(out["readings"]["latency_s"])
        q = max(1, len(lat) // 4)
        growth = float(np.median(lat[-q:]) / np.median(lat[:q]))
        served = out["e2e"]["served_rate"]
        sustained = bool(served >= 0.9 * rate and growth <= 1.2)
        print(json.dumps({
            "rate": rate, "requests": out["attempted"],
            "served_rate": served,
            "p50_ms": 1e3 * nearest_rank(lat, 0.5),
            "p95_ms": out["e2e"]["latency_p95_ms"],
            "last_over_first_quarter": growth,
            "sender_lag_p95_ms": 1e3 * nearest_rank(
                out["readings"]["sender_lag_s"], 0.95),
            "sustained": sustained,
            "correct": all(passed(c) for c in out["checks"].values()),
            "setup_s": out["setup_s"]}), flush=True)
        if args.stop and not sustained:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
