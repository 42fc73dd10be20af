#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--control-seeds 4 5 6 ...]

Runs the cell's own loop on each seed of ``--seeds`` (the program as the
configuration states it) and of ``--control-seeds`` (the control: the
same program fed the data in the next lower precision, bfloat16 for a
float32 configuration, whose answers the limits have to refuse).  Prints
one JSON line per run with every compared number and its limit, and the
largest program reading and smallest control reading of each number.
Needs the chip, like ``bench/run.py``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as bench_run  # noqa: E402
from bench.harness import Context, passed  # noqa: E402

LOWER = {"float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    _bench, _cell, config, traffic, devices, _peak, _cache = \
        bench_run.open_cell(args.workload)
    worst: dict = {}
    for side, seeds, precision in (
            ("program", args.seeds, None),
            ("control", args.control_seeds, LOWER[config["dtype"]])):
        for seed in seeds:
            ctx = Context(workload=args.workload, seed=seed,
                          seconds=args.seconds, trace=False, config=config,
                          traffic=traffic, t_start=time.perf_counter(),
                          devices=devices,
                          precision=precision)
            out = bench_run.measure(ctx, traffic["loop"])
            print(json.dumps({"side": side, "seed": seed,
                              "dtype": ctx.dtype,
                              "correct": all(passed(c) for c in
                                             out["checks"].values()),
                              "attempted": out["attempted"],
                              "failed": out["failed"], "e2e": out["e2e"],
                              "checks": out["checks"]}), flush=True)
            for name, c in out["checks"].items():
                pick = max if side == "program" else min
                key = (side, name)
                worst[key] = pick(worst.get(key, c["value"]), c["value"])
    for (side, name), v in sorted(worst.items()):
        print(f"# {side} {'largest' if side == 'program' else 'smallest'} "
              f"{name}: {v!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
