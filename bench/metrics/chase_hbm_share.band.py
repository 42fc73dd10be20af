"""Stage 2's share of the chip's HBM bandwidth over one whole reduction (%).

The bytes are the paper's cache-less chase traffic for the configuration's
(n, bw), counted by ``bench/work.py`` with tw = bw - 1, whatever the
program's own tile width or fuse depth.  The time is the device's busy time
in the traced window divided by the reductions it held, so it covers stage
2 and stage 3 and every op that implements them.  The share can only
understate stage 2, and exceeds 100 % only if a reduction took less than
bytes / peak (1.0 ms at n = 1024, bw = 32 on a v5e).
"""

from bench import work


def read(run):
    trace, count = run.get("trace"), run["readings"].get("reductions")
    if not trace or not count or trace["busy_s"] <= 0:
        return None
    cfg = run["config"]
    least_s = (work.chase_bytes(cfg["n"], cfg["bw"], cfg["dtype"])
               / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (trace["busy_s"] / count)
