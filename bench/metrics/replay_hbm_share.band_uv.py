"""The tape replay's share of the chip's HBM bandwidth over one whole
reduction (%).

The bytes are the cache-less replay traffic for the configuration's
(n, bw), counted by ``bench/work_uv.py`` with tw = bw - 1 (8.85 GB at
n = 1024, bw = 32: 10.8 ms at 819 GB/s).  The time is the device's busy
time in the traced window divided by the reductions it held: all of a
reduction, stage 2, the replay, stage 3 and compose, as
``chase_hbm_share.band`` divides by.  So the share can only understate the
replay, and a later replay that keeps U^T and V^T on chip can never read
above 100 %.  None without a trace, as on an untraced run.
"""

from bench import work_uv


def read(run):
    trace, count = run.get("trace"), run["readings"].get("reductions")
    if not trace or not count or trace["busy_s"] <= 0:
        return None
    cfg = run["config"]
    least_s = (work_uv.replay_bytes(cfg["n"], cfg["bw"], cfg["dtype"])
               / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (trace["busy_s"] / count)
