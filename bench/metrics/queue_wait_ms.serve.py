"""Mean time a request waited in the engine's queue before its dispatch,
over the window (ms): the engine's own queue-age record
(``ServeMetrics.snapshot()["latency"]["queue_age"]``, one observation per
request at dispatch), taken as the difference of its sums at the window's
start and end."""


def read(run):
    snaps = run["readings"].get("serve_metrics")
    if not snaps:
        return None
    a, b = (snaps[k]["latency"]["queue_age"] for k in ("start", "end"))
    count = b.get("count", 0) - a.get("count", 0)
    if count <= 0:
        return None
    total = (b["count"] * b["mean_ms"]
             - a.get("count", 0) * a.get("mean_ms", 0.0))
    return total / count
