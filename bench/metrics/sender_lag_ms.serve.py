"""95th percentile of how late the benchmark's own sender submitted a
request after its due time (ms), by nearest rank over the window's
requests.  A large value means the client, not the server, held the
traffic back."""

from bench.harness import nearest_rank


def read(run):
    lags = run["readings"].get("sender_lag_s")
    if not lags:
        return None
    return 1e3 * nearest_rank(lags, 0.95)
