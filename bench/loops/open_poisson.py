"""Open loop of independent clients: requests sent on a fixed schedule.

Traffic keys:

- ``rate``: offered requests per second; ``rate * --seconds`` requests
  (rounded) are due in the window;
- ``schedule_seed``: the seed of the gaps between arrivals (a Poisson
  process given its count: sorted uniform times over the window), which
  are the same set in every run; ``--seed`` orders them;
- ``sizes``: ``[{"n", "bw", "weight"}, ...]``; each size class gets its
  share of the requests exactly (largest remainder);
- ``compute_uv_share``: the share of each class's requests that ask for
  U and V^T as well;
- ``trace_seconds``: how much of the schedule a traced run sends;
- ``check_sample``: how many answers, drawn from the seed, are compared
  with the reference (all of them when it is absent).

The run's ``--seed`` decides the order of the gaps, which request is sent
at which time and the matrices' values (standard normal), so every seed
offers the same work on its own schedule.  Configuration keys: ``engine``
(keyword arguments of ``repro.serve.AsyncSVDEngine``), ``dtype``,
``limits``, and ``path``: the engine tiers and kernel backends the timed
path has to run on.

Set-up builds the requests, starts the engine and sends two rounds of one
request per bucket (compile, from the persistent cache after the first
run, then a steady dispatch).  In the window each request is sent when it
is due, on an absolute schedule, so a late send does not delay the
next.  A request's latency runs from its due time to the resolution of its
future; one that fails or never resolves (within a minute of the
window's close) counts as infinitely late.  ``served_rate`` is the
requests answered over the time from the window's start to the last
answer, less those of the compared sample that were wrong.  Afterwards the
compared answers (every one, or the seeded sample) are checked against the
float64 reference: sigma against LAPACK's, U and V^T by residual and
orthogonality; a request that was never answered fails ``missing``.  The
engine's own counters, read at the window's start and end, have to show
that every answer came from the timed path: no request served on the
degraded fallback, no retry, no bucket quarantined, no shard re-sent, and
no dispatch on a tier or backend outside the configuration's ``path``.
"""

from __future__ import annotations

import concurrent.futures
import functools
import gc
import time

import numpy as np

from bench import reference
from bench.harness import (BenchError, CompileCounter, Context, check,
                           memory_peak_bytes, nearest_rank)
from bench.trace import WINDOW, WindowTrace

GRACE_S = 60.0       # how long past the window's close an answer may come


def largest_remainder(weights, total: int) -> list[int]:
    """Integer counts summing to ``total`` in proportion to ``weights``."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * total
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:total - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def schedule(traffic: dict, seconds: float, rng):
    """(due times in seconds from the window's start, request kinds
    ``(n, bw, compute_uv)`` in canonical order).  The gaps between arrivals
    are the same set for every seed, sent in the order ``rng`` draws, so
    the last request is due at the same time in every run."""
    total = max(1, round(traffic["rate"] * seconds))
    due = np.sort(np.random.default_rng(traffic["schedule_seed"]).uniform(
        0.0, seconds, total))
    due = np.cumsum(np.diff(due, prepend=0.0)[rng.permutation(total)])
    sizes = traffic["sizes"]
    kinds = []
    share = traffic.get("compute_uv_share", 0.0)
    for cls, count in zip(sizes, largest_remainder(
            [c["weight"] for c in sizes], total)):
        uv = round(count * share)
        kinds += [(cls["n"], cls["bw"], True)] * uv
        kinds += [(cls["n"], cls["bw"], False)] * (count - uv)
    return due, kinds


def path_checks(config: dict, start: dict, end: dict) -> dict:
    """Checks, each with the limit 0, that the window's answers came from
    the timed path, from the engine's counters at the window's start and
    end: requests served on the degraded fallback, primary-path retries,
    buckets quarantined at the end, mesh shards re-sent, and dispatches on
    a tier or in a bucket whose backend is outside the configuration's
    ``path``."""
    path = config["path"]
    off_tier = sum(row["batches"] - start["tiers"].get(t, {}).get("batches", 0)
                   for t, row in end["tiers"].items()
                   if t not in path["tiers"])
    off_backend = sum(b["backend"] not in path["backends"]
                      for b in end["bucket_tiers"].values())
    return {name: check(value, 0) for name, value in (
        ("degraded", end["degraded"] - start["degraded"]),
        ("retried", end["retried"] - start["retried"]),
        ("quarantined", len(end["quarantined_buckets"])),
        ("sharded_retries", end["sharded_retries"] - start["sharded_retries"]),
        ("off_path", off_tier + off_backend))}


def run(ctx: Context) -> dict:
    import jax
    from repro.serve import AsyncSVDEngine, SVDRequest

    rng = np.random.default_rng(ctx.seed)
    due, kinds = schedule(ctx.traffic, ctx.seconds, rng)
    kinds = [kinds[i] for i in rng.permutation(len(kinds))]
    keep = due < ctx.window_s
    due, kinds = due[keep], [k for k, kept in zip(kinds, keep) if kept]
    mats = [rng.standard_normal((n, n), dtype=np.float32)
            for n, _bw, _uv in kinds]
    feed_dtype = np.dtype(ctx.dtype)
    reqs = [SVDRequest(uid=i, matrix=m.astype(feed_dtype), bw=bw,
                       compute_uv=uv)
            for i, (m, (_n, bw, uv)) in enumerate(zip(mats, kinds))]

    eng = AsyncSVDEngine(**ctx.config.get("engine", {}))
    try:
        for _round in range(2):
            warm = [eng.submit(SVDRequest(
                uid=-1, bw=bw, compute_uv=uv, matrix=rng.standard_normal(
                    (n, n), dtype=np.float32).astype(feed_dtype)))
                for n, bw, uv in sorted(set(kinds))]
            for f in warm:
                if f.exception() is not None:
                    raise BenchError(f"warm-up request failed: "
                                     f"{f.exception()!r}")

        sent = np.zeros(len(reqs))
        done_at = np.full(len(reqs), np.inf)

        def on_done(i, _fut):
            done_at[i] = time.perf_counter()

        # The requests made in set-up stay alive through the window: keep
        # the collector from walking them again and again in it.
        gc.collect()
        gc.freeze()
        capture = WindowTrace(ctx.trace, chips=len(ctx.devices))
        capture.start()
        with CompileCounter() as compiles, \
                jax.profiler.TraceAnnotation(WINDOW):
            snap0 = eng.metrics.snapshot()
            t_w = time.perf_counter()
            futs = []
            for i, r in enumerate(reqs):
                delay = t_w + due[i] - time.perf_counter()
                if delay > 0:
                    with jax.profiler.TraceAnnotation("bench/sleep"):
                        time.sleep(delay)
                with jax.profiler.TraceAnnotation("bench/submit"):
                    sent[i] = time.perf_counter()
                    fut = eng.submit(r)
                fut.add_done_callback(functools.partial(on_done, i))
                futs.append(fut)
            with jax.profiler.TraceAnnotation("bench/await"):
                concurrent.futures.wait(futs, timeout=max(
                    0.0, t_w + ctx.window_s + GRACE_S - time.perf_counter()))
            snap1 = eng.metrics.snapshot()
        trace = capture.stop()
    finally:
        eng.stop(drain=False, timeout=GRACE_S)
        gc.unfreeze()
    peak = memory_peak_bytes(ctx.devices)

    answered = [f.done() and not f.cancelled() and f.exception() is None
                for f in futs]
    limits = {k: ctx.limit(k) for k in ("sigma_err", "uv_resid", "uv_orth")}
    answered_ids = [i for i, ok in enumerate(answered) if ok]
    size = min(len(answered_ids), ctx.traffic.get("check_sample",
                                                  len(answered_ids)))
    sample = sorted(rng.choice(answered_ids, size, replace=False)) \
        if size else []
    sig_err, resid, orth, wrong = [], [], [], 0
    for i in sample:
        r, m = reqs[i], mats[i]
        sig_err.append(reference.sigma_error(
            r.sigma, reference.singular_values(m)))
        right = sig_err[-1] <= limits["sigma_err"]
        if r.compute_uv:
            resid.append(reference.residual(m, r.u, r.sigma, r.vt))
            orth.append(reference.orthogonality(r.u, r.vt))
            right = (right and resid[-1] <= limits["uv_resid"]
                     and orth[-1] <= limits["uv_orth"])
        wrong += int(not right)
    good = len(answered_ids) - wrong
    missing = len(reqs) - len(answered_ids)
    checks = {"missing": check(missing, 0),
              "sigma_err": check(max(sig_err, default=float("inf")),
                                 limits["sigma_err"])}
    if resid:
        checks["uv_resid"] = check(max(resid, default=float("inf")),
                                   limits["uv_resid"])
        checks["uv_orth"] = check(max(orth, default=float("inf")),
                                  limits["uv_orth"])
    checks.update(path_checks(ctx.config, snap0, snap1))

    t_due = t_w + due
    latency = np.where(answered, done_at - t_due, np.inf)
    last = max((d for d, ok in zip(done_at, answered) if ok), default=t_w)
    return {
        "setup_s": t_w - ctx.t_start,
        "e2e": {"served_rate": good / (last - t_w) if last > t_w else 0.0,
                "latency_p95_ms": 1e3 * nearest_rank(latency, 0.95)},
        "attempted": len(reqs),
        "failed": len(reqs) - good,
        "checks": checks,
        "memory_peak_bytes": peak,
        "trace": trace,
        "readings": {"compiles_in_window": compiles.count,
                     "compared": len(sample),
                     "longest_gap_between_answers_s": float(np.max(np.diff(
                         np.sort(done_at[np.isfinite(done_at)])),
                         initial=0.0)),
                     "serve_metrics": {"start": snap0, "end": snap1},
                     "sender_lag_s": (sent - t_due).tolist(),
                     "latency_s": latency.tolist()},
    }
