"""Serve-tier load generator: open-loop Poisson traffic vs the async engine.

Two measurements (DESIGN.md §12):

* **Throughput** — the same B-heavy mixed workload served two ways:
  ``serial`` (per-request values-only/full ``core.svd`` calls, the
  no-serving-tier baseline) vs ``engine`` (one ``AsyncSVDEngine`` burst,
  micro-batched into the bucketed pipeline).  The speedup is the paper's
  batching argument made service-shaped: concurrent small-matrix requests
  aggregate into the wide fused batches a single caller never forms.
  Results are cross-checked against the direct values-only path to 1e-12.

* **Latency under open-loop Poisson arrivals** — a submitter thread draws
  exponential inter-arrival gaps and NEVER waits for completions (open
  loop: arrival pressure is independent of service rate), mixed
  shape/dtype/compute_uv traffic; reports p50/p95/p99 latency, throughput,
  and the engine metrics snapshot.

CLI (the CI serve smoke step, blocking):

  PYTHONPATH=src python -m benchmarks.serve_load --smoke --json out.json

asserts zero dropped/timed-out/rejected requests and a p99 budget, and
exits non-zero on violation.  Full mode (``--check``, minutes) additionally
asserts the >= 3x engine-over-serial throughput acceptance bar.  As a
``benchmarks.run`` suite it emits the usual ``name,us_per_call,derived``
rows (us_per_call = mean per-request service/latency — the stable,
regression-gated column; percentiles ride in ``derived``).

``--chaos`` (DESIGN.md §15) re-runs the same measurement under a seeded
:class:`repro.serve.FaultPlan` — scripted + probabilistic dispatch errors
and NaN sigma corruption on the primary path — and asserts the fabric
absorbed every injected fault: ZERO client-visible failures, sigma still
within the oracle bar, p99 still within budget, and the plan actually
fired (a chaos gate that injected nothing would be a no-op gate).

Accounting is unified client-side (:func:`_client_account`): every
submitted request is classified from its FUTURE's resolution into exactly
one of ok / failed / timed_out / dropped — so the four always sum to
``submitted`` — and cross-checked against the engine's own counters
(completed / failed+rejected / timed_out), with any disagreement flagged
as ``consistent=False`` and failed by the gate.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

if __package__ in (None, ""):                 # direct script execution
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _REPO)
    sys.path.insert(0, os.path.join(_REPO, "src"))

import numpy as np

# Workload mixes: (n, bw, dtype, compute_uv, weight).  B-heavy: the dominant
# entry concentrates traffic in one Eq.-1-starved bucket so micro-batching
# has a wavefront deficit to fill (weights need not sum to 1 exactly).
SMOKE_MIX = ((24, 4, "float64", False, 0.7),
             (24, 4, "float64", True, 0.15),
             (32, 4, "float64", False, 0.15))
FULL_MIX = ((96, 8, "float64", False, 0.7),
            (96, 8, "float64", True, 0.1),
            (64, 8, "float32", False, 0.2))


def _mix_cover(mix, seed=0):
    """One request per mix entry (warms every bucket/compile exactly once)."""
    from repro.serve import SVDRequest
    rng = np.random.default_rng(seed)
    return [SVDRequest(uid=-(i + 1),
                       matrix=rng.standard_normal((n, n)).astype(dt),
                       bw=bw, compute_uv=uv)
            for i, (n, bw, dt, uv, _w) in enumerate(mix)]


def _requests(mix, count, seed=0):
    """Materialize ``count`` requests drawn from the mix, round-robin-ish
    deterministic: weights -> per-entry counts, then shuffled."""
    from repro.serve import SVDRequest
    rng = np.random.default_rng(seed)
    total_w = sum(w for *_, w in mix)
    picks = rng.choice(len(mix), size=count,
                       p=[w / total_w for *_, w in mix])
    reqs = []
    for uid, i in enumerate(picks):
        n, bw, dtype, uv, _w = mix[int(i)]
        m = rng.standard_normal((n, n)).astype(dtype)
        reqs.append(SVDRequest(uid=uid, matrix=m, bw=bw, compute_uv=uv))
    return reqs


def _tune_bucket_cache(mix, *, backend="ref", seed=0):
    """Batch-axis autotune for every bucket in the mix (DESIGN.md §11).

    Full (non-smoke) mode only: searches ``(tw, batch)`` including
    the batch axis for each distinct ``(n, bw, dtype, uv)`` and persists
    the winners to one throwaway cache file; the engine then consumes it
    via ``autotune=True`` — the measured ``max_batch`` replaces the Eq.-1
    analytic bucket default, exactly the serve-tier integration the tuned
    cache exists for.
    """
    import tempfile
    from repro.autotune import cache as at_cache
    from repro.autotune import model as at_model
    from repro.autotune import run_search

    path = os.path.join(tempfile.mkdtemp(prefix="serve-load-at-"),
                        "cache.json")
    bests = []
    for n, bw, dtype, uv, _w in mix:
        res = run_search(n, bw, dtype=np.dtype(dtype), backend=backend,
                         compute_uv=uv, top_k=2,
                         batches=(4, 8, 16), iters=1, seed=seed)
        at_cache.store(res.to_entry(), device_kind=at_model.device_kind(),
                       n=n, bw=bw, dtype=np.dtype(dtype).name,
                       compute_uv=uv, backend=backend, path=path)
        bests.append(res.best)
    return path, bests


def _client_account(reqs, done_at, errors, snap):
    """Client-view accounting, unified for both drivers (DESIGN.md §15).

    Classifies every submitted request from its future's resolution into
    EXACTLY one of ``ok`` / ``failed`` / ``timed_out`` / ``dropped``, so
    the identity ``ok + failed + timed_out + dropped == submitted`` holds
    by construction.  The engine's own counters are a different view of
    the same run (admission rejections resolve the future but never reach
    ``_finish``, so they count ``rejected`` there and ``failed`` here);
    ``consistent`` is the cross-check that the two views describe the
    same requests:

    * client ``ok``        == engine ``completed``
    * client ``timed_out`` == engine ``timed_out``
    * client ``failed``    == engine ``failed`` + ``rejected``
    * client ``dropped``   == submitted - every engine-finished request

    The pre-fix bug this replaces: ``poisson_run`` reported the engine's
    ``failed`` next to a future-view ``dropped``, so an admission-rejected
    request was invisible in both columns and the totals did not add up.
    """
    ok = failed = timed_out = 0
    for r in reqs:
        if r.uid not in done_at:
            continue                              # dropped: never resolved
        exc = errors.get(r.uid)
        if exc is None:
            ok += 1
        elif isinstance(exc, TimeoutError):
            timed_out += 1
        else:
            failed += 1
    submitted = len(reqs)
    dropped = submitted - len([r for r in reqs if r.uid in done_at])
    engine_finished = (snap["completed"] + snap["failed"]
                       + snap["timed_out"] + snap["rejected"])
    return {
        "submitted": submitted, "ok": ok, "failed": failed,
        "timed_out": timed_out, "dropped": dropped,
        "consistent": (ok == snap["completed"]
                       and timed_out == snap["timed_out"]
                       and failed == snap["failed"] + snap["rejected"]
                       and dropped == submitted - engine_finished),
    }


def _serial_serve(reqs, cfgs):
    """The no-serving-tier baseline: one pipeline call per request."""
    import jax.numpy as jnp
    from repro.core import svd as svdmod
    out = []
    for r in reqs:
        cfg = cfgs[r.key()]
        m = jnp.asarray(r.matrix)
        if r.compute_uv:
            u, sig, vt = svdmod.svd(m, config=cfg, compute_uv=True)
            out.append(np.asarray(sig))
        else:
            out.append(np.asarray(svdmod.svd_batched(m[None], config=cfg)[0]))
    return out


def _engine_cfgs(eng, reqs):
    """Resolve (and memoize) every bucket config once, serial-compatible."""
    return {key: eng._cfg_for(key) for key in {r.key() for r in reqs}}


def throughput_compare(mix, count, *, backend="ref", seed=0, window_s=0.002,
                       autotune_cache=None, fused_n_max=None, dc_n_min=None,
                       faults=None, tracer=None):
    """Serial vs micro-batched engine throughput on an identical workload.

    Returns ``(rows, result)`` — CSV rows plus a dict with the speedup and
    the max |sigma - direct values-only sigma| cross-check.  With
    ``autotune_cache`` (see :func:`_tune_bucket_cache`) the engine buckets
    at the MEASURED per-bucket optimum instead of the analytic default;
    the serial baseline resolves through the same configs, so the speedup
    isolates batching, not knob differences.  ``faults`` (a seeded
    ``repro.serve.FaultPlan``, the ``--chaos`` path) is injected into the
    ENGINE only — the serial baseline stays the clean oracle the engine's
    fault-absorbed answers are checked against.
    """
    from benchmarks.common import row
    from repro.core import svd as svdmod
    from repro.serve import AsyncSVDEngine, ServeMetrics
    import jax.numpy as jnp

    reqs_serial = _requests(mix, count, seed)
    reqs_engine = _requests(mix, count, seed)      # same matrices, fresh reqs
    eng = AsyncSVDEngine(backend=backend, batch_window_s=window_s,
                         autotune=autotune_cache is not None,
                         autotune_cache=autotune_cache,
                         max_batch=32 if autotune_cache else None,
                         fused_n_max=fused_n_max, dc_n_min=dc_n_min,
                         faults=faults, tracer=tracer)
    cfgs = _engine_cfgs(eng, reqs_engine)

    # Warm every compiled program OUTSIDE the timed windows (bucket-capacity
    # batch for the engine, B=1 for the serial path) — one request per mix
    # entry so no bucket compiles inside a measurement.
    warm = _mix_cover(mix, seed + 1)
    _serial_serve(warm, _engine_cfgs(eng, warm))
    [f.result() for f in [eng.submit(r) for r in _mix_cover(mix, seed + 2)]]
    eng.metrics = ServeMetrics()         # report the timed burst, not warmup

    t0 = time.monotonic()
    serial_sig = _serial_serve(reqs_serial, cfgs)
    t_serial = time.monotonic() - t0

    t0 = time.monotonic()
    futs = [eng.submit(r) for r in reqs_engine]    # open-loop burst
    done, errors = [], {}
    for r, f in zip(reqs_engine, futs):
        try:
            done.append(f.result())
        except Exception as exc:                   # noqa: BLE001 — report,
            done.append(None)                      # don't abort the harness
            errors[r.uid] = exc
    t_engine = time.monotonic() - t0
    eng.stop()
    eng_failures = [repr(e) for e in errors.values()]

    # Correctness at equal precision: engine sigma vs the direct
    # values-only path on the same matrices.  The 1e-12 acceptance bar
    # applies at fp64; fp32 buckets are served at fp32 (B=1 vs B=16
    # programs may round differently at ~1e-6) and get their own bound.
    err64 = err32 = 0.0
    for r, s_direct in zip(done, serial_sig):
        if r is None:
            continue
        e = float(np.abs(np.asarray(r.sigma) - s_direct).max())
        if np.dtype(r.matrix.dtype) == np.float64:
            err64 = max(err64, e)
        else:
            err32 = max(err32, e)
    for r in done[:4]:
        if r is not None and r.compute_uv:
            cfg_vo = dataclasses.replace(cfgs[r.key()], compute_uv=False)
            s_vo = np.asarray(svdmod.svd_batched(
                jnp.asarray(r.matrix)[None], config=cfg_vo)[0])
            e = float(np.abs(np.asarray(r.sigma) - s_vo).max())
            if np.dtype(r.matrix.dtype) == np.float64:
                err64 = max(err64, e)
            else:
                err32 = max(err32, e)

    snap = eng.metrics.snapshot()
    speedup = t_serial / t_engine
    tag = f"x{count}"
    rows = [
        row(f"serve_load/serial/{tag}", t_serial / count * 1e6,
            f"mats_per_s={count / t_serial:.2f}"),
        row(f"serve_load/engine/{tag}", t_engine / count * 1e6,
            f"mats_per_s={count / t_engine:.2f};speedup={speedup:.2f}x;"
            f"fill={snap['batch_fill_ratio']:.2f};"
            f"batches={snap['batches']}"),
    ]
    # Unified client-view accounting (same classifier as poisson_run): a
    # burst driver resolves every future, so dropped is 0 here — but the
    # identity and the engine cross-check are asserted all the same.
    acct = _client_account(reqs_engine,
                           {r.uid: True for r in reqs_engine}, errors, snap)
    return rows, {"t_serial_s": t_serial, "t_engine_s": t_engine,
                  "speedup": speedup, "sigma_max_err": err64,
                  "sigma_max_err_f32": err32,
                  "engine_failures": eng_failures,
                  "accounting": acct,
                  "engine_metrics": snap}


def poisson_run(mix, count, rate, *, backend="ref", seed=0, window_s=0.005,
                timeout_s=None, autotune_cache=None, fused_n_max=None,
                dc_n_min=None, faults=None, tracer=None, metrics_server=None):
    """Open-loop Poisson arrivals at ``rate`` req/s; per-request latency.

    Returns ``(rows, result)``; ``result`` carries the latency percentiles,
    achieved throughput, the unified client-view accounting
    (:func:`_client_account` — ok/failed/timed_out/dropped summing to
    submitted, cross-checked against the engine counters), and the engine
    metrics snapshot the smoke gate asserts on (every request must
    COMPLETE: served or failed with an error on the request — never
    silently dropped).  ``faults`` injects a ``repro.serve.FaultPlan``
    into the engine's primary path (the ``--chaos`` gate).

    Latency percentiles are HISTOGRAM-driven (DESIGN.md §16): each
    successful completion streams its client-view latency into a
    fixed-log-bucket :class:`repro.obs.StreamingHistogram` inside the
    future callback — the reported p50/p95/p99 come from the histogram,
    not a raw-sample array.  A shadow list of exact samples is kept ONLY
    for the smoke gate's cross-check (``latency_exact_ms``), which asserts
    the histogram percentiles land within one bucket width of numpy's
    exact ones.  ``tracer`` (a :class:`repro.obs.Tracer`) threads into the
    engine for dispatch/retry/degraded spans; ``metrics_server`` (a
    :class:`repro.obs.MetricsServer`) gets the live engine metrics
    registered under ``"svd"`` before traffic starts, so the run is
    scrapeable while in flight.
    """
    from benchmarks.common import row
    from repro.obs import StreamingHistogram
    from repro.serve import AsyncSVDEngine, ServeMetrics

    rng = np.random.default_rng(seed + 7)
    reqs = _requests(mix, count, seed)
    eng = AsyncSVDEngine(backend=backend, batch_window_s=window_s,
                         default_timeout_s=timeout_s,
                         autotune=autotune_cache is not None,
                         autotune_cache=autotune_cache,
                         max_batch=32 if autotune_cache else None,
                         fused_n_max=fused_n_max, dc_n_min=dc_n_min,
                         faults=faults, tracer=tracer)
    # Warm every bucket's compile outside the timed run (never under the
    # engine's default deadline — compiles take seconds).
    [f.result() for f in [eng.submit(r, timeout_s=float("inf"))
                          for r in _mix_cover(mix, seed + 1)]]
    eng.metrics = ServeMetrics()         # report the timed run, not warmup
    if metrics_server is not None:
        metrics_server.register("svd", eng.metrics)

    done_at: dict[int, float] = {}
    errors: dict[int, Exception] = {}
    hist = StreamingHistogram()              # client-view latency, seconds
    exact_s: list[float] = []                # shadow samples (smoke check)
    ev = threading.Event()

    def _cb(req):
        def cb(fut):
            now = time.monotonic()
            done_at[req.uid] = now
            exc = fut.exception()
            if exc is not None:
                errors[req.uid] = exc
            elif req.arrived is not None:
                # Successful only — admission rejections never reach
                # _finish, so their req.error stays None while the future
                # carries the exception; counting them would skew the
                # percentiles low.
                lat = now - req.arrived
                hist.add(lat)
                exact_s.append(lat)
            if len(done_at) == count:
                ev.set()
        return cb

    gaps = rng.exponential(1.0 / rate, count)
    t0 = time.monotonic()
    for r, gap in zip(reqs, gaps):
        time.sleep(gap)                          # open loop: never waits
        eng.submit(r).add_done_callback(_cb(r))
    ev.wait(timeout=600)
    t_total = time.monotonic() - t0
    eng.stop()

    snap = eng.metrics.snapshot()
    lat = hist.summary()                     # histogram-driven percentiles
    # Client-view accounting (the unified classifier shared with
    # throughput_compare): ok + failed + timed_out + dropped == submitted,
    # with the engine-counter cross-check in acct["consistent"].
    acct = _client_account(reqs, done_at, errors, snap)
    result = {
        "requests": count, "rate_rps": rate,
        "completed": acct["ok"], "failed": acct["failed"],
        "timed_out": acct["timed_out"],
        "rejected": int(snap["rejected"]),
        "dropped": acct["dropped"],              # future never resolved
        "accounting": acct,
        "throughput_rps": hist.count / t_total if t_total > 0 else 0.0,
        "latency_ms": {"p50": lat["p50_ms"], "p95": lat["p95_ms"],
                       "p99": lat["p99_ms"], "mean": lat["mean_ms"],
                       "max": lat["max_ms"]},
        "latency_hist": hist.to_dict(),
        "latency_exact_ms": sorted(v * 1e3 for v in exact_s),
        "latency_bucket_ratio": hist.bucket_width_ratio(),
        "engine_metrics": snap,
    }
    # Gated column = per-request service interval from achieved THROUGHPUT
    # (stable across hosts); queueing latency diverges nonlinearly near
    # saturation under open-loop arrivals, so the percentiles ride in
    # ``derived`` where the regression gate never reads them.
    svc_us = (1e6 / result["throughput_rps"] if result["throughput_rps"]
              else 0.0)
    lm = result["latency_ms"]
    rows = [row(f"serve_load/poisson_thpt/x{count}@r{rate:g}", svc_us,
                f"p50={lm['p50']:.1f}ms;p95={lm['p95']:.1f}ms;"
                f"p99={lm['p99']:.1f}ms;"
                f"mean={lm['mean']:.1f}ms;"
                f"thpt={result['throughput_rps']:.1f}rps;"
                f"timed_out={result['timed_out']};"
                f"fill={snap['batch_fill_ratio']:.2f}")]
    return rows, result


def _dc_tier_smoke(*, backend="ref", seed=0):
    """Stage-3 D&C routing check for the smoke gate (DESIGN.md §14).

    The smoke mix is all small-n (fused-tier territory), so the D&C tier
    would never fire there; this runs a tiny dedicated burst with the
    fused tier off and the crossover pinned to 1 (``fused_n_max=0,
    dc_n_min=1``) — every staged bucket MUST route "staged-dc", and the
    served sigma must agree with ``numpy.linalg.svd`` to 1e-12 relative.
    Returns a list of failure strings (empty = pass).
    """
    from repro.serve import SVDEngine, SVDRequest

    rng = np.random.default_rng(seed + 11)
    eng = SVDEngine(backend=backend, fused_n_max=0, dc_n_min=1)
    mats = [rng.standard_normal((n, n)) for n in (24, 24, 48)]
    for i, m in enumerate(mats):
        eng.submit(SVDRequest(uid=i, matrix=m, bw=4))
    done = {r.uid: r for r in eng.run()}
    failures = []
    snap = eng.metrics.snapshot()
    for key, info in snap.get("bucket_tiers", {}).items():
        if info["tier"] != "staged-dc":
            failures.append(f"dc smoke: bucket {key} served on "
                            f"{info['tier']!r}, expected 'staged-dc'")
    if not snap.get("tiers", {}).get("staged-dc", {}).get("batches"):
        failures.append("dc smoke: no staged-dc dispatches recorded")
    for i, m in enumerate(mats):
        r = done.get(i)
        if r is None or r.error is not None:
            failures.append(f"dc smoke: request {i} failed: "
                            f"{r.error if r else 'missing'}")
            continue
        ref = np.linalg.svd(m, compute_uv=False)
        err = float(np.abs(np.asarray(r.sigma) - ref).max() / ref.max())
        if err > 1e-12:
            failures.append(f"dc smoke: sigma disagrees with LAPACK by "
                            f"{err:.2e} rel > 1e-12 (n={m.shape[0]})")
    return failures


def multihost_run(mix, count, rate, *, hosts=2, backend="ref", seed=0,
                  window_ms=25.0, timeout_s=None, kill_host=False,
                  jax_distributed=False, host_devices=0, snap_prefix=""):
    """Open-loop Poisson traffic through :class:`repro.serve.SVDRouter`
    over ``hosts`` real worker PROCESSES (DESIGN.md §17).

    The router lives in this process; each worker is a
    ``python -m repro.serve.worker`` subprocess running its own
    ``AsyncSVDEngine`` (optionally with ``host_devices`` forced host
    devices, optionally joined into one multi-process jax via
    ``jax_distributed`` — never combined with ``kill_host``: a killed
    peer fatally cascades through the XLA coordination service, which is
    exactly why the fabric's multi-processness lives at the socket
    level).

    ``kill_host`` SIGKILLs the worker that owns the dominant mix bucket
    immediately after a request for that bucket is submitted (the engine
    micro-batch window guarantees it is still in flight), exercising the
    full drop path: reader EOF -> host quarantine -> in-flight requeue to
    the survivor -> every future still resolves.  Warmup broadcasts every
    bucket to every host first, so requeued work never pays a compile.

    Returns ``(rows, result)``: the same client-view accounting identity
    as :func:`poisson_run` (ok + failed + timed_out + dropped ==
    submitted, cross-checked against the ROUTER's counters), the fp64
    sigma oracle error vs ``numpy.linalg.svd``, and the fleet view whose
    merged histogram the gate checks against pooled exact samples.  With
    ``snap_prefix`` the per-host engine snapshots and the fleet view are
    written as ``{prefix}.host-{id}.json`` / ``{prefix}.fleet.json`` (the
    CI artifacts).
    """
    from benchmarks.common import row
    from repro.obs import StreamingHistogram
    from repro.serve import SVDRouter
    from repro.serve.worker import check_fleet_fits, spawn_worker_process

    check_fleet_fits(hosts)
    if kill_host and jax_distributed:
        raise ValueError("kill_host + jax_distributed: a SIGKILLed peer "
                         "fatally cascades through the XLA coordination "
                         "service (DESIGN.md §17)")
    rng = np.random.default_rng(seed + 7)
    reqs = _requests(mix, count, seed)
    router = SVDRouter(heartbeat_s=0.25, heartbeat_timeout_s=2.0,
                       default_timeout_s=timeout_s)
    coordinator = ""
    if jax_distributed:
        import socket
        with socket.socket() as s:               # free rendezvous port
            s.bind(("127.0.0.1", 0))
            coordinator = f"127.0.0.1:{s.getsockname()[1]}"
    procs = {
        f"w{i}": spawn_worker_process(
            router.address, f"w{i}", backend=backend, window_ms=window_ms,
            devices=host_devices,
            coordinator=coordinator,
            num_processes=hosts if coordinator else 0,
            process_id=i if coordinator else -1)
        for i in range(hosts)}
    victim = None
    artifacts = []
    try:
        if not router.wait_for_hosts(hosts, timeout=240):
            raise RuntimeError(f"only {len(router.alive_hosts())}/{hosts} "
                               f"worker hosts connected")
        # Broadcast-warm every bucket on EVERY host (requeued requests
        # must never pay a compile), then report only the timed window.
        router.warm(_mix_cover(mix, seed + 1))
        router.reset_stats()

        done_at: dict[int, float] = {}
        errors: dict[int, Exception] = {}
        results: dict[int, object] = {}
        hist = StreamingHistogram()          # client-view shadow histogram
        exact_s: list[float] = []            # pooled exact samples (gate)
        ev = threading.Event()

        def _cb(req):
            def cb(fut):
                now = time.monotonic()
                done_at[req.uid] = now
                exc = fut.exception()
                if exc is not None:
                    errors[req.uid] = exc
                else:
                    results[req.uid] = fut.result()
                    lat = now - req.arrived
                    hist.add(lat)
                    exact_s.append(lat)
                if len(done_at) == count:
                    ev.set()
            return cb

        kill_after = int(count * 0.4) if kill_host else count + 1
        if kill_host:
            n0, bw0, dt0, uv0, _w = mix[0]
            victim = router.owner_of((n0, bw0, dt0, False, uv0))
        gaps = rng.exponential(1.0 / rate, count)
        t0 = time.monotonic()
        killed = False
        for idx, (r, gap) in enumerate(zip(reqs, gaps)):
            time.sleep(gap)                      # open loop: never waits
            router.submit(r).add_done_callback(_cb(r))
            if (not killed and idx + 1 >= kill_after and victim is not None
                    and router.owner_of(r.key()) == victim):
                # SIGKILL right behind a victim-owned submit: the worker's
                # micro-batch window still holds it, so the drop path has
                # guaranteed in-flight work to requeue.
                procs[victim].kill()
                killed = True
        ev.wait(timeout=600)
        t_total = time.monotonic() - t0

        host_stats = router.collect_host_stats()
        fleet = router.fleet()
        snap = fleet["router"]
        acct = _client_account(reqs, done_at, errors, snap)
        err64 = err32 = 0.0                      # sigma oracle, ALL results
        for r in reqs:
            res = results.get(r.uid)
            if res is None:
                continue
            ref = np.linalg.svd(r.matrix.astype(np.float64),
                                compute_uv=False)
            e = float(np.abs(np.asarray(res.sigma, dtype=np.float64)
                             - ref).max() / ref.max())
            if np.dtype(r.matrix.dtype) == np.float64:
                err64 = max(err64, e)
            else:
                err32 = max(err32, e)
        merged = fleet["latency"]["merged_summary"]
        if snap_prefix:
            for hid, payload in sorted(host_stats.items()):
                path = f"{snap_prefix}.host-{hid}.json"
                with open(path, "w") as f:
                    json.dump(payload, f, indent=2, sort_keys=True)
                artifacts.append(path)
            path = f"{snap_prefix}.fleet.json"
            with open(path, "w") as f:
                json.dump(fleet, f, indent=2, sort_keys=True)
            artifacts.append(path)
        result = {
            "hosts": hosts, "requests": count, "rate_rps": rate,
            "kill_host": bool(kill_host), "victim": victim,
            "victim_returncode": (procs[victim].poll()
                                  if victim is not None else None),
            "jax_distributed": bool(jax_distributed),
            "completed": acct["ok"], "failed": acct["failed"],
            "timed_out": acct["timed_out"],
            "rejected": int(snap["rejected"]),
            "dropped": acct["dropped"], "accounting": acct,
            "throughput_rps": hist.count / t_total if t_total > 0 else 0.0,
            "sigma_max_rel_err": err64, "sigma_max_rel_err_f32": err32,
            "latency_ms": {"p50": merged["p50_ms"], "p95": merged["p95_ms"],
                           "p99": merged["p99_ms"],
                           "mean": merged["mean_ms"],
                           "max": merged["max_ms"]},
            "latency_exact_ms": sorted(v * 1e3 for v in exact_s),
            "latency_bucket_ratio": fleet["latency"]["bucket_ratio"],
            "fleet": fleet,
            "host_stats_collected": sorted(host_stats),
            "artifacts": artifacts,
        }
    finally:
        router.stop()
        for p in procs.values():
            try:
                p.wait(timeout=30)
            except Exception:                    # noqa: BLE001 — cleanup
                p.kill()
    lm = result["latency_ms"]
    tag = (f"x{count}@h{hosts}"
           + ("+kill" if kill_host else "")
           + ("+dist" if jax_distributed else ""))
    svc_us = (1e6 / result["throughput_rps"] if result["throughput_rps"]
              else 0.0)
    rows = [row(f"serve_load/multihost/{tag}", svc_us,
                f"p50={lm['p50']:.1f}ms;p95={lm['p95']:.1f}ms;"
                f"p99={lm['p99']:.1f}ms;"
                f"thpt={result['throughput_rps']:.1f}rps;"
                f"retried={snap['retried']};"
                f"alive={len(fleet['alive_hosts'])}/{hosts}")]
    return rows, result


def main_multihost(args) -> None:
    """The ``--hosts N`` driver + blocking gate (the CI multihost step)."""
    mix = SMOKE_MIX if args.smoke else FULL_MIX
    count = args.requests or (24 if args.smoke else 96)
    rate = args.rate or (120.0 if args.smoke else 60.0)
    p99_budget = args.p99_ms or (8000.0 if args.smoke else 0.0)
    prefix = ""
    if args.json:
        prefix = (args.json[:-5] if args.json.endswith(".json")
                  else args.json)

    print("name,us_per_call,derived")
    rows, res = multihost_run(
        mix, count, rate, hosts=args.hosts, backend="ref", seed=args.seed,
        kill_host=args.kill_host, jax_distributed=args.jax_distributed,
        host_devices=args.host_devices, snap_prefix=prefix)
    for line in rows:
        print(line, flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# json written to {args.json}", flush=True)
        for path in res["artifacts"]:
            print(f"# artifact written to {path}", flush=True)

    failures = []
    fleet = res["fleet"]
    snap = fleet["router"]
    # Zero client-visible failures — the headline gate: every submitted
    # request resolved ok, even with a host SIGKILLed mid-run.
    for what in ("dropped", "timed_out", "rejected", "failed"):
        if res[what]:
            failures.append(f"{res[what]} request(s) {what} (must be 0)")
    if not res["accounting"]["consistent"]:
        failures.append(f"accounting inconsistent: client view "
                        f"{res['accounting']} vs router counters {snap}")
    if res["sigma_max_rel_err"] > 1e-12:
        failures.append(f"fp64 sigma mismatch vs numpy.linalg.svd: "
                        f"{res['sigma_max_rel_err']:.2e} rel > 1e-12")
    if res["sigma_max_rel_err_f32"] > 1e-4:
        failures.append(f"fp32 sigma mismatch vs numpy.linalg.svd: "
                        f"{res['sigma_max_rel_err_f32']:.2e} rel > 1e-4")
    # Merged-histogram fidelity (DESIGN.md §16/§17): the fleet percentiles
    # come from per-host histograms folded with StreamingHistogram.merge;
    # each must land within one log-bucket width of the POOLED exact
    # samples (numpy method="higher", the histogram's rank convention).
    exact = np.asarray(res["latency_exact_ms"])
    if exact.size:
        ratio = res["latency_bucket_ratio"]
        for q in (50, 95, 99):
            e = float(np.percentile(exact, q, method="higher"))
            h = res["latency_ms"][f"p{q}"]
            if not (e / ratio <= h <= e * ratio):
                failures.append(
                    f"merged histogram p{q}={h:.3f}ms off pooled exact "
                    f"{e:.3f}ms by more than one bucket width "
                    f"(r={ratio:.3f})")
    else:
        failures.append("no exact latency samples for the merged-histogram "
                        "fidelity check")
    if args.kill_host:
        # The drop path must have actually fired: the victim died, was
        # quarantined at host granularity, and its in-flight requests were
        # requeued (retried) onto a survivor.
        if res["victim"] is None:
            failures.append("kill gate: no victim host resolved")
        elif res["victim_returncode"] is None:
            failures.append(f"kill gate: victim {res['victim']} still "
                            f"running")
        elif res["victim"] not in fleet["dead_hosts"]:
            failures.append(f"kill gate: victim {res['victim']} not in "
                            f"dead_hosts {fleet['dead_hosts']}")
        if not snap["retried"]:
            failures.append("kill gate: no requests requeued (retried=0 — "
                            "the kill landed with nothing in flight)")
        if not snap["quarantined"]:
            failures.append("kill gate: no host quarantine recorded")
        requeued = sum(h.get("requeued", 0)
                       for hid, h in snap.get("hosts", {}).items()
                       if hid != res["victim"])
        if not requeued:
            failures.append("kill gate: no survivor host attributed with "
                            "requeued work")
    if args.jax_distributed:
        # The bootstrap gate: every worker joined one multi-process jax —
        # hello-reported process counts and the global/local device split
        # must be coherent (this is the serve_mesh local-devices premise).
        infos = {h: v for h, v in fleet["hosts"].items()}
        local_total = sum(v.get("devices", 0) for v in infos.values())
        for hid, v in sorted(infos.items()):
            if v.get("processes") != args.hosts:
                failures.append(f"distributed gate: host {hid} reports "
                                f"processes={v.get('processes')} != "
                                f"{args.hosts}")
            if v.get("global_devices") != local_total:
                failures.append(f"distributed gate: host {hid} reports "
                                f"global_devices={v.get('global_devices')} "
                                f"!= sum of local devices {local_total}")
        seen_idx = sorted(v.get("process_index", -1) for v in infos.values())
        if seen_idx != list(range(args.hosts)):
            failures.append(f"distributed gate: process indices {seen_idx} "
                            f"!= 0..{args.hosts - 1}")
    if p99_budget and res["latency_ms"]["p99"] > p99_budget:
        failures.append(f"p99 latency {res['latency_ms']['p99']:.1f}ms "
                        f"> budget {p99_budget:g}ms")
    print(f"# hosts={len(fleet['alive_hosts'])}/{args.hosts} alive "
          f"victim={res['victim']} retried={snap['retried']} "
          f"sigma_err={res['sigma_max_rel_err']:.2e} "
          f"p99={res['latency_ms']['p99']:.1f}ms "
          f"dropped={res['dropped']} timed_out={res['timed_out']}",
          flush=True)
    if failures:
        for f in failures:
            print(f"# SERVE GATE FAIL: {f}", flush=True)
        sys.exit(1)
    print("# serve gate OK", flush=True)


def run(smoke: bool = False):
    """benchmarks.run suite entry: CSV rows (CI gates only us_per_call)."""
    mix = SMOKE_MIX if smoke else FULL_MIX
    count = 24 if smoke else 96
    rate = 120.0 if smoke else 60.0
    cache = None if smoke else _tune_bucket_cache(mix)[0]
    rows, _ = throughput_compare(mix, count, backend="ref",
                                 autotune_cache=cache)
    prows, _ = poisson_run(mix, count if smoke else 48, rate, backend="ref",
                           autotune_cache=cache)
    return rows + prows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, seconds-scale (the CI serve gate)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write the full latency/throughput report to PATH")
    ap.add_argument("--requests", type=int, default=0,
                    help="override the workload size")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="override the Poisson arrival rate (req/s)")
    ap.add_argument("--p99-ms", type=float, default=0.0, metavar="MS",
                    help="p99 latency budget (default: 4000 smoke / none "
                         "full)")
    ap.add_argument("--check", action="store_true",
                    help="assert the >=3x engine-over-serial acceptance bar "
                         "(implied in --smoke the bar stays off: smoke "
                         "shapes are too small to be meaningful)")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a seeded FaultPlan (scripted + 5%% dispatch "
                         "errors, 1%% NaN sigma) into the engines and assert "
                         "the fabric absorbed every fault (DESIGN.md §15)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus-format engine metrics on "
                         "127.0.0.1:PORT during the run (0 = ephemeral "
                         "port); the gate scrapes /metrics afterwards and "
                         "asserts the exposition is well-formed "
                         "(DESIGN.md §16)")
    ap.add_argument("--trace-jsonl", default="", metavar="PATH",
                    help="export engine dispatch/retry/degraded spans to "
                         "PATH as JSONL (repro.obs.Tracer; DESIGN.md §16)")
    ap.add_argument("--hosts", type=int, default=0, metavar="N",
                    help="multi-host mode (DESIGN.md §17): route the Poisson "
                         "run through repro.serve.SVDRouter over N worker "
                         "PROCESSES; gates zero client-visible failures, "
                         "the fp64 sigma oracle, and merged-histogram "
                         "fidelity across hosts")
    ap.add_argument("--kill-host", action="store_true",
                    help="[--hosts] SIGKILL the worker owning the dominant "
                         "bucket mid-run and assert the router requeued its "
                         "in-flight work with zero client-visible failures")
    ap.add_argument("--jax-distributed", action="store_true",
                    help="[--hosts] bootstrap the workers into one "
                         "multi-process jax (jax.distributed.initialize) "
                         "and assert the hello-reported process/device "
                         "topology; incompatible with --kill-host")
    ap.add_argument("--host-devices", type=int, default=0, metavar="D",
                    help="[--hosts] XLA_FLAGS-forced host device count per "
                         "worker (0: leave the workers' env alone)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.hosts >= 2:
        return main_multihost(args)

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.autotune.model import device_kind

    mix = SMOKE_MIX if args.smoke else FULL_MIX
    count = args.requests or (24 if args.smoke else 96)
    rate = args.rate or (120.0 if args.smoke else 60.0)
    p99_budget = args.p99_ms or (4000.0 if args.smoke else 0.0)

    print("name,us_per_call,derived")
    cache = None
    if not args.smoke:
        cache, bests = _tune_bucket_cache(mix, seed=args.seed)
        for (n, bw, dt, uv, _w), best in zip(mix, bests):
            print(f"# tuned bucket n={n} bw={bw} {dt} uv={int(uv)}: "
                  f"tw={best.tw} max_batch={best.batch}",
                  flush=True)
    faults_thr = faults_poi = None
    if args.chaos:
        # One plan per engine (each is stateful); scripted ordinals land
        # past the warmup dispatches (one per mix bucket) so at least one
        # dispatch error and one NaN corruption are GUARANTEED to hit the
        # measured run, on top of the probabilistic rates.
        from repro.serve import FaultPlan
        nwarm = len(mix)
        faults_thr = FaultPlan(seed=args.seed + 101,
                               dispatch_error_rate=0.05, nan_rate=0.01,
                               dispatch_errors_at=(nwarm,),
                               nan_at=(nwarm + 1,))
        faults_poi = FaultPlan(seed=args.seed + 202,
                               dispatch_error_rate=0.05, nan_rate=0.01,
                               dispatch_errors_at=(nwarm,),
                               nan_at=(nwarm + 1,))
    tracer = None
    if args.trace_jsonl:
        from repro.obs import Tracer
        tracer = Tracer("serve_load", jsonl=args.trace_jsonl)
    mserver = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        mserver = MetricsServer(port=args.metrics_port)
        print(f"# metrics endpoint: {mserver.url}", flush=True)
    t_rows, thr = throughput_compare(mix, count, backend="ref",
                                     seed=args.seed, autotune_cache=cache,
                                     faults=faults_thr, tracer=tracer)
    p_rows, poi = poisson_run(mix, max(count // 2, 12), rate, backend="ref",
                              seed=args.seed, autotune_cache=cache,
                              faults=faults_poi, tracer=tracer,
                              metrics_server=mserver)
    for line in t_rows + p_rows:
        print(line, flush=True)

    report = {
        "smoke": bool(args.smoke),
        "device_kind": device_kind(),
        "device_count": jax.device_count(),
        "jax": jax.__version__,
        "throughput": thr,
        "poisson": poi,
    }
    if args.chaos:
        report["chaos"] = {"throughput_faults": faults_thr.snapshot(),
                           "poisson_faults": faults_poi.snapshot()}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# json written to {args.json}", flush=True)

    failures = []
    for exc in thr["engine_failures"]:
        failures.append(f"engine request failed: {exc}")
    if thr["sigma_max_err"] > 1e-12:
        failures.append(f"fp64 sigma mismatch vs values-only path: "
                        f"{thr['sigma_max_err']:.2e} > 1e-12")
    if thr["sigma_max_err_f32"] > 1e-4:
        failures.append(f"fp32 sigma mismatch vs values-only path: "
                        f"{thr['sigma_max_err_f32']:.2e} > 1e-4")
    for what in ("dropped", "timed_out", "rejected", "failed"):
        if poi[what]:
            failures.append(f"{poi[what]} request(s) {what} "
                            f"(must be 0)")
    for name, res in (("throughput", thr), ("poisson", poi)):
        if not res["accounting"]["consistent"]:
            failures.append(f"{name} accounting inconsistent: client view "
                            f"{res['accounting']} vs engine counters "
                            f"{res['engine_metrics']}")
    if args.chaos:
        # The chaos gate (DESIGN.md §15): the plans must have actually
        # fired (an inert chaos run gates nothing), and everything above —
        # zero client-visible failures, the sigma oracle bar, the p99
        # budget — must STILL hold; the fault-tolerance counters show the
        # absorption happened on the fabric's retry/degraded paths.
        for name, plan in (("throughput", faults_thr), ("poisson", faults_poi)):
            snap_f = plan.snapshot()
            fired = (snap_f["dispatch_error"] + snap_f["device_loss"]
                     + snap_f["nan"] + snap_f["inf"])
            if not fired:
                failures.append(f"chaos: no faults injected into the "
                                f"{name} run ({snap_f})")
        absorbed = sum(res["engine_metrics"][k]
                       for res in (thr, poi)
                       for k in ("retried", "degraded"))
        if not absorbed:
            print("# chaos note: all injected faults landed outside the "
                  "measured window (absorbed during warmup)", flush=True)
    if args.smoke:
        # Fused-tier routing (DESIGN.md §13): every smoke-mix bucket is
        # small-n (n <= DEFAULT_FUSED_CROSSOVER), so the metrics MUST show
        # it served on the fused one-dispatch tier — this is the CI
        # assertion that the serve path actually exercises the tier, not
        # just that the backend exists.
        from repro.core.tuning import DEFAULT_FUSED_CROSSOVER
        snap = poi["engine_metrics"]
        for key, info in snap.get("bucket_tiers", {}).items():
            if info["n"] <= DEFAULT_FUSED_CROSSOVER and info["tier"] != "fused":
                failures.append(f"bucket {key} (n={info['n']}) served on "
                                f"{info['tier']!r}, expected 'fused'")
        if not snap.get("tiers", {}).get("fused", {}).get("batches"):
            failures.append("no fused-tier dispatches recorded in the smoke "
                            "run (tiers metrics empty)")
        # Stage-3 D&C routing (DESIGN.md §14): a dedicated tiny burst with
        # the crossover pinned low, asserting the staged-dc tier fires AND
        # its sigma agrees with LAPACK to 1e-12 — the CI assertion that the
        # serve path actually exercises the D&C solver.
        failures.extend(_dc_tier_smoke(seed=args.seed))
        # Histogram fidelity (DESIGN.md §16): the reported percentiles come
        # from the fixed-log-bucket histogram; assert each lands within one
        # bucket width (a factor of r) of the exact sample percentile.  The
        # histogram's rank convention matches numpy's method="higher", so
        # the only divergence is the bucket-midpoint quantization.
        exact = np.asarray(poi.get("latency_exact_ms", []))
        if exact.size:
            ratio = poi["latency_bucket_ratio"]
            for q in (50, 95, 99):
                e = float(np.percentile(exact, q, method="higher"))
                h = poi["latency_ms"][f"p{q}"]
                if not (e / ratio <= h <= e * ratio):
                    failures.append(
                        f"histogram p{q}={h:.3f}ms off exact {e:.3f}ms by "
                        f"more than one bucket width (r={ratio:.3f})")
        else:
            failures.append("no exact latency samples for the histogram "
                            "fidelity check")
    if mserver is not None:
        # Scrape gate (DESIGN.md §16): the endpoint must answer, carry the
        # serve series the run just produced, and every sample line must
        # parse as ``name{labels} value`` — the exposition is hand-emitted,
        # so CI asserts its shape, not just its existence.
        import urllib.request
        text = ""
        try:
            with urllib.request.urlopen(mserver.url, timeout=10) as resp:
                text = resp.read().decode("utf-8")
        except Exception as exc:                 # noqa: BLE001 — gate
            failures.append(f"metrics scrape failed: {exc!r}")
        for needed in ("repro_serve_requests_total",
                       "repro_serve_latency_seconds_bucket",
                       "repro_serve_queue_age_seconds_count",
                       "repro_serve_health_status"):
            if text and needed not in text:
                failures.append(f"metrics exposition missing {needed}")
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name_part, _, value_part = line.rpartition(" ")
            try:
                float(value_part)
                ok_line = bool(name_part)
            except ValueError:
                ok_line = False
            if not ok_line:
                failures.append(f"malformed exposition line: {line!r}")
                break
        mserver.stop()
    if tracer is not None:
        print(f"# trace jsonl written to {args.trace_jsonl}", flush=True)
    if p99_budget and poi["latency_ms"]["p99"] > p99_budget:
        failures.append(f"p99 latency {poi['latency_ms']['p99']:.1f}ms "
                        f"> budget {p99_budget:g}ms")
    if args.check and not args.smoke and thr["speedup"] < 3.0:
        failures.append(f"engine speedup {thr['speedup']:.2f}x < 3x "
                        f"acceptance bar")
    chaos_tail = ""
    if args.chaos:
        tm, pm = thr["engine_metrics"], poi["engine_metrics"]
        chaos_tail = (f" chaos_retried={tm['retried'] + pm['retried']}"
                      f" chaos_degraded={tm['degraded'] + pm['degraded']}")
    print(f"# speedup={thr['speedup']:.2f}x "
          f"sigma_err={thr['sigma_max_err']:.2e} "
          f"p99={poi['latency_ms']['p99']:.1f}ms "
          f"timed_out={poi['timed_out']} dropped={poi['dropped']}"
          f"{chaos_tail}",
          flush=True)
    if failures:
        for f in failures:
            print(f"# SERVE GATE FAIL: {f}", flush=True)
        sys.exit(1)
    print("# serve gate OK", flush=True)


if __name__ == "__main__":
    main()
