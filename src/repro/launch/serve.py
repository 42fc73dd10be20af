"""Serving drivers: batched token decoding, and the async SVD serve tier.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --svd --requests 32 --rate 200

The ``--svd`` mode drives :class:`repro.serve.AsyncSVDEngine` with an
open-loop request stream (arrivals do not wait for completions) and prints
latency percentiles plus the engine metrics snapshot.  With
``REPRO_SERVE_MESH`` set (see ``repro.launch.mesh.serve_mesh``) full
buckets are batch-sharded across all configured local devices.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.configs.base import get_config, smoke_of
from repro.launch import compile_cache
from repro.models import build
from repro.serve import Engine, Request, ServeConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: smoke, CPU-runnable)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--svd", action="store_true",
                    help="drive the async SVD serve tier instead of the "
                         "token engine")
    ap.add_argument("--svd-n", type=int, default=64, metavar="N",
                    help="[--svd] matrix size")
    ap.add_argument("--svd-bw", type=int, default=8, metavar="BW",
                    help="[--svd] stage-1 target bandwidth")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="[--svd] open-loop Poisson arrival rate, req/s")
    ap.add_argument("--timeout-ms", type=float, default=0.0,
                    help="[--svd] per-request deadline (0: none)")
    ap.add_argument("--autotune", action="store_true",
                    help="[--svd] per-bucket tuned-config cache (DESIGN.md "
                         "§11)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="[--svd] serve Prometheus-format engine metrics at "
                         "127.0.0.1:PORT/metrics for the lifetime of the "
                         "run (0 = ephemeral port; DESIGN.md §16)")
    ap.add_argument("--hosts", type=int, default=0, metavar="N",
                    help="[--svd] multi-host mode: spawn N worker processes "
                         "and route through repro.serve.SVDRouter "
                         "(DESIGN.md §17)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.svd and args.hosts >= 2:
        return main_svd_multihost(args)
    if args.svd:
        return main_svd(args)

    cfg = get_config(args.arch) if args.full else smoke_of(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(max_batch=args.max_batch,
                                            max_seq=args.max_seq))
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        prompt = list(map(int, rng.integers(1, cfg.vocab,
                                            int(rng.integers(2, 9)))))
        frames = (rng.standard_normal((cfg.enc_seq, cfg.d_model)).astype("f")
                  if cfg.kind == "encdec" else None)
        eng.submit(Request(uid=uid, prompt=prompt,
                           max_new_tokens=args.new_tokens, frames=frames))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    ntok = sum(len(r.output) for r in done)
    for r in done[:4]:
        print(f"req {r.uid}: {r.output}")
    print(f"served {len(done)} requests / {ntok} tokens in {dt:.1f}s "
          f"({ntok / max(dt, 1e-9):.1f} tok/s)")


def main_svd(args):
    """Open-loop async SVD serving demo (DESIGN.md §12), in float32 — the
    precision the chip computes in."""
    from repro.launch.mesh import serve_mesh
    from repro.serve import AsyncSVDEngine, SVDRequest

    mesh = serve_mesh()
    n, bw = args.svd_n, args.svd_bw
    rng = np.random.default_rng(0)
    matrix = lambda: rng.standard_normal((n, n)).astype(np.float32)
    eng = AsyncSVDEngine(
        backend="auto", autotune=args.autotune, mesh=mesh,
        default_timeout_s=(args.timeout_ms / 1e3 or None))
    mserver = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        mserver = MetricsServer(port=args.metrics_port)
        mserver.register("svd", eng.metrics)
        print(f"metrics endpoint: {mserver.url}")
    # Warm the bucket (one compile) outside the timed window — never under
    # the engine's default deadline (compiles take seconds).
    eng.submit(SVDRequest(uid=-1, matrix=matrix(), bw=bw),
               timeout_s=float("inf")).result()
    # Hand-rolled open loop rather than benchmarks/serve_load.py's
    # poisson_run on purpose: src/ must stay importable with PYTHONPATH=src
    # alone (benchmarks/ lives outside the package).  The harness over
    # there is the canonical measurement tool; this is the demo.
    gaps = rng.exponential(1.0 / args.rate, args.requests)
    futs, lat, resolved = [], [], []

    def _stamp(req):
        # Latency must be sampled INSIDE the callback (when the future
        # resolves), not when the loop below gets around to reading it;
        # `resolved` counts every outcome so the wait below has a barrier.
        def cb(fut):
            if fut.exception() is None:
                lat.append(time.monotonic() - req.arrived)
            resolved.append(req.uid)
        return cb

    t0 = time.time()
    for uid in range(args.requests):
        time.sleep(gaps[uid])
        r = SVDRequest(uid=uid, matrix=matrix(), bw=bw)
        f = eng.submit(r)
        f.add_done_callback(_stamp(r))
        futs.append(f)
    settle = time.time() + 600
    while len(resolved) < args.requests and time.time() < settle:
        time.sleep(0.01)
    for f in futs:
        try:
            f.result()
        except Exception as exc:                 # noqa: BLE001 — demo report
            print(f"request failed: {exc!r}")
    dt = time.time() - t0
    eng.stop()
    snap = eng.metrics.snapshot()
    if lat:
        p50, p95, p99 = np.percentile(np.asarray(lat) * 1e3, [50, 95, 99])
        print(f"served {len(lat)}/{args.requests} requests in {dt:.2f}s "
              f"({len(lat) / dt:.1f} req/s) on "
              f"{'mesh ' + str(mesh.shape) if mesh else 'one device'}")
        print(f"latency p50/p95/p99 = {p50:.1f}/{p95:.1f}/{p99:.1f} ms")
    print("metrics:", {k: round(v, 3) if isinstance(v, float) else v
                       for k, v in sorted(snap.items())})
    # Operator health view (DESIGN.md §15): headline status plus the
    # failure-taxonomy counters (retries, quarantines, degraded traffic).
    health = eng.metrics.health()
    print("health:", {k: round(v, 4) if isinstance(v, float) else v
                      for k, v in health.items()})
    if mserver is not None:
        mserver.stop()


def main_svd_multihost(args):
    """Two-plus-process serve demo (DESIGN.md §17): a router in this
    process, ``--hosts`` worker processes, the same open loop as
    :func:`main_svd` routed fleet-wide.  The canonical measurement tool
    is ``benchmarks/serve_load.py --hosts N``; this is the demo."""
    from repro.serve import SVDRequest
    from repro.serve.router import SVDRouter
    from repro.serve.worker import check_fleet_fits, spawn_worker_process

    check_fleet_fits(args.hosts)
    n, bw = args.svd_n, args.svd_bw
    rng = np.random.default_rng(0)
    matrix = lambda: rng.standard_normal((n, n)).astype(np.float32)
    router = SVDRouter(
        default_timeout_s=(args.timeout_ms / 1e3 or None))
    procs = [spawn_worker_process(router.address, f"w{i}", backend="auto")
             for i in range(args.hosts)]
    mserver = None
    try:
        if not router.wait_for_hosts(args.hosts, timeout=120):
            raise RuntimeError(
                f"only {len(router.alive_hosts())}/{args.hosts} worker "
                f"hosts connected")
        if args.metrics_port is not None:
            from repro.obs import MetricsServer, render_fleet_metrics
            mserver = MetricsServer(port=args.metrics_port)
            mserver.register("router", router.metrics)
            mserver.register_provider(
                "fleet", lambda: render_fleet_metrics(router.fleet()))
            print(f"metrics endpoint: {mserver.url}")
        # Warm every host's bucket compile outside the timed window.
        router.warm([SVDRequest(uid=-1, matrix=matrix(), bw=bw)])
        gaps = rng.exponential(1.0 / args.rate, args.requests)
        futs, lat = [], []
        t0 = time.time()
        for uid in range(args.requests):
            time.sleep(gaps[uid])
            r = SVDRequest(uid=uid, matrix=matrix(), bw=bw)
            futs.append((r, router.submit(r)))
        for r, f in futs:
            try:
                f.result(timeout=600)
                lat.append(time.monotonic() - r.arrived)
            except Exception as exc:             # noqa: BLE001 — demo report
                print(f"request {r.uid} failed: {exc!r}")
        dt = time.time() - t0
        fleet = router.fleet()
        if lat:
            p50, p95, p99 = np.percentile(np.asarray(lat) * 1e3,
                                          [50, 95, 99])
            print(f"served {len(lat)}/{args.requests} requests in "
                  f"{dt:.2f}s ({len(lat) / dt:.1f} req/s) across "
                  f"{len(fleet['alive_hosts'])} hosts")
            print(f"latency p50/p95/p99 = {p50:.1f}/{p95:.1f}/{p99:.1f} ms")
        print("fleet hosts:", {h: row for h, row
                               in fleet["router"]["hosts"].items()})
        print("merged latency:", fleet["latency"]["merged_summary"])
    finally:
        router.stop()
        if mserver is not None:
            mserver.stop()
        for p in procs:
            try:
                p.wait(timeout=15)
            except Exception:                    # noqa: BLE001 — cleanup
                p.kill()


if __name__ == "__main__":
    main()
