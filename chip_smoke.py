#!/usr/bin/env python3
"""Smoke test of the SVD system on a TPU: the quickest proof that it still
starts on the chip and gives right answers there.

    python chip_smoke.py             # one chip: served path, banded chase,
                                     # one large reduction
    python chip_smoke.py --chips 4   # four chips: one bucket sharded over a
                                     # ("data",) mesh vs the same bucket on one chip

One process drives the chip(s).  Every phase runs through the public entry
points with ``backend="auto"``, which must resolve to the compiled Pallas
kernels (never interpret mode, never a fallback tier); every singular value
is checked against the fp64 LAPACK oracle of ``core/reference.py`` with the
normwise bound ``max|dsigma| / sigma_max <= 10 * n * eps(float32)``.  The
seconds printed per phase (compile, steady run; ``block_until_ready``-fenced)
are a bring-up record, not benchmark numbers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every phase passed.  Without a TPU, or outside a
checkout of the repository, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Served-path mix: (n, bw, compute_uv) buckets on both sides of the
# fused/staged crossover (tuning.DEFAULT_FUSED_CROSSOVER = 256).
SERVE_BUCKETS = ((32, 8, False), (128, 16, False), (128, 16, True),
                 (512, 32, False))
SERVE_REQUESTS = 48          # seeded open-loop stream over SERVE_BUCKETS
SERVE_RATE = 100.0           # offered arrival rate, requests/s
LARGE_N = 4096               # the single dense reduction, bw = 32


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, degraded or fallen-back result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def sigma_tol(n: int) -> float:
    import numpy as np
    return 10.0 * n * float(np.finfo(np.float32).eps)


def require_tpu(chips: int):
    """The devices to run on; raises SmokeFailure unless they are TPUs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise SmokeFailure(f"no TPU: JAX found no usable backend ({exc})")
    platform = devices[0].platform
    check(platform == "tpu",
          f"no TPU: JAX's default backend is {platform!r}; this smoke test "
          f"runs only on the chip")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} TPU devices, JAX sees "
          f"{len(devices)}")
    from repro.autotune import model
    try:
        prof = model.profile_for(model.device_kind(devices[0]))
    except ValueError as exc:                    # a chip with no profile row
        raise SmokeFailure(str(exc)) from None
    log(f"# device: {devices[0].device_kind} x{len(devices)} "
        f"(profile {prof.device_kind!r})")
    return devices


def check_chip_config(cfg, where: str) -> None:
    check(cfg.backend in ("pallas", "fused_small"),
          f"{where}: resolved backend {cfg.backend!r}, not a Pallas tier")
    check(not cfg.interpret, f"{where}: Pallas resolved in interpret mode")


def served_phase(seed: int, requests: int = SERVE_REQUESTS,
                 rate: float = SERVE_RATE) -> None:
    """Open-loop stream through AsyncSVDEngine(backend="auto")."""
    import numpy as np

    from repro.core import reference
    from repro.serve import AsyncSVDEngine, SVDRequest

    rng = np.random.default_rng(seed)
    eng = AsyncSVDEngine(backend="auto", batch_window_s=0.02,
                         residual_check=True)
    try:
        t0 = time.perf_counter()
        warm = [eng.submit(SVDRequest(
                    uid=-1 - i, bw=bw, compute_uv=uv,
                    matrix=rng.standard_normal((n, n)).astype(np.float32)),
                    timeout_s=float("inf"))
                for i, (n, bw, uv) in enumerate(SERVE_BUCKETS)]
        for f in warm:
            f.result(timeout=900)
        t_warm = time.perf_counter() - t0

        picks = rng.integers(0, len(SERVE_BUCKETS), requests)
        gaps = rng.exponential(1.0 / rate, requests)
        reqs, futs = [], []
        t0 = time.perf_counter()
        for uid in range(requests):
            time.sleep(gaps[uid])
            n, bw, uv = SERVE_BUCKETS[picks[uid]]
            r = SVDRequest(uid=uid, bw=bw, compute_uv=uv,
                           matrix=rng.standard_normal((n, n)).astype(
                               np.float32))
            reqs.append(r)
            futs.append(eng.submit(r))
        for f in futs:
            f.result(timeout=900)
        t_stream = time.perf_counter() - t0
    finally:
        eng.stop()

    snap = eng.metrics.snapshot()
    done = sum(r.done and r.error is None for r in reqs)
    log(f"served: {done}/{requests} requests completed, "
        f"{snap['batches']} batches")
    counters = {k: snap[k] for k in ("degraded", "retried",
                                     "sharded_retries", "failed",
                                     "timed_out", "rejected")}
    log(f"served: counters {counters}")
    check(done == requests, f"served: only {done}/{requests} completed")
    check(not any(counters.values()), f"served: fallbacks or failures "
          f"{counters}")
    check(not snap["bucket_errors"],
          f"served: bucket errors {snap['bucket_errors']}")
    for key, row in sorted(snap["bucket_tiers"].items()):
        log(f"served: bucket {key} -> tier {row['tier']}, "
            f"backend {row['backend']}")
    for key, cfg in eng._cfg_memo.items():
        check_chip_config(cfg, f"served bucket {key}")

    worst = {}
    for r in reqs:
        n = r.matrix.shape[0]
        err = reference.sigma_error(r.sigma, r.matrix)
        tag = (n, r.bw, r.compute_uv)
        worst[tag] = max(worst.get(tag, 0.0), err)
        if r.compute_uv:
            resid = (np.linalg.norm(r.matrix - (r.u * r.sigma) @ r.vt)
                     / np.linalg.norm(r.matrix))
            check(resid <= sigma_tol(n),
                  f"served: uid {r.uid} residual {resid:.3e} > "
                  f"{sigma_tol(n):.3e}")
    for (n, bw, uv), err in sorted(worst.items()):
        log(f"served: n={n} bw={bw} compute_uv={uv} "
            f"max|dsigma|/sigma_max={err:.3e} (tol {sigma_tol(n):.3e})")
        check(err <= sigma_tol(n), f"served: n={n} bw={bw} sigma error "
              f"{err:.3e} > {sigma_tol(n):.3e}")
    log(f"served: compile+first-run {t_warm:.3f}s (one request per bucket), "
        f"stream {t_stream:.3f}s for {requests} requests at "
        f"{rate:g} req/s offered")


def large_phase(seed: int, n: int, bw: int) -> None:
    """One dense n x n reduction through ``singular_values``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import reference, svd, tuning

    cfg = tuning.PipelineConfig.resolve(bw=bw, n=n, dtype=jnp.float32)
    check_chip_config(cfg, "large")
    a = jax.random.normal(jax.random.PRNGKey(seed), (n, n), jnp.float32)
    a.block_until_ready()

    # The ahead-of-time compile times the compile alone (and leaves the
    # executable in the persistent cache for the call below).
    t0 = time.perf_counter()
    compiled = svd._three_stage.lower(a, config=cfg.kernel()).compile()
    t_compile = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    check(kernels >= 2, f"large: compiled pipeline holds {kernels} Pallas "
          f"kernels, expected the stage-1 WY apply and the chase")

    t0 = time.perf_counter()
    sig = svd.singular_values(a, config=cfg).block_until_ready()
    t_run = time.perf_counter() - t0

    t0 = time.perf_counter()
    err = reference.sigma_error(np.asarray(sig), np.asarray(a))
    t_oracle = time.perf_counter() - t0
    log(f"large: n={n} bw={bw} tw={cfg.tw} backend={cfg.backend} "
        f"kernels={kernels} max|dsigma|/sigma_max={err:.3e} "
        f"(tol {sigma_tol(n):.3e})")
    log(f"large: compile {t_compile:.3f}s, run {t_run:.3f}s (one "
        f"singular_values call after the compile), host fp64 oracle "
        f"{t_oracle:.3f}s")
    check(err <= sigma_tol(n), f"large: sigma error {err:.3e} > "
          f"{sigma_tol(n):.3e}")


def chase_phase(seed: int, n: int, bw: int) -> None:
    """A banded matrix through ``banded_singular_values`` (stages 2 + 3)
    on the default configuration: its stage 2 is the band-resident chase
    kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import reference, svd, tuning

    rng = np.random.default_rng(seed + 1)
    dense = np.triu(rng.standard_normal((n, n)))
    band = dense - np.triu(dense, bw + 1)            # upper band, width bw
    a = jax.device_put(band.astype(np.float32))
    cfg = tuning.PipelineConfig.resolve(bw=bw, n=n, dtype=jnp.float32)
    check_chip_config(cfg, "chase")
    t0 = time.perf_counter()
    sig = svd.banded_singular_values(a, config=cfg).block_until_ready()
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    sig = svd.banded_singular_values(a, config=cfg).block_until_ready()
    t_steady = time.perf_counter() - t0
    err = reference.sigma_error(np.asarray(sig), band)
    log(f"chase: n={n} bw={bw} tw={cfg.tw} "
        f"max|dsigma|/sigma_max={err:.3e} (tol {sigma_tol(n):.3e}); "
        f"first call {t_first:.3f}s, steady {t_steady:.3f}s")
    check(err <= sigma_tol(n), f"chase: sigma error {err:.3e}")


def sharded_phase(seed: int, devices, n: int, bw: int, batch: int) -> None:
    """One full bucket over a 4-chip ("data",) mesh vs one chip."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed, reference, svd, tuning
    from repro.launch.mesh import serve_mesh

    os.environ["REPRO_SERVE_MESH"] = str(len(devices))
    mesh = serve_mesh()
    check(mesh is not None and mesh.shape["data"] == len(devices),
          f"sharded: serve_mesh() gave {mesh}")
    cfg = tuning.PipelineConfig.resolve(bw=bw, n=n, dtype=jnp.float32,
                                        max_batch=batch)
    check_chip_config(cfg, "sharded")
    rng = np.random.default_rng(seed)
    mats = rng.standard_normal((batch, n, n)).astype(np.float32)
    retries = []

    def dispatch():
        return distributed.sharded_pipeline_dispatch(
            jnp.asarray(mats), mesh, config=cfg.kernel(),
            on_shard_retry=retries.append).block_until_ready()

    t0 = time.perf_counter()
    sig4 = dispatch()
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    sig4 = dispatch()
    t_steady = time.perf_counter() - t0
    shard_devs = sorted({s.device.id for s in sig4.addressable_shards})
    log(f"sharded: B={batch} n={n} bw={bw} over mesh {dict(mesh.shape)}; "
        f"result shards on devices {shard_devs}; sharded_retries="
        f"{sum(retries)}")
    check(len(shard_devs) == len(devices),
          f"sharded: shards on {len(shard_devs)} devices, not {len(devices)}")
    check(not retries, f"sharded: {sum(retries)} shard re-dispatches")

    one = jnp.asarray(mats)            # committed to the default device
    t0 = time.perf_counter()
    sig1 = svd.svd_batched(one, config=cfg.kernel()).block_until_ready()
    t_one_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    sig1 = svd.svd_batched(one, config=cfg.kernel()).block_until_ready()
    t_one = time.perf_counter() - t0
    s4, s1 = np.asarray(sig4), np.asarray(sig1)
    agree = float(np.max(np.abs(s4 - s1)) / np.max(np.abs(s1)))
    err = max(reference.sigma_error(s4[b], mats[b]) for b in range(batch))
    log(f"sharded: 4-chip vs 1-chip max|dsigma|/sigma_max={agree:.3e}; "
        f"4-chip vs fp64 oracle {err:.3e} (tol {sigma_tol(n):.3e})")
    log(f"sharded: 4 chips first {t_first:.3f}s steady {t_steady:.3f}s; "
        f"1 chip first {t_one_first:.3f}s steady {t_one:.3f}s")
    check(agree <= sigma_tol(n), f"sharded: 4-chip and 1-chip sigma differ "
          f"by {agree:.3e}")
    check(err <= sigma_tol(n), f"sharded: sigma error {err:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: served path + large reduction; 4: the sharded "
                         "bucket dispatch and its one-chip comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repro sources are not at {SRC}; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch import compile_cache

    try:
        cache = compile_cache.enable()
        devices = require_tpu(args.chips)
        log(f"# compile cache: {cache}")
        if args.chips == 4:
            sharded_phase(args.seed, devices[:4], n=512, bw=32, batch=8)
        else:
            served_phase(args.seed)
            chase_phase(args.seed, n=1024, bw=32)
            large_phase(args.seed, LARGE_N, bw=32)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
