"""Closed loop over single large matrices: one reduction at a time.

Traffic keys: ``pool`` (how many seeded matrices are made and cycled
through).  Configuration keys: ``entry`` (the public function of
``repro.core.svd`` that is timed), ``n``, ``bw``, ``dtype``, ``limits``.

Set-up makes the pool on the device in one jitted call from the seed and
runs the entry once on the first matrix (compile, from the persistent
cache after the first run).  The window then starts reductions back to
back, each fenced by ``block_until_ready``, while fewer than ``--seconds``
have passed.  ``reduce_s`` is the mean wall time of the reductions in the
window.  Afterwards every result is compared with the float64 reference
of its matrix.
"""

from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.harness import (CompileCounter, Context, check, jax_key,
                           memory_peak_bytes)
from bench.trace import WINDOW, WindowTrace


def make_pool(ctx: Context):
    """``pool`` upper-banded n x n matrices of bandwidth ``bw``, standard
    normal inside the band, made on the device in one call."""
    import jax
    import jax.numpy as jnp
    n, bw, count = ctx.config["n"], ctx.config["bw"], ctx.traffic["pool"]

    @jax.jit
    def make(key):
        a = jax.random.normal(key, (count, n, n), ctx.config["dtype"])
        i = jnp.arange(n)
        keep = (i[None, :] >= i[:, None]) & (i[None, :] <= i[:, None] + bw)
        return jnp.where(keep, a, 0)

    stack = make(jax_key(ctx.seed))
    return [stack[i] for i in range(count)]


def run(ctx: Context) -> dict:
    import jax
    from repro.core import svd

    bw = ctx.config["bw"]
    entry = getattr(svd, ctx.config["entry"])
    pool = make_pool(ctx)
    feed = [a.astype(ctx.dtype) for a in pool]

    def reduce(a):
        return entry(a, bw=bw).block_until_ready()

    t = time.perf_counter()
    reduce(feed[0])
    warm_s = time.perf_counter() - t

    capture = WindowTrace(ctx.trace, chips=len(ctx.devices))
    capture.start()
    times, outs = [], []
    with CompileCounter() as compiles, jax.profiler.TraceAnnotation(WINDOW):
        t_w = time.perf_counter()
        while time.perf_counter() - t_w < ctx.window_s:
            with jax.profiler.TraceAnnotation("bench/reduce"):
                t = time.perf_counter()
                outs.append(reduce(feed[len(outs) % len(feed)]))
                times.append(time.perf_counter() - t)
    trace = capture.stop()
    peak = memory_peak_bytes(ctx.devices)

    bands = [np.asarray(a) for a in pool]
    sigmas = [np.asarray(s) for s in outs]
    del pool, feed, outs
    refs = [reference.singular_values(b) for b in bands]
    errs = [reference.sigma_error(s, refs[i % len(refs)])
            for i, s in enumerate(sigmas)]
    limit = ctx.limit("sigma_err")
    return {
        "setup_s": t_w - ctx.t_start,
        "e2e": {"reduce_s": sum(times) / len(times)},
        "attempted": len(errs),
        "failed": sum(not e <= limit for e in errs),
        "checks": {"sigma_err": check(max(errs), limit)},
        "memory_peak_bytes": peak,
        "trace": trace,
        "readings": {"reductions": len(times), "reduce_times_s": times,
                     "warm_s": warm_s, "compiles_in_window": compiles.count},
    }
