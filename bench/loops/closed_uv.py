"""Closed loop over single large banded matrices, full SVD: one
``(U, sigma, V^T)`` at a time.

Traffic keys: ``pool`` (how many seeded matrices are made and cycled
through), ``trace_seconds``.  Configuration keys: ``entry`` (the public
function of ``repro.core.svd`` that is timed; it returns ``(U, sigma,
V^T)``), ``n``, ``bw``, ``dtype``, ``limits`` (``sigma_err``, ``uv_resid``,
``uv_orth``).

The bands are ``closed_single``'s, made by its ``make_pool`` from the
seed.  Set-up runs the entry once on the first matrix (compile).  The
window then starts reductions back to back, each fenced on the whole
tuple, while fewer than ``--seconds`` have passed; ``reduce_s`` is the
mean wall time of the reductions in the window.  Afterwards every answer
is compared with the float64 reference of its matrix: sigma, the residual
and the orthogonality of U and V^T.

``readings`` carry the window's deltas of the program's counters
``repro_tape_bytes_total`` (by stage) and ``repro_chase_stages_total`` (by
path), where the program has them.  A traced run also splits the window's
device time by the program's stage scopes (``bench/scopes.py``:
``scope_s``, ``idle_by_span``), which the per-layer readers of this cell
take apart.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import time

import numpy as np

from bench import reference
from bench.harness import (CompileCounter, Context, check,
                           memory_peak_bytes)
from bench.loops.closed_single import make_pool
from bench.scopes import reduce_scopes
from bench.trace import WINDOW, WindowTrace, reduce_xplane


class ScopedWindowTrace(WindowTrace):
    """A :class:`WindowTrace` whose reduction also holds ``scope_s`` and
    ``idle_by_span`` of the same capture, read before it is deleted."""

    def stop(self) -> dict | None:
        if not self.enabled:
            return None
        import jax
        try:
            jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise ValueError(f"the profiler wrote no trace under {self.dir}")
            path = max(paths, key=os.path.getmtime)
            return dict(reduce_xplane(path, self.chips), **reduce_scopes(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def counters() -> dict[str, dict[str, int]]:
    """The program's tape and chase-path counters, those it has."""
    from repro import obs
    out = {}
    for key, name in (("tape_bytes_total", "tape_bytes"),
                      ("chase_stages_total", "chase_stage_counts")):
        fn = getattr(obs, name, None)
        if fn is not None:
            out[key] = dict(fn())
    return out


def delta(before: dict, after: dict) -> dict:
    return {key: dict(collections.Counter(after[key])
                      - collections.Counter(before.get(key, {})))
            for key in after}


def run(ctx: Context) -> dict:
    import jax
    from repro.core import svd

    bw = ctx.config["bw"]
    entry = getattr(svd, ctx.config["entry"])
    pool = make_pool(ctx)
    feed = [a.astype(ctx.dtype) for a in pool]

    def reduce(a):
        return jax.block_until_ready(entry(a, bw=bw))

    t = time.perf_counter()
    reduce(feed[0])
    warm_s = time.perf_counter() - t

    capture = ScopedWindowTrace(ctx.trace, chips=len(ctx.devices))
    before = counters()
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
    counted = delta(before, counters())
    peak = memory_peak_bytes(ctx.devices)

    bands = [np.asarray(a) for a in pool]
    answers = [tuple(np.asarray(x) for x in out) for out in outs]
    del pool, feed, outs
    refs = [reference.singular_values(b) for b in bands]
    names = ("sigma_err", "uv_resid", "uv_orth")
    readings = []
    for i, (u, s, vt) in enumerate(answers):
        band = bands[i % len(bands)]
        readings.append((reference.sigma_error(s, refs[i % len(refs)]),
                         reference.residual(band, u, s, vt),
                         reference.orthogonality(u, vt)))
    limits = [ctx.limit(name) for name in names]
    return {
        "setup_s": t_w - ctx.t_start,
        "e2e": {"reduce_s": sum(times) / len(times)},
        "attempted": len(readings),
        "failed": sum(not all(r <= lim for r, lim in zip(rs, limits))
                      for rs in readings),
        "checks": {name: check(max(rs[j] for rs in readings), limits[j])
                   for j, name in enumerate(names)},
        "memory_peak_bytes": peak,
        "trace": trace,
        "readings": {"reductions": len(times), "reduce_times_s": times,
                     "warm_s": warm_s, "compiles_in_window": compiles.count,
                     **counted},
    }
