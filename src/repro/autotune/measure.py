"""Blocking timing harness — the ONE wall-clock path shared by the
autotuner and the ``benchmarks/`` suites.

``measure_seconds`` is the primitive: jit-warmup then median-of-k
``block_until_ready`` wall times (``benchmarks/common.timeit`` delegates
here, so a fix to the methodology lands everywhere at once).
``time_stage2`` is the autotuner's workload: one batched stage-2 reduction
at a candidate ``(tw, batch)`` — either the full ``bw -> 1``
tile-width plan (``full=True``, what the search ranks: it charges small
``tw`` for the extra stages it forces) or a single stage at the entry
bandwidth (``full=False``, what ``benchmarks/hyperparams.py`` sweeps).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = ["measure_seconds", "banded_input", "time_stage2"]


def measure_seconds(fn, *args, warmup: int = 1, iters: int = 3,
                    label: str = "measure") -> float:
    """Median wall seconds of ``fn(*args)`` (jax-blocking).

    ``warmup`` calls are discarded (jit compilation + device spin-up);
    ``iters`` timed calls then give a median — robust to the one-off
    scheduling hiccups a mean would smear in.

    With an ambient :class:`repro.obs.Tracer` active, every call emits a
    span tree under ``label``: a ``warmup`` child per discarded call (the
    FIRST warmup is where jit compilation lands, so its duration is the
    compile-dominated one — the tracer attributes it ``compile="warmup0"``)
    and a ``rep`` child per timed call, so a tuning run's trace shows
    exactly what the reported median was computed from.
    """
    with obs.span(label, warmup=warmup, iters=iters) as sp:
        for i in range(max(warmup, 0)):
            with obs.span("warmup", i=i) as w:
                if i == 0:
                    w.set(compile="warmup0")
                jax.block_until_ready(fn(*args))
        ts = []
        for i in range(max(iters, 1)):
            with obs.span("rep", i=i):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                ts.append(time.perf_counter() - t0)
        med = sorted(ts)[len(ts) // 2]
        sp.set(median_s=med)
    return med


def banded_input(n: int, bw: int, *, batch: int = 1, dtype=jnp.float32,
                 seed: int = 0) -> jax.Array:
    """Dense upper-banded test matrices ``(batch, n, n)`` (batch=1 squeezed
    to ``(n, n)``), same construction as ``benchmarks/common.banded``."""
    rng = np.random.default_rng(seed)
    shape = (batch, n, n) if batch > 1 else (n, n)
    a = np.triu(rng.standard_normal(shape))
    a = np.triu(a) - np.triu(a, bw + 1)
    return jnp.asarray(a.astype(jnp.dtype(dtype).name))


def time_stage2(n: int, bw: int, *, tw: int, batch: int = 1,
                backend: str = "ref", dtype=jnp.float32, tape: bool = False,
                full: bool = True, warmup: int = 1, iters: int = 3,
                seed: int = 0) -> float:
    """Median seconds of ONE batched stage-2 call at the candidate config.

    Returns the time of the whole batched call — divide by ``batch`` for
    the per-matrix figure the search compares.  The packed input is built
    once outside the timed region (the serve layer amortizes packing the
    same way).
    """
    from repro.core import band as bandmod
    from repro.core import bulge_chasing as bc

    a = banded_input(n, bw, batch=batch, dtype=dtype, seed=seed)
    tw0 = min(tw, max(bw - 1, 1))
    packed = bandmod.pack(a, bw, tw0)

    if full:
        def call():
            out = bc.bidiagonalize_packed(packed, n=n, bw=bw, tw=tw,
                                          backend=backend, tape=tape)
            return out[:2] if tape else out
    else:
        def call():
            return bc.reduce_stage_packed(packed, n=n, b_in=bw, tw=tw0,
                                          backend=backend, tape=tape)

    return measure_seconds(
        call, warmup=warmup, iters=iters,
        label=f"time_stage2/n{n}/bw{bw}/tw{tw}/b{batch}")
