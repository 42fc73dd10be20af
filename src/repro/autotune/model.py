"""Analytic stage-2 cost model (the paper's §III-C/D performance model,
made falsifiable).

The paper's methodological core is a *hardware-aware performance model* that
ranks configurations before any kernel runs; measurement then only has to
confirm (or refute) the top of the ranking.  This module is that model for
our wavefront chase: given a candidate ``(tw, batch)`` it composes

* **bytes moved** — from the packed-band layout: each chase cycle streams
  its window ``(H, W)``, ``H = b_in + 2*tw + 1``, ``W = b_in + tw + 1``,
  through fast memory once, i.e. costs ``2*H*W`` words of slow-memory
  round trip (gather + scatter);
* **launch overhead** — one dispatch per wavefront cycle ``T ~
  3*nsweeps`` regardless of batch (the batch axis folds into the same
  grid);
* **wavefront occupancy** — paper Eq. 1: achieved bandwidth scales with the
  fraction of execution units the ``batch * G`` concurrent windows cover,
  saturating at 1;
* **feasibility** — a candidate whose ``tuning.vmem_working_set_bytes``
  exceeds the profile's fast-memory budget is infeasible (``inf`` cost):
  the VMEM cliff.

roofline-composed with a per-device :class:`DeviceProfile` table that
generalizes the hard-coded v5e constants of ``roofline/hw.py``.  The model
is deliberately cheap (pure ints/floats, no jax arrays) so the search can
rank the full grid and measure only the top-K (``autotune/search.py``),
printing predicted-vs-measured error — the model is falsifiable, not
decorative.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import tuning
from repro.roofline import hw

__all__ = [
    "DeviceProfile", "PROFILES", "device_kind", "profile_for",
    "total_chase_cycles", "CostBreakdown", "stage_cost", "pipeline_cost",
    "fused_cost", "predicted_crossover", "FUSED_FAST_BW_RATIO",
    "stage3_cost", "predicted_stage3_crossover", "DC_DEFLATION_FACTOR",
]


# ---------------------------------------------------------------------------
# Per-device profile table (generalizes roofline/hw.py beyond v5e)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceProfile:
    """What the cost model needs to know about one device kind.

    ``mem_bw`` is the achievable slow-memory stream bandwidth feeding the
    chase (HBM on TPU/GPU; DRAM on the CPU ref path), ``launch_overhead_s``
    the per-dispatch fixed cost (measured by
    ``benchmarks/kernels_bench.py::_launch_overhead``), ``fast_mem_bytes``
    the per-core budget the working set must fit (VMEM on TPU; the model
    reuses it as the residency cliff on every platform), and
    ``execution_units`` the number of cores the wavefront must cover for
    full occupancy (paper Eq. 1; TensorCores on TPU).
    """
    device_kind: str
    mem_bw: float                   # bytes/s
    launch_overhead_s: float        # per dispatch
    fast_mem_bytes: int             # residency budget per core
    execution_units: int


PROFILES: dict[str, DeviceProfile] = {
    # v5e constants are the roofline/hw.py values (single source of truth).
    "tpu v5e": DeviceProfile("tpu v5e", mem_bw=hw.HBM_BW,
                             launch_overhead_s=3e-6,
                             fast_mem_bytes=tuning.VMEM_BUDGET_BYTES,
                             execution_units=2),
    "tpu v4": DeviceProfile("tpu v4", mem_bw=1.2e12, launch_overhead_s=3e-6,
                            fast_mem_bytes=tuning.VMEM_BUDGET_BYTES,
                            execution_units=2),
    "tpu v5p": DeviceProfile("tpu v5p", mem_bw=2.765e12,
                             launch_overhead_s=3e-6,
                             fast_mem_bytes=tuning.VMEM_BUDGET_BYTES,
                             execution_units=2),
    # Generic GPU entry: the paper's native target; kept so cached entries
    # from a CUDA host carry a sane profile even though our kernels are
    # TPU/ref.  fast_mem ~ L2-resident working set.
    "gpu": DeviceProfile("gpu", mem_bw=1.0e12, launch_overhead_s=5e-6,
                         fast_mem_bytes=32 * 2 ** 20, execution_units=64),
    # CPU ref path: the "launch" is one fori_loop cycle of the jnp
    # wavefront (~hundreds of us — see BENCH_stage2.json chase_launch rows),
    # which dominates; mem_bw is a DRAM-stream figure.
    "cpu": DeviceProfile("cpu", mem_bw=2.0e10, launch_overhead_s=250e-6,
                         fast_mem_bytes=32 * 2 ** 20, execution_units=1),
}


def device_kind(device=None) -> str:
    """Cache-key identity of the default (or given) jax device."""
    dev = device if device is not None else jax.devices()[0]
    kind = getattr(dev, "device_kind", "") or dev.platform
    return str(kind).lower()


def profile_for(kind: str | None = None) -> DeviceProfile:
    """The profile row for a device kind string (normalized prefix match:
    "TPU v5 lite" and "tpu v5e" both hit the v5e row).  A kind with no row
    raises ``ValueError``: costing one chip with another's constants would
    silently mis-tune it."""
    k = (kind if kind is not None else device_kind()).lower()
    norm = k.replace("tpu v5 lite", "tpu v5e").replace("tpu v5litepod",
                                                       "tpu v5e")
    for name, prof in PROFILES.items():
        if norm.startswith(name) or name.startswith(norm):
            return prof
    raise ValueError(f"no device profile for kind {kind!r}; known kinds: "
                     f"{sorted(PROFILES)}")


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def total_chase_cycles(n: int, b_in: int, tw: int) -> int:
    """Count of chase cycles one stage executes, over all sweeps.

    Sweep R runs local cycles 0..j_max(R), ``j_max = (n-1-R-b_out)//b_in``.
    """
    b_out = b_in - tw
    return sum((n - 1 - r - b_out) // b_in + 1
               for r in range(max(n - 1 - b_out, 0)))


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    """One stage's predicted cost, decomposed for the validation table."""
    seconds: float                  # total for the batched call (inf: cliff)
    mem_seconds: float
    launch_seconds: float
    bytes_moved: float              # slow-memory round-trip bytes, all slots
    cycles: int                     # chase cycles, all sweeps
    dispatches: int                 # kernel dispatches (wavefront cycles)
    wavefront: int                  # concurrent windows per matrix (G)
    occupancy: float                # Eq.-1 utilization in [1/eu, 1]
    vmem_bytes: int                 # per-slot working set vs the budget
    feasible: bool

    @property
    def per_matrix_seconds(self) -> float:
        return self.seconds          # callers divide by batch explicitly


def stage_cost(n: int, b_in: int, tw: int, *, batch: int = 1,
               dtype=jnp.float32, profile: DeviceProfile | None = None,
               tape: bool = False) -> CostBreakdown:
    """Predicted wall seconds of ONE batched stage reduction ``b_in ->
    b_in - tw`` (the model of the module docstring).  Infeasible working
    sets return ``seconds=inf``."""
    from repro.core import bulge_chasing as bc

    prof = profile if profile is not None else profile_for()
    assert 1 <= tw <= b_in - 1 or b_in == 1, (b_in, tw)
    assert batch >= 1, batch
    s = jnp.dtype(dtype).itemsize
    h = b_in + 2 * tw + 1
    w = b_in + tw + 1
    cycles = total_chase_cycles(n, b_in, tw)
    _, dispatches, g = bc.stage_schedule(n, b_in, tw)
    vmem = tuning.vmem_working_set_bytes(b_in, tw, dtype, tape=tape)
    feasible = vmem <= prof.fast_mem_bytes
    # Slow-memory traffic: each cycle's (H, W) window round trip (gather +
    # scatter), plus its tape slice.
    words_per_cycle = 2.0 * h * w
    if tape:
        words_per_cycle += 2.0 * (tw + 2)      # (v, tau) pair per cycle
    bytes_moved = batch * cycles * words_per_cycle * s
    occupancy = min(1.0, batch * max(g, 1) / prof.execution_units)
    occupancy = max(occupancy, 1.0 / prof.execution_units)
    t_mem = bytes_moved / (prof.mem_bw * occupancy)
    t_launch = dispatches * prof.launch_overhead_s
    total = (t_mem + t_launch) if feasible else math.inf
    return CostBreakdown(seconds=total, mem_seconds=t_mem,
                         launch_seconds=t_launch, bytes_moved=bytes_moved,
                         cycles=cycles, dispatches=dispatches, wavefront=g,
                         occupancy=occupancy, vmem_bytes=vmem,
                         feasible=feasible)


def pipeline_cost(n: int, bw: int, tw: int, *, batch: int = 1,
                  dtype=jnp.float32, profile: DeviceProfile | None = None,
                  tape: bool = False) -> float:
    """Predicted seconds of the whole stage-2 reduction ``bw -> 1`` — the
    sum over ``tuning.stage_plan(bw, tw)`` stage costs (what
    ``measure.time_stage2(full=True)`` times, hence what the search ranks).
    ``inf`` as soon as any stage's working set misses the budget."""
    total = 0.0
    for b_in, twi in tuning.stage_plan(bw, tw):
        c = stage_cost(n, b_in, twi, batch=batch, dtype=dtype,
                       profile=profile, tape=tape)
        if not c.feasible:
            return math.inf
        total += c.seconds
    return total


# ---------------------------------------------------------------------------
# Fused small-n tier (DESIGN.md §13)
# ---------------------------------------------------------------------------

# Fast-memory (VMEM / L1-resident) streaming advantage over slow memory the
# fused kernel's in-place reflector applies enjoy.  Deliberately coarse —
# the term it scales is only compared against the staged path's
# launch-dominated cost, where the crossover is decided by the dispatch
# count, not by a few percent of compute time.
FUSED_FAST_BW_RATIO = 8.0


def fused_cost(n: int, bw: int, *, batch: int = 1, dtype=jnp.float32,
               profile: DeviceProfile | None = None,
               compute_uv: bool = False) -> CostBreakdown:
    """Predicted wall seconds of ONE fused_small dispatch over a (B, n, n)
    stack — the whole pipeline (stage 1 + every chase cycle + bisection)
    as a single launch with the matrix fast-memory resident.

    * ONE ``launch_overhead_s`` total — the entire point of the tier; the
      staged path pays one per wavefront cycle (``stage_cost``).
    * slow-memory traffic: the stack streamed in and the results out, once.
    * in-kernel work: each reflector cycle touches the (n, n) working set a
      few times (extract, matvec, rank-1 update, fix) served from fast
      memory at ``FUSED_FAST_BW_RATIO * mem_bw``; ``compute_uv`` triples it
      (A plus the two accumulators); the values path adds the vectorized
      bisection sweep.
    * infeasible when ``tuning.fused_working_set_bytes`` misses the
      profile's fast-memory budget (no fallback tiling in this tier).
    """
    prof = profile if profile is not None else profile_for()
    assert batch >= 1, batch
    s = jnp.dtype(dtype).itemsize
    bw_eff = max(1, min(bw, max(n - 1, 1)))
    vmem = tuning.fused_working_set_bytes(n, dtype, compute_uv=compute_uv)
    feasible = vmem <= prof.fast_mem_bytes
    cyc2 = (total_chase_cycles(n, bw_eff, bw_eff - 1)
            if bw_eff >= 2 and n >= 3 else 0)
    cycles = max(n - 1, 0) + cyc2
    io_words = n * n + n + (2 * n * n + 2 * n if compute_uv else 0)
    bytes_moved = float(batch) * io_words * s
    work_words = cycles * 6.0 * n * n * (3.0 if compute_uv else 1.0)
    if not compute_uv:
        max_iter = 60 if jnp.dtype(dtype).itemsize == 8 else 40
        work_words += max_iter * (2.0 * n) * (2.0 * n)   # Sturm bisection
    par = max(1.0, min(float(batch), float(prof.execution_units)))
    occupancy = max(min(1.0, batch / prof.execution_units),
                    1.0 / prof.execution_units)
    t_mem = bytes_moved / prof.mem_bw
    t_compute = (batch * work_words * s
                 / (FUSED_FAST_BW_RATIO * prof.mem_bw) / par)
    t_launch = prof.launch_overhead_s
    total = (t_mem + t_compute + t_launch) if feasible else math.inf
    return CostBreakdown(seconds=total, mem_seconds=t_mem + t_compute,
                         launch_seconds=t_launch, bytes_moved=bytes_moved,
                         cycles=cycles, dispatches=1, wavefront=1,
                         occupancy=occupancy, vmem_bytes=vmem,
                         feasible=feasible)


def predicted_crossover(bw: int, *, dtype=jnp.float32, batch: int = 8,
                        profile: DeviceProfile | None = None,
                        compute_uv: bool = False,
                        ns: tuple[int, ...] = (8, 16, 24, 32, 48, 64, 96,
                                               128, 192, 256, 384, 512, 768,
                                               1024)) -> int:
    """Model-predicted fused-vs-staged crossover: the largest n in ``ns``
    where the fused tier's per-matrix cost beats the staged stage-2 cost.

    Conservative by construction — the staged side is charged for stage 2
    only (its dispatch-dominated core) while the fused side carries the
    whole pipeline, so a real measurement can only move the crossover UP.
    Seeds ``search.search_fused_crossover``; 0 means "never fused".
    """
    prof = profile if profile is not None else profile_for()
    best = 0
    for n in sorted(ns):
        bw_eff = max(1, min(bw, max(n - 1, 1)))
        fc = fused_cost(n, bw_eff, batch=batch, dtype=dtype, profile=prof,
                        compute_uv=compute_uv)
        if not fc.feasible:
            break
        tw = max(1, min(tuning.default_tilewidth(bw_eff, dtype),
                        max(bw_eff - 1, 1)))
        staged = pipeline_cost(n, bw_eff, tw, batch=batch,
                               dtype=dtype, profile=prof, tape=compute_uv)
        if fc.seconds < staged:
            best = n
    return best


# ---------------------------------------------------------------------------
# Stage-3 solver tier (DESIGN.md §14)
# ---------------------------------------------------------------------------

# Fraction of a merge's poles/roots that stay ACTIVE after deflation in a
# typical D&C merge.  Deliberately coarse (real spectra deflate anywhere
# from ~0 to ~99%); since the solver skips all-deflated blocks on BOTH the
# root and the pole axis, the surviving quadratic work scales with the
# SQUARE of this fraction, and the measured search
# (``search.search_stage3_crossover``) overrides the prediction anyway.
DC_DEFLATION_FACTOR = 0.35

# Full-width secular passes per merge: the midpoint/anchor pass plus the
# handful of adaptive exact-polish trips the early exit typically allows
# (the windowed middle-way iterations in between are O(active * K), not
# O(m^2), and ride in the level bookkeeping below).
_DC_FULL_PASSES = 6.0

# Streaming passes one D&C merge level makes over the padded problem
# (sort, two stable partitions, Givens scan, window/heavy-pole gathers,
# z-hat recompute, vector assembly) — the O(big) bookkeeping between
# secular solves.
_DC_LEVEL_PASSES = 64.0

# Fixed word-equivalent cost per merge level, independent of problem size:
# the latency-bound parts (sequential Givens scan steps, top_k, argsorts,
# gather setup) do not stream at memory bandwidth, and at small n they, not
# the quadratic secular work, are what keeps D&C behind bisection.  5e7
# words ~ 2.5 ms on the cpu profile — calibrated so the predicted crossover
# tracks the measured one (~2048 on the dev container, fp64).
_DC_LEVEL_FLOOR_WORDS = 5.0e7


def stage3_cost(n: int, *, solver: str, dtype=jnp.float64, batch: int = 1,
                profile: DeviceProfile | None = None, leaf_n: int = 32,
                newton_iters: int = 30) -> CostBreakdown:
    """Predicted wall seconds of ONE batched stage-3 bidiagonal solve.

    Both solvers work on the Golub–Kahan tridiagonal of size ``m = 2n`` and
    are single dispatches (one jit call); they differ only in arithmetic
    volume, modeled as fast-memory streaming words:

    * ``solver="bisect"``: the lockstep Sturm sweep — ``max_iter`` fixed
      iterations, each scanning all m poles for all m roots
      (``max_iter * m^2`` words; max_iter = 60 fp64 / 40 fp32, matching
      ``core.bidiag_svd.default_bisect_iters``).
    * ``solver="dc"``: leaves solved by the same bisection at size
      ``lm ~ 2*leaf_n`` (``max_iter * lm * big`` words across all leaves),
      then ``levels = ceil(log2(big/lm))`` secular merges.  Merge sizes
      double up to ``big``, so the full-width secular passes telescope to
      ``~2 * _DC_FULL_PASSES * big^2`` scaled by the SQUARED deflation
      survival fraction (all-deflated blocks are skipped on both the root
      and the pole axis; the windowed middle-way iterations are O(m*K) and
      fold into the bookkeeping), plus ``_DC_LEVEL_PASSES * big`` streaming
      and a ``_DC_LEVEL_FLOOR_WORDS`` latency floor per level.  ``big``
      carries the power-of-two padding (up to 2x of m).

    The decisive structural difference at large n is the constant:
    ``_DC_FULL_PASSES * DC_DEFLATION_FACTOR^2`` of quadratic work against
    bisection's ``max_iter`` — below the crossover the padding and
    per-level passes make D&C the loser.  Seeds
    ``predicted_stage3_crossover``.
    """
    prof = profile if profile is not None else profile_for()
    assert solver in ("bisect", "dc"), solver
    assert batch >= 1, batch
    s = jnp.dtype(dtype).itemsize
    max_iter = 60 if s == 8 else 40
    m = max(2 * n, 1)
    if solver == "bisect":
        words = float(max_iter) * m * m
        vmem = 4 * m * s
    else:
        lm = max(1, min(2 * leaf_n, m))
        levels = 0
        big = lm
        while big < m:
            big *= 2
            levels += 1
        words = float(max_iter) * lm * big                  # leaf bisection
        alive = DC_DEFLATION_FACTOR * DC_DEFLATION_FACTOR
        words += 2.0 * _DC_FULL_PASSES * alive * big * big
        # windowed iterations: K = 128 index-nearest + 32 heavy poles/root
        words += 2.0 * newton_iters * 160.0 * DC_DEFLATION_FACTOR * big
        words += levels * (_DC_LEVEL_PASSES * big + _DC_LEVEL_FLOOR_WORDS)
        vmem = 3 * big * big * s        # eigvec two-sided products per level
    occupancy = max(min(1.0, batch / prof.execution_units),
                    1.0 / prof.execution_units)
    bytes_moved = batch * words * s
    t_mem = bytes_moved / (FUSED_FAST_BW_RATIO * prof.mem_bw) / max(
        1.0, min(float(batch), float(prof.execution_units)))
    t_launch = prof.launch_overhead_s
    return CostBreakdown(seconds=t_mem + t_launch, mem_seconds=t_mem,
                         launch_seconds=t_launch, bytes_moved=bytes_moved,
                         cycles=max_iter if solver == "bisect" else newton_iters,
                         dispatches=1, wavefront=1, occupancy=occupancy,
                         vmem_bytes=vmem, feasible=True)


def predicted_stage3_crossover(*, dtype=jnp.float64, batch: int = 1,
                               profile: DeviceProfile | None = None,
                               leaf_n: int = 32,
                               ns: tuple[int, ...] = (128, 256, 512, 1024,
                                                      2048, 4096, 8192)
                               ) -> int:
    """Model-predicted bisect-vs-D&C crossover: the smallest n in ``ns``
    from which D&C stays cheaper for every larger probed n (both curves are
    monotone in the model, so "first win that never flips back" is exact).
    Returns ``1 + max(ns)`` when D&C never wins — a beyond-any-probed-n
    threshold, NOT a miss, so ``PipelineConfig`` "auto" keeps bisection.
    Seeds ``search.search_stage3_crossover``.
    """
    prof = profile if profile is not None else profile_for()
    probe = sorted(set(int(x) for x in ns if x >= 1))
    best = 1 + (max(probe) if probe else 0)
    for n in reversed(probe):
        dc = stage3_cost(n, solver="dc", dtype=dtype, batch=batch,
                         profile=prof, leaf_n=leaf_n)
        bi = stage3_cost(n, solver="bisect", dtype=dtype, batch=batch,
                         profile=prof, leaf_n=leaf_n)
        if dc.seconds < bi.seconds:
            best = n
        else:
            break
    return best
