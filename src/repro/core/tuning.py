"""Hyperparameter heuristics and the occupancy/performance model (paper §III-C/D).

The paper exposes three knobs — inner tilewidth TW, threads-per-block TPB, and
max concurrent blocks — and shows (Fig. 4) that the dominant one is TW, whose
optimum matches the cache-line width (32 for fp32, 16 for fp64 on 128-byte
lines).  The TPU translation:

* TW         -> still the dominant knob.  The analogue of "fill one cache line"
               is "fill one 128-lane vreg row": reflector length TW+1 padded to
               the lane count.  bf16 packs 2/lane-row, fp32 1.
* TPB        -> ROWS_PER_STEP: how many band rows one grid step applies the
               reflector to per VREG pass (sublane tiling, multiples of 8).
* max blocks -> MAX_CONCURRENT_SWEEPS per core (wavefront width hosted by one
               TensorCore's grid) — beyond it, sweeps serialize in the grid,
               trading occupancy for VMEM locality exactly like the paper's
               software loop unrolling.

The occupancy model (paper Eq. 1): full utilization needs
``n / (3 * CBW) >= execution_units``; for a TPU pod the execution unit is a
TensorCore (2 per chip on v5e-class parts).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

__all__ = [
    "default_tilewidth", "rows_per_step", "SWEEP_SEPARATION",
    "max_concurrent_sweeps", "occupancy_matrix_size",
    "vmem_working_set_bytes", "check_vmem_budget",
    "fused_working_set_bytes", "check_fused_vmem_budget",
    "resident_band_layout", "resident_band_bytes", "tape_stage_lanes",
    "REPLAY_CHUNK", "replay_layout", "resident_replay_bytes",
    "replay_stripe_cols",
    "DEFAULT_FUSED_CROSSOVER", "STAGE3_CHOICES",
    "stage_plan", "default_bucket_batch", "ChaseConfig", "PipelineConfig",
]

LANE = 128          # TPU vector lane count
SUBLANE = 8         # TPU sublane count (f32)
VMEM_BUDGET_BYTES = 16 * 2 ** 20   # per-TensorCore VMEM (v4/v5-class parts)

# The paper's 3-cycle rule: sweep R starts at global cycle 3R, so the pivot
# stride between adjacent in-flight sweeps is 3*b_in - 1 >= b_in + tw + 1,
# the window width, for every valid tw <= b_in - 1 — concurrent windows are
# disjoint.  ``tests/test_batched.py`` asserts the disjointness.
SWEEP_SEPARATION = 3


def _bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def default_tilewidth(bw: int, dtype=jnp.float32) -> int:
    """Paper Fig. 4: optimal TW fills one cache line; TPU: one lane row.

    Reflector length TW+1; we pick TW so the VMEM window stays small while the
    per-row apply saturates lanes.  Capped at bw-1 (cannot peel more than the
    band).  fp32 -> 32, bf16 -> 64, fp64 (CPU oracle) -> 16, matching the
    paper's per-precision optima scaled to the TPU lane granularity.
    """
    per_line = 128 // _bytes(dtype)      # elements per 128B GPU cache line
    tw = max(8, min(per_line, LANE // 2))
    return max(1, min(tw, bw - 1))


def rows_per_step(b_in: int, tw: int, dtype=jnp.float32) -> int:
    """TPB analogue: rows applied per VREG pass, sublane-aligned."""
    rows = b_in + tw + 1
    return min(64, max(SUBLANE, SUBLANE * (rows // SUBLANE)))


def max_concurrent_sweeps(n: int, b_in: int) -> int:
    """Wavefront width (paper: #blocks) for one stage: the paper's Eq.-1
    analogue ``ceil(n / (3*CBW - 1)) + 1``, from the pivot stride
    ``SWEEP_SEPARATION * b_in - 1`` between adjacent in-flight sweeps."""
    stride = SWEEP_SEPARATION * b_in - 1
    return max(1, -(-n // stride) + 1)


def occupancy_matrix_size(cbw: int, execution_units: int) -> int:
    """Paper Eq. 1 / Table I: min n saturating all execution units."""
    return 3 * cbw * execution_units


def vmem_working_set_bytes(b_in: int, tw: int, dtype=jnp.float32, *,
                           tape: bool = False) -> int:
    """Per-slot VMEM working set of one streamed chase cycle (paper
    §III-C): the window ``(H, W)``, **x2** for the double-buffered
    BlockSpec pipeline (Pallas prefetches step i+1's window while step i
    computes — the TPU analogue of the paper's L1 residency), one
    reflector pair, and with ``tape=True`` the double-buffered tape output
    blocks of that pair."""
    h = b_in + 2 * tw + 1
    w = b_in + tw + 1
    words = 2 * h * w + 2 * (tw + 1)
    if tape:
        words += 2 * 2 * (tw + 2)
    return words * _bytes(dtype)


def check_vmem_budget(b_in: int, tw: int, dtype=jnp.float32, *,
                      tape: bool = False,
                      budget_bytes: int | None = None) -> int:
    """Raise (clearly) when the chase window's working set misses the budget.

    When ``vmem_working_set_bytes(b_in, tw)`` exceeds the budget,
    proceeding would silently mis-tile (the kernel's window could never be
    fast-memory resident, the exact regime the paper's model exists to
    exclude).  Called by
    ``ChaseConfig.resolve`` / ``PipelineConfig.resolve``; returns the
    working-set bytes on success so callers can report headroom.
    """
    budget = VMEM_BUDGET_BYTES if budget_bytes is None else budget_bytes
    need = vmem_working_set_bytes(b_in, tw, dtype, tape=tape)
    if need > budget:
        raise ValueError(
            f"chase window working set for b_in={b_in}, tw={tw}, "
            f"dtype={jnp.dtype(dtype).name} (tape={tape}) needs {need} B "
            f"of fast memory but the budget is {budget} B; "
            f"reduce the tilewidth/bandwidth (tw <= {tw} shrinks the "
            f"window H x W = (b_in + 2*tw + 1) x (b_in + tw + 1)) or "
            f"raise budget_bytes")
    return need


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def resident_band_layout(n: int, b_in: int, tw: int) -> tuple[int, int]:
    """(rows, lanes) of the band-resident stage kernel's VMEM band
    (DESIGN.md §9): the band, transposed and row-reversed — the n + W
    columns a window can touch, sublane-aligned, by the H stored diagonals
    padded to the lane width.  Its shear workspace is (lanes, lanes)."""
    w = b_in + tw + 1
    return _round_up(n + w, SUBLANE), _round_up(b_in + 2 * tw + 1, LANE)


def resident_band_bytes(n: int, b_in: int, tw: int, dtype=jnp.float32, *,
                        tape: bool = False) -> int:
    """VMEM bytes the band-resident stage kernel allocates for one matrix:
    one copy of the band (copied in and out by hand, not double-buffered),
    the shear workspace, and the (H, W) window, each at its tiled size;
    with ``tape``, also the two reflector-tape staging slots of (2G,
    ``tape_stage_lanes(tw)``) each.  ``reduce_stage_packed`` takes the
    resident path only when this fits ``VMEM_BUDGET_BYTES``."""
    rows, lanes = resident_band_layout(n, b_in, tw)
    h, w = b_in + 2 * tw + 1, b_in + tw + 1
    words = (rows * lanes + lanes * lanes
             + _round_up(h, SUBLANE) * _round_up(w, LANE))
    if tape:
        pairs = _round_up(2 * max_concurrent_sweeps(n, b_in), SUBLANE)
        words += 2 * pairs * tape_stage_lanes(tw)
    return words * _bytes(dtype)


def tape_stage_lanes(tw: int) -> int:
    """Lanes of one reflector-tape row of the band-resident stage kernel:
    v's tw + 1 entries, then tau, padded to the lane width."""
    return _round_up(tw + 2, LANE)


# Cycles of chase tape per DMA of the resident replay kernel: a multiple of
# the 3-cycle sweep separation, so the kernel splits t = 3*t3 + r statically,
# and of every count of cycles one tape block may hold (1 to 4).
REPLAY_CHUNK = 12


def replay_layout(n: int, b_in: int, tw: int) -> tuple[int, int, int, int]:
    """(rows, block_rows, lanes, cycles_per_block) of the resident replay
    kernel (DESIGN.md §8).  One apply touches an 8-aligned block of
    ``block_rows`` = round_up(tw + 9, 8) rows starting at p - p % 8,
    which holds the reflector's rows [p, p + tw] whatever p % 8 is, and
    one row more; the accumulator stripe has ``rows`` = round_up(n +
    block_rows, 8), so a block at a pivot p <= n - 1 stays inside it.  A
    tape block of (block_rows, lanes) holds ``cycles_per_block`` cycles of
    one side: cycle i's slot g in lane i * G + g, v shifted down to row
    p % 8 and tau in the last row."""
    g = max_concurrent_sweeps(n, b_in)
    blk = _round_up(tw + SUBLANE + 1, SUBLANE)
    lanes = _round_up(g, LANE)
    per_block = max(c for c in (1, 2, 3, 4) if c * g <= lanes)
    return _round_up(n + blk, SUBLANE), blk, lanes, per_block


def resident_replay_bytes(n: int, b_in: int, tw: int, cols: int,
                          dtype=jnp.float32) -> int:
    """VMEM bytes the resident replay kernel allocates for a stripe of
    ``cols`` accumulator columns: the (rows, cols) stripe, copied in and
    out by hand, and two tape buffers of ``REPLAY_CHUNK`` cycles each."""
    rows, blk, lanes, per_block = replay_layout(n, b_in, tw)
    tape = 2 * (REPLAY_CHUNK // per_block) * blk * lanes
    return (rows * cols + tape) * _bytes(dtype)


def replay_stripe_cols(n: int, b_in: int, tw: int, dtype=jnp.float32, *,
                       budget_bytes: int | None = None) -> int:
    """Column-stripe width of the resident replay: the fewest stripes of
    whole 128-lane tiles whose widest fits ``budget_bytes`` (default
    ``VMEM_BUDGET_BYTES``) as :func:`resident_replay_bytes` counts it, each
    as narrow as that count allows; 0 when not even one lane tile fits."""
    budget = VMEM_BUDGET_BYTES if budget_bytes is None else budget_bytes
    width = _round_up(n, LANE)
    widest = width
    while widest and resident_replay_bytes(n, b_in, tw, widest,
                                           dtype) > budget:
        widest -= LANE
    if not widest:
        return 0
    stripes = -(-width // widest)
    return _round_up(-(-n // stripes), LANE)


# Default fused-vs-staged crossover (DESIGN.md §13): the ROADMAP names
# n <= 256 as the launch-bound serve regime; the autotuner's measured
# crossover (autotune.search.search_fused_crossover, persisted per
# device/dtype) replaces this when available.
DEFAULT_FUSED_CROSSOVER = 256

# Stage-3 solver policy values (DESIGN.md §14).  "bisect" is the lockstep
# Sturm bisection (O(n^2) work, bit-stable oracle), "dc" the batched
# divide-and-conquer solve (O(n log n) secular merges — wins for large n),
# "auto" picks per problem size via ``PipelineConfig.stage3_for``.
STAGE3_CHOICES = ("bisect", "dc", "auto")


def fused_working_set_bytes(n: int, dtype=jnp.float32, *,
                            compute_uv: bool = False) -> int:
    """VMEM bytes one fused_small grid step keeps resident (DESIGN.md §13).

    The whole (n, n) matrix lives in VMEM for the kernel's lifetime; the
    reflector scratch is a handful of (n,) vectors plus the (m = 2n-1)
    bisection state; ``compute_uv`` adds the two (n, n) transform
    accumulators.  Pallas double-buffers the block pipeline, hence the
    factor 2 on the streamed operands.
    """
    s = _bytes(dtype)
    mats = (3 if compute_uv else 1) * n * n
    scratch = 12 * n
    return 2 * mats * s + scratch * s


def check_fused_vmem_budget(n: int, dtype=jnp.float32, *,
                            compute_uv: bool = False,
                            budget_bytes: int | None = None) -> int:
    """Raise when one matrix cannot be VMEM-resident for the fused kernel.

    The fused tier has no fallback tiling — its whole point is the matrix
    never leaving fast memory — so an oversized n must be rejected up front
    (the engines then keep such buckets on the staged path).  Returns the
    working-set bytes on success.
    """
    budget = VMEM_BUDGET_BYTES if budget_bytes is None else budget_bytes
    need = fused_working_set_bytes(n, dtype, compute_uv=compute_uv)
    if need > budget:
        raise ValueError(
            f"fused_small working set for n={n}, "
            f"dtype={jnp.dtype(dtype).name} (compute_uv={compute_uv}) "
            f"needs {need} B of fast memory but the budget is {budget} B; "
            f"route this bucket to the staged pipeline instead")
    return need


def stage_plan(bw: int, tw: int) -> tuple[tuple[int, int], ...]:
    """Tile-width schedule: ((b_in, tw_i), ...) reducing bw -> 1, <= tw/stage."""
    plan = []
    b = bw
    while b > 1:
        twi = min(tw, b - 1)
        plan.append((b, twi))
        b -= twi
    return tuple(plan)


def default_bucket_batch(n: int, b_in: int, execution_units: int = 2,
                         oversub: int = 8) -> int:
    """Batch size that refills the wavefront when one matrix cannot (Eq. 1).

    A single matrix hosts ``max_concurrent_sweeps(n, b_in)`` concurrent
    windows; full utilization wants at least one per execution unit (paper
    Eq. 1), and ``oversub``x that to hide the gather/scatter latency between
    cycles (the paper's concurrent-blocks headroom).  Independent problems in
    a batch multiply the wavefront width, so the deficit is made up by
    batching.  Clamped to [1, 64].
    """
    per_matrix = max_concurrent_sweeps(n, b_in)
    want = execution_units * oversub
    return max(1, min(64, -(-want // per_matrix)))


@dataclasses.dataclass(frozen=True)
class ChaseConfig:
    """Resolved hyperparameters for one reduction stage."""
    b_in: int
    tw: int
    rows_per_step: int
    max_sweeps: int

    @staticmethod
    def resolve(n: int, b_in: int, dtype=jnp.float32, tw: int | None = None
                ) -> "ChaseConfig":
        tw = tw if tw is not None else default_tilewidth(b_in, dtype)
        check_vmem_budget(b_in, tw, dtype)
        return ChaseConfig(
            b_in=b_in, tw=tw,
            rows_per_step=rows_per_step(b_in, tw, dtype),
            max_sweeps=max_concurrent_sweeps(n, b_in),
        )


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Fully-resolved configuration for the three-stage pipeline.

    Extends :class:`ChaseConfig` from one reduction stage to the whole
    pipeline: it owns the concrete kernel backend (resolved once through the
    registry in ``kernels/ops.py`` — no "auto" strings survive resolution),
    the tile-width schedule ``bw -> 1``, and the serve-layer batch/bucket
    sizes.  It is hashable (all-primitive fields), so it can be a static jit
    argument, and it is the ONE object every layer accepts: ``core/svd.py``,
    ``core/bulge_chasing.py``, ``core/stage1.py``, ``kernels/ops.py`` and
    ``serve/engine.py`` all take ``config=`` instead of loose
    ``backend=``/``tw=`` strings (the legacy kwargs remain as overrides).
    """
    bw: int                     # stage-1 output / stage-2 input bandwidth
    tw: int                     # inner tilewidth (dominant knob, paper Fig. 4)
    backend: str                # concrete registry key ("ref", "pallas", ...)
    interpret: bool             # Pallas interpret mode (CPU correctness runs)
    dtype: str = "float32"      # working precision of stages 1-2
    max_batch: int = 8          # serve bucket capacity (leading batch axis B)
    compute_uv: bool = False    # full SVD: record + replay reflector tapes
    stage3: str = "bisect"      # bidiagonal solver: "bisect" | "dc" | "auto"
    dc_leaf_n: int = 32         # D&C recursion floor (leaves solve by bisection)
    dc_n_min: int = 2048        # "auto" routes n >= dc_n_min to "dc"

    @property
    def plan(self) -> tuple[tuple[int, int], ...]:
        """The tile-width schedule ((b_in, tw_i), ...) down to bidiagonal."""
        return stage_plan(self.bw, self.tw)

    def stage3_for(self, n: int) -> str:
        """Concrete stage-3 solver for a problem of size n.

        ``stage3="auto"`` survives :meth:`resolve` only when no ``n`` was
        known at resolution time (serve engines size buckets later); this is
        where it collapses: "dc" iff ``n >= dc_n_min`` (the measured or
        default crossover), else "bisect".  Explicit policies pass through.
        """
        if self.stage3 != "auto":
            return self.stage3
        return "dc" if n >= self.dc_n_min else "bisect"

    def kernel(self) -> "PipelineConfig":
        """Identity for the traced computation: serve-only fields (max_batch)
        are normalized so configs differing only in bucket sizing share one
        jit cache entry instead of recompiling the numeric pipeline."""
        return dataclasses.replace(self, max_batch=0)

    def chase(self, n: int, b_in: int | None = None) -> ChaseConfig:
        """Per-stage view (the legacy ChaseConfig) for a given problem size."""
        return ChaseConfig.resolve(n, b_in if b_in is not None else self.bw,
                                   jnp.dtype(self.dtype), tw=self.tw)

    @classmethod
    def resolve(cls, *, bw: int = 32, tw: int | None = None,
                backend: str = "auto", interpret: bool | None = None,
                dtype=jnp.float32, n: int | None = None,
                max_batch: int | None = None, compute_uv: bool = False,
                autotune: bool = False,
                autotune_cache: str | None = None,
                stage3: str = "bisect", dc_leaf_n: int | None = None,
                dc_n_min: int | None = None) -> "PipelineConfig":
        """Resolve every knob to a concrete value.

        ``backend="auto"`` and ``interpret=None`` are resolved by the backend
        registry (pallas on TPU, ref elsewhere and for float64 on TPU;
        interpret off-TPU only);
        ``tw=None`` falls back to the cache-line/lane heuristic;
        ``max_batch=None`` uses the Eq.-1 occupancy deficit for (n, bw).
        ``bw`` is clamped to >= 1 (bw = 0 — e.g. a 1x1 problem — would zero
        the stage-1 panel width; a bw-1 "band" is already bidiagonal, so
        stage 2 is a no-op pass-through either way).  A (bw, tw) pair whose
        chase window cannot be fast-memory resident raises
        (``check_vmem_budget``) instead of silently mis-tiling.

        ``autotune=True`` (DESIGN.md §11) consults the persistent tuned
        cache (``repro.autotune.cache``, keyed by device kind, n, bw,
        dtype, compute_uv and the RESOLVED backend) and uses the measured
        optimum for every knob still at its neutral default — ``tw=None``,
        ``max_batch=None``; explicit values always win.  On a cache miss (or without ``n``) the analytic defaults
        above apply unchanged.  ``autotune_cache`` overrides the cache
        path (else ``$REPRO_AUTOTUNE_CACHE`` / the XDG default).

        ``stage3`` picks the bidiagonal solver (DESIGN.md §14): "bisect"
        (the default — the lockstep Sturm oracle), "dc" (the batched
        divide-and-conquer solve of ``core.bidiag_dc``), or "auto" — "dc"
        iff ``n >= dc_n_min``.  ``dc_n_min=None`` takes the measured
        stage-3 crossover from the autotune cache when ``autotune=True``
        (``cache.lookup_stage3``), else the static default
        ``core.bidiag_dc.DEFAULT_DC_N_MIN``; ``dc_leaf_n=None`` means
        ``DEFAULT_DC_LEAF_N``.  With ``n`` known "auto" collapses here; on
        an n-free resolve the string survives and :meth:`stage3_for`
        collapses it per problem size (the serve engines' per-bucket path).
        """
        from repro.kernels import ops  # deferred: registry lives kernels-side

        bw = max(bw, 1)
        if n is not None:
            bw = min(bw, max(n, 1))
        backend, interpret = ops.resolve_backend(backend, interpret, dtype)
        tuned = None
        if autotune and n is not None:
            from repro.autotune import cache as _at_cache   # deferred: cycle
            from repro.autotune import model as _at_model
            tuned = _at_cache.lookup(
                device_kind=_at_model.device_kind(), n=n, bw=bw,
                dtype=jnp.dtype(dtype).name, compute_uv=compute_uv,
                backend=backend, path=autotune_cache)
        if tuned is not None:
            tw = tw if tw is not None else tuned["tw"]
            if max_batch is None:
                # max_batch is only in the entry when the search actually
                # explored the batch axis; otherwise the Eq.-1 analytic
                # default below stays in charge of bucket sizing.
                max_batch = tuned.get("max_batch")
        tw = tw if tw is not None else default_tilewidth(bw, dtype)
        tw = max(1, min(tw, max(bw - 1, 1)))
        check_vmem_budget(bw, tw, dtype, tape=compute_uv)
        if backend == "fused_small" and n is not None:
            # the fused tier keeps the whole matrix VMEM-resident: infeasible
            # n must fail here, not silently spill inside the kernel
            check_fused_vmem_budget(n, dtype, compute_uv=compute_uv)
        if max_batch is None:
            max_batch = default_bucket_batch(n, bw) if n else 8
        if stage3 not in STAGE3_CHOICES:
            raise ValueError(f"stage3 must be one of {STAGE3_CHOICES}, "
                             f"got {stage3!r}")
        from repro.core import bidiag_dc as _dc   # deferred: import cycle
        if dc_leaf_n is None:
            dc_leaf_n = _dc.DEFAULT_DC_LEAF_N
        if dc_n_min is None:
            tuned_x = None
            if autotune:
                from repro.autotune import cache as _at_cache
                from repro.autotune import model as _at_model
                tuned_x = _at_cache.lookup_stage3(
                    device_kind=_at_model.device_kind(),
                    dtype=jnp.dtype(dtype).name, compute_uv=compute_uv,
                    path=autotune_cache)
            dc_n_min = tuned_x if tuned_x is not None else _dc.DEFAULT_DC_N_MIN
        dc_leaf_n = max(int(dc_leaf_n), 1)
        dc_n_min = max(int(dc_n_min), 1)
        if stage3 == "auto" and n is not None:
            stage3 = "dc" if n >= dc_n_min else "bisect"
        return cls(bw=bw, tw=tw, backend=backend, interpret=interpret,
                   dtype=jnp.dtype(dtype).name, max_batch=max_batch,
                   compute_uv=compute_uv, stage3=stage3,
                   dc_leaf_n=dc_leaf_n, dc_n_min=dc_n_min)

    @classmethod
    def of(cls, config: "PipelineConfig | None", *, bw: int | None = None,
           tw: int | None = None, backend: str = "auto", dtype=jnp.float32,
           n: int | None = None) -> "PipelineConfig":
        """Adopt an already-resolved config, or resolve the legacy kwargs.

        Passing BOTH a config and a conflicting legacy kwarg (or input dtype)
        raises — the config is supposed to be the single source of truth, and
        silently preferring either side would mask the mistake at the call
        site.  The returned config is ``kernel()``-normalized (it feeds the
        jit static args of the numeric path).
        """
        if config is not None:
            if bw is not None and bw != config.bw:
                raise ValueError(f"bw={bw} conflicts with config.bw={config.bw}")
            if tw is not None and tw != config.tw:
                raise ValueError(f"tw={tw} conflicts with config.tw={config.tw}")
            if backend not in ("auto", config.backend):
                raise ValueError(f"backend={backend!r} conflicts with "
                                 f"config.backend={config.backend!r}")
            if dtype is not None and jnp.dtype(dtype).name != config.dtype:
                raise ValueError(f"input dtype {jnp.dtype(dtype).name} "
                                 f"conflicts with config.dtype={config.dtype}")
            return config.kernel()
        return cls.resolve(bw=bw if bw is not None else 32, tw=tw,
                           backend=backend, dtype=dtype, n=n).kernel()
