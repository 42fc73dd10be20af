"""Three-stage SVD pipeline (paper §I), batch-native:

  dense --stage1--> banded --stage2 (paper: bulge chasing)--> bidiagonal
        --stage3--> singular values [+ vectors via reflector-tape replay]

``singular_values`` runs all three stages on-device; ``banded_singular_values``
enters at stage 2 (the paper's direct use case: banded inputs from spectral
PDE methods etc.).  All functions are jit-friendly, dtype-polymorphic, and
accept leading batch axes: a stacked ``(B, n, n)`` input runs the whole
pipeline batch-native — stage 2 merges all B wavefronts into one fused kernel
call per global cycle (grid ``(B·G,)``), which is how small matrices recover
the occupancy a single chase cannot reach (paper Eq. 1; DESIGN.md §4).
``batched_singular_values`` / ``svd_batched`` make the batched contract
explicit; the serve layer (``serve/engine.py``) buckets traffic onto them.

Full SVD (beyond-paper; the paper names transform accumulation as §VII
future work): ``svd(a)`` / ``svd_batched(..., compute_uv=True)`` /
``banded_svd(a)`` return ``(U, sigma, V^T)``.  Stages 1–2 run in ``tape``
mode (recording every Householder reflector, DESIGN.md §8),
``core/transforms.py`` replays the tapes into U/V^T with the chase's own
wavefront batching, and stage 3 adds the bidiagonal's vectors via inverse
iteration seeded by the same Sturm bisection — sigma is bit-identical to
the values-only path.

Configuration: every entry point takes ``config=``, a resolved
``tuning.PipelineConfig`` that owns the backend (kernel registry key), the
tile-width schedule, batch sizing, and the ``compute_uv`` default.  The
legacy ``bw=/tw=/backend=`` kwargs remain and are resolved into a config
internally; passing a kwarg that conflicts with a supplied config raises:

    cfg = PipelineConfig.resolve(bw=16, dtype=jnp.float32)   # once
    sigma = svd_batched(stacked, config=cfg)                 # everywhere
    u, s, vt = svd_batched(stacked, config=cfg, compute_uv=True)
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bulge_chasing as bc
from repro.core import stage1 as s1
from repro.core import bidiag_dc as s3dc
from repro.core import bidiag_svd as s3
from repro.core import transforms
from repro.core import tuning
from repro.core.householder import exact_matmul
from repro.kernels import ops

__all__ = ["singular_values", "banded_singular_values", "bidiagonal_of",
           "batched_singular_values", "svd_batched", "svd", "banded_svd",
           "NumericalFault", "validate_sigma", "validate_uv",
           "spot_check_svd"]


# ---------------------------------------------------------------------------
# Numerical-health guards (DESIGN.md §15)
# ---------------------------------------------------------------------------

class NumericalFault(ArithmeticError):
    """A pipeline result failed post-solve validation (non-finite,
    negative, or unsorted sigma; non-finite vectors; residual blow-up).

    Raised by :func:`validate_sigma` / :func:`validate_uv` /
    :func:`spot_check_svd` — and by the entry points below under
    ``check=True``.  The serve retry layer (DESIGN.md §15) treats it as
    retryable-once-then-degrade: a numerically-poisoned dispatch rarely
    heals on replay, so after one retry the request is re-served on the
    trusted ref tier instead of burning more attempts.
    """


def _sigma_tol(s: np.ndarray) -> float:
    """Slack for the non-negativity / descending-order checks: rounding
    may leave sigma off by a few ulps of the spectrum's scale."""
    if s.size == 0:
        return 0.0
    eps = np.finfo(s.dtype).eps if np.issubdtype(s.dtype, np.floating) else 0.0
    smax = float(np.max(np.abs(s[np.isfinite(s)]))) if np.isfinite(s).any() \
        else 1.0
    return 16.0 * eps * max(smax, 1.0)


def validate_sigma(sig, *, name: str = "sigma") -> None:
    """Cheap post-solve health check on a sigma block (any leading axes):
    every value finite, non-negative (to rounding slack), and descending
    along the last axis.  Raises :class:`NumericalFault` on violation.

    Runs on host (forces a device sync) — call it OUTSIDE jit, after the
    result is already needed on host anyway (the serve engines validate
    the numpy block they are about to hand to callers).
    """
    s = np.asarray(sig)
    if s.size == 0:
        return
    if not np.isfinite(s).all():
        bad = int(np.size(s) - np.count_nonzero(np.isfinite(s)))
        raise NumericalFault(f"{name}: {bad} non-finite value(s)")
    tol = _sigma_tol(s)
    mn = float(s.min())
    if mn < -tol:
        raise NumericalFault(f"{name}: negative value {mn:.3e} < -{tol:.1e}")
    if s.shape[-1] >= 2:
        rise = float((s[..., 1:] - s[..., :-1]).max())
        if rise > tol:
            raise NumericalFault(
                f"{name}: not descending (adjacent rise {rise:.3e} "
                f"> {tol:.1e})")


def validate_uv(u, vt, *, name: str = "uv") -> None:
    """Finiteness check on the accumulated singular-vector factors."""
    for tag, m in (("U", u), ("V^T", vt)):
        if m is None:
            continue
        a = np.asarray(m)
        if not np.isfinite(a).all():
            raise NumericalFault(f"{name}: non-finite entries in {tag}")


def spot_check_svd(a, u, sig, vt, *, rtol: float | None = None) -> None:
    """Residual and orthogonality spot-check on the FIRST matrix of a
    (possibly batched) full-SVD result — three matmuls of one matrix, not
    a per-matrix sweep.  Raises :class:`NumericalFault` when the relative
    residual ``||A - U diag(s) V^T||_F / ||A||_F`` or the orthogonality
    ``max(||U^T U - I||_F, ||V^T V - I||_F) / sqrt(n)`` exceeds ``rtol``
    (default: ``50 * n * eps`` of the working dtype, loose enough for every
    healthy backend).  A residual alone passes factors that are not
    orthogonal but still multiply back to A."""
    a = np.asarray(a).reshape((-1,) + np.asarray(a).shape[-2:])[0]
    u0 = np.asarray(u).reshape((-1,) + np.asarray(u).shape[-2:])[0]
    vt0 = np.asarray(vt).reshape((-1,) + np.asarray(vt).shape[-2:])[0]
    s0 = np.asarray(sig).reshape((-1, np.asarray(sig).shape[-1]))[0]
    n = a.shape[-1]
    if rtol is None:
        rtol = 50.0 * n * float(np.finfo(a.dtype).eps)
    denom = max(float(np.linalg.norm(a)), np.finfo(a.dtype).tiny)
    resid = float(np.linalg.norm(a - (u0 * s0) @ vt0)) / denom
    if not np.isfinite(resid) or resid > rtol:
        raise NumericalFault(
            f"residual spot-check failed: ||A - USV^T||/||A|| = "
            f"{resid:.3e} > {rtol:.1e} (n={n})")
    u0, vt0 = (x.astype(np.promote_types(x.dtype, np.float32))
               for x in (u0, vt0))
    eye = np.eye(n, dtype=u0.dtype)
    orth = max(float(np.linalg.norm(u0.T @ u0 - eye)),
               float(np.linalg.norm(vt0 @ vt0.T - eye))) / np.sqrt(n)
    if not np.isfinite(orth) or orth > rtol:
        raise NumericalFault(
            f"orthogonality spot-check failed: ||U^T U - I||/sqrt(n) or "
            f"||V^T V - I||/sqrt(n) = {orth:.3e} > {rtol:.1e} (n={n})")


def _stage3_values(d: jax.Array, e: jax.Array,
                   cfg: tuning.PipelineConfig) -> jax.Array:
    """Stage-3 dispatch (DESIGN.md §14): the config's ``stage3`` policy picks
    the bidiagonal solver — Sturm bisection (the oracle) or the batched
    divide-and-conquer solve, "auto" collapsing per problem size through
    ``stage3_for``.  Both accept leading batch axes and agree on sigma to
    ~1e-12 relative (gated by tests/test_bidiag_dc.py)."""
    solver = cfg.stage3_for(d.shape[-1])
    with obs.span("stage3", solver=solver):
        if solver == "dc":
            return s3dc.bidiag_dc_singular_values(d, e, leaf_n=cfg.dc_leaf_n)
        return s3.bidiag_singular_values(d, e)


def _stage3_svd(d: jax.Array, e: jax.Array, cfg: tuning.PipelineConfig):
    """Full-SVD stage-3 dispatch; both solvers share the inverse-iteration
    vector machinery, so (U, V^T) quality is policy-independent."""
    solver = cfg.stage3_for(d.shape[-1])
    with obs.span("stage3", solver=solver, compute_uv=True):
        if solver == "dc":
            return s3dc.bidiag_dc_svd(d, e, leaf_n=cfg.dc_leaf_n)
        return s3.bidiag_svd(d, e)


@contextlib.contextmanager
def _entry(name: str, trace):
    """The root span of one entry-point call (DESIGN.md §16), recorded into
    ``trace`` when one is given, else into the ambient tracer, if any.  The
    tracer only chooses where spans are recorded: the code path is the
    same with or without one."""
    with obs.activated(trace), obs.span(name) as root:
        yield root


def _config(a, config, **legacy) -> tuning.PipelineConfig:
    with obs.span("config"):
        return tuning.PipelineConfig.of(config, dtype=a.dtype, n=a.shape[-1],
                                        **legacy)


def _validated(sig):
    """``sig`` after the post-solve health guard (:func:`validate_sigma`)."""
    with obs.span("validate"):
        validate_sigma(sig)
    return sig


def _span_attrs(a, cfg: tuning.PipelineConfig, **extra) -> dict:
    lead = a.shape[:-2]
    batch = 1
    for dim in lead:
        batch *= int(dim)
    return dict(n=int(a.shape[-1]), bw=cfg.bw, tw=cfg.tw, dtype=str(a.dtype),
                backend=cfg.backend, batch=batch, **extra)


@jax.jit
@obs.scope("compose")
def _compose(u2, ub, vtb, vt2):
    """The singular vectors of A from the reduction's transforms and the
    bidiagonal's: A = U2 B V2^T and B = Ub S Vb^T, so U = U2 Ub and
    V^T = Vb^T V2^T.  Its own ``repro.compose`` device scope."""
    return exact_matmul(u2, ub), exact_matmul(vtb, vt2)


def _fused_path(a: jax.Array, cfg: tuning.PipelineConfig, *,
                compute_uv: bool):
    """DESIGN.md §13: the one-dispatch fused small-n tier.

    Any entry point whose resolved config says ``backend="fused_small"``
    lands here instead of the staged pipeline.  Banded inputs need no
    separate path — the in-kernel stage-1 reflectors are exact no-ops on
    already-zero tails.  Values mode is one dispatch end to end; uv mode is
    two (the fused reduction, then one batched ``bidiag_svd`` composing the
    vectors from the kernel's accumulated transforms).
    """
    lead = a.shape[:-2]
    n = a.shape[-1]
    mats = a.reshape((-1,) + a.shape[-2:])
    if not compute_uv:
        with obs.span("fused"):
            sig = ops.fused_svd(mats, bw=cfg.bw, compute_uv=False, config=cfg)
        return sig.reshape(lead + (n,))
    with obs.span("fused", compute_uv=True):
        d, e, u2, vt2 = ops.fused_svd(mats, bw=cfg.bw, compute_uv=True,
                                      config=cfg)
    ub, sig, vtb = _stage3_svd(d, e, cfg)
    with obs.span("compose"):
        u, vt = _compose(u2, ub, vtb, vt2)
    return (u.reshape(lead + (n, n)), sig.reshape(lead + (n,)),
            vt.reshape(lead + (n, n)))


def bidiagonal_of(a: jax.Array, *, bw: int | None = None,
                  tw: int | None = None, backend: str = "auto",
                  config: tuning.PipelineConfig | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Stage 2 only: dense upper-banded (..., n, n) -> (diag, superdiag)."""
    cfg = tuning.PipelineConfig.of(config, bw=bw, tw=tw, backend=backend,
                                   dtype=a.dtype, n=a.shape[-1])
    return bc.bidiagonalize(a, bw=cfg.bw, tw=cfg.tw, config=cfg)


def banded_singular_values(a: jax.Array, *, bw: int | None = None,
                           tw: int | None = None, backend: str = "auto",
                           config: tuning.PipelineConfig | None = None,
                           check: bool = False, trace=None) -> jax.Array:
    """Singular values of upper-banded (..., n, n) (stages 2+3), descending.

    ``check=True`` runs the post-solve health guard (:func:`validate_sigma`,
    DESIGN.md §15) on the result — raising :class:`NumericalFault` instead
    of returning garbage when a chase went numerically bad.  It forces a
    host sync, so leave it off inside jit-hot loops.

    ``trace=`` takes a :class:`repro.obs.Tracer` (DESIGN.md §16) to record
    this call's spans into (``config``, ``pack``, one ``stage2`` per stage
    of the tile-width plan, ``extract``, ``stage3``, ``validate``); an
    ambient tracer (``obs.activated``/``obs.install``) records them too.
    Either way the call runs the same executables.
    """
    with _entry("banded_singular_values", trace) as root:
        cfg = _config(a, config, bw=bw, tw=tw, backend=backend)
        root.set(**_span_attrs(a, cfg))
        if cfg.backend == "fused_small":
            sig = _fused_path(a, cfg, compute_uv=False)
        else:
            d, e = bc.bidiagonalize(a, bw=cfg.bw, tw=cfg.tw, config=cfg)
            sig = _stage3_values(d, e, cfg)
        return _validated(sig) if check else sig


@functools.partial(jax.jit, static_argnames=("config",))
def _three_stage(a: jax.Array, *, config: tuning.PipelineConfig) -> jax.Array:
    banded = s1.band_reduce(a, nb=config.bw, config=config)
    d, e = bc.bidiagonalize(banded, bw=config.bw, tw=config.tw, config=config)
    return _stage3_values(d, e, config)


def singular_values(a: jax.Array, *, bw: int | None = None,
                    tw: int | None = None, backend: str = "auto",
                    config: tuning.PipelineConfig | None = None,
                    check: bool = False, trace=None) -> jax.Array:
    """All singular values of dense (..., n, n), descending (3 stages).

    ``bw`` defaults to 32 when neither it nor ``config`` is given; passing a
    legacy kwarg that CONFLICTS with a supplied config raises (no silent
    precedence).  Config resolution happens outside the jit boundary, and the
    config's serve-only fields are normalized out of the cache key, so
    configs differing only in bucket sizing do not recompile.

    ``check=True`` validates the result post-solve (finite, non-negative,
    descending — :func:`validate_sigma`) and raises
    :class:`NumericalFault` on violation (DESIGN.md §15).

    ``trace=`` (or an ambient ``repro.obs`` tracer) records this call's
    host spans (DESIGN.md §16).  The three stages run as one jitted
    executable, so they have no host spans of their own: their device time
    is found in a profiler trace by the ``repro.stage1``/``repro.stage2``/
    ``repro.stage3`` scopes in each op's ``op_name`` (``obs.scope``).
    """
    with _entry("singular_values", trace) as root:
        cfg = _config(a, config, bw=bw, tw=tw, backend=backend)
        root.set(**_span_attrs(a, cfg))
        if cfg.backend == "fused_small":
            sig = _fused_path(a, cfg, compute_uv=False)
        else:
            sig = _three_stage(a, config=cfg)
        return _validated(sig) if check else sig


def batched_singular_values(mats: jax.Array, *, bw: int | None = None,
                            tw: int | None = None, backend: str = "auto",
                            config: tuning.PipelineConfig | None = None,
                            check: bool = False, trace=None) -> jax.Array:
    """Batch-native three-stage pipeline: (B, n, n) -> (B, n) descending.

    Unlike a vmapped loop, the B chases share one wavefront: every global
    cycle issues a single fused kernel call over all B*G windows.  For small
    n this is the difference between an idle and a saturated chip.
    """
    assert mats.ndim == 3, f"expected stacked (B, n, n), got {mats.shape}"
    return singular_values(mats, bw=bw, tw=tw, backend=backend, config=config,
                           check=check, trace=trace)


def svd_batched(mats: jax.Array,
                config: tuning.PipelineConfig | None = None, *,
                compute_uv: bool | None = None, trace=None, **overrides):
    """Config-first batched entry point: ``svd_batched(stacked, cfg)``.

    Sugar over :func:`batched_singular_values` for callers that already hold
    a resolved :class:`tuning.PipelineConfig` (the serve engine, benchmarks).
    ``overrides`` are the legacy ``bw=/tw=/backend=`` kwargs (conflicts with
    the config raise).  ``compute_uv=True`` (or a config with
    ``compute_uv=True``) returns ``(U, sigma, V^T)`` instead of sigma alone;
    sigma is bit-identical between the two modes.
    """
    if compute_uv is None:
        compute_uv = config.compute_uv if config is not None else False
    if compute_uv:
        assert mats.ndim == 3, f"expected stacked (B, n, n), got {mats.shape}"
        return svd(mats, config=config, compute_uv=True, trace=trace,
                   **overrides)
    return batched_singular_values(mats, config=config, trace=trace,
                                   **overrides)


# ---------------------------------------------------------------------------
# Full SVD: reflector tapes -> (U, sigma, V^T)
# ---------------------------------------------------------------------------

def _uv_pipeline(a: jax.Array, *, config: tuning.PipelineConfig,
                 banded: bool):
    """Tape-mode pipeline: returns (U, sigma, V^T) with A = U diag(s) V^T.

    Stage-1/2 band arithmetic is identical to the values-only path (the tape
    is recorded alongside, never read by it), so (d, e) — and the bisection
    sigma — are bit-identical.  The tapes are then replayed into transposed
    accumulators (``transforms.accumulate_transforms``: the resident
    ``replay_stage`` kernel or the streamed ``tape_apply`` loop per chase
    stage), and stage 3's bidiagonal vectors are composed on top.
    """
    n = a.shape[-1]
    lead = a.shape[:-2]
    if banded:
        s1_tape = None
        band_in = a
    else:
        with obs.span("stage1", **_span_attrs(a, config, tape=True)):
            band_in, s1_tape = s1.band_reduce(a, nb=config.bw, config=config,
                                              tape=True)
        obs.count_tape_bytes("stage1", sum(x.size * x.dtype.itemsize
                                           for x in s1_tape))
    d, e, chase_tapes = bc.bidiagonalize(band_in, bw=config.bw, tw=config.tw,
                                         config=config, tape=True)
    with obs.span("replay", n=int(n)):
        u2, vt2 = transforms.accumulate_transforms(
            n, s1_tape=s1_tape, chase_tapes=chase_tapes, lead=lead,
            dtype=a.dtype, config=config)
    ub, sig, vtb = _stage3_svd(d, e, config)
    with obs.span("compose"):
        u, vt = _compose(u2, ub, vtb, vt2)
    return u, sig, vt


def _checked_uv(a, out, *, check: bool):
    """Post-solve health guard for a full-SVD result (DESIGN.md §15):
    sigma invariants, U/V^T finiteness, and the one-matrix residual and
    orthogonality spot-check — the cheapest test that the FACTORS (not
    just the spectrum) are trustworthy."""
    if check:
        u, sig, vt = out
        with obs.span("validate"):
            validate_sigma(sig)
            validate_uv(u, vt)
            spot_check_svd(a, u, sig, vt)
    return out


def _full_svd(name: str, a: jax.Array, *, banded: bool, bw, tw, backend,
              config, check: bool, trace):
    with _entry(name, trace) as root:
        cfg = _config(a, config, bw=bw, tw=tw, backend=backend)
        root.set(**_span_attrs(a, cfg, compute_uv=True))
        if cfg.backend == "fused_small":
            out = _fused_path(a, cfg, compute_uv=True)
        else:
            out = _uv_pipeline(a, config=cfg, banded=banded)
        return _checked_uv(a, out, check=check)


def svd(a: jax.Array, *, bw: int | None = None, tw: int | None = None,
        backend: str = "auto", config: tuning.PipelineConfig | None = None,
        compute_uv: bool = True, check: bool = False, trace=None):
    """Full SVD of dense (..., n, n): ``(U, sigma, V^T)``, sigma descending.

    ``compute_uv=False`` degrades to :func:`singular_values` (and the sigma
    returned either way are bit-identical — the tape mode records reflectors
    alongside the same band arithmetic, it never alters it).  Batched inputs
    run batch-native end to end, including the tape replay (one resident
    kernel per chase stage over all B matrices, or on the streamed path
    one fused ``tape_apply`` call over all B*G wavefront slots per cycle).

    ``check=True`` (DESIGN.md §15) validates sigma, checks U/V^T
    finiteness, and spot-checks the residual and the orthogonality of the
    first matrix (:func:`spot_check_svd`); violations raise
    :class:`NumericalFault`.  ``trace=`` as in
    :func:`banded_singular_values`.
    """
    if not compute_uv:
        return singular_values(a, bw=bw, tw=tw, backend=backend,
                               config=config, check=check, trace=trace)
    return _full_svd("svd", a, banded=False, bw=bw, tw=tw, backend=backend,
                     config=config, check=check, trace=trace)


def banded_svd(a: jax.Array, *, bw: int | None = None, tw: int | None = None,
               backend: str = "auto",
               config: tuning.PipelineConfig | None = None,
               compute_uv: bool = True, check: bool = False, trace=None):
    """Full SVD of upper-banded (..., n, n) (stages 2+3 only); ``check=``
    as in :func:`svd`, ``trace=`` as in :func:`banded_singular_values`."""
    if not compute_uv:
        return banded_singular_values(a, bw=bw, tw=tw, backend=backend,
                                      config=config, check=check,
                                      trace=trace)
    return _full_svd("banded_svd", a, banded=True, bw=bw, tw=tw,
                     backend=backend, config=config, check=check,
                     trace=trace)
