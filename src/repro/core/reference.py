"""Sequential numpy oracles for the band -> bidiagonal reduction (fp64).

These are the ground-truth implementations the JAX/Pallas paths are tested
against: full-range reflector applies, obviously orthogonally equivalent,
no scheduling cleverness.  They live apart from ``core/bulge_chasing.py``
so the hot module (jitted wavefront code) does not import numpy oracles;
``bulge_chasing`` re-exports them for back-compat.

* ``reduce_stage_dense_ref`` / ``bidiagonalize_dense_ref`` — values-only
  SBR oracle (paper Alg. 1, sequential).
* ``bidiagonalize_dense_ref_uv`` — the same chase with left/right transform
  accumulation (paper §VII future work): returns (d, e, U, V) with
  ``U^T A V == B``.  This is the oracle the reflector-tape pipeline
  (``core/transforms.py``) is verified against.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = [
    "sigma_error",
    "reduce_stage_dense_ref",
    "bidiagonalize_dense_ref",
    "bidiagonalize_dense_ref_uv",
]


def sigma_error(sigma, a) -> float:
    """Normwise error ``max|sigma - sigma_ref| / sigma_max`` of a computed
    spectrum against the fp64 LAPACK singular values of the input ``a`` —
    the oracle every precision of the pipeline is judged against."""
    ref = np.linalg.svd(np.asarray(a, np.float64), compute_uv=False)
    smax = max(float(np.max(ref)), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(np.asarray(sigma, np.float64) - ref)) / smax)


def _np_reflector(x: np.ndarray):
    alpha = x[0]
    sigma = float(np.dot(x[1:], x[1:]))
    if sigma == 0.0:
        return None, 0.0, alpha
    mu = math.sqrt(alpha * alpha + sigma)
    beta = -mu if alpha >= 0 else mu
    tau = (beta - alpha) / beta
    v = np.concatenate([[1.0], x[1:] / (alpha - beta)])
    return v, tau, beta


def reduce_stage_dense_ref(a: np.ndarray, b_in: int, tw: int) -> np.ndarray:
    """One SBR stage, sequential, full-range applies. a: (n, n) float64."""
    a = np.array(a, dtype=np.float64)
    n = a.shape[0]
    b_out = b_in - tw
    assert b_out >= 1
    for R in range(0, max(n - 1 - b_out, 0)):
        p = R + b_out
        r = R
        while p <= n - 1:
            hi = min(p + tw + 1, n)
            # right reflector: annihilate a[r, p+1:hi]
            v, tau, beta = _np_reflector(a[r, p:hi])
            if tau != 0.0:
                w = a[:, p:hi] @ v
                a[:, p:hi] -= tau * np.outer(w, v)
                a[r, p + 1 : hi] = 0.0
                a[r, p] = beta
            # left reflector: annihilate a[p+1:hi, p]
            v, tau, beta = _np_reflector(a[p:hi, p])
            if tau != 0.0:
                w = v @ a[p:hi, :]
                a[p:hi, :] -= tau * np.outer(v, w)
                a[p + 1 : hi, p] = 0.0
                a[p, p] = beta
            r = p
            p = p + b_in
    return a


def bidiagonalize_dense_ref(a: np.ndarray, bw: int, tw: int):
    """Full SBR to bidiagonal: stages bw -> bw-tw -> ... -> 1. Returns (d, e, A)."""
    a = np.array(a, dtype=np.float64)
    b = bw
    while b > 1:
        twi = min(tw, b - 1)
        a = reduce_stage_dense_ref(a, b, twi)
        b -= twi
    n = a.shape[0]
    d = np.diagonal(a).copy()
    e = np.diagonal(a, 1).copy()
    return d, e, a


def bidiagonalize_dense_ref_uv(a: np.ndarray, bw: int, tw: int):
    """SBR with transform accumulation: A = U B V^T with B bidiagonal.

    The paper computes singular values only and names vector accumulation as
    future work (§VII); this oracle-level extension accumulates the left/right
    reflector products alongside the chase (each chase reflector also updates
    U's columns / V's columns — O(n * tw) extra per cycle, the same wavefront
    parallelism applies).  Returns (d, e, U, V) with U^T A V == B.
    """
    a = np.array(a, dtype=np.float64)
    n = a.shape[0]
    u = np.eye(n)
    v = np.eye(n)
    b = bw
    while b > 1:
        twi = min(tw, b - 1)
        b_out = b - twi
        for R in range(0, max(n - 1 - b_out, 0)):
            p = R + b_out
            r = R
            while p <= n - 1:
                hi = min(p + twi + 1, n)
                vec, tau, beta = _np_reflector(a[r, p:hi])
                if tau != 0.0:
                    w = a[:, p:hi] @ vec
                    a[:, p:hi] -= tau * np.outer(w, vec)
                    a[r, p + 1 : hi] = 0.0
                    a[r, p] = beta
                    wv = v[:, p:hi] @ vec
                    v[:, p:hi] -= tau * np.outer(wv, vec)
                vec, tau, beta = _np_reflector(a[p:hi, p])
                if tau != 0.0:
                    w = vec @ a[p:hi, :]
                    a[p:hi, :] -= tau * np.outer(vec, w)
                    a[p + 1 : hi, p] = 0.0
                    a[p, p] = beta
                    wu = u[:, p:hi] @ vec
                    u[:, p:hi] -= tau * np.outer(wu, vec)
                r = p
                p = p + b
        b -= twi
    d = np.diagonal(a).copy()
    e = np.diagonal(a, 1).copy()
    return d, e, u, v
