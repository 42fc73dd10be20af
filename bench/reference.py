"""The plain reference that decides ``correct``: float64 LAPACK through NumPy.

It takes the matrices the benchmark made and nothing the program made, and
imports nothing of the program.  Every check returns one number; the
run compares each number with the limit its configuration file states.
"""

from __future__ import annotations

import numpy as np


def singular_values(a) -> np.ndarray:
    """Singular values of ``a`` in float64, descending."""
    return np.linalg.svd(np.asarray(a, np.float64), compute_uv=False)


def sigma_error(sigma, ref: np.ndarray) -> float:
    """Normwise error ``max |sigma - ref| / ref_max``; inf when ``sigma`` is
    missing, of the wrong length or not finite."""
    if sigma is None:
        return float("inf")
    s = np.asarray(sigma, np.float64).reshape(-1)
    if s.shape != ref.shape or not np.isfinite(s).all():
        return float("inf")
    return float(np.max(np.abs(s - ref)) / max(float(ref[0]), np.finfo(float).tiny))


def residual(a, u, sigma, vt) -> float:
    """``||A - U diag(sigma) V^T||_F / ||A||_F`` in float64; inf when a factor
    is missing or not finite."""
    if u is None or vt is None or sigma is None:
        return float("inf")
    a64 = np.asarray(a, np.float64)
    u64, s64, vt64 = (np.asarray(x, np.float64) for x in (u, sigma, vt))
    r = float(np.linalg.norm(a64 - (u64 * s64) @ vt64) / np.linalg.norm(a64))
    return r if np.isfinite(r) else float("inf")


def orthogonality(u, vt) -> float:
    """``max(||U^T U - I||_F, ||V^T V - I||_F) / sqrt(n)`` in float64; inf
    when a factor is missing or not finite."""
    if u is None or vt is None:
        return float("inf")
    u64, vt64 = np.asarray(u, np.float64), np.asarray(vt, np.float64)
    n = u64.shape[-1]
    eye = np.eye(n)
    r = max(float(np.linalg.norm(u64.T @ u64 - eye)),
            float(np.linalg.norm(vt64 @ vt64.T - eye))) / np.sqrt(n)
    return r if np.isfinite(r) else float("inf")
