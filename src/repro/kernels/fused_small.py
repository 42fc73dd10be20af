"""One-dispatch fused small-n SVD kernel (DESIGN.md §13).

For the serve tier's dominant workload — thousands of small matrices per
step — the staged pipeline pays one kernel dispatch per streamed chase cycle,
so launch overhead, not bandwidth, bounds latency.  Following the batched
small-size design point (Abdelfattah & Fasi, PAPERS.md: one thread block
per matrix, whole problem resident on chip), this module runs the ENTIRE
per-matrix reduction inside a single ``pallas_call`` over a ``(B,)`` grid:

* phase 1 — dense -> upper-banded(bw): per-column left reflector (zero the
  subdiagonal tail) + right reflector pivoted at ``j + bw`` (truncate the
  row to bw superdiagonals).  Already-banded inputs cost nothing extra:
  zero tails give ``tau = 0`` reflectors, exact no-ops (householder.py).
* phase 2 — band -> bidiagonal: ONE SBR stage with ``b_in = bw``,
  ``tw = bw - 1`` (b_out = 1), the same sweep/pivot walk as the numpy
  oracle ``core.reference.reduce_stage_dense_ref`` — every bulge-chase
  cycle runs in-kernel, no per-cycle dispatch, no host round-trips.
* phase 3 — singular values: the Golub–Kahan Sturm-count bisection of
  ``core.bidiag_svd.bidiag_singular_values`` inlined and vectorized over
  all n values at once (identical per-element arithmetic).

The (n, n) working set plus an (n,) scratch vector — and for
``compute_uv=True`` the two (n, n) accumulators — stay VMEM-resident for
the kernel's lifetime (budget math: ``core.tuning.fused_working_set_bytes``).
``compute_uv=True`` returns ``(d, e, U2, V2^T)`` instead: the bidiagonal
plus the accumulated two-sided transforms; the caller composes the final
vectors with one batched ``bidiag_svd`` call (two dispatches total — the
values path, the B-heavy serve workload, is the one-dispatch tier).

Reflectors use a *masked* form of ``core.householder.reflector_parts``:
full-length (1, n) rows or (n, 1) columns with support ``[lo, hi]``
selected by iota masks, so every loop iteration has static shapes and
inactive cycles (pivot past the edge) degenerate to exact no-ops through
the same ``tau = 0`` path that handles zero tails.

CPU CI runs this kernel under ``interpret=True`` (small n only — interpret
mode evaluates the bisection's fori steps eagerly); the production CPU path
is the jitted twin ``kernels.ref.fused_small_svd_ref`` which vmaps the same
`_reduce_single` body and delegates phase 3 to ``bidiag_singular_values``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.householder import reflector_parts

__all__ = ["fused_small_svd_pallas"]


# ---------------------------------------------------------------------------
# masked reflector + structural fixes (static shapes, iota masks)
#
# Every value is at least 2-D — vectors are (1, n) rows or (n, 1) columns,
# scalars (1, 1) — and every matrix-vector product is a broadcast multiply
# plus a lane or sublane reduction, so Mosaic lowers the whole body to VPU
# ops with no 1-D layouts, matvecs or relayouts.
# ---------------------------------------------------------------------------

def _fori(lo: int, hi: int, body, carry):
    """``lax.fori_loop`` with an int32 counter whatever jax_enable_x64 says
    (static bounds would otherwise become a scan over an i64 counter,
    which Mosaic cannot lower)."""
    def step(c):
        return c[0] + 1, body(c[0], c[1])
    return jax.lax.while_loop(lambda c: c[0] < hi, step,
                              (np.int32(lo), carry))[1]


def _masked_reflector(x, lo, hi, idx):
    """(v, tau, beta) for the reflector over ``x[lo:hi+1]`` (pivot ``lo``),
    returned as a full-length masked vector of ``x``'s orientation:
    ``v[lo] = 1``, support-only tail, zeros elsewhere; ``tau``/``beta`` are
    (1, 1).  Empty / out-of-range / zero-tail supports give ``tau = 0`` —
    the formula of ``householder.reflector_parts``, which every
    implementation shares.
    """
    dt = x.dtype
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    axis = 1 if x.shape[0] == 1 else 0
    xa = x.astype(acc)
    tail = (idx > lo) & (idx <= hi)
    alpha = jnp.sum(jnp.where(idx == lo, xa, 0), axis=axis, keepdims=True)
    tau, v_tail, beta = reflector_parts(alpha, jnp.where(tail, xa, 0),
                                        axis=axis)
    v = v_tail + (idx == lo).astype(acc)
    return v.astype(dt), tau.astype(dt), beta.astype(dt)


def _fix_row(a, rows2, cols2, r, lo, hi, beta, tau):
    """Post-right-reflector structural fix: row ``r`` gets exact zeros on
    ``(lo, hi]`` and ``beta`` at ``lo`` — gated on ``tau != 0`` exactly like
    the numpy oracle's ``if tau != 0.0`` branch."""
    inrow = rows2 == r
    fixed = jnp.where(inrow & (cols2 > lo) & (cols2 <= hi),
                      jnp.zeros_like(a), a)
    fixed = jnp.where(inrow & (cols2 == lo), beta, fixed)
    return jnp.where(tau != 0, fixed, a)


def _fix_col(a, rows2, cols2, c, lo, hi, beta, tau):
    incol = cols2 == c
    fixed = jnp.where(incol & (rows2 > lo) & (rows2 <= hi),
                      jnp.zeros_like(a), a)
    fixed = jnp.where(incol & (rows2 == lo), beta, fixed)
    return jnp.where(tau != 0, fixed, a)


# ---------------------------------------------------------------------------
# single-matrix whole-pipeline body (shared by the pallas kernel and the
# kernels/ref.py CPU twin)
# ---------------------------------------------------------------------------

def _reduce_single(a, *, bw, compute_uv):
    """Phases 1+2 on one (n, n) matrix: returns ``(a, u, v, d, e)`` with
    ``a`` bidiagonal, ``u^T a_in v`` bidiagonal when ``compute_uv`` (else
    ``u``/``v`` are (1, 1) dummies), and (d, e) as (1, n) rows in the
    e[0]-unused convention of ``bidiag_singular_values``."""
    n = a.shape[0]
    dt = a.dtype
    rows2 = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols2 = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    diag = rows2 == cols2
    zero = jnp.zeros_like(a)
    if compute_uv:
        u = diag.astype(dt)
        v = diag.astype(dt)
    else:
        u = v = jnp.zeros((1, 1), dt)

    def right(carry, r, lo, hi):
        a, u, v = carry
        row = jnp.sum(jnp.where(rows2 == r, a, zero), axis=0, keepdims=True)
        vec, tau, beta = _masked_reflector(row, lo, hi, lane)     # (1, n)
        av = jnp.sum(a * vec, axis=1, keepdims=True)              # a @ vec
        a = a - tau * (av * vec)
        a = _fix_row(a, rows2, cols2, r, lo, hi, beta, tau)
        if compute_uv:
            v = v - tau * (jnp.sum(v * vec, axis=1, keepdims=True) * vec)
        return a, u, v

    def left(carry, lo, hi):
        a, u, v = carry
        col = jnp.sum(jnp.where(cols2 == lo, a, zero), axis=1, keepdims=True)
        vec, tau, beta = _masked_reflector(col, lo, hi, sub)      # (n, 1)
        va = jnp.sum(vec * a, axis=0, keepdims=True)              # vec @ a
        a = a - tau * (vec * va)
        a = _fix_col(a, rows2, cols2, lo, lo, hi, beta, tau)
        if compute_uv:
            # column -> row without a relayout: mask the diagonal, reduce
            vrow = jnp.sum(jnp.where(diag, vec, zero), axis=0, keepdims=True)
            u = u - tau * (jnp.sum(u * vrow, axis=1, keepdims=True) * vrow)
        return a, u, v

    # phase 1: dense -> upper-banded(bw).  Banded inputs: all tau = 0.
    def p1(j, carry):
        carry = left(carry, j, n - 1)          # zero a[j+1:, j]
        return right(carry, j, j + bw, n - 1)  # zero a[j, j+bw+1:]

    carry = _fori(0, max(n - 1, 0), p1, (a, u, v))

    # phase 2: one SBR stage b_in = bw, tw = bw - 1 (b_out = 1) — the
    # sweep/pivot walk of reference.reduce_stage_dense_ref, every cycle
    # in-kernel.  bw == 1 means phase 1 already left a bidiagonal.
    if bw >= 2 and n >= 3:
        ncyc = (n - 2) // bw + 1

        def cyc(R, jc, carry):
            p = R + 1 + jc * bw
            r = jnp.where(jc == 0, R, p - bw)
            hi = jnp.minimum(p + bw - 1, n - 1)
            carry = right(carry, r, p, hi)     # chase the bulge row
            return left(carry, p, hi)          # re-zero the bulge column

        def sweep(R, carry):
            return _fori(0, ncyc, lambda jc, c: cyc(R, jc, c), carry)

        carry = _fori(0, n - 2, sweep, carry)

    a, u, v = carry
    d = jnp.sum(jnp.where(diag, a, zero), axis=0, keepdims=True)
    e = jnp.sum(jnp.where(cols2 == rows2 + 1, a, zero), axis=0, keepdims=True)
    return a, u, v, d, e


def _sigma_from_bidiag(d, e, *, max_iter=None):
    """In-kernel phase 3 on (1, n) rows (d, e): the arithmetic of
    ``bidiag_singular_values``, vectorized over all n shift searches at once
    instead of vmapped (identical per-element float ops: same z, same
    power-of-two prescale, same bound, same Sturm recurrence and guards,
    same iteration count).  Returns sigma descending as an (n, 1) column.
    ``max_iter=None`` picks the dtype default, mirroring the core path."""
    n = d.shape[-1]
    dt = d.dtype
    if n == 1:
        return jnp.abs(d)
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    m = 2 * n - 1
    im = jax.lax.broadcasted_iota(jnp.int32, (m, n), 0)
    jn = jax.lax.broadcasted_iota(jnp.int32, (m, n), 1)
    # z = (d_1, e_1, d_2, ..., e_{n-1}, d_n) as an (m, 1) column, and its
    # one-step shift z[i+1], both via one-hot masks (exact: one nonzero term).
    da = d.astype(acc)
    ea = e.astype(acc)

    def interleave(shift):
        return (jnp.sum(jnp.where(im + shift == 2 * jn, da, 0), axis=1,
                        keepdims=True)
                + jnp.sum(jnp.where(im + shift == 2 * jn - 1, ea, 0), axis=1,
                          keepdims=True))

    z = interleave(0)
    # Power-of-two prescale, mirroring core ``_gk_prescale``: keeps the
    # squared Sturm pivots in range for extreme input magnitudes while
    # changing no mantissa bits.
    zmax = jnp.max(jnp.abs(z), axis=0, keepdims=True)
    sc = jnp.exp2(jnp.round(
        jnp.log2(jnp.where(zmax > 0, zmax, 1)))).astype(acc)
    z = z / sc
    az = jnp.abs(z)
    az_next = jnp.abs(interleave(1) / sc)
    idxm = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    # Gershgorin bound == max(pad[:-1] + pad[1:]) + 1 with zero end-padding.
    pair = jnp.where(idxm < m - 1, az + az_next, 0)
    ends = jnp.where((idxm == 0) | (idxm == m - 1), az, 0)
    bound = jnp.max(jnp.maximum(pair, ends), axis=0,
                    keepdims=True) + jnp.asarray(1, acc)
    if max_iter is None:
        max_iter = 60 if acc == jnp.float64 else 40
    tiny = jnp.asarray(jnp.finfo(acc).tiny * 4, acc)
    ks = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) + 1   # 1-indexed

    def sturm_vec(lam):                            # lam: (1, n) shifts
        def body(k, carry):
            t, cnt = carry
            t = jnp.where(jnp.abs(t) < tiny,
                          jnp.where(t < 0, -tiny, tiny), t)
            zk = jnp.sum(jnp.where(idxm == k - 1, z, 0), axis=0,
                         keepdims=True)
            t_next = -lam - (zk * zk) / t
            return t_next, cnt + (t_next < 0)

        t0 = -lam
        _, cnt = _fori(1, m + 1, body, (t0, (t0 < 0).astype(jnp.int32)))
        return cnt

    def bis(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = (sturm_vec(mid) - n) >= ks
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = _fori(0, max_iter, bis, (jnp.zeros((1, n), acc),
                                      jnp.zeros((1, n), acc) + bound))
    sig = 0.5 * (lo + hi)
    rev = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           + jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)) == (n - 1)
    return (jnp.sum(jnp.where(rev, sig, 0), axis=1, keepdims=True)
            * sc).astype(dt)


# ---------------------------------------------------------------------------
# pallas kernel: grid (B,), one matrix per grid step, VMEM-resident
# ---------------------------------------------------------------------------

def _values_kernel(a_ref, sig_ref, *, bw, max_iter):
    _, _, _, d, e = _reduce_single(a_ref[...], bw=bw, compute_uv=False)
    sig_ref[...] = _sigma_from_bidiag(d, e, max_iter=max_iter)


def _uv_kernel(a_ref, d_ref, e_ref, u_ref, vt_ref, *, bw):
    _, u, v, d, e = _reduce_single(a_ref[...], bw=bw, compute_uv=True)
    d_ref[...] = d
    e_ref[...] = e
    u_ref[...] = u
    vt_ref[...] = v.T


def effective_bw(n: int, bw: int) -> int:
    """Clamp a requested bandwidth to the fused kernel's valid range
    (bw = 0 requests mean "pick for me" and become 1; bw beyond n - 1 is
    structurally meaningless for an n x n matrix)."""
    return int(max(1, min(int(bw), max(int(n) - 1, 1))))


_I0 = np.int32(0)   # int32 block index literal, whatever jax_enable_x64 says


@functools.partial(jax.jit,
                   static_argnames=("bw", "compute_uv", "interpret",
                                    "max_iter"))
def fused_small_svd_pallas(mats, *, bw, compute_uv=False, interpret=False,
                           max_iter=None):
    """Whole-pipeline SVD of a (B, n, n) stack, one grid step per matrix.

    Values mode returns sigma (B, n) descending — ONE dispatch end to end.
    ``compute_uv=True`` returns ``(d, e, u2, vt2)``; compose vectors with
    one batched ``bidiag_svd`` (see ``core.svd``).  ``max_iter=None`` picks
    the dtype-default bisection sweeps; an explicit value must be >= 1.
    Per-matrix vector outputs are (1, n) / (n, 1) blocks of (B, 1, n) /
    (B, n, 1) arrays — whole trailing dims, as the TPU tiling requires.
    """
    if max_iter is not None and max_iter < 1:
        raise ValueError(
            f"max_iter must be None (auto) or >= 1, got {max_iter}")
    mats = jnp.asarray(mats)
    assert mats.ndim == 3 and mats.shape[-1] == mats.shape[-2], mats.shape
    b, n, _ = mats.shape
    bw_eff = effective_bw(n, bw)
    one = lambda i: (i, _I0, _I0)
    mat_spec = pl.BlockSpec((None, n, n), one)
    row = jax.ShapeDtypeStruct((b, 1, n), mats.dtype)
    if compute_uv:
        kern = functools.partial(_uv_kernel, bw=bw_eff)
        sq = jax.ShapeDtypeStruct((b, n, n), mats.dtype)
        row_spec = pl.BlockSpec((None, 1, n), one)
        d, e, u2, vt2 = pl.pallas_call(
            kern,
            out_shape=(row, row, sq, sq),
            grid=(b,),
            in_specs=[mat_spec],
            out_specs=(row_spec, row_spec, mat_spec, mat_spec),
            interpret=interpret,
            name="fused_small_uv",
        )(mats)
        return d[:, 0], e[:, 0], u2, vt2
    kern = functools.partial(_values_kernel, bw=bw_eff, max_iter=max_iter)
    shape = (b, 1, n) if n == 1 else (b, n, 1)
    sig = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(shape, mats.dtype),
        grid=(b,),
        in_specs=[mat_spec],
        out_specs=pl.BlockSpec((None,) + shape[1:], one),
        interpret=interpret,
        name="fused_small_values",
    )(mats)
    return sig.reshape(b, n)
