"""Stage 3: singular values of an upper-bidiagonal matrix.

Golub–Kahan form: the permuted matrix [[0, B^T], [B, 0]] is symmetric
tridiagonal of size 2n with zero diagonal and off-diagonal sequence
``z = (d_1, e_1, d_2, e_2, ..., e_{n-1}, d_n)``; its eigenvalues are ±sigma.
We count eigenvalues below a shift with a Sturm / LDL^T negative-pivot count
(stable zero-diagonal recurrence, cf. LAPACK ``bdsvdx``) and bisect —
embarrassingly parallel over singular values (vmapped), branch-free
(lax.fori_loop), dtype-polymorphic.

This is the same third stage the paper delegates to LAPACK BDSDC; a native JAX
implementation keeps the full pipeline on-device.

Singular VECTORS (``bidiag_svd``): inverse iteration on the same Golub–Kahan
tridiagonal, seeded by the bisection values.  The eigenvector of T_GK at
``+sigma`` interleaves the right and left bidiagonal vectors —
``x = (v_1, u_1, v_2, u_2, ...)/sqrt(2)`` with ``B v = sigma u`` — so one
guarded tridiagonal (Thomas) solve per value recovers both.  Like the
values, this is embarrassingly parallel over (singular value, batch) and
vmaps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.householder import exact_matmul as _mm

__all__ = ["gk_offdiag", "sturm_count", "bidiag_singular_values",
           "bidiag_svd", "default_bisect_iters"]


def default_bisect_iters(acc) -> int:
    """Bisection sweeps that take the Gershgorin bracket below 1 ulp:
    60 halvings cover fp64's 52-bit mantissa plus headroom, 40 cover fp32."""
    return 60 if acc == jnp.float64 else 40


def _check_max_iter(max_iter):
    if max_iter is not None and max_iter < 1:
        raise ValueError(
            f"max_iter must be None (auto) or >= 1, got {max_iter}")


def gk_offdiag(d: jax.Array, e: jax.Array) -> jax.Array:
    """Interleave (d, e) -> Golub–Kahan off-diagonal z of length 2n-1.

    d: (n,) main diagonal; e: (n,) with e[0] unused (e[i] = B[i-1, i]).
    """
    n = d.shape[0]
    if n == 1:
        # degenerate fast path: the (2n-1,) = (1,) off-diagonal is just d —
        # the interleave below would strided-set an empty e slice.
        return d
    z = jnp.zeros((2 * n - 1,), d.dtype)
    z = z.at[0::2].set(d)
    z = z.at[1::2].set(e[1:])
    return z


def sturm_count(z: jax.Array, lam: jax.Array) -> jax.Array:
    """#eigenvalues of the zero-diagonal tridiagonal (offdiag z) below ``lam``.

    LDL^T pivot recurrence  t_k = -lam - z_{k-1}^2 / t_{k-1},  t_1 = -lam,
    counting negative pivots; division guarded against exact zeros.
    """
    acc = jnp.float32 if z.dtype in (jnp.bfloat16, jnp.float16) else z.dtype
    z = z.astype(acc)
    lam = lam.astype(acc)
    tiny = jnp.asarray(jnp.finfo(acc).tiny * 4, acc)
    m = z.shape[0] + 1

    def body(k, carry):
        t, cnt = carry
        t = jnp.where(jnp.abs(t) < tiny, jnp.where(t < 0, -tiny, tiny), t)
        t_next = -lam - (z[k - 1] * z[k - 1]) / t
        return t_next, cnt + (t_next < 0)

    t0 = -lam
    cnt0 = (t0 < 0).astype(jnp.int32)
    _, cnt = jax.lax.fori_loop(1, m, body, (t0, cnt0))
    return cnt


def _gk_prescale(z: jax.Array) -> jax.Array:
    """Exact power-of-two scale of max|z| (1 when z == 0): dividing it out
    keeps z^2 inside the exponent range for 1e-300..1e300 inputs (the Sturm
    pivots square z) without touching any mantissa bits."""
    acc = z.dtype
    zmax = jnp.max(jnp.abs(z))
    expo = jnp.round(jnp.log2(jnp.where(zmax > 0, zmax, 1)))
    return jnp.exp2(expo).astype(acc)


@functools.partial(jax.jit, static_argnames=("max_iter",))
@obs.scope("stage3")
def bidiag_singular_values(d: jax.Array, e: jax.Array, *,
                           max_iter: int | None = None) -> jax.Array:
    """All singular values of the bidiagonal (d, e), descending.

    e[0] is ignored (convention: e[i] = B[i-1, i]).  Bisection on [0, bound]
    where bound = ||T_GK||_inf via Gershgorin, after a power-of-two prescale
    so extreme input magnitudes neither overflow the squared Sturm pivots
    nor drown in the bracket's absolute slack.  ``max_iter=None`` picks the
    dtype-matched sweep count (:func:`default_bisect_iters`); an explicit
    value must be >= 1.  Accepts stacked bidiagonals ``(..., n)`` —
    bisection is embarrassingly parallel across both singular values and
    batch, so the batch axes simply vmap.
    """
    _check_max_iter(max_iter)
    if d.ndim > 1:
        lead = d.shape[:-1]
        fn = jax.vmap(lambda dd, ee: bidiag_singular_values(dd, ee,
                                                            max_iter=max_iter))
        out = fn(d.reshape((-1, d.shape[-1])), e.reshape((-1, e.shape[-1])))
        return out.reshape(lead + (d.shape[-1],))
    n = d.shape[0]
    if n == 1:
        # degenerate fast path (B is 1x1): sigma = |d| exactly — bisection
        # on an empty Sturm recurrence would only approximate it.
        return jnp.abs(d)
    acc = jnp.float32 if d.dtype in (jnp.bfloat16, jnp.float16) else d.dtype
    z = gk_offdiag(d.astype(acc), e.astype(acc))
    sc = _gk_prescale(z)
    z = z / sc
    az = jnp.abs(z)
    pad = jnp.concatenate([jnp.zeros(1, acc), az, jnp.zeros(1, acc)])
    bound = jnp.max(pad[:-1] + pad[1:]) + jnp.asarray(1, acc)
    if max_iter is None:
        max_iter = default_bisect_iters(acc)

    # sigma_k (1-indexed ascending) = inf{ lam : count_sigma(lam) >= k },
    # count_sigma(lam) = sturm_count(z, lam) - n   (the n eigenvalues -sigma).
    ks = jnp.arange(1, n + 1)

    def solve_one(k):
        def body(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            c = sturm_count(z, mid) - n
            return jnp.where(c >= k, lo, mid), jnp.where(c >= k, mid, hi)

        lo, hi = jax.lax.fori_loop(0, max_iter, body,
                                   (jnp.asarray(0, acc), bound))
        return 0.5 * (lo + hi)

    sig = jax.vmap(solve_one)(ks)
    return (sig[::-1] * sc).astype(d.dtype)


# ---------------------------------------------------------------------------
# Singular vectors: inverse iteration on the Golub–Kahan tridiagonal
# ---------------------------------------------------------------------------

def _tridiag_solve(z: jax.Array, lam: jax.Array, b: jax.Array) -> jax.Array:
    """Solve (T - lam*I) x = b, T the zero-diagonal tridiagonal with
    off-diagonal ``z`` (m = len(z)+1).  Thomas elimination with pivots
    guarded away from zero — near-singular shifts are the POINT of inverse
    iteration (the guarded solve just scales the eigen-direction up).
    """
    acc = z.dtype
    eps = jnp.finfo(acc).eps
    tiny = eps * jnp.maximum(jnp.max(jnp.abs(z)), 1)

    def guard(p):
        return jnp.where(jnp.abs(p) < tiny, jnp.where(p < 0, -tiny, tiny), p)

    piv0 = guard(-lam)
    y0 = b[0] / piv0

    def fwd(carry, inp):
        piv_prev, y_prev = carry
        z_im1, b_i = inp
        c_im1 = z_im1 / piv_prev                 # elimination multiplier
        piv = guard(-lam - z_im1 * c_im1)
        y = (b_i - z_im1 * y_prev) / piv
        return (piv, y), (y, c_im1)

    (_, _), (ys, cs) = jax.lax.scan(fwd, (piv0, y0), (z, b[1:]))
    ys_full = jnp.concatenate([y0[None], ys])    # y_0 .. y_{m-1}

    def bwd(x_next, inp):
        y_i, c_i = inp
        x = y_i - c_i * x_next
        return x, x

    x_last = ys_full[-1]
    _, xs = jax.lax.scan(bwd, x_last, (ys_full[:-1], cs), reverse=True)
    return jnp.concatenate([xs, x_last[None]])


def _vectors_from_sigma(d: jax.Array, e: jax.Array, sig: jax.Array, *,
                        inv_iters: int = 2):
    """(U, V^T) of the bidiagonal (d, e) given its singular values ``sig``
    (descending) — ``inv_iters`` rounds of inverse iteration on the
    Golub–Kahan tridiagonal at each sigma, whose eigenvector interleaves
    (v, u), then cluster reorthogonalization + left/right re-pairing.

    sigma-agnostic on purpose: the values may come from bisection OR from
    the divide-and-conquer path (``core.bidiag_dc``) — any sigma accurate
    to a few ulps seeds the same vector machinery.  1-D inputs, n >= 2;
    callers own batching and the n == 1 fast path.
    """
    n = d.shape[0]
    dt = d.dtype
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    z = gk_offdiag(d.astype(acc), e.astype(acc))
    sc = _gk_prescale(z)
    z = z / sc
    m = 2 * n
    dd = d.astype(acc)
    ee = e.astype(acc)

    def vectors_one(lam, kidx):
        # deterministic, k-dependent start: decorrelates degenerate clusters
        t = jnp.arange(1, m + 1, dtype=acc)
        b0 = jnp.sin(t * (kidx.astype(acc) + 1) * jnp.asarray(0.7, acc)) \
            + jnp.asarray(0.01, acc)
        x = b0 / jnp.linalg.norm(b0)
        for _ in range(inv_iters):
            x = _tridiag_solve(z, lam, x)
            x = x / jnp.maximum(jnp.linalg.norm(x), jnp.finfo(acc).tiny)
        v = x[0::2]
        u = x[1::2]
        nv = jnp.linalg.norm(v)
        nu = jnp.linalg.norm(u)
        ok = jnp.minimum(nv, nu) > jnp.asarray(1e-6, acc)
        onehot = (jnp.arange(n) == kidx).astype(acc)
        v = jnp.where(ok, v / jnp.where(ok, nv, 1), onehot)
        u = jnp.where(ok, u / jnp.where(ok, nu, 1), onehot)
        return u, v

    us, vs = jax.vmap(vectors_one)(sig.astype(acc) / sc, jnp.arange(n))
    us, vs = _orthonormalize_pairs(us, vs, sig.astype(acc), dd, ee)
    return us.T.astype(dt), vs.astype(dt)


@functools.partial(jax.jit, static_argnames=("max_iter", "inv_iters"))
@obs.scope("stage3")
def bidiag_svd(d: jax.Array, e: jax.Array, *, max_iter: int | None = None,
               inv_iters: int = 2):
    """Full SVD of the upper bidiagonal (d, e): returns (U, sigma, V^T).

    sigma comes from the SAME bisection as :func:`bidiag_singular_values`
    (bit-identical — the vector path never recomputes values); vectors come
    from :func:`_vectors_from_sigma` (inverse iteration seeded by sigma).
    ``max_iter=None`` picks the dtype-matched bisection sweep count; an
    explicit value must be >= 1.  Accepts stacked bidiagonals ``(..., n)``
    (vmapped).
    """
    _check_max_iter(max_iter)
    if d.ndim > 1:
        lead = d.shape[:-1]
        fn = jax.vmap(lambda dd, ee: bidiag_svd(dd, ee, max_iter=max_iter,
                                                inv_iters=inv_iters))
        u, s, vt = fn(d.reshape((-1, d.shape[-1])),
                      e.reshape((-1, e.shape[-1])))
        n = d.shape[-1]
        return (u.reshape(lead + (n, n)), s.reshape(lead + (n,)),
                vt.reshape(lead + (n, n)))

    n = d.shape[0]
    dt = d.dtype
    sig = bidiag_singular_values(d, e, max_iter=max_iter)       # descending
    if n == 1:
        # 1x1 fast path: d = u * sigma * v with u = 1, v = sign(d).
        sgn = jnp.where(d[0] < 0, -1.0, 1.0).astype(dt)
        return (jnp.ones((1, 1), dt), sig, sgn[None, None])

    u, vt = _vectors_from_sigma(d, e, sig, inv_iters=inv_iters)
    return (u, sig, vt)


def _orthonormalize_pairs(us, vs, sig, dd, ee):
    """Cluster reorthogonalization + left/right re-pairing (cf. LAPACK stein).

    Plain inverse iteration gives independent but NOT orthogonal vectors
    inside a repeated/clustered sigma group.  Sequentially (descending k):
    Gram-Schmidt v_k against every earlier v_j whose sigma falls in the same
    cluster (generous 1e-3 relative width — for well-separated values the
    subtracted projections are ~eps and harmless), then re-derive the left
    vector from the pairing identity ``u_k = B v_k / ||B v_k||`` (exact for a
    true right vector, and automatically sign-aligned: u^T B v > 0).  For
    sigma ~ 0 the identity degenerates, so the zero cluster orthogonalizes
    the u's directly instead.  Rows of us/vs are vectors; O(n^2) per step.
    """
    acc = vs.dtype
    n = sig.shape[0]
    eps = jnp.finfo(acc).eps
    scale = jnp.maximum(sig[0], jnp.asarray(1, acc))
    ctol = jnp.asarray(1e-3, acc) * scale        # cluster width (relative)
    stol = jnp.sqrt(eps) * scale                 # below this: zero cluster
    tiny = jnp.finfo(acc).tiny
    karr = jnp.arange(n)

    def mgs(k, rows, vec, kidx):
        """vec minus its projection on rows[j] for prior same-cluster j,
        renormalized; falls back to an orthogonalized one-hot on collapse."""
        mask = ((karr < k) & ((sig - sig[k]) < ctol)).astype(acc)

        def clean(w):
            w = w - _mm(mask * _mm(rows, w), rows)
            return w, jnp.linalg.norm(w)

        w1, n1 = clean(vec)
        w2, n2 = clean((karr == kidx).astype(acc))
        good = n1 > jnp.asarray(0.01, acc)
        return jnp.where(good, w1 / jnp.maximum(n1, tiny),
                         w2 / jnp.maximum(n2, tiny))

    def body(k, uv):
        us, vs = uv
        v = mgs(k, vs, vs[k], k)
        bv = dd * v + jnp.concatenate([ee[1:] * v[1:], jnp.zeros(1, acc)])
        nbv = jnp.linalg.norm(bv)
        u_zero = mgs(k, us, us[k], k)            # sigma ~ 0: pair is free
        u = jnp.where(sig[k] > stol, bv / jnp.maximum(nbv, tiny), u_zero)
        return us.at[k].set(u), vs.at[k].set(v)

    return jax.lax.fori_loop(0, n, body, (us, vs))
