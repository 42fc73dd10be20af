"""Stage 1: dense -> upper-banded reduction (blocked two-sided Householder).

Alternating QR panel (zero below the diagonal in an ``nb``-column stripe) and
LQ panel (zero beyond the ``nb``-th superdiagonal in an ``nb``-row stripe),
with compact-WY blocked trailing updates — the GEMM/MXU-heavy stage of the
three-stage SVD (paper §I; our stage-2 bulge-chasing kernel consumes its
output).

Implementation notes (fixed shapes, single jit per (n, nb)):

* The matrix is zero-padded to a panel multiple so every stripe slice is
  aligned; padded reflectors are identity (tau = 0) by construction.
* Panels are factorized unblocked (rank-1 applies on the stripe); the blocked
  trailing update applies ``I - V T' V^T`` at full width with already-final
  columns masked out of the inner product — already-reduced regions hold exact
  structural zeros (re-established after every reflector, as LAPACK does), so
  full-width applies cannot corrupt them.
* Everything runs inside one ``lax.fori_loop`` over panels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.householder import exact_matmul as _mm
from repro.core.householder import reflector_parts

__all__ = ["band_reduce", "wy_t_factor"]


def _acc_dtype(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt


def _masked_reflector(col: jax.Array, pivot: jax.Array):
    """Householder (v, tau, beta) for entries of ``col`` at indices >= pivot.

    v[pivot] = 1, zeros above; tau = 0 (identity) when the tail below the
    pivot is zero (covers out-of-range / padded pivots, whose columns are 0).
    """
    m = col.shape[0]
    idx = jnp.arange(m)
    piv = jnp.clip(pivot, 0, m - 1)
    tau, v, beta = reflector_parts(col[piv], jnp.where(idx > pivot, col, 0))
    v = v.at[piv].set(jnp.where(pivot < m, 1.0, 0.0))
    return v, tau, beta


def wy_t_factor(v: jax.Array, taus: jax.Array) -> jax.Array:
    """Compact-WY T (upper triangular): H_0 H_1 ... H_{k-1} = I - V T V^T."""
    k = taus.shape[0]
    vtv = _mm(v.T, v)

    def body(j, t):
        col = -taus[j] * _mm(t, jnp.where(jnp.arange(k) < j, vtv[:, j], 0))
        col = col.at[j].set(taus[j])
        keep = jnp.arange(k) <= j
        return t.at[:, j].set(jnp.where(keep, col, 0))

    return jax.lax.fori_loop(0, k, body, jnp.zeros((k, k), v.dtype))


@functools.partial(jax.jit, static_argnames=("nb", "backend", "config",
                                             "tape"))
@obs.scope("stage1")
def band_reduce(a: jax.Array, *, nb: int, backend: str | None = None,
                config=None, tape: bool = False):
    """Reduce dense (..., n, n) to upper-banded form with bandwidth ``nb``.

    Singular values are preserved exactly (two-sided orthogonal transforms).
    Leading batch axes are vmapped (stage 1 is GEMM-bound; the MXU batches
    naturally — the wavefront trick is only needed for stage 2).
    ``backend="pallas"`` routes the blocked QR trailing update through the
    compact-WY Pallas kernel (kernels/hh_apply.py): the kernel applies at
    full width (already-final panel columns are restored afterwards — regions
    left of the panel hold exact zeros in V's row support, so the apply is a
    no-op there).  An explicit ``backend=`` wins; otherwise a resolved
    ``config`` supplies it; otherwise "ref".

    With ``tape=True`` returns ``(banded, (vq, tq, vl, tl))`` — the per-panel
    compact-WY reflector tape: ``vq/vl (..., P, n, nb)`` (QR / LQ reflector
    blocks, rows truncated to n — padding rows are structurally zero) and
    ``tq/tl (..., P, nb, nb)`` (their T factors).  Replayed into ``U``/``V^T``
    by ``core/transforms.py``; the banded output is bit-identical either way.
    """
    if backend is None:
        backend = config.backend if config is not None else "ref"
    if a.ndim > 2:
        fn = lambda m: _band_reduce_2d(m, nb=nb, backend=backend,
                                       config=config, tape=tape)
        for _ in range(a.ndim - 2):
            fn = jax.vmap(fn)
        return fn(a)
    return _band_reduce_2d(a, nb=nb, backend=backend, config=config, tape=tape)


def _band_reduce_2d(a: jax.Array, *, nb: int, backend: str,
                    config=None, tape: bool = False):
    n = a.shape[0]
    dt = a.dtype
    acc = _acc_dtype(dt)
    n_panels = max(1, -(-(n - 1) // nb))
    big = (n_panels + 2) * nb                  # padded size: all slices aligned
    a = jnp.zeros((big, big), acc).at[:n, :n].set(a.astype(acc))
    idx = jnp.arange(big)

    def panel(k, carry):
        a = carry[0] if tape else carry
        c0 = k * nb

        # -------- QR panel: columns [c0, c0+nb), pivot row c0+j --------------
        def qr_reflector(j, carry):
            a, v_blk, taus = carry
            c = c0 + j
            stripe = jax.lax.dynamic_slice(a, (0, c0), (big, nb))
            v, tau, beta = _masked_reflector(stripe[:, j], c)
            w = _mm(v, stripe)
            stripe = stripe - tau * jnp.outer(v, w)
            newcol = jnp.where(idx > c, 0.0, stripe[:, j])       # structural 0s
            newcol = newcol.at[c].set(jnp.where(tau != 0, beta, newcol[c]))
            stripe = stripe.at[:, j].set(newcol)
            a = jax.lax.dynamic_update_slice(a, stripe, (0, c0))
            return a, v_blk.at[:, j].set(v), taus.at[j].set(tau)

        v0 = jnp.zeros((big, nb), acc)
        t0 = jnp.zeros((nb,), acc)
        with jax.named_scope("stage1_qr_panel"):
            a, v_blk, taus = jax.lax.fori_loop(0, nb, qr_reflector,
                                               (a, v0, t0))
        t = wy_t_factor(v_blk, taus)
        # blocked trailing update (Q^T = I - V T^T V^T) on columns >= c0+nb
        if backend == "pallas":
            from repro.kernels import ops
            stripe = jax.lax.dynamic_slice(a, (0, c0), (big, nb))
            # config threads the resolved interpret flag; the explicit
            # backend kwarg still selects the kernel route.
            a = ops.hh_block_apply(v_blk, t.T, a, backend="pallas",
                                   config=config)
            # restore final panel columns (double-applied by the full-width
            # kernel); columns < c0 are exact-zero in V's row support, so the
            # kernel was a no-op there already.
            a = jax.lax.dynamic_update_slice(a, stripe, (0, c0))
        else:
            u = _mm(v_blk.T, a)
            u = jnp.where(idx[None, :] >= c0 + nb, u, 0)
            a = a - _mm(v_blk, _mm(t.T, u))

        # -------- LQ panel: rows [c0, c0+nb), pivot col c0+nb+j --------------
        def lq_reflector(j, carry):
            a, v_blk, taus = carry
            r = c0 + j
            c_piv = c0 + nb + j
            stripe = jax.lax.dynamic_slice(a, (c0, 0), (nb, big))
            v, tau, beta = _masked_reflector(stripe[j, :], c_piv)
            w = _mm(stripe, v)
            stripe = stripe - tau * jnp.outer(w, v)
            newrow = jnp.where(idx > c_piv, 0.0, stripe[j, :])
            newrow = newrow.at[c_piv].set(jnp.where(tau != 0, beta, newrow[c_piv]))
            stripe = stripe.at[j, :].set(newrow)
            a = jax.lax.dynamic_update_slice(a, stripe, (c0, 0))
            return a, v_blk.at[:, j].set(v), taus.at[j].set(tau)

        with jax.named_scope("stage1_lq_panel"):
            a, vr_blk, taus_r = jax.lax.fori_loop(0, nb, lq_reflector,
                                                  (a, v0, t0))
        tr = wy_t_factor(vr_blk, taus_r)
        # blocked trailing update from the right on rows >= c0+nb
        w = _mm(a, vr_blk)
        w = jnp.where(idx[:, None] >= c0 + nb, w, 0)
        a = a - _mm(w, _mm(tr, vr_blk.T))
        if not tape:
            return a
        vqs, tqs, vls, tls = carry[1:]
        return (a, vqs.at[k].set(v_blk), tqs.at[k].set(t),
                vls.at[k].set(vr_blk), tls.at[k].set(tr))

    if tape:
        z_v = jnp.zeros((n_panels, big, nb), acc)
        z_t = jnp.zeros((n_panels, nb, nb), acc)
        a, vqs, tqs, vls, tls = jax.lax.fori_loop(
            0, n_panels, panel, (a, z_v, z_t, z_v, z_t))
        # rows >= n of every reflector block are structurally zero (the
        # padded matrix region never becomes nonzero), so the tape can be
        # truncated to matrix rows — replay then lives in (n, n) space.
        return (a[:n, :n].astype(dt),
                (vqs[:, :n], tqs, vls[:, :n], tls))
    a = jax.lax.fori_loop(0, n_panels, panel, a)
    return a[:n, :n].astype(dt)
