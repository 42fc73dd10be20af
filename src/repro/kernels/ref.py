"""Pure-jnp oracles for the Pallas kernels.

``chase_cycle_ref`` is the reference for ``kernels/bulge_chase.py``; it operates on
*rolled dense windows* of the packed band storage (see core/bulge_chasing.py for the
rolling scheme).  One window = one bulge-chase cycle of one sweep (paper Alg. 2):

  window[y, w] = A[i0 + y, p + w],   i0 = p - b_in - tw,
  H = b_in + 2*tw + 1,  W = b_in + tw + 1   ("1 + BW + TW consecutive elements")

Cycle = (1) right reflector annihilating the TW-element row bulge of row
``r = p - b_in`` (or ``r = R = p - b_out`` on a sweep's first cycle — paper Alg. 1
line 7), then (2) left reflector annihilating the TW-element column bulge of the
pivot column ``p``, applied to all W window columns.

``hh_block_apply_ref`` is the oracle for the stage-1 WY blocked reflector apply;
``tape_apply_ref`` for the batched compact-WY tape replay (core/transforms.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.householder import exact_matmul as _mm
from repro.core.householder import make_reflector

_HI = jax.lax.Precision.HIGHEST       # as exact_matmul, for the einsums

__all__ = ["chase_window_ref", "chase_cycle_ref", "hh_block_apply_ref",
           "tape_apply_ref", "fused_small_svd_ref"]


def _chase_window(window: jax.Array, is_first: jax.Array, *, b_in: int,
                  tw: int):
    """One chase cycle on a rolled dense window, returning the reflector pair.

    window: (H, W) with H = b_in + 2*tw + 1, W = b_in + tw + 1.
    is_first: scalar bool — first cycle of its sweep (overhang row at y=2*tw
    instead of y=tw; the rows in between are already-reduced zeros, so the
    unconditional apply over y >= tw is a no-op on them).

    Returns ``(window, (v, tau), (v2, tau2))`` — the right reflector (spans
    matrix columns [p, p+tw], accumulates into V on replay) and the left one
    (spans matrix rows [p, p+tw], accumulates into U).
    """
    H, W = window.shape
    assert H == b_in + 2 * tw + 1 and W == b_in + tw + 1, (H, W, b_in, tw)
    dt = window.dtype

    # ---- right reflector: annihilate row bulge, columns [0, tw] of row y_r ----
    y_r = jnp.where(is_first, 2 * tw, tw)
    x = jax.lax.dynamic_slice(window, (y_r, 0), (1, tw + 1))[0]
    v, tau, beta = make_reflector(x)
    blk = window[tw:, : tw + 1]                                   # rows [tw, H)
    w_dot = _mm(blk, v)                                           # (H - tw,)
    blk = blk - tau * jnp.outer(w_dot, v)
    window = window.at[tw:, : tw + 1].set(blk.astype(dt))
    # structural zeros for the annihilated row (avoid round-off debris)
    row_fix = jnp.zeros((1, tw + 1), dt).at[0, 0].set(beta)
    keep = jax.lax.dynamic_slice(window, (y_r, 0), (1, tw + 1))
    row_fix = jnp.where(tau != 0, row_fix, keep)
    window = jax.lax.dynamic_update_slice(window, row_fix, (y_r, 0))

    # ---- left reflector: annihilate column bulge of pivot column (w=0) ----
    y0 = H - 1 - tw                                               # matrix row p
    xc = window[y0:, 0]
    v2, tau2, beta2 = make_reflector(xc)
    blk2 = window[y0:, :]                                         # (tw+1, W)
    w2 = _mm(v2, blk2)
    blk2 = blk2 - tau2 * jnp.outer(v2, w2)
    col_fix = jnp.zeros((tw + 1,), dt).at[0].set(beta2)
    col_fix = jnp.where(tau2 != 0, col_fix, blk2[:, 0].astype(dt))
    blk2 = blk2.astype(dt).at[:, 0].set(col_fix)
    window = window.at[y0:, :].set(blk2)
    return window, (v.astype(dt), tau.astype(dt)), (v2.astype(dt),
                                                    tau2.astype(dt))


def chase_window_ref(window: jax.Array, is_first: jax.Array, *, b_in: int, tw: int) -> jax.Array:
    """Process one chase cycle on a rolled dense window (values only)."""
    out, _, _ = _chase_window(window, is_first, b_in=b_in, tw=tw)
    return out


def chase_cycle_ref(windows: jax.Array, is_first: jax.Array, *, b_in: int,
                    tw: int, with_tape: bool = False):
    """vmapped oracle over a batch of disjoint windows: (G, H, W).

    ``with_tape=True`` additionally returns the reflector tape slice for the
    wavefront: ``vs (G, 2, tw+1)`` and ``taus (G, 2)`` (pair axis: right
    reflector first, then left)."""
    def fn(w, f):
        out, (v, tau), (v2, tau2) = _chase_window(w, f, b_in=b_in, tw=tw)
        return out, jnp.stack([v, v2]), jnp.stack([tau, tau2])

    out, vs, taus = jax.vmap(fn)(windows, is_first)
    if with_tape:
        return out, vs, taus
    return out


def hh_block_apply_ref(v: jax.Array, t: jax.Array, c: jax.Array) -> jax.Array:
    """WY blocked reflector apply oracle:  C <- (I - V T V^T) C.

    v: (m, k) unit-lower-trapezoidal reflector block, t: (k, k) upper-triangular
    compact-WY factor, c: (m, ncols).  The single-slot view of
    :func:`tape_apply_ref` — one oracle serves both.
    """
    return tape_apply_ref(v[None], t[None], c[None])[0]


def tape_apply_ref(v: jax.Array, t: jax.Array, c: jax.Array) -> jax.Array:
    """Batched compact-WY left apply oracle: per slot s,

        C[s] <- (I - V[s] T[s] V[s]^T) C[s]

    v: (S, m, k), t: (S, k, k), c: (S, m, w).  The tape-replay workhorse
    (core/transforms.py): stage-1 panels use k = nb blocks, the chase tape
    uses k = 1 (rank-1 Householder, t = tau).
    """
    acc = jnp.float32 if c.dtype in (jnp.bfloat16, jnp.float16) else c.dtype
    vv, tt, cc = v.astype(acc), t.astype(acc), c.astype(acc)
    w1 = jnp.einsum("smk,smw->skw", vv, cc, precision=_HI)
    tw1 = jnp.einsum("skj,sjw->skw", tt, w1, precision=_HI)
    out = cc - jnp.einsum("smk,skw->smw", vv, tw1, precision=_HI)
    return out.astype(c.dtype)


def fused_small_svd_ref(mats, *, bw: int, compute_uv: bool = False,
                        max_iter: int | None = None):
    """CPU/interpret twin of ``fused_small.fused_small_svd_pallas``.

    vmaps the SAME single-matrix whole-pipeline body (`_reduce_single`,
    phases 1+2) over the batch but delegates phase 3 to the existing
    vmapped ``core.bidiag_svd.bidiag_singular_values`` — on CPU one jitted
    XLA computation replaces the kernel's grid, which is exactly the fused
    tier's point (one dispatch per bucket, no per-cycle launches).  Values
    mode returns sigma (B, n) descending; ``compute_uv=True`` returns
    ``(d, e, u2, vt2)`` like the pallas kernel.
    """
    import functools

    from repro.core import bidiag_svd as _s3
    from repro.kernels import fused_small as _fs

    mats = jnp.asarray(mats)
    assert mats.ndim == 3 and mats.shape[-1] == mats.shape[-2], mats.shape
    n = mats.shape[-1]
    bw_eff = _fs.effective_bw(n, bw)
    red = jax.vmap(functools.partial(_fs._reduce_single, bw=bw_eff,
                                     compute_uv=compute_uv))
    _, u, v, d, e = red(mats)
    d, e = d[:, 0], e[:, 0]                      # (B, 1, n) rows -> (B, n)
    if compute_uv:
        return d, e, u, jnp.swapaxes(v, -1, -2)
    return _s3.bidiag_singular_values(d, e, max_iter=max_iter)
