"""Stage 3, divide-and-conquer backend: bidiagonal singular values that
scale with n (DESIGN.md §14).

The bisection path (``core.bidiag_svd``) does 60 sequential Sturm sweeps of
depth 2n per singular value — its critical path grows like n even though the
roots are independent.  This module solves the same Golub–Kahan (GK)
tridiagonal ``[[0, B^T], [B, 0]]`` by Cuppen's divide-and-conquer instead:

  split   T = diag(T1', T2') + rho * u u^T  at the middle off-diagonal
          (rho = |b_mid|, u = e_p + sign(b_mid) e_{p+1}; the boundary
          diagonal entries of the halves absorb -rho),
  leaves  generalized Sturm bisection — the same guarded LDL^T pivot
          recurrence as the existing path, extended to a nonzero diagonal —
          below the ``leaf_n`` cutoff, plus guarded inverse iteration for
          the leaf eigenvector rows,
  merge   bottom-up through the secular equation
          1 + rho * sum_i z_i^2 / (d_i - mu) = 0: deflation first
          (negligible z components, then near-equal poles via a Givens
          scan), then a vectorized fixed-iteration-count safeguarded Newton
          solve across ALL batch x subproblem x root axes at once — every
          merge level is ONE dispatch, not a per-root loop.

Only the spectrum and the FIRST and LAST eigenvector rows (f, l) are carried
through the recursion — that is all a parent merge needs to form its z
vector (z = concat(l_left, sign * f_right)) — so the per-level state is
O(m), not O(m^2).  Stability of the merge follows Gu/Eisenstat: after the
roots are found, z is RECOMPUTED from the Loewner interlacing identity
(all factors positive, evaluated as log1p sums) so eigenvector weights stay
accurate even for tightly clustered poles.

Odd / non-power-of-two sizes are padded with decoupled sentinel poles below
the spectrum; they deflate for free at every merge and are sliced off at the
end.  Deflation is exploited STRUCTURALLY, not just numerically: actives
form a contiguous prefix after the merge partitions, so every full-width
pass (secular f evaluations, the Loewner product, the eigenvector-row sums)
runs as a blocked reduction whose all-deflated blocks are skipped by a
``lax.cond`` at run time — a random n=4k spectrum keeps ~1.5% of its poles
active at the top merge, and the skips turn that into wall-clock.  The cost
is that batches go through ``lax.map`` (sequential per matrix), not vmap:
vmap would lower the skip conds to both-branch selects.

``sigma``-agreement with the bisection oracle to <= 1e-12 (fp64) gates this
module in CI (tests/test_bidiag_dc.py, benchmarks/stage3.py --check).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import obs

from .householder import exact_matmul as _mm
from .bidiag_svd import (_gk_prescale, _vectors_from_sigma,
                         bidiag_singular_values, bidiag_svd,
                         default_bisect_iters, gk_offdiag)

__all__ = ["DEFAULT_DC_LEAF_N", "DEFAULT_DC_N_MIN",
           "bidiag_dc_singular_values", "bidiag_dc_svd"]

# Bidiagonal sizes at or below this solve with the existing bisection path
# outright; inside a D&C recursion it is also the leaf width (GK leaves are
# 2*leaf_n).  32 keeps the leaf bisection's sequential depth trivial while
# the merge tree stays shallow (log2(n/32) levels).
DEFAULT_DC_LEAF_N = 32

# Static crossover: below this n the bisection path wins (its critical path
# is short and it skips the merge-tree overhead); ``stage3="auto"`` uses the
# autotune-cache measurement instead when one exists (DESIGN.md §14).
DEFAULT_DC_N_MIN = 2048

# Roots per secular-solve block: bounds the (m, chunk) broadcast that each
# full-width secular pass materializes, so the top-level merge of an n=16k
# problem never asks for an O(m^2) temporary in one piece.
_SECULAR_CHUNK = 512

# Poles gathered around each root for the windowed model iteration: the
# middle-way updates run against the K index-nearest poles exactly plus a
# first-order (value + slope) far-field model frozen at the interval
# midpoint.  Far poles contribute a function that is smooth across one
# pole gap, so the linearization error sits orders of magnitude inside
# what the exact polish passes absorb, while the per-iteration work drops
# from m*m to m*K (~64x at n=8k).
_DC_WINDOW_K = 128

# Globally heaviest poles added to every root's window regardless of index
# distance.  GK eigenvectors of random bidiagonals localize, so z^2 spans
# many orders of magnitude and an index-far pole can carry O(1) of the
# rank-one mass — linearizing across such a pole is what breaks the
# far-field model (observed ~1e-3 model roots).  Gathering the top-K
# weights keeps the residual far field made of LIGHT poles only, for which
# the first-order model holds.
_DC_HEAVY_K = 32

# Cap on the exact full-width middle-way passes after the windowed
# iteration, run against the ORIGINAL safeguard bracket (the windowed
# phase brackets on MODEL signs, which must not constrain the true root).
# The loop exits as soon as EVERY active root's residual reaches the
# rounding floor of its secular sum — typically 3-5 passes from the
# windowed start — so the cap only bounds adversarial spectra.  These
# passes dominate large-n merge cost: the early exit is the dc-vs-bisect
# crossover lever.
_DC_POLISH_ITERS = 12


def _acc_dtype(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt


# ---------------------------------------------------------------------------
# Leaves: generalized Sturm bisection + inverse iteration
# ---------------------------------------------------------------------------

def _tridiag_count(a: jax.Array, b: jax.Array, lam: jax.Array) -> jax.Array:
    """#eigenvalues below ``lam`` of the symmetric tridiagonal (diag a,
    offdiag b) — the zero-diagonal ``sturm_count`` recurrence with the
    diagonal restored: q_k = (a_k - lam) - b_{k-1}^2 / q_{k-1}."""
    acc = a.dtype
    tiny = jnp.asarray(jnp.finfo(acc).tiny * 4, acc)
    m = a.shape[0]

    def body(k, carry):
        q, cnt = carry
        q = jnp.where(jnp.abs(q) < tiny, jnp.where(q < 0, -tiny, tiny), q)
        q_next = (a[k] - lam) - (b[k - 1] * b[k - 1]) / q
        return q_next, cnt + (q_next < 0)

    q0 = a[0] - lam
    cnt0 = (q0 < 0).astype(jnp.int32)
    _, cnt = jax.lax.fori_loop(1, m, body, (q0, cnt0))
    return cnt


def _tridiag_solve_diag(a: jax.Array, b: jax.Array, lam: jax.Array,
                        rhs: jax.Array) -> jax.Array:
    """Solve (T - lam*I) x = rhs for symmetric tridiagonal T (diag a, offdiag
    b): Thomas elimination with pivots guarded away from zero, exactly as the
    zero-diagonal ``_tridiag_solve`` — near-singular shifts are the point."""
    acc = a.dtype
    eps = jnp.finfo(acc).eps
    tiny = eps * jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(a)), jnp.max(jnp.abs(b))), 1)

    def guard(p):
        return jnp.where(jnp.abs(p) < tiny, jnp.where(p < 0, -tiny, tiny), p)

    piv0 = guard(a[0] - lam)
    y0 = rhs[0] / piv0

    def fwd(carry, inp):
        piv_prev, y_prev = carry
        a_i, b_im1, r_i = inp
        c_im1 = b_im1 / piv_prev
        piv = guard(a_i - lam - b_im1 * c_im1)
        y = (r_i - b_im1 * y_prev) / piv
        return (piv, y), (y, c_im1)

    (_, _), (ys, cs) = jax.lax.scan(fwd, (piv0, y0), (a[1:], b, rhs[1:]))
    ys_full = jnp.concatenate([y0[None], ys])

    def bwd(x_next, inp):
        y_i, c_i = inp
        x = y_i - c_i * x_next
        return x, x

    x_last = ys_full[-1]
    _, xs = jax.lax.scan(bwd, x_last, (ys_full[:-1], cs), reverse=True)
    return jnp.concatenate([xs, x_last[None]])


def _leaf_eigen(a: jax.Array, b: jax.Array, *, bisect_iters: int,
                inv_iters: int):
    """Full spectrum (ascending) + first/last eigenvector rows of one leaf.

    Values by the generalized Sturm bisection above (all eigenvalue indices
    bracket-refined in lockstep); vectors by guarded inverse iteration with
    deterministic k-dependent starts and a sequential same-cluster
    Gram-Schmidt (the leaf-size analog of ``_orthonormalize_pairs``).
    """
    acc = a.dtype
    lm = a.shape[0]
    ab = jnp.abs(b)
    pad = jnp.concatenate([jnp.zeros(1, acc), ab, jnp.zeros(1, acc)])
    rad = pad[:-1] + pad[1:]
    scale = jnp.maximum(jnp.max(jnp.abs(a) + rad), jnp.asarray(1, acc))
    lo0 = jnp.min(a - rad) - jnp.finfo(acc).eps * scale
    hi0 = jnp.max(a + rad) + jnp.finfo(acc).eps * scale
    ks = jnp.arange(lm)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jax.vmap(lambda x: _tridiag_count(a, b, x))(mid)
        ge = cnt >= ks + 1
        return jnp.where(ge, lo, mid), jnp.where(ge, mid, hi)

    lo, hi = jax.lax.fori_loop(
        0, bisect_iters, body,
        (jnp.full((lm,), lo0, acc), jnp.full((lm,), hi0, acc)))
    lam = 0.5 * (lo + hi)

    def vec_one(lamk, kidx):
        t = jnp.arange(1, lm + 1, dtype=acc)
        x0 = jnp.sin(t * (kidx.astype(acc) + 1) * jnp.asarray(0.7, acc)) \
            + jnp.asarray(0.01, acc)
        x = x0 / jnp.linalg.norm(x0)
        for _ in range(inv_iters):
            x = _tridiag_solve_diag(a, b, lamk, x)
            x = x / jnp.maximum(jnp.linalg.norm(x), jnp.finfo(acc).tiny)
        return x

    vecs = jax.vmap(vec_one)(lam, ks)            # rows are eigenvectors

    # Sequential same-cluster Gram-Schmidt: inverse iteration returns
    # near-parallel vectors inside a (near-)degenerate group; project each
    # against its earlier cluster mates, with an orthogonalized one-hot as
    # the collapse fallback (mirrors _orthonormalize_pairs).
    eps = jnp.finfo(acc).eps
    ctol = jnp.maximum(jnp.asarray(1e-3, acc) * scale,
                       jnp.asarray(64, acc) * eps * scale)
    tiny = jnp.finfo(acc).tiny

    def body_k(k, rows):
        mask = ((ks < k) & (lam[k] - lam < ctol)).astype(acc)

        def clean(w):
            w = w - _mm(mask * _mm(rows, w), rows)
            return w, jnp.linalg.norm(w)

        w1, n1 = clean(rows[k])
        w2, n2 = clean((ks == k).astype(acc))
        good = n1 > jnp.asarray(0.01, acc)
        v = jnp.where(good, w1 / jnp.maximum(n1, tiny),
                      w2 / jnp.maximum(n2, tiny))
        return rows.at[k].set(v)

    vecs = jax.lax.fori_loop(1, lm, body_k, vecs)
    return lam, vecs[:, 0], vecs[:, -1]


# ---------------------------------------------------------------------------
# Merge: deflation + one vectorized secular solve per level
# ---------------------------------------------------------------------------

def _chunked_cols(fn, tree, m: int):
    """Apply ``fn`` (pytree of (..., c) blocks -> pytree of (..., c) blocks)
    over the last axis in ``_SECULAR_CHUNK``-wide blocks via ``lax.map`` so
    the per-block broadcast stays bounded; single call when m is small."""
    if m <= _SECULAR_CHUNK:
        return fn(tree)
    nb = m // _SECULAR_CHUNK

    def reshape_in(x):
        blk = x.reshape(x.shape[:-1] + (nb, _SECULAR_CHUNK))
        return jnp.moveaxis(blk, -2, 0)

    def reshape_out(x):
        return jnp.moveaxis(x, 0, -2).reshape(
            x.shape[1:-1] + (nb * _SECULAR_CHUNK,))

    out = jax.lax.map(fn, jax.tree.map(reshape_in, tree))
    return jax.tree.map(reshape_out, out)


def _axis_blocks(tree, m: int):
    """Stack (..., m) leaves into (nb, ..., CH) reduction blocks (nb = 1
    when m fits one chunk) for a skip-capable blocked sum."""
    if m <= _SECULAR_CHUNK:
        return jax.tree.map(lambda x: x[None], tree)
    nb = m // _SECULAR_CHUNK

    def r(x):
        blk = x.reshape(x.shape[:-1] + (nb, _SECULAR_CHUNK))
        return jnp.moveaxis(blk, -2, 0)

    return jax.tree.map(r, tree)


def _skip_block_sum(fn, blocks, pred_fn, proto):
    """``sum_b fn(block_b)`` over the leading block axis, with blocks where
    ``pred_fn(block)`` is False contributing zeros WITHOUT doing the work.

    This is where deflation turns into wall-clock: active poles/roots form a
    contiguous prefix after the merge partitions, so all-deflated blocks —
    the vast majority at the top merge levels of a random spectrum — reduce
    to one predicate evaluation.  The predicate must stay a SCALAR for
    ``lax.cond`` to stay a branch (vmap would lower it to a select that runs
    both sides), which is why the drivers batch with ``lax.map``, not vmap.
    ``proto`` is a zeros pytree of one block's output."""
    def one(blk):
        return jax.lax.cond(pred_fn(blk), fn, lambda _: proto, blk)

    parts = jax.lax.map(one, blocks)
    return jax.tree.map(lambda x: jnp.sum(x, axis=0), parts)


def _secular_roots(d, w, gap, act, d_next, a_next, *, newton_iters: int):
    """Roots mu_j of 1 + sum_i w_i / (d_i - mu) = 0, one per ACTIVE pole,
    mu_j in (d_j, d_j + gap_j), returned as (anchor, tau) with
    mu_j = anchor_j + tau_j.

    Each root is anchored at its NEAREST pole (chosen from the sign of f at
    the interval midpoint, as dlaed4 does): a root hugging the upper pole is
    represented as a small negative shift from d_{j+1} instead of a
    nearly-cancelling ``gap + tiny`` shift from d_j, which is what keeps
    pole distances ``d_i - mu_j`` computable to full relative accuracy both
    here and in the downstream Loewner / eigenvector-row formulas.

    The iteration is the dlaed4 "middle way": fit
    ``c + s/(D1-eta) + S/(D2-eta)`` matching f AND f' at the current
    iterate (mass split by the psi'/phi' one-sided derivative sums between
    the two bracketing poles) and jump to the model root — quadratic near
    convergence, monotone globally.  A sign-driven bracket with midpoint
    fallback safeguards every step, and an iterate whose residual reaches
    the rounding floor of the secular sum is frozen so noise-level sign
    flips cannot un-converge it.  All roots advance in lockstep, so a merge
    level is one fixed-shape dispatch rather than a per-root loop.

    Cost shape (the reason dc beats bisection at large n): only ONE
    full-width secular evaluation per root (the midpoint pass, which picks
    the anchor AND freezes a first-order model of the far field) plus
    ``_DC_POLISH_ITERS`` exact passes at the end; the ``newton_iters``
    middle-way updates in between run against the ``_DC_WINDOW_K``
    index-nearest poles exactly with the far field linearized, m*K work
    instead of m*m.  Far-pole sums are smooth across one pole gap, so the
    model root lands within the linearization error and the bracketed
    exact polish converges it to the rounding floor."""
    acc = d.dtype
    eps = jnp.finfo(acc).eps
    m = d.shape[-1]
    one = jnp.asarray(1, acc)
    zero = jnp.asarray(0, acc)
    iarr = jnp.arange(m)
    kwin = min(_DC_WINDOW_K, m)
    # Pole-axis reduction blocks, shared by every full-width pass: blocks
    # whose weights are all zero (the deflated suffix) are skipped at run
    # time, so a heavily deflated merge pays for its ACTIVE poles only.
    pblocks = _axis_blocks({"d": d, "w": w, "i": iarr}, m)

    def active_block(blk):
        dj, gapj, actj, jidx, dnx, nxtj = (
            blk["d"], blk["gap"], blk["act"], blk["idx"], blk["dnx"],
            blk["nxt"])
        gap_safe = jnp.where(actj & (gapj > 0), gapj, one)
        half = 0.5 * gap_safe

        def full_sums(anc_, t):
            # One-sided sums at mu = anc + t: psi (poles i <= j, all terms
            # <= 0 since w >= 0 and d_i <= d_j < mu), phi (i > j, terms
            # >= 0), and their derivative splits.  The sign structure makes
            # the |.|-scale free (sum|terms| = phi - psi, an ADDITION of
            # magnitudes) and the w == 0 guard exact (0/1 == 0).  psi'/phi'
            # must stay separate masked reductions: both are positive, so
            # deriving one as ``total' - other'`` cancels catastrophically
            # for a root hugging one pole, and a garbage off-side slope
            # degrades the middle-way step to bracket bisection.
            tc = t[..., None, :]
            ancc = anc_[..., None, :]
            proto = (jnp.zeros_like(t),) * 4

            def one_blk(pb):
                wcb = pb["w"][..., :, None]
                leftb = pb["i"][..., :, None] <= jidx[..., None, :]
                denom = (pb["d"][..., :, None] - ancc) - tc
                safe = jnp.where(wcb == 0, one, denom)
                r = wcb / safe
                r2 = r / safe
                tot = jnp.sum(r, axis=-2)
                psi = jnp.sum(jnp.where(leftb, r, zero), axis=-2)
                psip = jnp.sum(jnp.where(leftb, r2, zero), axis=-2)
                phip = jnp.sum(jnp.where(leftb, zero, r2), axis=-2)
                return psi, tot - psi, psip, phip

            return _skip_block_sum(one_blk, pblocks,
                                   lambda pb: jnp.any(pb["w"] != 0), proto)

        # Index-nearest pole window per root (clipped at the spectrum ends;
        # out-of-range slots carry zero weight so they drop out of every
        # sum), plus the _DC_HEAVY_K globally heaviest poles (zeroed where
        # they duplicate an index-window slot).  Gathered once per block —
        # the windowed loop streams only (..., chunk, K) arrays.
        base = jidx[..., None] - (kwin // 2) + jnp.arange(kwin)
        gidx = jnp.clip(base, 0, m - 1)
        flat = gidx.reshape(gidx.shape[:-2] + (-1,))
        dw = jnp.take_along_axis(d, flat, axis=-1).reshape(gidx.shape)
        ww = jnp.take_along_axis(w, flat, axis=-1).reshape(gidx.shape)
        ww = jnp.where((base >= 0) & (base < m), ww, zero)
        leftw = base <= jidx[..., None]

        ktop = min(_DC_HEAVY_K, m)
        wt, hidx = jax.lax.top_k(w, ktop)                # (..., ktop)
        dh = jnp.take_along_axis(d, hidx, axis=-1)
        hcol = hidx[..., None, :]                        # (..., 1, ktop)
        bmin = jidx[..., None] - (kwin // 2)
        wh = jnp.where((hcol >= bmin) & (hcol < bmin + kwin),
                       zero, wt[..., None, :])           # (..., c, ktop)
        lefth = hcol <= jidx[..., None]

        def win_sums(deltaw, wwc, leftc, t):
            denomw = deltaw - t[..., None]
            safew = jnp.where(wwc == 0, one, denomw)
            rw = wwc / safew
            rw2 = rw / safew
            totw = jnp.sum(rw, axis=-1)
            psiw = jnp.sum(jnp.where(leftc, rw, zero), axis=-1)
            psipw = jnp.sum(jnp.where(leftc, rw2, zero), axis=-1)
            phipw = jnp.sum(jnp.where(leftc, zero, rw2), axis=-1)
            return psiw, totw - psiw, psipw, phipw

        def near_sums(dwin, dhvy, t):
            pw, fw, ppw, fpw = win_sums(dwin, ww, leftw, t)
            ph, fh, pph, fph = win_sums(dhvy, wh, lefth, t)
            return pw + ph, fw + fh, ppw + pph, fpw + fph

        def mw_update(f, fscale, psip, phip, t, lo, hi):
            # At |f| ~ eps * sum|terms| the root is resolved to rounding;
            # freeze it so a sign flip in the noise cannot un-converge t
            # (the midpoint fallback would teleport it back to mid-bracket).
            done = jnp.abs(f) <= 8 * eps * fscale
            upd = ~done
            lo = jnp.where(upd & (f < 0), t, lo)
            hi = jnp.where(upd & (f >= 0), t, hi)
            # Middle-way step: c*eta^2 - a*eta + b = 0 with
            #   a = (D1+D2) f - D1 D2 f',  b = D1 D2 f,
            #   c = f - D1 psi' - D2 phi',
            # D1/D2 the (anchor-relative) distances to the bracketing poles.
            d1 = -off - t
            d2 = (gap_safe - off) - t
            fp = psip + phip
            aq = (d1 + d2) * f - d1 * d2 * fp
            bq = d1 * d2 * f
            cq = f - d1 * psip - d2 * phip
            disc = jnp.sqrt(jnp.maximum(aq * aq - 4 * bq * cq, 0))
            eta_pos = 2 * bq / (aq + disc)
            eta_neg = (aq - disc) / (2 * jnp.where(cq == 0, one, cq))
            eta = jnp.where(aq > 0, eta_pos,
                            jnp.where(cq == 0,
                                      bq / jnp.where(aq == 0, one, aq),
                                      eta_neg))
            cand = t + eta
            inside = (cand > lo) & (cand < hi)
            t_new = jnp.where(inside, cand, 0.5 * (lo + hi))
            return jnp.where(done, t, t_new), lo, hi

        # THE full-width midpoint pass: f0's sign picks the nearest-pole
        # anchor, and subtracting the window's share leaves the far field's
        # value and slope at the midpoint mu0 = d_j + gap/2 — the frozen
        # linear model the windowed iteration adds to its exact near sums.
        # Sign clamps keep the far parts on the right side of zero when the
        # subtraction is all cancellation (window covers everything).
        psi0, phi0, psip0, phip0 = full_sums(dj, half)
        f0 = 1 + psi0 + phi0
        psiw0, phiw0, psipw0, phipw0 = near_sums(
            dw - dj[..., None], dh[..., None, :] - dj[..., None], half)
        psi_f = jnp.minimum(psi0 - psiw0, zero)
        phi_f = jnp.maximum(phi0 - phiw0, zero)
        psip_f = jnp.maximum(psip0 - psipw0, zero)
        phip_f = jnp.maximum(phip0 - phipw0, zero)

        # Nearest-pole anchor: f(mid) < 0 puts the root in the upper half,
        # so shift the origin to the next pole (when one exists; the top
        # root's upper end is the sum_w bound, not a pole — stay at d_j).
        upper = (f0 < 0) & nxtj
        anc = jnp.where(upper, dnx, dj)
        off = jnp.where(upper, gap_safe, zero)           # anc - d_j
        lo0 = jnp.where(upper, -half,
                        jnp.where(f0 < 0, half, zero))
        hi0 = jnp.where(upper, zero,
                        jnp.where(f0 < 0, gap_safe, half))

        deltaw = dw - anc[..., None]                     # exact: both poles
        deltah = dh[..., None, :] - anc[..., None]

        def wbody(_, state):
            t, lo, hi = state
            s = (off - half) + t                         # mu - mu0
            psiw, phiw, psipw, phipw = near_sums(deltaw, deltah, t)
            psi_m = psi_f + psip_f * s + psiw
            phi_m = phi_f + phip_f * s + phiw
            f = 1 + psi_m + phi_m
            fscale = 1 + jnp.abs(phi_m) + jnp.abs(psi_m)
            return mw_update(f, fscale, psip_f + psipw, phip_f + phipw,
                             t, lo, hi)

        t0 = 0.5 * (lo0 + hi0)
        t1, _, _ = jax.lax.fori_loop(0, newton_iters, wbody, (t0, lo0, hi0))
        # The windowed bracket moved on MODEL signs — discard it.  Polish
        # restarts from the original bracket; a model root that escaped it
        # (far-field error beyond the gap, only possible for near-deflated
        # noise roots) falls back to the midpoint.
        t1 = jnp.where((t1 > lo0) & (t1 < hi0), t1, t0)

        def pcond(state):
            it, _, _, _, quiet = state
            return (it < _DC_POLISH_ITERS) & ~quiet

        def pbody(state):
            it, t, lo, hi, _ = state
            psi, phi, psip, phip = full_sums(anc, t)
            f = 1 + psi + phi
            fscale = 1 + phi - psi
            t_new, lo, hi = mw_update(f, fscale, psip, phip, t, lo, hi)
            # Exit once every active root in the block is frozen at its
            # rounding floor — the freeze predicate inside mw_update, one
            # step behind (a root converging THIS pass exits NEXT pass).
            quiet = jnp.all((jnp.abs(f) <= 8 * eps * fscale) | ~actj)
            return it + 1, t_new, lo, hi, quiet

        _, t, _, _, _ = jax.lax.while_loop(
            pcond, pbody,
            (jnp.asarray(0), t1, lo0, hi0, jnp.asarray(False)))
        return {"anc": jnp.where(actj, anc, dj),
                "tau": jnp.where(actj, t, zero)}

    def solve_block(blk):
        # Root-chunk skip: active roots are a contiguous prefix, so chunks
        # past it (most of the spectrum at a heavily deflated merge) return
        # mu = d_j without touching the window gathers or any secular pass.
        return jax.lax.cond(
            jnp.any(blk["act"]), active_block,
            lambda b: {"anc": b["d"], "tau": jnp.zeros_like(b["d"])}, blk)

    tree = {"d": d, "gap": gap, "act": act, "dnx": d_next, "nxt": a_next,
            "idx": jnp.broadcast_to(iarr, d.shape)}
    out = _chunked_cols(solve_block, tree, m)
    return out["anc"], out["tau"]


def _merge_pair(d1, f1, l1, d2, f2, l2, rho_b, *, newton_iters: int,
                need_rows: bool = True):
    """One merge level: children (ascending spectra + first/last eigenvector
    rows, stacked on the leading axes) -> parent triple of twice the size.
    ``rho_b`` is the signed coupling off-diagonal.

    ``need_rows=False`` (the TOP level, whose output feeds no parent merge)
    skips the Loewner z-recomputation and the f/l row passes — two of the
    level's O(m^2) sweeps — and returns zero rows."""
    acc = d1.dtype
    eps = jnp.finfo(acc).eps
    h = d1.shape[-1]
    m = 2 * h
    rho = jnp.abs(rho_b)[..., None]                          # (..., 1)
    sgn = jnp.where(rho_b < 0, -1.0, 1.0).astype(acc)[..., None]

    d = jnp.concatenate([d1, d2], axis=-1)
    z = jnp.concatenate([l1, sgn * f2], axis=-1)
    fe = jnp.concatenate([f1, jnp.zeros_like(f2)], axis=-1)
    le = jnp.concatenate([jnp.zeros_like(l1), l2], axis=-1)

    order = jnp.argsort(d, axis=-1)
    take = lambda x: jnp.take_along_axis(x, order, axis=-1)  # noqa: E731
    d, z, fe, le = take(d), take(z), take(fe), take(le)

    norm_scale = jnp.max(jnp.abs(d), axis=-1, keepdims=True) + 2 * rho
    tol = jnp.maximum(8 * eps * norm_scale,
                      jnp.asarray(jnp.finfo(acc).tiny * 16, acc))

    # -- deflation pass 1: negligible rank-one weight ------------------------
    active = rho * jnp.abs(z) > tol

    # Partition: active poles first (still ascending — stable sort), deflated
    # last.  Adjacent-pole deflation and the secular brackets then only ever
    # look at neighbors inside a contiguous active prefix.
    part = jnp.argsort(jnp.where(active, 0, 1), axis=-1, stable=True)
    takep = lambda x: jnp.take_along_axis(x, part, axis=-1)  # noqa: E731
    d, z, fe, le, active = (takep(d), takep(z), takep(fe), takep(le),
                            takep(active))

    # -- deflation pass 2: near-equal poles (Givens scan) --------------------
    # Sequentially fold runs of near-equal active poles together: rotate the
    # pair so one z component vanishes, hand its (weighted) pole over as a
    # deflated eigenvalue, and keep accumulating mass in the survivor.  The
    # dropped off-diagonal |c*s*(d_i - d_c)| <= tol is the deflation error.
    def scan_step(carry, col):
        d_c, z_c, f_c, l_c, a_c = carry
        d_i, z_i, f_i, l_i, a_i = col
        r2 = z_c * z_c + z_i * z_i
        r = jnp.sqrt(r2)
        r_safe = jnp.where(r > 0, r, jnp.asarray(1, acc))
        cg = jnp.where(r > 0, z_i / r_safe, jnp.asarray(1, acc))
        sg = jnp.where(r > 0, z_c / r_safe, jnp.asarray(0, acc))
        off = jnp.abs(cg * sg * (d_i - d_c))
        mrg = a_c & a_i & (off <= tol[..., 0])
        emit = (jnp.where(mrg, cg * cg * d_c + sg * sg * d_i, d_c),
                jnp.where(mrg, jnp.asarray(0, acc), z_c),
                jnp.where(mrg, cg * f_c - sg * f_i, f_c),
                jnp.where(mrg, cg * l_c - sg * l_i, l_c),
                a_c & ~mrg)
        # The rotation moves BOTH diagonal entries (dlaed2 does the same):
        # the deflation criterion also fires for well-separated poles with
        # very imbalanced z, where the surviving pole lands near d_c, not
        # d_i — keeping d_i would hang the combined weight on the wrong
        # pole.  Both new values stay inside [d_c, d_i], so the ascending
        # active order survives.
        new = (jnp.where(mrg, sg * sg * d_c + cg * cg * d_i, d_i),
               jnp.where(mrg, r, z_i),
               jnp.where(mrg, sg * f_c + cg * f_i, f_i),
               jnp.where(mrg, sg * l_c + cg * l_i, l_i),
               a_i)
        return new, emit

    cols = tuple(jnp.moveaxis(x, -1, 0) for x in (d, z, fe, le, active))
    init = tuple(c[0] for c in cols)
    rest = tuple(c[1:] for c in cols)
    last, emitted = jax.lax.scan(scan_step, init, rest)
    d, z, fe, le, active = tuple(
        jnp.moveaxis(jnp.concatenate([em, la[None]], axis=0), 0, -1)
        for em, la in zip(emitted, last))

    # Re-partition: the Givens pass punches holes in the active prefix (an
    # emitted survivor pair leaves a deflated slot mid-prefix); without this
    # second stable partition a root below such a hole would see a_next ==
    # False and get the top-of-spectrum bracket instead of its real
    # next-active-pole gap.  The scan keeps d ascending among actives, so a
    # stable actives-first sort restores a contiguous ascending prefix.
    part = jnp.argsort(jnp.where(active, 0, 1), axis=-1, stable=True)
    d, z, fe, le, active = (takep(d), takep(z), takep(fe), takep(le),
                            takep(active))

    # -- secular solve over the active prefix --------------------------------
    w = jnp.where(active, rho * z * z, jnp.asarray(0, acc))
    sum_w = jnp.sum(w, axis=-1, keepdims=True)
    d_next = jnp.concatenate(
        [d[..., 1:], jnp.zeros_like(d[..., :1])], axis=-1)
    a_next = jnp.concatenate(
        [active[..., 1:], jnp.zeros_like(active[..., :1])], axis=-1)
    gap = jnp.where(a_next, d_next - d,
                    sum_w * (1 + 4 * eps) + 4 * eps * norm_scale)
    anc, tau = _secular_roots(d, w, gap, active, d_next, a_next,
                              newton_iters=newton_iters)
    mu = jnp.where(active, anc + tau, d)
    if not need_rows:
        order2 = jnp.argsort(mu, axis=-1)
        mu = jnp.take_along_axis(mu, order2, axis=-1)
        return mu, jnp.zeros_like(mu), jnp.zeros_like(mu)
    # Shift from each root's OWN pole (anc may be the next pole up);
    # accurate relative to far poles, cancellation-prone only where the
    # anchored form (anc - d_i) + tau takes over below.
    t = jnp.where(active, (anc - d) + tau, jnp.asarray(0, acc))

    # -- Loewner recomputation of z (Gu's trick) -----------------------------
    # rho * zhat_i^2 = t_i * prod_{j != i} (mu_j - d_i) / (d_j - d_i); every
    # ratio is positive by interlacing.  Far poles (ratio near 1) go through
    # log1p(t_j / (d_j - d_i)); near poles switch to the anchored numerator
    # (anc_j - d_i) + tau_j, which is exact at the anchor itself.
    m_all = d.shape[-1]
    tiny = jnp.asarray(jnp.finfo(acc).tiny, acc)
    # Root-axis reduction blocks for the Loewner product: deflated roots
    # contribute log(1) = 0, and they sit in a contiguous suffix, so whole
    # blocks of them are skipped at run time.
    rblocks = _axis_blocks(
        {"d": d, "t": t, "anc": anc, "tau": tau, "act": active}, m_all)

    def zhat_block(blk):
        def run(b):
            di, acti = b["d"], b["act"]

            def one_blk(rb):
                deltaji = rb["d"][..., :, None] - di[..., None, :]
                safe = jnp.where(deltaji == 0, jnp.asarray(1, acc), deltaji)
                x = rb["t"][..., :, None] / safe
                num = ((rb["anc"][..., :, None] - di[..., None, :])
                       + rb["tau"][..., :, None])
                ratio = num / safe
                logr = jnp.where(
                    jnp.abs(x) < 0.5,
                    jnp.log1p(jnp.maximum(x, jnp.asarray(-0.75, acc))),
                    jnp.log(jnp.maximum(ratio, tiny)))
                mask = (rb["act"][..., :, None] & acti[..., None, :] &
                        (deltaji != 0))
                return jnp.sum(jnp.where(mask, logr, jnp.asarray(0, acc)),
                               axis=-2)

            return _skip_block_sum(one_blk, rblocks,
                                   lambda rb: jnp.any(rb["act"]),
                                   jnp.zeros_like(di))

        # Target-chunk skip: deflated targets keep zhat = 0 regardless.
        return jax.lax.cond(jnp.any(blk["act"]), run,
                            lambda b: jnp.zeros_like(b["d"]), blk)

    logprod = _chunked_cols(zhat_block, {"d": d, "act": active}, m_all)
    rho_safe = jnp.where(rho > 0, rho, jnp.asarray(1, acc))
    zhat2 = jnp.where(active, t / rho_safe * jnp.exp(logprod),
                      jnp.asarray(0, acc))
    zhat = jnp.where(z < 0, -jnp.sqrt(zhat2), jnp.sqrt(zhat2))

    # -- parent first/last rows ----------------------------------------------
    # Pole-axis blocks: deflated poles carry zhat = 0 and contribute nothing
    # to the eigenvector sums — whole zero-weight blocks are skipped.
    vblocks = _axis_blocks({"d": d, "zh": zhat, "fe": fe, "le": le}, m_all)

    def fl_block(blk):
        def run(b):
            ancj, tj, actj = b["anc"], b["tau"], b["act"]

            def one_blk(pb):
                delta = pb["d"][..., :, None] - ancj[..., None, :]
                denom = delta - tj[..., None, :]              # d_i - mu_j
                zc = pb["zh"][..., :, None]
                bad = (zc == 0) | (denom == 0)
                safe = jnp.where(bad, jnp.asarray(1, acc), denom)
                wv = jnp.where(bad, jnp.asarray(0, acc), zc / safe)
                return (jnp.sum(wv * wv, axis=-2),
                        jnp.sum(pb["fe"][..., :, None] * wv, axis=-2),
                        jnp.sum(pb["le"][..., :, None] * wv, axis=-2))

            s2, sf, sl = _skip_block_sum(
                one_blk, vblocks, lambda pb: jnp.any(pb["zh"] != 0),
                (jnp.zeros_like(ancj),) * 3)
            nrm = jnp.sqrt(jnp.maximum(
                s2, jnp.asarray(jnp.finfo(acc).tiny, acc)))
            keep = ~actj
            return (jnp.where(keep, 0.0, sf / nrm),
                    jnp.where(keep, 0.0, sl / nrm))

        # Root-chunk skip: deflated roots keep their child rows verbatim.
        return jax.lax.cond(
            jnp.any(blk["act"]), run,
            lambda b: (jnp.zeros_like(b["anc"]), jnp.zeros_like(b["anc"])),
            blk)

    fj, lj = _chunked_cols(
        fl_block, {"anc": anc, "tau": tau, "act": active}, m_all)
    f_par = jnp.where(active, fj, fe)
    l_par = jnp.where(active, lj, le)

    order2 = jnp.argsort(mu, axis=-1)
    take2 = lambda x: jnp.take_along_axis(x, order2, axis=-1)  # noqa: E731
    return take2(mu), take2(f_par), take2(l_par)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("leaf_n", "newton_iters", "inv_iters"))
@obs.scope("stage3")
def bidiag_dc_singular_values(d: jax.Array, e: jax.Array, *,
                              leaf_n: int = DEFAULT_DC_LEAF_N,
                              newton_iters: int = 30,
                              inv_iters: int = 2) -> jax.Array:
    """All singular values of the bidiagonal (d, e) by divide-and-conquer,
    descending — same contract as :func:`bidiag_singular_values` (e[0]
    ignored; stacked bidiagonals ``(..., n)`` vmap).

    n <= ``leaf_n`` short-circuits to the bisection path; larger problems
    pad the GK tridiagonal to a power-of-two leaf grid, bisect the leaves,
    and run log2(n/leaf_n) secular merge levels, each one batched dispatch.
    """
    if leaf_n < 2:
        raise ValueError(f"leaf_n must be >= 2, got {leaf_n}")
    if d.ndim > 1:
        lead = d.shape[:-1]
        # Sequential per-matrix batching, NOT vmap: the deflation skips in
        # the merges are lax.cond branches on scalar "any active here?"
        # predicates, and vmap would lower them to selects that compute BOTH
        # sides — erasing the entire skip win.  Within one matrix every
        # merge level stays fully batched over its subproblem axis, which is
        # where the device-level parallelism lives.
        out = jax.lax.map(
            lambda de: bidiag_dc_singular_values(
                de[0], de[1], leaf_n=leaf_n, newton_iters=newton_iters,
                inv_iters=inv_iters),
            (d.reshape((-1, d.shape[-1])), e.reshape((-1, e.shape[-1]))))
        return out.reshape(lead + (d.shape[-1],))
    n = d.shape[0]
    if n <= leaf_n:
        return bidiag_singular_values(d, e)
    dt = d.dtype
    acc = _acc_dtype(dt)
    z = gk_offdiag(d.astype(acc), e.astype(acc))
    sc = _gk_prescale(z)
    z = z / sc

    m = 2 * n
    lm = 2 * leaf_n
    levels = max(0, math.ceil(math.log2(m / lm)))
    big = lm << levels                                   # padded GK size
    bisect_iters = default_bisect_iters(acc)

    a = jnp.zeros((big,), acc)
    b = jnp.zeros((big - 1,), acc)
    b = b.at[: m - 1].set(z)
    if big > m:
        # Decoupled sentinel poles strictly below the (scaled) spectrum:
        # their z components are exactly zero at every merge, so they
        # deflate for free and sort to the bottom.
        bound = jnp.max(jnp.abs(z)) * 2 + 1
        a = a.at[m:].set(-(bound + jnp.arange(big - m, dtype=acc) + 1))

    # Cuppen boundary corrections for EVERY level at once: each interior
    # leaf boundary i is the split point of exactly one merge, whose rank-one
    # term absorbs rho = |b_i| from both touching diagonal entries.
    idx = jnp.arange(big - 1)
    corr = jnp.where((idx + 1) % lm == 0, jnp.abs(b), 0)
    a = a - jnp.concatenate([corr, jnp.zeros(1, acc)])
    a = a - jnp.concatenate([jnp.zeros(1, acc), corr])

    nleaf = big // lm
    a_leaf = a.reshape(nleaf, lm)
    b_leaf = jnp.concatenate([b, jnp.zeros(1, acc)]).reshape(
        nleaf, lm)[:, : lm - 1]
    with jax.named_scope("dc_leaves"):
        lam, f, el = jax.vmap(functools.partial(
            _leaf_eigen, bisect_iters=bisect_iters,
            inv_iters=inv_iters))(a_leaf, b_leaf)

    # Device-side attribution per merge level (DESIGN.md §16): this loop
    # runs under jit, so host spans are meaningless here — named_scope
    # labels each level's ops in `jax.profiler.trace` captures instead.
    for lev in range(levels):
        sz = lm << lev
        npair = big // (2 * sz)
        pos = (2 * jnp.arange(npair) + 1) * sz - 1
        rho_b = b[pos]
        lam2 = lam.reshape(npair, 2, sz)
        f2 = f.reshape(npair, 2, sz)
        l2 = el.reshape(npair, 2, sz)
        with jax.named_scope(f"dc_merge_level_{lev}"):
            lam, f, el = _merge_pair(
                lam2[:, 0], f2[:, 0], l2[:, 0],
                lam2[:, 1], f2[:, 1], l2[:, 1], rho_b,
                newton_iters=newton_iters, need_rows=lev + 1 < levels)

    lam = lam.reshape(big)
    sig = jnp.abs(lam[big - n:][::-1])                   # top n, descending
    return (sig * sc).astype(dt)


@functools.partial(jax.jit,
                   static_argnames=("leaf_n", "newton_iters", "inv_iters"))
@obs.scope("stage3")
def bidiag_dc_svd(d: jax.Array, e: jax.Array, *,
                  leaf_n: int = DEFAULT_DC_LEAF_N,
                  newton_iters: int = 30,
                  inv_iters: int = 2):
    """Full SVD of the bidiagonal (d, e) with divide-and-conquer values:
    (U, sigma, V^T), same contract as :func:`core.bidiag_svd.bidiag_svd`.

    sigma comes from :func:`bidiag_dc_singular_values`; vectors reuse the
    sigma-agnostic inverse-iteration machinery (``_vectors_from_sigma``) —
    any few-ulp-accurate sigma seeds the same guarded GK solves, so the
    vector path needs no D&C-specific code and U/V stay consistent with the
    bisection backend's.
    """
    if leaf_n < 2:
        raise ValueError(f"leaf_n must be >= 2, got {leaf_n}")
    if d.ndim > 1:
        lead = d.shape[:-1]
        # lax.map, not vmap: see bidiag_dc_singular_values — vmap would
        # turn the merge-level deflation skips into both-branch selects.
        u, s, vt = jax.lax.map(
            lambda de: bidiag_dc_svd(
                de[0], de[1], leaf_n=leaf_n, newton_iters=newton_iters,
                inv_iters=inv_iters),
            (d.reshape((-1, d.shape[-1])), e.reshape((-1, e.shape[-1]))))
        n = d.shape[-1]
        return (u.reshape(lead + (n, n)), s.reshape(lead + (n,)),
                vt.reshape(lead + (n, n)))
    n = d.shape[0]
    if n <= leaf_n:
        return bidiag_svd(d, e, inv_iters=inv_iters)
    sig = bidiag_dc_singular_values(
        d, e, leaf_n=leaf_n, newton_iters=newton_iters,
        inv_iters=inv_iters)
    u, vt = _vectors_from_sigma(d, e, sig, inv_iters=inv_iters)
    return (u, sig, vt)
