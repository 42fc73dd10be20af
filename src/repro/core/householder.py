"""Householder reflector numerics (LAPACK ``larfg``-style, per paper §III-B:
"Details of the Householder reflector computation and the treatment of near-zero
elements are implemented according to prior work on tile-QR decomposition").

A reflector over ``x = [alpha, x2]`` produces ``(I - tau v v^T) x = [beta, 0]``
with ``v[0] = 1``.  Zero tails (``x2 == 0``) and fully-zero vectors yield
``tau = 0`` (identity) — this is what makes edge/padding handling in the chase
free: padded entries are exactly zero, so reflectors never touch them.

All functions are dtype-polymorphic (fp64/fp32/bf16) and vmap-safe.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["make_reflector", "reflector_parts", "apply_left", "apply_right",
           "reflector_matrix", "exact_matmul"]


def exact_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at the operands' full precision.

    A TPU runs an f32 dot as one bf16 pass unless asked for more, which
    would cap every f32 result at ~3 significant digits; the pipeline's
    accuracy contract (a few n*eps of the working dtype) needs the exact
    passes.  Other platforms compute f32/f64 dots exactly either way.
    """
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)



# A vector whose squared norm is below _TINY_SQ may have entries whose
# squares underflowed (float32's smallest normal is 2^-126); summed that way,
# sigma drifts from the tail that v is divided out of, and tau * ||v||^2
# leaves 2: the reflector is no longer orthogonal.  Such a vector is lifted
# by the exact power of two _LIFT before the norm is taken.  Every normal
# float32 then squares to a normal, and tau and v, which do not depend on
# x's scale, come out as for any other x; every other x takes the unlifted
# formulas bit for bit.
_TINY_SQ = 2.0 ** -60
_LIFT = 2.0 ** 64


def reflector_parts(alpha: jax.Array, tail: jax.Array, axis=None):
    """LAPACK ``larfg`` on ``x = [alpha, tail]``: ``(tau, v_tail, beta)``.

    The one copy of the reflector formula every implementation calls: the
    chase kernels (``kernels/ref.py``, ``kernels/bulge_chase.py``), stage 1
    and the fused tier.  ``tail`` holds x's other entries, zero wherever
    it is not part of x, reduced over ``axis`` (all axes when None; kept
    as a length-1 axis otherwise, as Mosaic wants); ``alpha`` broadcasts
    against the reduction.  ``v_tail`` is the tail of ``v`` (``v[0] = 1``
    is the caller's), zero where ``tail`` is; a tail whose squares sum to
    zero gives ``tau = 0`` (an identity, whatever ``v``) and ``beta =
    alpha``.  Tiny vectors are lifted first (``_TINY_SQ``).
    """
    keep = axis is not None
    sigma = jnp.sum(tail * tail, axis=axis, keepdims=keep)
    up = tail * _LIFT
    sigma_up = jnp.sum(up * up, axis=axis, keepdims=keep)
    small = alpha * alpha + sigma < _TINY_SQ
    a = jnp.where(small, alpha * _LIFT, alpha)
    sigma = jnp.where(small, sigma_up, sigma)
    mu = jnp.sqrt(a * a + sigma)
    # beta gets the sign opposite to alpha (avoids cancellation).
    beta = jnp.where(a >= 0, -mu, mu)
    safe = sigma > 0
    tau = jnp.where(safe, (beta - a) / jnp.where(safe, beta, 1.0), 0.0)

    def unlift(y):          # back to x's own scale, exactly
        return jnp.where(small, y * (1.0 / _LIFT), y)

    v_tail = tail / jnp.where(safe, unlift(a - beta), 1.0)
    return tau, v_tail, jnp.where(safe, unlift(beta), alpha)


def make_reflector(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compute (v, tau, beta) for a length-L vector x (L static).

    v[0] == 1 whenever tau != 0. Safe for zero vectors: returns tau = 0,
    v = e_0, beta = x[0].
    """
    dt = x.dtype
    # Accumulate norms in f32 at minimum (bf16 sums are too lossy).
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    tau, v2, beta = reflector_parts(x[0].astype(acc), x[1:].astype(acc))
    v = jnp.concatenate([jnp.ones((1,), acc), v2])
    return v.astype(dt), tau.astype(dt), beta.astype(dt)


def apply_left(v: jax.Array, tau: jax.Array, c: jax.Array) -> jax.Array:
    """C <- (I - tau v v^T) C,  v: (L,), C: (L, m)."""
    acc = jnp.float32 if c.dtype in (jnp.bfloat16, jnp.float16) else c.dtype
    vv = v.astype(acc)
    w = exact_matmul(vv, c.astype(acc))  # (m,)
    out = c.astype(acc) - tau.astype(acc) * jnp.outer(vv, w)
    return out.astype(c.dtype)


def apply_right(v: jax.Array, tau: jax.Array, c: jax.Array) -> jax.Array:
    """C <- C (I - tau v v^T),  v: (L,), C: (m, L)."""
    acc = jnp.float32 if c.dtype in (jnp.bfloat16, jnp.float16) else c.dtype
    vv = v.astype(acc)
    w = exact_matmul(c.astype(acc), vv)  # (m,)
    out = c.astype(acc) - tau.astype(acc) * jnp.outer(w, vv)
    return out.astype(c.dtype)


def reflector_matrix(v: jax.Array, tau: jax.Array) -> jax.Array:
    """Dense (I - tau v v^T) — test/debug helper."""
    return jnp.eye(v.shape[0], dtype=v.dtype) - tau * jnp.outer(v, v)
