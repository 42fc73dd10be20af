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

__all__ = ["make_reflector", "apply_left", "apply_right", "reflector_matrix",
           "exact_matmul"]


def exact_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at the operands' full precision.

    A TPU runs an f32 dot as one bf16 pass unless asked for more, which
    would cap every f32 result at ~3 significant digits; the pipeline's
    accuracy contract (a few n*eps of the working dtype) needs the exact
    passes.  Other platforms compute f32/f64 dots exactly either way.
    """
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)



def make_reflector(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compute (v, tau, beta) for a length-L vector x (L static).

    v[0] == 1 whenever tau != 0. Safe for zero vectors: returns tau = 0,
    v = e_0, beta = x[0].
    """
    dt = x.dtype
    # Accumulate norms in f32 at minimum (bf16 sums are too lossy).
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    alpha = x[0].astype(acc)
    x2 = x[1:].astype(acc)
    sigma = jnp.sum(x2 * x2)
    mu = jnp.sqrt(alpha * alpha + sigma)
    # beta gets the sign opposite to alpha (avoids cancellation).
    beta = jnp.where(alpha >= 0, -mu, mu)
    denom = alpha - beta
    safe = sigma > 0
    denom = jnp.where(safe, denom, 1.0)
    tau = jnp.where(safe, (beta - alpha) / beta, 0.0)
    v2 = jnp.where(safe, x2 / denom, 0.0)
    v = jnp.concatenate([jnp.ones((1,), acc), v2])
    beta_out = jnp.where(safe, beta, alpha)
    return v.astype(dt), tau.astype(dt), beta_out.astype(dt)


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
