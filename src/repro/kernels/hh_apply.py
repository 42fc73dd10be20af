"""Pallas TPU kernels: compact-WY blocked reflector applies.

``hh_block_apply_pallas`` (stage-1 hotspot):

    C <- (I - V T V^T) C

V: (m, k) reflector block (k = panel width, small), T: (k, k), C: (m, n).
Grid tiles the columns of C; V and T stay VMEM-resident across grid steps
(their index_map is constant, so the pipeline fetches them once), while C
streams through in ``block_cols`` stripes — three MXU matmuls per stripe.
This is the GEMM-dense counterpart of the memory-bound chase kernel: stage 1
is where the paper's pipeline earns its "compute density" (paper §I).

``tape_apply_pallas`` (tape replay, DESIGN.md §8) is the slot-batched
variant used by ``core/transforms.py`` to replay reflector tapes into
``U``/``V^T``: per wavefront slot ``s`` it applies ``(I - V_s T_s V_s^T)``
to that slot's accumulator slice, grid ``(S, column stripes)`` — the same
wavefront batching (``S = B*G``) as the chase itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["hh_block_apply_pallas", "tape_apply_pallas"]


@functools.partial(jax.jit, static_argnames=("interpret", "block_cols"))
def hh_block_apply_pallas(v: jax.Array, t: jax.Array, c: jax.Array, *,
                          interpret: bool = False, block_cols: int = 512
                          ) -> jax.Array:
    """C <- (I - V T V^T) C with column-striped pipelining.

    The single-problem view of :func:`tape_apply_pallas` (slot count 1) —
    one kernel serves both the stage-1 trailing update and the tape replay.
    """
    return tape_apply_pallas(v[None], t[None], c[None], interpret=interpret,
                             block_cols=block_cols)[0]


def _tape_kernel(v_ref, t_ref, c_ref, o_ref):
    acc = jnp.float32 if c_ref.dtype in (jnp.bfloat16, jnp.float16) else c_ref.dtype
    # f32-exact MXU passes: the single bf16 pass TPUs default to would cost
    # the replayed factors ~3 decimal digits.
    hi = jax.lax.Precision.HIGHEST
    v = v_ref[...].astype(acc)                             # (m, k)
    t = t_ref[...].astype(acc)                             # (k, k)
    c = c_ref[...].astype(acc)                             # (m, bc)
    w1 = jax.lax.dot_general(v, c, (((0,), (0,)), ((), ())), precision=hi,
                             preferred_element_type=acc)   # V^T C: (k, bc)
    w2 = jnp.dot(t, w1, precision=hi, preferred_element_type=acc)
    o_ref[...] = (c - jnp.dot(v, w2, precision=hi,
                              preferred_element_type=acc)).astype(o_ref.dtype)


_I0 = np.int32(0)   # int32 block index literal, whatever jax_enable_x64 says
_C_BLOCK_BYTES = 2 ** 20   # one streamed C stripe; x4 with in/out double-buffering


def _stripe_cols(m: int, w: int, block_cols: int, itemsize: int) -> int:
    """Stripe width: ``block_cols`` capped so an (m, stripe) block stays
    within ``_C_BLOCK_BYTES``, in whole 128-lane tiles (or all of ``w``)."""
    cap = max(128, _C_BLOCK_BYTES // (m * itemsize) // 128 * 128)
    return min(block_cols, w, cap)


@functools.partial(jax.jit, static_argnames=("interpret", "block_cols"))
def tape_apply_pallas(v: jax.Array, t: jax.Array, c: jax.Array, *,
                      interpret: bool = False, block_cols: int = 512
                      ) -> jax.Array:
    """Per-slot C[s] <- (I - V[s] T[s] V[s]^T) C[s].

    v: (S, m, k), t: (S, k, k), c: (S, m, w).  V/T are VMEM-resident per
    slot; C streams in column stripes of at most ``block_cols`` (fewer for
    tall m, see ``_stripe_cols``), grid ``(S, stripes)``.
    """
    s, m, k = v.shape
    w = c.shape[-1]
    bc = _stripe_cols(m, w, block_cols, c.dtype.itemsize)
    pad = (-w) % bc
    cp = jnp.pad(c, ((0, 0), (0, 0), (0, pad))) if pad else c
    grid = (s, cp.shape[-1] // bc)
    out = pl.pallas_call(
        _tape_kernel,
        out_shape=jax.ShapeDtypeStruct(cp.shape, c.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, m, k), lambda i, j: (i, _I0, _I0)),  # V per slot
            pl.BlockSpec((None, k, k), lambda i, j: (i, _I0, _I0)),  # T per slot
            pl.BlockSpec((None, m, bc), lambda i, j: (i, _I0, j)),   # C streamed
        ],
        out_specs=pl.BlockSpec((None, m, bc), lambda i, j: (i, _I0, j)),
        interpret=interpret,
        name="tape_apply",
    )(v, t, cp)
    return out[..., :w] if pad else out
