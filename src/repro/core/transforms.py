"""Reflector-tape replay: turn recorded Householder tapes into U / V^T.

The values-only pipeline discards its orthogonal transforms; with
``tape=True`` each stage records them instead (DESIGN.md §8):

* stage 1 (``core/stage1.py``) — per-panel compact-WY blocks
  ``(V_qr, T_qr, V_lq, T_lq)``;
* stage 2 (``core/bulge_chasing.py``) — per (global cycle, wavefront slot)
  Householder pairs ``(v, tau)`` with static shapes ``(T, G, 2, tw+1)``.

This module replays those tapes into accumulators, producing ``U`` and
``V^T`` with ``A = U B V^T`` (B the bidiagonal the chase produced).  Both
accumulators are kept TRANSPOSED (``U^T`` and ``V^T``) so every recorded
reflector — left or right — is replayed as the same primitive: a compact-WY
*left* apply ``X <- (I - V T V^T) X``, dispatched through the kernel
registry (``kernels/ops.py``: ``tape_apply``, with ``ref`` and ``pallas``
impls, and the Pallas-only ``replay_stage``, both in
``kernels/hh_apply.py``).

A chase stage's tape is replayed on one of two paths, chosen by
:func:`replay_path` from what the call can observe, as ``bc.stage_path``
chooses stage 2's.  The *resident* path (Pallas, 32-bit accumulators, a
128-lane column stripe fits fast memory) is one ``ops.replay_stage``
kernel per stage: each column stripe of ``U^T`` / ``V^T`` stays in VMEM
while every reflector of the stage is applied to it in cycle order.  The
*streamed* path (the ``ref`` backend, float64) keeps the wavefront
batching of the chase itself: per global cycle, the G per-slot row slices
of all B problems are gathered into one fused ``tape_apply`` call over
``B*G`` slots and scattered back — the 3-cycle separation that makes chase
windows disjoint also makes the replayed row ranges ``[p, p+tw]``
disjoint, so the scatter is race-free.  Memory cost of a stage tape is
``O(n·tw)`` per cycle (two ``(tw+1)``-reflectors per slot,
``G ~ n / (3 b_in)`` slots).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import bulge_chasing as bc

__all__ = ["ChaseTape", "accumulate_transforms", "replay_stage1",
           "replay_chase", "replay_path"]


@dataclasses.dataclass(frozen=True)
class ChaseTape:
    """Reflector tape of one chase stage (static schedule metadata + arrays).

    ``v``: (..., T, G, 2, tw+1) reflectors, pair axis = (right -> V,
    left -> U); ``tau``: (..., T, G, 2) with tau = 0 on inactive slots.
    Both stage-2 paths record this one layout.
    """
    n: int
    b_in: int
    tw: int
    v: jax.Array
    tau: jax.Array

    @property
    def nbytes(self) -> int:
        """Bytes of the tape, from its static shapes (batch included)."""
        return sum(x.size * x.dtype.itemsize for x in (self.v, self.tau))


def _acc_dtype(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt


@functools.partial(jax.jit, static_argnames=("config",))
@obs.scope("replay")
def replay_stage1(ut: jax.Array, vt: jax.Array, tape, *, config=None):
    """Replay the stage-1 panel tape into the transposed accumulators.

    ut/vt: (B, n, n) holding U^T / V^T so far.  Panel k recorded
    ``Q_k = I - Vq Tq Vq^T`` (left, QR) and ``R_k = I - Vl Tl Vl^T``
    (right, LQ) with ``A_banded = Q_P^T ... Q_0^T A R_0 ... R_P``; replay
    therefore left-applies ``Q_k^T = I - Vq Tq^T Vq^T`` to U^T (and the
    R_k analogue to V^T) in panel order.
    """
    from repro.kernels import ops

    vq, tq, vl, tl = tape
    n_panels = vq.shape[-3]

    def body(k, carry):
        ut, vt = carry
        ut = ops.tape_apply(vq[:, k], jnp.swapaxes(tq[:, k], -1, -2), ut,
                            config=config)
        vt = ops.tape_apply(vl[:, k], jnp.swapaxes(tl[:, k], -1, -2), vt,
                            config=config)
        return ut, vt

    return jax.lax.fori_loop(0, n_panels, body, (ut, vt))


def replay_path(dtype, *, n: int, b_in: int, tw: int, backend: str = "auto",
                config=None) -> str:
    """``"resident"`` or ``"streamed"``: which implementation
    :func:`replay_chase` runs for one chase stage's tape of ``dtype`` data
    (DESIGN.md §8).  Resident iff the resolved backend is "pallas"
    (interpret mode off the TPU), the accumulators are 32-bit (bf16 data
    accumulates in f32) and a 128-lane column stripe fits
    ``tuning.VMEM_BUDGET_BYTES`` (``tuning.replay_stripe_cols``)."""
    from repro.core import tuning
    from repro.kernels import ops
    acc = _acc_dtype(jnp.dtype(dtype))
    if jnp.dtype(acc).itemsize != 4:
        return "streamed"
    if ops.resolved_backend(backend, config, acc) != "pallas":
        return "streamed"
    fits = tuning.replay_stripe_cols(n, b_in, tw, acc) > 0
    return "resident" if fits else "streamed"


@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw", "config"))
@obs.scope("replay")
def replay_chase(ut: jax.Array, vt: jax.Array, tape_v: jax.Array,
                 tape_tau: jax.Array, *, n: int, b_in: int, tw: int,
                 config=None):
    """Replay one chase stage's tape into the transposed accumulators.

    ut/vt: (B, n, n).  On the path :func:`replay_path` picks: the resident
    ``ops.replay_stage`` kernel, or :func:`_replay_chase_streamed`; both
    recover each slot's pivot from the chase schedule — the tape stores
    only (v, tau), the row ranges are shape-derived, exactly like the
    chase's own window gather.
    """
    if replay_path(ut.dtype, n=n, b_in=b_in, tw=tw,
                   config=config) == "resident":
        from repro.kernels import ops
        return ops.replay_stage(ut, vt, tape_v, tape_tau, n=n, b_in=b_in,
                                tw=tw, config=config)
    return _replay_chase_streamed(ut, vt, tape_v, tape_tau, n=n, b_in=b_in,
                                  tw=tw, config=config)


def _replay_chase_streamed(ut, vt, tape_v, tape_tau, *, n: int, b_in: int,
                           tw: int, config=None):
    """The streamed replay: per global cycle, gather every slot's rows of
    U^T and V^T, apply with one ``ops.tape_apply`` call per side, scatter
    back.  ``chase_cycle_indices`` gives each slot's pivot; inactive slots
    were recorded with tau = 0 and are routed to disjoint dump rows
    (identity applies on scratch space)."""
    from repro.kernels import ops

    nsweeps, T, G = bc.stage_schedule(n, b_in, tw)
    if nsweeps == 0 or T == 0:
        return ut, vt
    B = ut.shape[0]
    W = b_in + tw + 1
    k = tw + 1
    dump = n + W
    n_pad = dump + G * W
    pad = ((0, 0), (0, n_pad - n), (0, 0))
    utp = jnp.pad(ut, pad)
    vtp = jnp.pad(vt, pad)
    g_idx = jnp.arange(G)
    off = jnp.arange(k, dtype=jnp.int32)

    def cycle(t, carry):
        utp, vtp = carry
        _, _, p, active, _ = bc.chase_cycle_indices(t, g_idx, n, b_in, tw)
        p_safe = jnp.where(active, p, dump + g_idx * W).astype(jnp.int32)
        rows = p_safe[:, None] + off[None, :]                     # (G, k)
        vs = tape_v[:, t]                                         # (B, G, 2, k)
        ts = tape_tau[:, t]                                       # (B, G, 2)

        def apply(side, acc):
            v = vs[:, :, side].reshape(B * G, k, 1)
            tau = ts[:, :, side].reshape(B * G, 1, 1)
            sl = acc[:, rows].reshape(B * G, k, n)
            out = ops.tape_apply(v, tau, sl, config=config)
            return acc.at[:, rows].set(out.reshape(B, G, k, n))

        return apply(1, utp), apply(0, vtp)                       # left->U, right->V

    utp, vtp = jax.lax.fori_loop(0, T, cycle, (utp, vtp))
    return utp[:, :n], vtp[:, :n]


def accumulate_transforms(n: int, *, s1_tape=None, chase_tapes=(),
                          lead: tuple = (), dtype=jnp.float64, config=None):
    """Replay all tapes from identity: returns (u, vt) with A = U B V^T.

    ``lead`` is the batch shape; accumulators run in the fp32-or-better
    accumulation dtype of ``dtype`` and are cast back at the end.  Each
    chase stage's replay runs on the path :func:`replay_path` picks; its
    ``replay_chase`` span carries ``path=`` and ``obs.count_replay_stage``
    counts it.
    """
    acc = _acc_dtype(jnp.dtype(dtype))
    b = 1
    for s in lead:
        b *= s
    eye = jnp.broadcast_to(jnp.eye(n, dtype=acc), (b, n, n))
    ut, vt = eye, eye
    if s1_tape is not None:
        flat = tuple(x.reshape((b,) + x.shape[len(lead):]).astype(acc)
                     for x in s1_tape)
        with obs.span("replay_stage1", n=int(n), batch=b):
            ut, vt = replay_stage1(ut, vt, flat, config=config)
    for tape in chase_tapes:
        tv = tape.v.reshape((b,) + tape.v.shape[len(lead):]).astype(acc)
        tt = tape.tau.reshape((b,) + tape.tau.shape[len(lead):]).astype(acc)
        path = replay_path(acc, n=tape.n, b_in=tape.b_in, tw=tape.tw,
                           config=config)
        obs.count_replay_stage(path)
        with obs.span("replay_chase", n=tape.n, b_in=tape.b_in, tw=tape.tw,
                      tape_bytes=tape.nbytes, path=path):
            ut, vt = replay_chase(ut, vt, tv, tt, n=tape.n, b_in=tape.b_in,
                                  tw=tape.tw, config=config)
    u = jnp.swapaxes(ut, -1, -2)
    out_dt = jnp.dtype(dtype)
    return (u.reshape(lead + (n, n)).astype(out_dt),
            vt.reshape(lead + (n, n)).astype(out_dt))
