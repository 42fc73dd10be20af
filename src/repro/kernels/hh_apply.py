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
wavefront batching (``S = B*G``) as the chase itself.  It serves the
stage-1 panel replay and the streamed chase-tape replay.

``replay_stage_pallas`` (DESIGN.md §8) replays a whole chase stage's
tape in one kernel: grid ``(B, side, column stripe)``, each stripe of
``U^T`` or ``V^T`` resident in VMEM while every rank-1 reflector of the
stage is applied to it on the VPU, the tape streamed in by DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["hh_block_apply_pallas", "tape_apply_pallas",
           "replay_stage_pallas", "replay_tape_blocks"]


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


# ---------------------------------------------------------------------------
# Resident chase-tape replay
# ---------------------------------------------------------------------------

_APPLY_LANES = 512   # accumulator lanes one apply holds in vregs at a time


def _replay_kernel(acc_hbm, tape_hbm, out_hbm, stripe, tbuf, sem, tsem, *,
                   n: int, b_in: int, tw: int, G: int, per_block: int,
                   n_chunks: int):
    """One column stripe of one side (0: V^T, right reflectors; 1: U^T,
    left) of one matrix, for a whole chase stage.  The stripe is copied
    into VMEM once; the tape streams through ``tbuf`` in chunks of
    ``REPLAY_CHUNK`` cycles, chunk c + 1's DMA in flight while chunk c is
    applied; the stripe is copied back once.  Every active slot's apply
    ``C <- C - (tau v) (v^T C)`` is f32 VPU arithmetic on the 8-aligned
    (block_rows, cols) block at p - p % 8, v sitting at row p % 8 of it."""
    from repro.core import tuning
    from repro.kernels.bulge_chase import _loop32
    b, side, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows, cols = stripe.shape
    blk_rows, lanes = tbuf.shape[-2:]
    chunk = tuning.REPLAY_CHUNK
    col = pl.multiple_of(s * cols, tuning.LANE)
    copy = pltpu.make_async_copy(acc_hbm.at[b, side, :, pl.ds(col, cols)],
                                 stripe, sem)
    copy.start()
    copy.wait()

    b_out = b_in - tw
    nsweeps = n - 1 - b_out
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (blk_rows, 1), 0)
    sub = min(cols, _APPLY_LANES)
    per_chunk = chunk // per_block

    def fetch(c, slot):
        return pltpu.make_async_copy(
            tape_hbm.at[b, side, pl.ds(c * per_chunk, per_chunk)],
            tbuf.at[slot], tsem.at[slot])

    def apply(blk, at_lane, p):
        x = jnp.sum(jnp.where(lane == at_lane, blk, 0), axis=1,
                    keepdims=True)                       # (blk_rows, 1)
        v = jnp.where(row < blk_rows - 1, x, 0)
        tv = x[blk_rows - 1:, :] * v
        at = pl.ds(pl.multiple_of(p - (p & 7), tuning.SUBLANE), blk_rows)
        for j in range(0, cols, sub):
            blk_c = stripe[at, j:j + sub]
            w = jnp.sum(v * blk_c, axis=0, keepdims=True)
            stripe[at, j:j + sub] = blk_c - tv * w

    def chunk_body(c):
        slot = c & 1
        fetch(c, slot).wait()

        @pl.when(c + 1 < n_chunks)
        def _():
            fetch(c + 1, 1 - slot).start()

        for k in range(chunk):
            # chase_cycle_indices for cycle t = 3*t3 + r, without the
            # scalar floor division Mosaic cannot lower under x64
            t3 = c * (chunk // 3) + k // 3
            r = k % 3
            base = (k % per_block) * G

            def slot_body(g, k=k, t3=t3, r=r, base=base):
                sweep = t3 - g
                p = sweep + b_out + (r + 3 * g) * b_in
                active = (sweep >= 0) & (sweep < nsweeps) & (p <= n - 1)

                @pl.when(active)
                def _():
                    apply(tbuf[slot, k // per_block], base + g, p)

            _loop32(G, slot_body)

    fetch(_I0, _I0).start()
    _loop32(n_chunks, chunk_body)
    copy = pltpu.make_async_copy(stripe, out_hbm.at[b, side, :,
                                                    pl.ds(col, cols)], sem)
    copy.start()
    copy.wait()


def replay_tape_blocks(tape_v: jax.Array, tape_tau: jax.Array, *, n: int,
                       b_in: int, tw: int) -> jax.Array:
    """The resident replay kernel's tape: (B, 2, T' / cycles_per_block,
    block_rows, lanes) as ``tuning.replay_layout`` gives them; side 0
    holds the right reflectors (into V^T), side 1 the left (into U^T);
    T' is T rounded up to ``REPLAY_CHUNK`` (the added cycles have no
    active slot).  Cycle t's slot g sits in block t // cycles_per_block,
    lane (t % cycles_per_block) * G + g: v shifted down to row p % 8, tau
    in the last row, zeros elsewhere."""
    from repro.core import tuning
    from repro.core.bulge_chasing import chase_cycle_indices
    bsz, T, G, _, k = tape_v.shape
    _, blk_rows, lanes, per_block = tuning.replay_layout(n, b_in, tw)
    t_idx = jnp.arange(T, dtype=jnp.int32)[:, None]
    g_idx = jnp.arange(G, dtype=jnp.int32)[None, :]
    _, _, p, active, _ = chase_cycle_indices(t_idx, g_idx, n, b_in, tw)
    off = jnp.where(active, p % tuning.SUBLANE, 0)[None, :, :, None, None]
    shifted = jnp.zeros(tape_v.shape[:-1] + (blk_rows,), tape_v.dtype)
    for o in range(tuning.SUBLANE):
        at = jnp.pad(tape_v, ((0, 0),) * 4 + ((o, blk_rows - k - o),))
        shifted = jnp.where(off == o, at, shifted)
    shifted = shifted.at[..., -1].set(tape_tau)         # (B, T, G, 2, rows)
    t_pad = -(-T // tuning.REPLAY_CHUNK) * tuning.REPLAY_CHUNK
    shifted = jnp.pad(shifted, ((0, 0), (0, t_pad - T)) + ((0, 0),) * 3)
    shifted = shifted.reshape(bsz, t_pad // per_block, per_block, G, 2,
                              blk_rows)
    blocks = shifted.transpose(0, 4, 1, 5, 2, 3).reshape(
        bsz, 2, t_pad // per_block, blk_rows, per_block * G)
    return jnp.pad(blocks, ((0, 0),) * 4 + ((0, lanes - per_block * G),))


@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw",
                                             "stripe_cols", "interpret"))
def replay_stage_pallas(ut: jax.Array, vt: jax.Array, tape_v: jax.Array,
                        tape_tau: jax.Array, *, n: int, b_in: int, tw: int,
                        stripe_cols: int | None = None,
                        interpret: bool = False):
    """Replay one chase stage's tape into U^T and V^T, (B, n, n) each, with
    each column stripe of each accumulator resident in VMEM for the whole
    stage (DESIGN.md §8).  Grid (B, side, stripe); the accumulators are
    padded to ``tuning.replay_layout`` rows (zeros, dropped at the end) and
    to whole stripes of ``stripe_cols`` columns (default
    ``tuning.replay_stripe_cols``).  ``tape_v (B, T, G, 2, tw+1)`` and
    ``tape_tau (B, T, G, 2)`` are the chase's tape, laid out for the kernel
    by :func:`replay_tape_blocks`."""
    from repro.core import tuning
    from repro.core.bulge_chasing import stage_schedule
    _, T, G = stage_schedule(n, b_in, tw)
    if T == 0:
        return ut, vt
    bsz = ut.shape[0]
    cols = stripe_cols or tuning.replay_stripe_cols(n, b_in, tw, ut.dtype)
    assert cols > 0 and cols % tuning.LANE == 0, cols
    rows, blk_rows, lanes, per_block = tuning.replay_layout(n, b_in, tw)
    width = -(-n // cols) * cols
    acc = jnp.pad(jnp.stack([vt, ut], axis=1),
                  ((0, 0), (0, 0), (0, rows - n), (0, width - n)))
    tape = replay_tape_blocks(tape_v.astype(ut.dtype),
                              tape_tau.astype(ut.dtype), n=n, b_in=b_in,
                              tw=tw)
    per_chunk = tuning.REPLAY_CHUNK // per_block
    kern = functools.partial(_replay_kernel, n=n, b_in=b_in, tw=tw, G=G,
                             per_block=per_block,
                             n_chunks=tape.shape[2] // per_chunk)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(bsz, 2, width // cols),
            in_specs=[any_, any_],
            out_specs=any_,
            scratch_shapes=[pltpu.VMEM((rows, cols), acc.dtype),
                            pltpu.VMEM((2, per_chunk, blk_rows, lanes),
                                       acc.dtype),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA((2,))]),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="replay_stage",
    )(acc, tape)
    return out[:, 1, :n, :n], out[:, 0, :n, :n]
