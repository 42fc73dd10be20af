"""Pallas TPU kernel for one wavefront of bulge-chase cycles (paper Alg. 2).

Memory mapping (GPU -> TPU, DESIGN.md §2):

* one thread block per sweep        -> one grid step per in-flight sweep
* reflector in shared memory (L1)   -> reflector in VMEM-resident window block
* TPB rows held in registers        -> row tiles materialized into VREGs from
                                       the VMEM window by the vector unit
* kernel-launch sync between cycles -> one ``pallas_call`` per stage
                                       (``chase_stage_pallas``), with or
                                       without a tape; the streamed
                                       fallback, one per wavefront cycle
                                       (``chase_cycle_pallas``)

Each grid step owns one *rolled dense window* (H, W) of the packed band
storage, H = b_in + 2*tw + 1, W = b_in + tw + 1 — the "1 + BW + TW" working
set of the paper, staged HBM -> VMEM by the BlockSpec pipeline (double-
buffered by Pallas, the TPU analogue of the paper's L1 residency), processed
entirely in VMEM, and written back.

Band-resident stage (DESIGN.md §9): a grid step owns one matrix's whole
band for a whole stage, copied into VMEM once, and runs the streamed
wavefront loop inside the kernel, every window through the same
``_chase_window_vmem``; HBM sees the band once in and once out.  A
reflector tape leaves by one DMA per cycle from a double-buffered VMEM
staging slot (DESIGN.md §8).

The window kernels are batch-oblivious: a window neither knows nor cares
which matrix it came from, so the batch-native pipeline (DESIGN.md §4)
simply flattens a (B, G, H, W) wavefront into grid (B·G,) — independent
problems widen the wavefront that a single small matrix cannot fill (paper
Eq. 1).

The kernel is data-precision-agnostic (fp32/bf16; accumulation in fp32),
mirroring the paper's precision-agnostic single-source claim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.householder import reflector_parts

__all__ = ["chase_cycle_pallas", "chase_stage_pallas"]


def _reflector_in_kernel(x, pos, axis, acc):
    """larfg on a VREG-resident 2-D vector; tau=0 on zero tails (edge no-op).

    ``x`` is a (1, L) row (``axis=1``) or an (L, 1) column (``axis=0``);
    ``pos`` is the int32 iota along that axis.  Scalars come back as (1, 1)
    arrays: Mosaic keeps every value at least 2-D.
    """
    xa = x.astype(acc)
    alpha = jnp.sum(jnp.where(pos == 0, xa, 0), axis=axis, keepdims=True)
    tau, v_tail, beta = reflector_parts(alpha, jnp.where(pos > 0, xa, 0),
                                        axis=axis)
    return jnp.where(pos > 0, v_tail, 1.0), tau, beta


def _chase_window_vmem(wr, first, *, b_in: int, tw: int):
    """One chase cycle, in place, on a VMEM ref holding a rolled dense
    window (H, W).

    Every read and write is a static-offset ref slice (no value-level
    scatter), so Mosaic lowers it to masked vector loads/stores.  Returns
    ``(v, tau, v2, tau2)``: the right reflector as a (1, tw+1) row, the left
    one transposed to a row too, and both taus as (1, 1) — shared by the
    per-cycle kernel and the band-resident stage kernel, so the two paths
    differ in data movement only, never in an arithmetic operation.
    """
    h = b_in + 2 * tw + 1
    w = b_in + tw + 1
    k = tw + 1
    dt = wr.dtype
    acc = jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)

    # ---- right reflector: annihilate the TW-element row bulge ------------
    # overhang row: y = tw (steady) or y = 2*tw (sweep's first cycle); rows in
    # between are structurally zero in cols [0, tw], so the apply is a no-op
    # on them — select statically instead of dynamic-slicing.
    x = jnp.where(first, wr[2 * tw:2 * tw + 1, :k], wr[tw:tw + 1, :k])
    v, tau, beta = _reflector_in_kernel(x, lane, 1, acc)
    blk = wr[tw:, :k].astype(acc)                      # rows [tw, H)
    wdot = jnp.sum(blk * v, axis=1, keepdims=True)     # (H - tw, 1)
    wr[tw:, :k] = (blk - tau * wdot * v).astype(dt)
    # structural zeros on the annihilated row
    fix = jnp.where(lane == 0, beta, 0.0).astype(dt)
    hit = tau != 0
    wr[tw:tw + 1, :k] = jnp.where(hit & jnp.logical_not(first), fix,
                                  wr[tw:tw + 1, :k])
    wr[2 * tw:2 * tw + 1, :k] = jnp.where(hit & first, fix,
                                          wr[2 * tw:2 * tw + 1, :k])

    # ---- left reflector: annihilate the TW-element column bulge ----------
    y0 = h - 1 - tw                                    # matrix row p (pivot)
    v2, tau2, beta2 = _reflector_in_kernel(wr[y0:, 0:1], sub, 0, acc)
    blk2 = wr[y0:, :].astype(acc)                      # (tw+1, W)
    w2 = jnp.sum(v2 * blk2, axis=0, keepdims=True)     # (1, W)
    blk2 = blk2 - tau2 * v2 * w2
    col0 = jax.lax.broadcasted_iota(jnp.int32, (k, w), 1) == 0
    colfix = jnp.where(sub == 0, beta2, 0.0)
    blk2 = jnp.where(col0 & (tau2 != 0), colfix, blk2)
    wr[y0:, :] = blk2.astype(dt)
    # column -> row without a relayout: mask the diagonal, reduce sublanes
    v2_row = jnp.sum(jnp.where(sub == lane, v2, 0.0), axis=0, keepdims=True)
    return v, tau, v2_row, tau2


def _record_pair(vs_ref, taus_ref, row: int, v, tau, v2, tau2):
    """Reflector tape (DESIGN.md §8): write the pair a cycle applied at tape
    rows ``row`` (right reflector: spans matrix columns [p, p+tw], replayed
    into V) and ``row + 1`` (left: rows [p, p+tw], into U) — the same
    VMEM-resident values the applies used."""
    dt = vs_ref.dtype
    vs_ref[row:row + 1, :] = v.astype(dt)
    vs_ref[row + 1:row + 2, :] = v2.astype(dt)
    taus_ref[row:row + 1, :] = tau.astype(dt)
    taus_ref[row + 1:row + 2, :] = tau2.astype(dt)


def _chase_kernel(first_ref, win_ref, out_ref, *refs, b_in: int, tw: int):
    # refs: optionally (vs_ref, taus_ref) when the reflector tape is recorded.
    out_ref[...] = win_ref[...]                        # (H, W) in VMEM
    first = first_ref[pl.program_id(0)] != 0           # SMEM scalar
    v, tau, v2, tau2 = _chase_window_vmem(out_ref, first, b_in=b_in, tw=tw)
    if refs:
        _record_pair(*refs, 0, v, tau, v2, tau2)


_I0 = np.int32(0)   # int32 block index literal, whatever jax_enable_x64 says


@functools.partial(jax.jit, static_argnames=("b_in", "tw", "interpret",
                                             "with_tape"))
def chase_cycle_pallas(windows: jax.Array, is_first: jax.Array, *, b_in: int,
                       tw: int, interpret: bool = False,
                       with_tape: bool = False):
    """windows: (G, H, W) disjoint rolled windows; is_first: (G,) bool.

    ``is_first`` is scalar-prefetched into SMEM; each grid step reads its own
    flag by ``pl.program_id``.  ``with_tape=True`` additionally returns the
    wavefront's reflector tape slice ``(vs (G, 2, tw+1), taus (G, 2))`` —
    the window update itself is computed by the identical instruction
    sequence either way."""
    g, h, w = windows.shape
    assert h == b_in + 2 * tw + 1 and w == b_in + tw + 1, (windows.shape, b_in, tw)
    first = is_first.astype(jnp.int32).reshape(g)
    kern = functools.partial(_chase_kernel, b_in=b_in, tw=tw)
    slot = lambda i, _f: (i, _I0, _I0)
    out_shape = [jax.ShapeDtypeStruct(windows.shape, windows.dtype)]
    out_specs = [pl.BlockSpec((None, h, w), slot)]
    if with_tape:
        out_shape += [jax.ShapeDtypeStruct((g, 2, tw + 1), windows.dtype),
                      jax.ShapeDtypeStruct((g, 2, 1), windows.dtype)]
        out_specs += [pl.BlockSpec((None, 2, tw + 1), slot),
                      pl.BlockSpec((None, 2, 1), slot)]
    res = pl.pallas_call(
        kern,
        out_shape=tuple(out_shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g,),
            in_specs=[pl.BlockSpec((None, h, w), slot)],  # window in VMEM
            out_specs=tuple(out_specs)),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="chase_cycle",
    )(first, windows)
    if with_tape:
        out, vs, taus = res
        return out, vs, taus[..., 0]
    return res[0]


# ---------------------------------------------------------------------------
# Band-resident stage
# ---------------------------------------------------------------------------

def _roll(x, shift: int, axis: int):
    """``pltpu.roll`` with an int32 shift whatever jax_enable_x64 says."""
    return pltpu.roll(x, np.int32(shift), axis)


def _loop32(count: int, body) -> None:
    """``for i in range(count): body(i)`` with an int32 counter whatever
    jax_enable_x64 says (``fori_loop`` with static bounds counts in the
    default int width, which Mosaic refuses at 64 bits)."""
    def step(i):
        body(i)
        return i + 1
    jax.lax.while_loop(lambda i: i < count, step, _I0)


def _chase_stage_kernel(band_hbm, out_hbm, *refs, n: int, b_in: int,
                        tw: int, T: int, G: int, with_tape: bool):
    """One matrix's whole stage.  ``rev_ref[c, r] = band[H-1-r, c]``: a
    window's W band columns are W consecutive rows at the dynamic sublane
    offset p, and its dense cells ``win[r + w, w] = rev[p + w, r]`` sit at
    a static lane offset w per column, so shear and un-shear are W static
    row rolls around one transpose each way of the (lanes, lanes)
    workspace ``ws_ref``.

    ``with_tape``: refs start with the tape output ``tape_hbm (B, T, 2G,
    L)`` and end with two staging slots ``stage (2, 2G, L)`` and their DMA
    semaphores, L = tw + 2 rounded up to the lane width.  Cycle t's pairs
    go to slot t % 2 as ``_record_pair`` writes them, row 2g + i holding
    reflector i's v in lanes [0, tw] and its tau in lane tw + 1; one DMA
    copies the slot to row t of the tape while cycle t + 1 chases into the
    other slot.  A slot is DMA'd whole: Mosaic copies only lane-aligned
    slices."""
    if with_tape:
        tape_hbm, rev_ref, ws_ref, win_ref, sem, stage, tsem = refs
        k = tw + 1
    else:
        rev_ref, ws_ref, win_ref, sem = refs
    h, w = b_in + 2 * tw + 1, b_in + tw + 1
    b_out = b_in - tw
    b = pl.program_id(0)
    copy = pltpu.make_async_copy(band_hbm.at[b], rev_ref, sem)
    copy.start()
    copy.wait()

    lanes = rev_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def tape_copy(s, t):
        return pltpu.make_async_copy(stage.at[s], tape_hbm.at[b, t],
                                     tsem.at[s])

    def slot(t3, r, g, s=None):
        # chase_cycle_indices for cycle t = 3*t3 + r, without the
        # scalar floor division Mosaic cannot lower under jax_enable_x64
        sweep, j = t3 - g, r + 3 * g
        p = sweep + b_out + j * b_in
        active = (sweep >= 0) & (sweep < n - 1 - b_out) & (p <= n - 1)
        first = j == 0

        @pl.when(active)
        def _():
            # shear: window column c holds the H - c cells of band column
            # p + c that map into storage; every other cell reads as 0
            ws_ref[...] = jnp.zeros(ws_ref.shape, ws_ref.dtype)
            for c in range(w):
                row = _roll(rev_ref[pl.ds(p + c, 1), :], c, 1)
                ws_ref[c:c + 1, :] = jnp.where((lane >= c) & (lane < h), row, 0)
            ws_ref[...] = ws_ref[...].T
            win_ref[...] = ws_ref[0:h, 0:w]
            pair = _chase_window_vmem(win_ref, first, b_in=b_in, tw=tw)
            ws_ref[0:h, 0:w] = win_ref[...]
            ws_ref[...] = ws_ref[...].T
            # un-shear, writing back only the cells that map into storage
            for c in range(w):
                old = rev_ref[pl.ds(p + c, 1), :]
                new = _roll(ws_ref[c:c + 1, :], (lanes - c) % lanes, 1)
                rev_ref[pl.ds(p + c, 1), :] = jnp.where(lane < h - c, new, old)
            if with_tape:
                # rows 2g (right reflector) and 2g + 1 (left), as
                # _record_pair writes them; a ref view narrower than the
                # lane tile is refused, so store into the slot directly
                for i in range(2):
                    row = pl.ds(2 * g + i, 1)
                    stage[s, row, 0:k] = pair[2 * i].astype(stage.dtype)
                    stage[s, row, k:k + 1] = pair[2 * i + 1].astype(
                        stage.dtype)

    def cycle(t3, r):
        if not with_tape:
            _loop32(G, lambda g: slot(t3, r, g))
            return
        t = 3 * t3 + r
        s = t & 1

        @pl.when(t < T)
        def _():
            # slot s last held cycle t - 2: its copy must land before the
            # slot is cleared; inactive slots keep v = tau = 0
            @pl.when(t >= 2)
            def _():
                tape_copy(s, t - 2).wait()
            stage[s] = jnp.zeros(stage.shape[1:], stage.dtype)

        _loop32(G, lambda g: slot(t3, r, g, s))

        @pl.when(t < T)
        def _():
            tape_copy(s, t).start()

    # all slots of cycle t before cycle t + 1, as the streamed wavefront;
    # cycles past T have no active slot (and no tape row)
    _loop32(-(-T // 3), lambda t3: _loop32(3, lambda r: cycle(t3, r)))
    if with_tape:
        for t in range(max(T - 2, 0), T):     # the two copies still in flight
            tape_copy(np.int32(t % 2), np.int32(t)).wait()
    copy = pltpu.make_async_copy(rev_ref, out_hbm.at[b], sem)
    copy.start()
    copy.wait()


@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw", "interpret",
                                             "with_tape"))
def chase_stage_pallas(band: jax.Array, *, n: int, b_in: int, tw: int,
                       interpret: bool = False, with_tape: bool = False):
    """band: (B, H, ncols) packed storage, ncols >= n.  Runs one whole
    stage (bandwidth b_in -> b_in - tw) with each matrix's band resident in
    VMEM, in the streamed wavefront order, so the output is the same
    band bit for bit.

    Grid (B,): step b copies matrix b's band in once, chases every cycle of
    the stage on it, and copies it back; the output aliases the input.  The
    transpose into the resident layout (``tuning.resident_band_layout``)
    and back is one XLA transpose each way here.

    ``with_tape=True`` also returns the stage's reflector tape as the
    streamed stage records it, ``(vs (B, T, G, 2, tw+1), taus (B, T,
    G, 2))``, with v = tau = 0 on inactive slots.  The kernel writes it to
    HBM by one DMA per cycle from a double-buffered VMEM staging slot
    (DESIGN.md §8): at O(n^2) words the tape cannot stay in VMEM."""
    from repro.core import tuning
    from repro.core.bulge_chasing import stage_schedule
    bsz, h, ncols0 = band.shape
    assert b_in - tw >= 1, (b_in, tw)
    assert h == b_in + 2 * tw + 1 and ncols0 >= n, (band.shape, b_in, tw)
    _, T, G = stage_schedule(n, b_in, tw)
    dt = band.dtype
    if with_tape and T == 0:
        return (band, jnp.zeros((bsz, 0, G, 2, tw + 1), dt),
                jnp.zeros((bsz, 0, G, 2), dt))
    rows, lanes = tuning.resident_band_layout(n, b_in, tw)
    cols = min(ncols0, rows)          # windows never touch columns >= rows
    revt = jnp.swapaxes(band[:, ::-1, :cols], 1, 2)
    revt = jnp.pad(revt, ((0, 0), (0, rows - cols), (0, lanes - h)))
    kern = functools.partial(_chase_stage_kernel, n=n, b_in=b_in, tw=tw,
                             T=T, G=G, with_tape=with_tape)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = [jax.ShapeDtypeStruct(revt.shape, dt)]
    scratch = [pltpu.VMEM((rows, lanes), dt),
               pltpu.VMEM((lanes, lanes), dt),   # W < H lanes
               pltpu.VMEM((h, b_in + tw + 1), dt),
               pltpu.SemaphoreType.DMA]
    if with_tape:
        pairs = (2 * G, tuning.tape_stage_lanes(tw))
        out_shape.append(jax.ShapeDtypeStruct((bsz, T) + pairs, dt))
        scratch += [pltpu.VMEM((2,) + pairs, dt),
                    pltpu.SemaphoreType.DMA((2,))]
    res = pl.pallas_call(
        kern,
        out_shape=tuple(out_shape) if with_tape else out_shape[0],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(bsz,),
            in_specs=[any_],
            out_specs=(any_,) * 2 if with_tape else any_,
            scratch_shapes=scratch),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="chase_stage",
    )(revt)
    revt = res[0] if with_tape else res
    out = jnp.swapaxes(revt[:, :cols, :h], 1, 2)[:, ::-1]
    if cols < ncols0:
        out = jnp.concatenate([out, band[:, :, cols:]], axis=-1)
    if with_tape:
        tape = res[1].reshape(bsz, T, G, 2, -1)
        return out, tape[..., :tw + 1], tape[..., tw + 1]
    return out
