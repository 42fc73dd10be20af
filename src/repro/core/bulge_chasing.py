"""Band -> bidiagonal reduction via memory-aware bulge chasing (paper Alg. 1).

``reduce_stage_packed`` / ``bidiagonalize_packed`` are the production JAX
path: static-shape wavefront execution on packed band storage.  Per global
cycle ``t`` every in-flight sweep executes one chase cycle; the paper's
3-cycle separation guarantees the per-sweep windows are disjoint
(stride between concurrent pivots = ``3*b_in - 1`` > window width
``b_in + tw + 1``), so all windows are gathered, processed by one batched
kernel call (Pallas on TPU / interpret or pure-jnp on CPU), and scattered
back race-free.

(The sequential numpy oracles — ``reduce_stage_dense_ref``,
``bidiagonalize_dense_ref``, ``bidiagonalize_dense_ref_uv`` — live in
``core/reference.py`` so this hot module stays numpy-free; they are
re-exported here for back-compat.)

Scheduling (stage reduces bandwidth ``b_in -> b_out = b_in - tw``):

  sweep R (R = 0..n-2-b_out) starts at global cycle 3R;
  at local cycle j it owns pivot column  p = R + b_out + j*b_in;
  cycle j=0 annihilates row R's outermost ``tw`` band elements
  (columns p+1..p+tw, pivot p) — paper Alg. 1 line 7 start correction;
  cycle j>0 annihilates the row bulge of row r = p - b_in;
  each cycle then annihilates the column bulge of pivot column p.

The window of one cycle covers matrix rows [p - b_in - tw, p + tw] and columns
[p, p + b_in + tw] — "1 + BW + TW consecutive elements" (paper §III-A) — and is
*rolled* so matrix rows align with window rows (dense tile), turning the
band-storage diagonal access pattern into contiguous VPU-friendly tiles.

Batch-native execution (DESIGN.md §4): every entry point below accepts a
leading batch axis — packed storage ``(B, H, ncols)``, dense input
``(B, n, n)``.  The schedule is shape-only, so all B problems share one
wavefront clock: per global cycle the gather produces ``(B, G, H, W)``
windows, flattened to one fused kernel call over ``B*G`` slots (grid
``(B·G,)``), and scattered back race-free.  This is how small matrices —
whose own wavefront ``G = ceil(n / (3*b_in - 1)) + 1`` cannot fill the
machine (paper Eq. 1) — recover occupancy: independent problems fill the
idle wavefront slots.

Reflector tapes (DESIGN.md §8): every entry point accepts ``tape=True``,
under which the chase additionally records each cycle's Householder pair
``(v, tau)`` per (global cycle, wavefront slot) into static-shape arrays —
the *reflector tape*.  ``core/transforms.py`` replays tapes into the left
and right transform accumulators (``U`` / ``V^T``) with the same wavefront
batching, which is what turns the values-only pipeline into a full SVD.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import band as bandmod

# Back-compat re-exports of the numpy oracles (historical home; the
# implementations moved to core/reference.py).  Lazy (PEP 562) so that
# importing this hot module does not pull in numpy or the oracle code —
# the point of the move.
_REFERENCE_EXPORTS = ("_np_reflector", "reduce_stage_dense_ref",
                      "bidiagonalize_dense_ref", "bidiagonalize_dense_ref_uv")


def __getattr__(name):
    if name in _REFERENCE_EXPORTS:
        from repro.core import reference
        return getattr(reference, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "reduce_stage_dense_ref",
    "bidiagonalize_dense_ref",
    "bidiagonalize_dense_ref_uv",
    "reduce_stage_packed",
    "bidiagonalize_packed",
    "bidiagonalize",
    "chase_cycle_indices",
    "stage_path",
    "stage_schedule",
]


# ---------------------------------------------------------------------------
# Wavefront schedule helpers
# ---------------------------------------------------------------------------

def stage_schedule(n: int, b_in: int, tw: int) -> tuple[int, int, int]:
    """(n_sweeps, total_cycles, max_concurrent) for one stage.

    Sweep R starts at global cycle ``3R`` (``tuning.SWEEP_SEPARATION``, the
    paper's 3-cycle rule) and runs ``j_max(R) + 1`` local cycles; finish
    times ``3R + j_max(R) + 1`` are increasing in R (``j_max`` drops by at
    most 1 per sweep), so the last sweep finishes last.  ``max_concurrent``
    is ``tuning.max_concurrent_sweeps`` (single source of truth for the
    wavefront width), including for the degenerate 0-sweep case.
    """
    from repro.core import tuning
    conc = tuning.max_concurrent_sweeps(n, b_in)
    b_out = b_in - tw
    nsweeps = max(n - 1 - b_out, 0)
    if nsweeps == 0:
        return 0, 0, conc
    last = nsweeps - 1
    max_j_last = max((n - 1 - last - b_out) // b_in, 0)
    total = tuning.SWEEP_SEPARATION * last + max_j_last + 1
    return nsweeps, total, conc


def chase_cycle_indices(t, g, n: int, b_in: int, tw: int):
    """Vectorized slot -> (sweep, local cycle, pivot, active, is_first).

    Slot g at global cycle t hosts sweep R = t//3 - g at local cycle
    j = t%3 + 3g (``tuning.SWEEP_SEPARATION``), pivot
    ``p = R + b_out + j*b_in``; the slot is active iff R is a real sweep
    and ``p <= n - 1``.  Works on traced or static ints.
    """
    from repro.core import tuning
    sep = tuning.SWEEP_SEPARATION
    b_out = b_in - tw
    nsweeps = max(n - 1 - b_out, 0)
    R = t // sep - g
    j = t - sep * R
    p = R + b_out + j * b_in
    active = (R >= 0) & (R < nsweeps) & (p <= n - 1)
    return R, j, p, active, (j == 0)


def stage_path(dtype, *, n: int, b_in: int, tw: int, backend: str = "auto",
               config=None, tape: bool = False) -> str:
    """``"resident"`` or ``"streamed"``: which implementation
    :func:`reduce_stage_packed` runs for one stage, from what the call can
    observe (DESIGN.md §9).  Resident iff the resolved backend is
    "pallas" (interpret mode off the TPU), the data is 32-bit and one
    matrix's band — with the tape's staging slots when a tape is recorded
    — fits ``tuning.VMEM_BUDGET_BYTES`` as ``tuning.resident_band_bytes``
    counts it."""
    from repro.core import tuning
    from repro.kernels import ops
    if jnp.dtype(dtype).itemsize != 4:
        return "streamed"
    if ops.resolved_backend(backend, config, dtype) != "pallas":
        return "streamed"
    fits = (tuning.resident_band_bytes(n, b_in, tw, dtype, tape=tape)
            <= tuning.VMEM_BUDGET_BYTES)
    return "resident" if fits else "streamed"


# ---------------------------------------------------------------------------
# Packed wavefront stage (JAX)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw", "backend",
                                             "config", "tape"))
@obs.scope("stage2")
def reduce_stage_packed(band: jax.Array, *, n: int, b_in: int, tw: int,
                        backend: str = "auto", config=None,
                        tape: bool = False):
    """One SBR stage on packed band storage, batch-native.

    band: (..., b_in + 2*tw + 1, >= n) — any leading batch axes (flattened to
    one B internally).  Returns same-shape storage with bandwidth reduced to
    ``b_in - tw`` (bulge space zeroed).

    Two implementations, chosen by :func:`stage_path` from the input alone
    (DESIGN.md §9).  The *resident* path — Pallas backend, 32-bit data, a
    band that fits fast memory — is one ``ops.chase_stage`` kernel per
    stage that keeps each matrix's band in VMEM and runs the whole
    wavefront loop inside, recording the tape when asked.
    Everything else takes the *streamed* path
    (:func:`_reduce_stage_streamed`), and the two give the same band bit
    for bit.  On the streamed path all B problems advance on one
    wavefront clock: per global cycle the (B, G, H, W) window gather is
    flattened into ONE fused kernel call over B*G slots, so independent
    problems fill wavefront slots a single small matrix leaves idle.

    With ``tape=True`` the stage additionally records the reflector tape and
    returns ``(band, tape_v, tape_tau)`` with static shapes
    ``tape_v: (..., T, G, 2, tw+1)`` and ``tape_tau: (..., T, G, 2)`` on
    either path (T = cycle count) — index 0 of the pair
    axis is the right reflector (accumulates into V), index 1 the left one
    (into U); inactive slots carry ``tau = 0`` (identity on replay).  The
    in-band arithmetic is byte-for-byte the same either way, so (d, e) —
    and hence sigma — do not change with the tape.

    An explicit ``backend=`` wins over ``config``; backend/interpret
    resolution itself is delegated to the kernel registry (ops._resolve)
    at the kernel call.
    """
    if stage_path(band.dtype, n=n, b_in=b_in, tw=tw, backend=backend,
                  config=config, tape=tape) == "resident":
        from repro.kernels import ops
        band3 = band.reshape((-1,) + band.shape[-2:])
        out = ops.chase_stage(band3, n=n, b_in=b_in, tw=tw, backend=backend,
                              config=config, with_tape=tape)
        if not tape:
            return out.reshape(band.shape)
        lead = band.shape[:-2]
        out, tv, tt = out
        return (out.reshape(band.shape), tv.reshape(lead + tv.shape[1:]),
                tt.reshape(lead + tt.shape[1:]))
    return _reduce_stage_streamed(band, n=n, b_in=b_in, tw=tw,
                                  backend=backend, config=config, tape=tape)


@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw", "backend",
                                             "config", "tape"))
def _reduce_stage_streamed(band: jax.Array, *, n: int, b_in: int, tw: int,
                           backend: str = "auto", config=None,
                           tape: bool = False):
    """The streamed stage: per global cycle, gather every slot's rolled
    window from the band in HBM, chase them with one ``ops.chase_cycle``
    call, scatter them back.  Same arguments and results as
    :func:`reduce_stage_packed`, which calls it for every input the
    resident path does not take."""
    from repro.kernels import ops  # local import to avoid cycles

    b_out = b_in - tw
    assert b_out >= 1, (b_in, tw)
    H = b_in + 2 * tw + 1
    W = b_in + tw + 1
    assert band.ndim >= 2 and band.shape[-2] == H, (band.shape, H)
    lead = band.shape[:-2]
    band3 = band.reshape((-1,) + band.shape[-2:])
    B = band3.shape[0]
    nsweeps, T, G = stage_schedule(n, b_in, tw)
    if nsweeps == 0 or T == 0:
        if tape:
            empty_v = jnp.zeros(lead + (0, G, 2, tw + 1), band.dtype)
            empty_t = jnp.zeros(lead + (0, G, 2), band.dtype)
            return band, empty_v, empty_t
        return band

    ncols0 = band3.shape[-1]
    dump = n + W                      # start of per-slot dump zones (inactive slots)
    n_pad = dump + G * W
    bandp = bandmod.pad_columns(band3, max(n_pad - ncols0, 0))

    yy = jnp.arange(H)[:, None]                      # (H, 1)
    ww = jnp.arange(W)[None, :]                      # (1, W)
    d_gather = jnp.clip(H - 1 + ww - yy, 0, H - 1)   # (H, W) band row per window cell
    gather_valid = yy >= ww                          # window cell maps into storage
    dd = jnp.arange(H)[:, None]
    y_back = jnp.clip(H - 1 + ww - dd, 0, H - 1)     # (H, W) window row per band cell
    back_valid = dd >= ww
    g_idx = jnp.arange(G)
    rows = jnp.arange(H)[None, :, None]              # (1, H, 1) band row per cell

    def cycle(t, carry):
        bandp = carry[0] if tape else carry
        _, _, p, active, is_first = chase_cycle_indices(t, g_idx, n, b_in, tw)
        p_safe = jnp.where(active, p, dump + g_idx * W).astype(jnp.int32)
        cols = p_safe[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]   # (G, W)
        # gather rolled dense windows: (B, G, H, W)
        win = bandp[:, d_gather[None], cols[:, None, :]]
        win = jnp.where(gather_valid[None, None], win, 0)
        with jax.named_scope("chase_cycle"):
            res = ops.chase_cycle(win.reshape(B * G, H, W),
                                  jnp.tile(is_first, B), b_in=b_in, tw=tw,
                                  backend=backend, config=config,
                                  with_tape=tape)
        out = res[0] if tape else res
        out = out.reshape(B, G, H, W)
        out = jnp.where(active[None, :, None, None], out, win)
        # shear back to band coords and scatter (windows disjoint per matrix)
        orig = bandp[:, rows, cols[:, None, :]]                  # (B, G, H, W)
        vals = out[:, g_idx[:, None, None], y_back[None], ww[None]]
        vals = jnp.where(back_valid[None, None], vals, orig)
        bandp = bandp.at[:, rows, cols[:, None, :]].set(vals)
        if not tape:
            return bandp
        tape_v, tape_tau = carry[1], carry[2]
        vs = res[1].reshape(B, G, 2, tw + 1)
        ts = res[2].reshape(B, G, 2)
        ts = jnp.where(active[None, :, None], ts, 0)             # identity replay
        return (bandp, tape_v.at[:, t].set(vs), tape_tau.at[:, t].set(ts))

    if tape:
        tape_v0 = jnp.zeros((B, T, G, 2, tw + 1), band.dtype)
        tape_tau0 = jnp.zeros((B, T, G, 2), band.dtype)
        bandp, tape_v, tape_tau = jax.lax.fori_loop(
            0, T, cycle, (bandp, tape_v0, tape_tau0))
        out = bandp[..., :ncols0]
        return (out.reshape(lead + out.shape[-2:]),
                tape_v.reshape(lead + tape_v.shape[1:]),
                tape_tau.reshape(lead + tape_tau.shape[1:]))
    bandp = jax.lax.fori_loop(0, T, cycle, bandp)
    out = bandp[..., :ncols0]
    return out.reshape(lead + out.shape[-2:])


def tw_schedule(bw: int, tw: int) -> list[tuple[int, int]]:
    """[(b_in, tw_i), ...] stage plan reducing bw -> 1 by <= tw per stage.

    (Canonical implementation: ``tuning.stage_plan`` — the PipelineConfig's
    tile-width schedule; kept here as the historical alias.)
    """
    from repro.core import tuning
    return list(tuning.stage_plan(bw, tw))


def bidiagonalize_packed(band: jax.Array, *, n: int, bw: int, tw: int,
                         backend: str = "auto", config=None,
                         tape: bool = False):
    """Full SBR bw -> 1 on packed storage. Returns (diag, superdiag).

    ``band`` must be packed with tw_0 = min(tw, bw-1) sub rows, i.e. via
    ``band.pack(a, bw, min(tw, bw-1))``; a leading batch axis (B, H, ncols)
    is threaded through every stage.  Host loop over stages (static,
    <= ceil((bw-1)/tw) iterations); each stage jits once per shape.

    With ``tape=True`` returns ``(diag, superdiag, tapes)`` where ``tapes``
    is a static-length list of :class:`repro.core.transforms.ChaseTape`,
    one per stage of the tile-width plan, in execution order.

    Each stage runs on the path :func:`stage_path` picks; its ``stage2``
    span carries ``path=``, and ``obs.count_chase_stage`` counts it here —
    per call when this runs eagerly, per trace inside a jitted pipeline;
    ``obs.count_tape_bytes`` counts each recorded tape the same way.

    Storage layout invariant entering each stage (b_in, tw_i):
      tw_i sub rows | diag row | b_in + tw_i sup rows  ==  b_in + 2*tw_i + 1.
    Between stages the storage is re-sliced (outer diagonals are now zero).
    """
    if tape:
        from repro.core import transforms  # deferred: transforms imports us
    plan = tw_schedule(bw, tw)
    if not plan:
        h = band.shape[-2]
        tw0 = (h - 2) // 2 if h > 2 else 0
        with obs.span("extract"):
            d = bandmod.band_extract_diag(band, tw0, 0, n)
            e = (bandmod.band_extract_diag(band, tw0, 1, n) if bw >= 1
                 else jnp.zeros(band.shape[:-2] + (n,), band.dtype))
        return (d, e, []) if tape else (d, e)
    cur = band
    tw_cur = plan[0][1]
    assert cur.shape[-2] == plan[0][0] + 2 * tw_cur + 1, (cur.shape, plan[0])
    tapes = []
    for b_in, twi in plan:
        path = stage_path(cur.dtype, n=n, b_in=b_in, tw=twi, backend=backend,
                          config=config, tape=tape)
        obs.count_chase_stage(path)
        # One span per stage of the tile-width plan (DESIGN.md §16); inside
        # `_three_stage` this loop is traced and the spans are no-ops.
        with obs.span("stage2", n=n, b_in=b_in, tw=twi, tape=tape,
                      path=path):
            # re-slice so exactly twi sub rows remain above the diagonal row
            h_i = b_in + 2 * twi + 1
            start = tw_cur - twi
            if start != 0 or cur.shape[-2] != h_i:
                cur = jax.lax.slice_in_dim(cur, start, start + h_i, axis=-2)
            if tape:
                cur, tv, tt = reduce_stage_packed(
                    cur, n=n, b_in=b_in, tw=twi, backend=backend,
                    config=config, tape=True)
                tapes.append(transforms.ChaseTape(n=n, b_in=b_in, tw=twi,
                                                  v=tv, tau=tt))
                obs.count_tape_bytes("stage2", tapes[-1].nbytes)
            else:
                cur = reduce_stage_packed(cur, n=n, b_in=b_in, tw=twi,
                                          backend=backend, config=config)
        tw_cur = twi
    with obs.span("extract"):
        d = bandmod.band_extract_diag(cur, tw_cur, 0, n)
        e = bandmod.band_extract_diag(cur, tw_cur, 1, n)
    return (d, e, tapes) if tape else (d, e)


def bidiagonalize(a: jax.Array, *, bw: int, tw: int, backend: str = "auto",
                  config=None, tape: bool = False):
    """Dense upper-banded (..., n, n) -> (..., n) diag + superdiag pair via
    packed wavefront SBR; a leading batch axis runs batch-native (one fused
    wavefront over all matrices), not as a vmapped loop.  ``tape=True``
    additionally returns the per-stage reflector tapes."""
    n = a.shape[-1]
    tw0 = min(tw, max(bw - 1, 1))
    with obs.span("pack", bw=bw, tw=tw0):
        packed = bandmod.pack(a, bw, tw0)
    return bidiagonalize_packed(packed, n=n, bw=bw, tw=tw, backend=backend,
                                config=config, tape=tape)
