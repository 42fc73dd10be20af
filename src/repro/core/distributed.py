"""Distributed spectral computation (the framework-facing face of the paper).

Production use: during training we need singular values for *many* weight
matrices at once (spectral monitoring, low-rank gradient compression).  The
natural mapping at pod scale is **batch dispatch**: each device owns a slice of
the matrix batch and runs the full three-stage pipeline locally — zero
collectives during the chase (the paper's single-GPU residency argument,
lifted to one-matrix-per-core), one gather at the end.

``sharded_singular_values`` shard_maps over the mesh's data axes;
``spectrum_of_params`` walks a parameter pytree, groups same-shape matrices,
and returns per-leaf spectra.  Matrices are padded/truncated to a common
square size per group (spectral monitoring uses the top-k values, which
square padding preserves: sigma(pad(A)) = sigma(A) plus zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core import svd as svdmod

__all__ = ["batched_singular_values", "sharded_singular_values",
           "sharded_svd", "sharded_pipeline_dispatch", "shard_pad",
           "spectrum_of_params", "square_embed", "process_info"]


def process_info() -> tuple[int, int]:
    """``(process_index, process_count)`` under multi-process JAX, or
    ``(0, 1)`` on any jax predating (or unconfigured for) the
    distributed runtime — callers (worker hello frames, mesh builders,
    DESIGN.md §17) never need their own hasattr dance."""
    try:
        return int(jax.process_index()), int(jax.process_count())
    except Exception:                        # noqa: BLE001 — single process
        return 0, 1


def square_embed(w: jax.Array, size: int) -> jax.Array:
    """Embed/crop a (m, k) matrix into (size, size); sigma is preserved for
    size >= max(m, k) (padding adds zero singular values only)."""
    m, k = w.shape
    if m < k:                       # sigma(A) == sigma(A^T); keep tall
        w = w.T
        m, k = k, m
    w = w[:size, :size]
    out = jnp.zeros((size, size), w.dtype)
    return out.at[: w.shape[0], : w.shape[1]].set(w)


def batched_singular_values(mats: jax.Array, *, bw: int | None = None,
                            tw: int | None = None, backend: str = "auto",
                            config=None, compute_uv: bool = False):
    """Batch-native three-stage pipeline: (B, n, n) -> (B, n) descending sigma.

    Delegates to ``core.svd`` (one fused wavefront over all B chases — the
    former vmapped-loop formulation is subsumed).  ``compute_uv=True``
    returns ``(U, sigma, V^T)`` via the reflector-tape pipeline.
    """
    if compute_uv:
        return svdmod.svd_batched(mats, config=config, compute_uv=True,
                                  bw=bw, tw=tw, backend=backend)
    return svdmod.batched_singular_values(mats, bw=bw, tw=tw, backend=backend,
                                          config=config)


def sharded_singular_values(mats: jax.Array, mesh: Mesh, *, bw: int = 32,
                            tw: int | None = None, backend: str = "auto",
                            batch_axes: tuple[str, ...] = ("data",),
                            compute_uv: bool = False, config=None):
    """Batch-dispatch spectra across the mesh: (B, n, n) -> (B, n).

    B must be divisible by the product of ``batch_axes`` sizes; each device
    group computes its matrices fully locally (GPU-residency -> core-residency).
    With ``compute_uv=True`` each shard additionally replays its reflector
    tapes locally — vector accumulation needs no collectives either (one
    matrix never crosses a core) — returning sharded ``(U, sigma, V^T)``.
    """
    if config is not None:
        # The resolved config is the single source of truth; dropping the
        # loose kwargs here keeps PipelineConfig.of's conflict check from
        # tripping on this function's own defaults.
        bw, tw, backend = None, None, "auto"
    spec = P(batch_axes)
    fn = functools.partial(batched_singular_values, bw=bw, tw=tw,
                           backend=backend, compute_uv=compute_uv,
                           config=config)
    out_specs = (spec, spec, spec) if compute_uv else spec
    shard_fn = jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                             out_specs=out_specs, check_vma=False)
    return shard_fn(mats)


def sharded_svd(mats: jax.Array, mesh: Mesh, *, bw: int = 32,
                tw: int | None = None, backend: str = "auto",
                batch_axes: tuple[str, ...] = ("data",)):
    """Full SVD batch-dispatched across the mesh: (B, n, n) ->
    ``(U (B, n, n), sigma (B, n), V^T (B, n, n))``, batch-sharded."""
    return sharded_singular_values(mats, mesh, bw=bw, tw=tw, backend=backend,
                                   batch_axes=batch_axes, compute_uv=True)


def shard_pad(b: int, shards: int) -> int:
    """Rows to append so a batch of ``b`` splits evenly over ``shards``."""
    assert shards >= 1, shards
    return (-b) % shards


def sharded_pipeline_dispatch(mats: jax.Array, mesh: Mesh, *, config,
                              banded: bool = False, compute_uv: bool = False,
                              batch_axes: tuple[str, ...] = ("data",),
                              faults=None, on_shard_retry=None):
    """Serve-tier mesh dispatch (DESIGN.md §12): pad the leading batch axis
    to shard divisibility, run the bucket's exact pipeline batch-sharded —
    every device chases its own sub-batch fully locally, zero collectives —
    and slice the padding back off the gathered result.

    ``config`` is the bucket's resolved :class:`PipelineConfig` (it closes
    over the shard_map body as a static value, so one compilation per bucket
    key survives sharding) — its ``stage3`` policy rides along, so a
    "dc"/"auto" bucket runs the divide-and-conquer bidiagonal solve on every
    shard with no extra plumbing here.  Mirrors the four local dispatch
    modes of ``serve.SVDEngine``: ``(banded, compute_uv)`` selects among
    ``svd_batched`` / ``banded_singular_values`` / ``svd`` / ``banded_svd``.
    Padding rows are independent zero matrices — sigma(0) = 0 — and are
    dropped before anyone sees them.

    Device-drop handling (DESIGN.md §15): a raising sharded dispatch (a
    real device/mesh failure takes the whole ``shard_map`` call down) is
    re-dispatched UNSHARDED through the same per-shard pipeline body — one
    compilation of the same program at full batch — so the batch still
    completes on whatever is left.  A *simulated* per-shard loss
    (``faults``, a :class:`~repro.serve.faults.FaultPlan` whose
    ``lost_shards`` names the dropped shard indices) voids the lost
    shards' slices and re-dispatches exactly those slices through the SAME
    compiled sharded program (the lost slice is tiled across the mesh and
    the victim shard's lane is read back) — the re-dispatched slice is
    therefore bitwise-identical to what the clean run would have produced,
    which ``tests/test_serve_faults.py`` asserts.  Every re-dispatched
    shard (and the all-shards unsharded fallback) is reported through
    ``on_shard_retry(count)`` — the engines wire it to
    ``ServeMetrics.sharded_retries``.
    """
    shards = 1
    for ax in batch_axes:
        shards *= mesh.shape[ax]
    b0 = mats.shape[0]
    pad = shard_pad(b0, shards)
    if pad:
        mats = jnp.concatenate(
            [mats, jnp.zeros((pad,) + mats.shape[1:], mats.dtype)])

    def local(ms):
        if compute_uv:
            fn = svdmod.banded_svd if banded else svdmod.svd
            return fn(ms, config=config, compute_uv=True)
        if banded:
            return svdmod.banded_singular_values(ms, bw=config.bw,
                                                 config=config)
        return svdmod.svd_batched(ms, config=config)

    spec = P(batch_axes)
    out_specs = (spec, spec, spec) if compute_uv else spec
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec,),
                       out_specs=out_specs, check_vma=False)
    # Host span for the whole mesh dispatch (DESIGN.md §16); the shard_map
    # body itself runs under jit tracing, where spans no-op by design.
    with obs.span("sharded_dispatch", shards=shards, pad=pad, batch=int(b0),
                  n=int(mats.shape[-1]), banded=banded,
                  compute_uv=compute_uv) as dsp:
        try:
            out = fn(mats)
        except Exception:                        # noqa: BLE001 — mesh down
            # Real failure path: the sharded dispatch is gone as a unit.
            # Re-dispatch the whole batch unsharded (same pipeline body).
            if on_shard_retry is not None:
                on_shard_retry(shards)
            with obs.span("sharded_fallback_unsharded", shards=shards):
                out = local(mats)
            dsp.set(fallback="unsharded")
        else:
            lost = faults.lost_shards(shards) if faults is not None else []
            if lost:
                per = mats.shape[0] // shards
                parts = list(out) if compute_uv else [out]
                for j in sorted(set(lost)):
                    sl = slice(j * per, (j + 1) * per)
                    # Void the lost shard's slice (its device's results are
                    # gone), then recompute it through the SAME compiled
                    # sharded program: tile the slice across the mesh so
                    # shard j sees exactly the bytes it saw in the clean run
                    # -> bitwise-identical recovery.
                    reps = (shards,) + (1,) * (mats.ndim - 1)
                    with obs.span("shard_retry", shard=j):
                        rout = fn(jnp.tile(mats[sl], reps))
                    rparts = list(rout) if compute_uv else [rout]
                    for i, (arr, rarr) in enumerate(zip(parts, rparts)):
                        voided = arr.at[sl].set(jnp.nan)
                        parts[i] = voided.at[sl].set(rarr[sl])
                    if on_shard_retry is not None:
                        on_shard_retry(1)
                out = tuple(parts) if compute_uv else parts[0]
    if compute_uv:
        u, sig, vt = out
        return u[:b0], sig[:b0], vt[:b0]
    return out[:b0]


def spectrum_of_params(params, *, size: int = 256, bw: int = 32,
                       tw: int | None = None, mesh: Mesh | None = None,
                       backend: str = "auto"):
    """Top spectra for every >=2D leaf of a parameter pytree.

    Returns a pytree of the same structure whose matrix leaves map to their
    length-``size`` singular value vectors (descending); other leaves -> None.
    Leaves with more than 2 dims are flattened on leading axes (e.g. stacked
    scan layers contribute their *per-layer* matrices batched).
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    mats, slots = [], []
    for i, leaf in enumerate(leaves):
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            continue
        w = leaf.reshape((-1,) + leaf.shape[-2:]) if leaf.ndim > 2 else leaf[None]
        for b in range(w.shape[0]):
            mats.append(square_embed(w[b], size))
            slots.append((i, w.shape[0]))
    if not mats:
        return jax.tree_util.tree_unflatten(treedef, [None] * len(leaves))
    batch = jnp.stack(mats)
    if mesh is not None:
        total = 1
        for ax in ("data",):
            total *= mesh.shape[ax]
        pad = (-batch.shape[0]) % total
        if pad:
            batch = jnp.concatenate([batch, jnp.zeros((pad,) + batch.shape[1:], batch.dtype)])
        sig = sharded_singular_values(batch, mesh, bw=bw, tw=tw, backend=backend)
        sig = sig[: len(mats)]
    else:
        sig = batched_singular_values(batch, bw=bw, tw=tw, backend=backend)
    out_leaves: list = [None] * len(leaves)
    k = 0
    for i, leaf in enumerate(leaves):
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            continue
        nmat = 1 if leaf.ndim == 2 else int(jnp.prod(jnp.asarray(leaf.shape[:-2])))
        vals = sig[k : k + nmat]
        out_leaves[i] = vals[0] if leaf.ndim == 2 else vals.reshape(leaf.shape[:-2] + (size,))
        k += nmat
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


# ---------------------------------------------------------------------------
# Distributed single-matrix chase (beyond-paper: the paper's §VI note that
# "the GPU algorithm could equally be extended to take advantage of multiple
# nodes").  The packed band is sharded column-wise; each device executes the
# wavefront windows whose pivots fall in its column block, with a W-column
# halo exchanged by collective_permute each cycle.  The 3-cycle separation
# guarantees at most ONE window crosses each shard boundary per cycle and
# that its writes are disjoint from the neighbor's own windows — the halo
# merge is therefore a static-masked overwrite (no reductions).
# ---------------------------------------------------------------------------

def reduce_stage_sharded(band: jax.Array, *, n: int, b_in: int, tw: int,
                         mesh: Mesh, axis: str = "data") -> jax.Array:
    """One SBR stage with the band column-sharded over ``axis``.

    band: (b_in + 2*tw + 1, ncols) with ncols % mesh.shape[axis] == 0 and
    ncols >= n + W.  Returns the same-sharded reduced band.
    """
    from jax.sharding import PartitionSpec as P
    from repro.core import bulge_chasing as bc
    from repro.kernels import ops

    d = mesh.shape[axis]
    h = b_in + 2 * tw + 1
    w = b_in + tw + 1
    assert band.shape[0] == h
    nsweeps, total, g_max = bc.stage_schedule(n, b_in, tw)
    if nsweeps == 0:
        return band
    ncols = band.shape[1]
    assert ncols % d == 0 and ncols >= n + w, (ncols, d, n, w)
    c = ncols // d
    assert c >= w, "shard width must cover one chase window"

    yy = jnp.arange(h)[:, None]
    ww_ = jnp.arange(w)[None, :]
    d_gather = jnp.clip(h - 1 + ww_ - yy, 0, h - 1)
    gather_valid = yy >= ww_
    dd = jnp.arange(h)[:, None]
    y_back = jnp.clip(h - 1 + ww_ - dd, 0, h - 1)
    back_valid = dd >= ww_
    g_idx = jnp.arange(g_max)

    def shard_fn(local):                       # local: (h, c) per device
        dev = jax.lax.axis_index(axis)
        lo = dev * c

        def cycle(t, local):
            # fresh halo: right neighbor's leading W columns (last device: 0s)
            head = local[:, :w]
            halo = jax.lax.ppermute(head, axis,
                                    [(i + 1, i) for i in range(d - 1)])
            dump = jnp.zeros((h, g_max * w), local.dtype)
            ext = jnp.concatenate([local, halo, dump], axis=1)

            _, _, p, active, is_first = bc.chase_cycle_indices(
                t, g_idx, n, b_in, tw)
            mine = active & (p >= lo) & (p < lo + c)
            start = jnp.where(mine, p - lo, c + w + g_idx * w).astype(jnp.int32)
            cols = start[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
            win = ext[d_gather[None], cols[:, None, :]]
            win = jnp.where(gather_valid[None], win, 0)
            out = ops.chase_cycle(win, is_first, b_in=b_in, tw=tw,
                                  backend="ref")
            out = jnp.where(mine[:, None, None], out, win)
            orig = ext[jnp.arange(h)[None, :, None], cols[:, None, :]]
            vals = out[g_idx[:, None, None], y_back[None], ww_[None]]
            vals = jnp.where(back_valid[None], vals, orig)
            ext = ext.at[jnp.arange(h)[None, :, None], cols[:, None, :]].set(vals)

            local_new = ext[:, :c]
            halo_out = ext[:, c : c + w]
            # send my updated halo right; receive the left neighbor's
            recv = jax.lax.ppermute(halo_out, axis,
                                    [(i, i + 1) for i in range(d - 1)])
            # how many of MY leading columns did the left neighbor write?
            # (its unique boundary-crossing window: pivot in (lo - w, lo))
            crossing = active & (p > lo - w) & (p < lo)
            m = jnp.max(jnp.where(crossing, p + w - lo, 0))
            take = jnp.arange(c) < m
            merged_head = jnp.where((jnp.arange(w) < m)[None, :],
                                    recv, local_new[:, :w])
            return local_new.at[:, :w].set(merged_head)

        return jax.lax.fori_loop(0, total, cycle, local)

    spec = P(None, axis)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    return fn(band)


def bidiagonalize_sharded(a: jax.Array, *, bw: int, tw: int, mesh: Mesh,
                          axis: str = "data"):
    """Full distributed SBR: dense banded (n, n) -> (diag, superdiag),
    band column-sharded over ``axis`` between stages."""
    from repro.core import band as bandmod
    from repro.core import bulge_chasing as bc

    n = a.shape[0]
    d = mesh.shape[axis]
    plan = bc.tw_schedule(bw, tw)
    if not plan:
        packed = bandmod.pack(a, bw, 0)
        return (bandmod.band_extract_diag(packed, 0, 0, n),
                bandmod.band_extract_diag(packed, 0, 1, n))
    tw0 = plan[0][1]
    cur = bandmod.pack(a, bw, tw0)
    tw_cur = tw0
    for b_in, twi in plan:
        h_i = b_in + 2 * twi + 1
        start = tw_cur - twi
        if start != 0 or cur.shape[0] != h_i:
            cur = jax.lax.slice_in_dim(cur, start, start + h_i, axis=0)
        w_i = b_in + twi + 1
        ncols = -(-(n + w_i) // d) * d
        ncols = max(ncols, d * w_i)
        cur = bandmod.pad_columns(cur, ncols - cur.shape[1])
        cur = reduce_stage_sharded(cur, n=n, b_in=b_in, tw=twi, mesh=mesh,
                                   axis=axis)
        cur = cur[:, :n]
        tw_cur = twi
    dvec = bandmod.band_extract_diag(cur, tw_cur, 0, n)
    evec = bandmod.band_extract_diag(cur, tw_cur, 1, n)
    return dvec, evec
