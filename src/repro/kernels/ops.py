"""jit'd public wrappers for the Pallas kernels, with registry-based dispatch.

Backends are entries in a small registry (``register_backend``) mapping a name
to per-op implementations; dispatch is a dict lookup instead of if/elif chains,
so new backends (future: a Mosaic-GPU port, a cuSOLVER shim) plug in without
touching call sites.  Built-ins:

  "ref"       — pure-jnp oracle (kernels/ref.py), any platform.
  "pallas"    — Pallas TPU kernel; on CPU runs in interpret mode (correctness).

``resolve_backend`` turns the user-facing "auto" into a concrete registry key
(pallas on TPU, ref elsewhere; ref for 64-bit data on TPU, where Pallas has
no float64) and is the single place platform sniffing happens — ``tuning.PipelineConfig.resolve`` calls it so resolved configs never
carry "auto".  Every wrapper also accepts ``config=`` (a resolved
``PipelineConfig``) as the preferred way to select a backend.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref as _ref

__all__ = ["chase_cycle", "chase_stage", "hh_block_apply", "tape_apply",
           "replay_stage",
           "fused_svd", "register_backend", "resolve_backend",
           "resolved_backend", "backend_names"]


def _platform() -> str:
    return jax.devices()[0].platform


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, dict[str, Callable]] = {}


def register_backend(name: str, **impls: Callable) -> None:
    """Register (or extend) a backend: op name -> impl.

    Every impl takes the op's arrays plus its static kwargs and an
    ``interpret`` kwarg (ignored by non-Pallas backends).  ``chase_cycle``
    impls additionally always receive ``with_tape`` (record the reflector
    tape, static).
    """
    _REGISTRY.setdefault(name, {}).update(impls)


def backend_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# Backends whose ops compile to Pallas TPU kernels off interpret mode.
_PALLAS_BACKENDS = ("pallas", "fused_small")


def resolve_backend(backend: str = "auto", interpret: bool | None = None,
                    dtype=None) -> tuple[str, bool]:
    """("auto", None) -> a concrete (registry key, interpret flag).

    ``dtype`` is the working dtype when known.  Pallas on TPU has no
    float64, so "auto" picks "ref" (plain XLA) for 64-bit data on a TPU,
    and an explicit Pallas backend with 64-bit data there raises.
    """
    on_tpu = _platform() == "tpu"
    wide = dtype is not None and jnp.dtype(dtype).itemsize == 8
    if backend == "auto":
        backend = "pallas" if on_tpu and not wide else "ref"
    if backend not in _REGISTRY:
        raise ValueError(
            f"unknown backend {backend!r}; registered: {backend_names()}")
    if interpret is None:
        interpret = not on_tpu
    if wide and on_tpu and not interpret and backend in _PALLAS_BACKENDS:
        raise ValueError(
            f"backend {backend!r} has no {jnp.dtype(dtype).name} kernels on "
            f"TPU; send float32 data or use backend='ref' or 'auto'")
    return backend, bool(interpret)


def _impl(op: str, backend: str) -> Callable:
    table = _REGISTRY.get(backend)
    if table is None or op not in table:
        raise ValueError(
            f"backend {backend!r} does not implement {op!r}; "
            f"registered: {backend_names()}")
    return table[op]


def _resolve(backend: str, interpret: bool | None, config,
             dtype=None) -> tuple[str, bool]:
    """Explicit kwargs win; the config fills whatever is still at its
    "auto"/None default (so a resolved config's interpret flag survives even
    when the caller passes the concrete backend name alongside it)."""
    if config is not None:
        if backend == "auto":
            backend = config.backend
        if interpret is None:
            interpret = config.interpret
    return resolve_backend(backend, interpret, dtype)


def resolved_backend(backend: str = "auto", config=None, dtype=None) -> str:
    """The registry key a wrapper called with ``backend=``/``config=``
    dispatches to for ``dtype`` data."""
    return _resolve(backend, None, config, dtype)[0]


# ---- built-in "ref" (pure jnp; interpret flag ignored) ---------------------

register_backend(
    "ref",
    chase_cycle=lambda windows, is_first, *, b_in, tw, with_tape, interpret:
        _ref.chase_cycle_ref(windows, is_first, b_in=b_in, tw=tw,
                             with_tape=with_tape),
    hh_block_apply=lambda v, t, c, *, block_cols, interpret:
        _ref.hh_block_apply_ref(v, t, c),
    tape_apply=lambda v, t, c, *, block_cols, interpret:
        _ref.tape_apply_ref(v, t, c),
    fused_svd=lambda mats, *, bw, compute_uv, interpret:
        _ref.fused_small_svd_ref(mats, bw=bw, compute_uv=compute_uv),
)


# ---- built-in "pallas" (lazy kernel imports keep CPU-only paths light) -----

def _pallas_chase(windows, is_first, *, b_in, tw, with_tape, interpret):
    from repro.kernels import bulge_chase
    return bulge_chase.chase_cycle_pallas(windows, is_first, b_in=b_in,
                                          tw=tw, interpret=interpret,
                                          with_tape=with_tape)


def _pallas_chase_stage(band, *, n, b_in, tw, with_tape, interpret):
    from repro.kernels import bulge_chase
    return bulge_chase.chase_stage_pallas(band, n=n, b_in=b_in, tw=tw,
                                          interpret=interpret,
                                          with_tape=with_tape)


def _pallas_hh(v, t, c, *, block_cols, interpret):
    from repro.kernels import hh_apply
    return hh_apply.hh_block_apply_pallas(v, t, c, interpret=interpret,
                                          block_cols=block_cols)


def _pallas_tape(v, t, c, *, block_cols, interpret):
    from repro.kernels import hh_apply
    return hh_apply.tape_apply_pallas(v, t, c, interpret=interpret,
                                      block_cols=block_cols)


def _pallas_replay_stage(ut, vt, tape_v, tape_tau, *, n, b_in, tw,
                         interpret):
    from repro.kernels import hh_apply
    return hh_apply.replay_stage_pallas(ut, vt, tape_v, tape_tau, n=n,
                                        b_in=b_in, tw=tw, interpret=interpret)


def _pallas_fused(mats, *, bw, compute_uv, interpret):
    from repro.kernels import fused_small
    return fused_small.fused_small_svd_pallas(mats, bw=bw,
                                              compute_uv=compute_uv,
                                              interpret=interpret)


register_backend("pallas", chase_cycle=_pallas_chase,
                 chase_stage=_pallas_chase_stage, hh_block_apply=_pallas_hh,
                 tape_apply=_pallas_tape, replay_stage=_pallas_replay_stage,
                 fused_svd=_pallas_fused)


# ---- "fused_small" (DESIGN.md §13): the one-dispatch small-n SVD tier ------
#
# A complete backend, not just an op: ``PipelineConfig(backend="fused_small")``
# is valid anywhere a backend name goes (including inside shard_map's local
# function, so PR 5's sharded dispatch serves a whole shard bucket as one
# kernel launch).  ``fused_svd`` is platform-routed — the Pallas kernel where
# Pallas compiles (TPU), the jitted jnp twin elsewhere (one XLA dispatch on
# CPU; interpret-mode Pallas would eagerly step ~1e4 fori iterations per
# matrix).  The staged ops delegate to the platform default so a
# fused_small-configured pipeline can still run any staged stage it needs.

def _fused_small_delegate(op: str) -> Callable:
    def impl(*args, **kwargs):
        base = "pallas" if _platform() == "tpu" else "ref"
        return _impl(op, base)(*args, **kwargs)
    return impl


register_backend("fused_small",
                 **{op: _fused_small_delegate(op)
                    for op in ("chase_cycle", "hh_block_apply", "tape_apply",
                               "fused_svd")})


# ---------------------------------------------------------------------------
# Public dispatching wrappers
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("b_in", "tw", "backend", "interpret",
                                    "config", "with_tape"))
def chase_cycle(windows: jax.Array, is_first: jax.Array, *, b_in: int, tw: int,
                backend: str = "auto", interpret: bool | None = None,
                config=None, with_tape: bool = False):
    """Process one wavefront of bulge-chase cycles.

    windows: (G, H, W) rolled dense windows (disjoint); is_first: (G,)
    bool.  With a leading batch axis folded in, G = B * G_matrix —
    independent problems simply widen the wavefront (one call either way).

    ``with_tape=True`` returns ``(windows, vs, taus)`` — the reflector-tape
    slice for this wavefront (right reflector at pair index 0, left at 1),
    recorded alongside the identical window update; shapes
    ``(G, 2, tw+1)``/``(G, 2)``.
    """
    backend, interpret = _resolve(backend, interpret, config, windows.dtype)
    return _impl("chase_cycle", backend)(windows, is_first, b_in=b_in, tw=tw,
                                         with_tape=with_tape,
                                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw", "backend",
                                             "interpret", "config",
                                             "with_tape"))
def chase_stage(band: jax.Array, *, n: int, b_in: int, tw: int,
                backend: str = "auto", interpret: bool | None = None,
                config=None, with_tape: bool = False):
    """One whole stage (bandwidth b_in -> b_in - tw) on packed storage
    ``(B, H, ncols)``, each matrix's band resident in fast memory for the
    stage (DESIGN.md §9).  ``with_tape=True`` returns ``(band, vs, taus)``
    with the stage's reflector tape ``(B, T, G, 2, tw+1)`` / ``(B, T, G,
    2)``.  Only the "pallas" backend implements it;
    ``core.bulge_chasing.stage_path`` says when it is used."""
    backend, interpret = _resolve(backend, interpret, config, band.dtype)
    return _impl("chase_stage", backend)(band, n=n, b_in=b_in, tw=tw,
                                         with_tape=with_tape,
                                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("backend", "interpret",
                                             "block_cols", "config"))
def tape_apply(v: jax.Array, t: jax.Array, c: jax.Array, *,
               backend: str = "auto", interpret: bool | None = None,
               block_cols: int = 512, config=None) -> jax.Array:
    """Slot-batched compact-WY left apply (the tape-replay workhorse):

        C[s] <- (I - V[s] T[s] V[s]^T) C[s]

    v: (S, m, k), t: (S, k, k), c: (S, m, w).  Chase-tape replay passes the
    rank-1 form (k = 1, t = tau); stage-1 panel replay passes k = nb blocks.
    """
    backend, interpret = _resolve(backend, interpret, config, c.dtype)
    return _impl("tape_apply", backend)(v, t, c, block_cols=block_cols,
                                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "b_in", "tw", "backend",
                                             "interpret", "config"))
def replay_stage(ut: jax.Array, vt: jax.Array, tape_v: jax.Array,
                 tape_tau: jax.Array, *, n: int, b_in: int, tw: int,
                 backend: str = "auto", interpret: bool | None = None,
                 config=None):
    """Replay one chase stage's reflector tape ``(B, T, G, 2, tw+1)`` /
    ``(B, T, G, 2)`` into the transposed accumulators ``U^T``, ``V^T``
    ``(B, n, n)``, each column stripe resident in fast memory for the
    stage (DESIGN.md §8); returns the updated ``(U^T, V^T)``.  Only the
    "pallas" backend implements it; ``core.transforms.replay_path`` says
    when it is used."""
    backend, interpret = _resolve(backend, interpret, config, ut.dtype)
    return _impl("replay_stage", backend)(ut, vt, tape_v, tape_tau, n=n,
                                          b_in=b_in, tw=tw,
                                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("backend", "interpret",
                                             "block_cols", "config"))
def hh_block_apply(v: jax.Array, t: jax.Array, c: jax.Array, *,
                   backend: str = "auto", interpret: bool | None = None,
                   block_cols: int = 512, config=None) -> jax.Array:
    """C <- (I - V T V^T) C — stage-1 WY blocked reflector apply."""
    backend, interpret = _resolve(backend, interpret, config, c.dtype)
    return _impl("hh_block_apply", backend)(v, t, c, block_cols=block_cols,
                                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bw", "compute_uv", "backend",
                                             "interpret", "config"))
@obs.scope("fused")
def fused_svd(mats: jax.Array, *, bw: int, compute_uv: bool = False,
              backend: str = "auto", interpret: bool | None = None,
              config=None):
    """Whole-pipeline small-n SVD, one dispatch per (B, n, n) stack.

    Values mode (default) returns sigma (B, n) descending.
    ``compute_uv=True`` returns ``(d, e, u2, vt2)`` — the bidiagonal plus
    the accumulated two-sided transforms; ``core.svd`` composes the final
    vectors with one batched ``bidiag_svd``.  ``backend="auto"`` follows the
    platform default; ``"fused_small"`` platform-routes (Pallas kernel on
    TPU, jitted jnp twin elsewhere).
    """
    backend, interpret = _resolve(backend, interpret, config, mats.dtype)
    return _impl("fused_svd", backend)(mats, bw=bw, compute_uv=compute_uv,
                                       interpret=interpret)
