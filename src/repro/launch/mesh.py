"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (device counts are locked at first jax init, and tests /
benches must see the real single device while the dry-run sees 512 host
devices via its own XLA_FLAGS).
"""

from __future__ import annotations

import os

import jax

from repro.parallel.sharding import AxisRules, DEFAULT_RULES, MULTIPOD_RULES

__all__ = ["make_production_mesh", "rules_for", "serve_mesh",
           "init_distributed"]

_DIST_INITIALIZED = False


def init_distributed(*, coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Multi-process JAX bootstrap (DESIGN.md §17).

    Calls ``jax.distributed.initialize`` with the given (or
    ``$REPRO_DIST_COORDINATOR`` / ``$REPRO_DIST_NUM_PROCESSES`` /
    ``$REPRO_DIST_PROCESS_ID``) rendezvous parameters — all three, so JAX
    looks nothing up — and returns True once the bootstrap ran.  An
    unset/partial config returns False (single-process operation is the
    default, not an error).  A configured bootstrap that fails raises: a
    process told to join a coordination service must not quietly serve as
    a single process instead.  Must run before the first device query
    locks the backend; idempotent (a second call is a no-op True).
    """
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return True
    env = os.environ.get
    coordinator = coordinator or env("REPRO_DIST_COORDINATOR", "")
    nproc = (num_processes if num_processes
             else int(env("REPRO_DIST_NUM_PROCESSES", "0") or 0))
    pid = (process_id if process_id is not None and process_id >= 0
           else int(env("REPRO_DIST_PROCESS_ID", "-1") or -1))
    if not coordinator or nproc < 2 or pid < 0:
        return False
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=nproc, process_id=pid)
    _DIST_INITIALIZED = True
    return True


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = (data, model) single pod; (2, 16, 16) = (pod, data, model)
    for the 2-pod, 512-chip production target."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def rules_for(mesh) -> AxisRules:
    import dataclasses
    base = MULTIPOD_RULES if "pod" in mesh.shape else DEFAULT_RULES
    return dataclasses.replace(base, mesh=mesh)


def serve_mesh(*, env_var: str = "REPRO_SERVE_MESH"):
    """Serve-tier dispatch mesh from ``$REPRO_SERVE_MESH``, or ``None``.

    The env var configures how many local devices the serving engines'
    sharded dispatch (DESIGN.md §12) spreads full buckets over:

    * unset / empty — ``None``: engines dispatch locally (single device);
    * ``"auto"``    — every visible device on one ``("data",)`` axis;
    * an integer    — that many devices (clamped to the visible count).

    Returns ``None`` — engines then dispatch locally — when fewer than 2
    devices would participate.  A value
    that parses as neither ``"auto"`` nor an integer raises — a typo'd
    explicit config should be loud, not silently single-device.  Like
    every mesh here this is a FUNCTION: importing the module never touches
    jax device state.
    """
    spec = os.environ.get(env_var, "").strip().lower()
    if not spec:
        return None
    if spec != "auto":
        try:
            int(spec)
        except ValueError:
            raise ValueError(
                f"${env_var}={spec!r}: expected unset, 'auto', or a device "
                f"count") from None
    # LOCAL devices only: the serve engines' sharded dispatch feeds host
    # arrays to this process's addressable devices.  Under multi-process
    # JAX (init_distributed) jax.device_count() is GLOBAL — building the
    # mesh from it would double-count every remote host's devices and
    # dispatch onto devices this process cannot feed (DESIGN.md §17).
    local = jax.local_devices()
    ndev = len(local) if spec == "auto" else int(spec)
    ndev = min(ndev, len(local))
    if ndev < 2:
        return None
    return jax.make_mesh((ndev,), ("data",), devices=local[:ndev],
                         axis_types=(jax.sharding.AxisType.Auto,))
