"""Kernel-level microbenchmarks (paper Table III analogue, structural).

Wall-clock of one batched chase cycle (ref backend, jitted — the XLA-fused
CPU realization of the kernel math) across (b_in, tw, wavefront width), plus
the per-window VMEM bytes the Pallas kernel would stage on TPU, plus a
kernel-dispatch launch-overhead probe (``_launch_overhead``).  Pallas
interpret-mode timing is NOT a
performance signal (python interpreter), so the TPU projection is the
roofline entry in EXPERIMENTS.md.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import row, timeit
from repro.core.tuning import vmem_working_set_bytes
from repro.kernels import ops

CASES = [(32, 8, 4), (32, 8, 16), (64, 16, 8), (128, 32, 4), (128, 32, 16)]
SMOKE_CASES = [(32, 8, 4), (64, 16, 8)]

# launch-overhead microbenchmark: (b_in, tw) of a one-slot chase call
LAUNCH_CASES = [(16, 4), (32, 8)]
LAUNCH_SMOKE = [(16, 4)]


def _launch_overhead(cases) -> list[str]:
    """Fixed per-dispatch cost: a single-slot ``chase_cycle`` call is
    almost pure dispatch overhead (one tiny window) — the figure
    ``autotune.model.DeviceProfile.launch_overhead_s`` stands for."""
    out = []
    rng = np.random.default_rng(1)
    for b_in, tw in cases:
        h, w = b_in + 2 * tw + 1, b_in + tw + 1
        win = jnp.asarray(rng.standard_normal((1, h, w)), jnp.float32)
        first = jnp.zeros((1,), bool)
        t1 = timeit(lambda: ops.chase_cycle(win, first, b_in=b_in, tw=tw,
                                            backend="ref"),
                    warmup=2, iters=5)
        out.append(row(f"chase_launch/b{b_in}_tw{tw}", t1 * 1e6, "slots=1"))
    return out


def run(smoke: bool = False) -> list[str]:
    out = []
    rng = np.random.default_rng(0)
    for b_in, tw, g in (SMOKE_CASES if smoke else CASES):
        h, w = b_in + 2 * tw + 1, b_in + tw + 1
        win = jnp.asarray(rng.standard_normal((g, h, w)), jnp.float32)
        first = jnp.zeros((g,), bool)
        fn = lambda x, f: ops.chase_cycle(x, f, b_in=b_in, tw=tw, backend="ref")
        t = timeit(fn, win, first, warmup=2, iters=5)
        bytes_win = vmem_working_set_bytes(b_in, tw, jnp.float32)
        traffic = g * h * w * 4 * 2                      # load + store
        out.append(row(
            f"chase_cycle/b{b_in}_tw{tw}_g{g}", t * 1e6,
            f"vmem_window_B={bytes_win};hbm_traffic_B={traffic};"
            f"annihilated={g * 2 * tw}"))
    out += _launch_overhead(LAUNCH_SMOKE if smoke else LAUNCH_CASES)
    return out
