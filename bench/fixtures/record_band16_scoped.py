#!/usr/bin/env python3
"""Record ``band16_scoped.xplane.pb``, the trace the self-check reads for
``scope_s`` and ``idle_by_span``.

    python3 bench/fixtures/record_band16_scoped.py OUT.xplane.pb

On one chip: two n = 16, bw = 4 float32 band reductions through
``banded_singular_values`` with the harness's window, reduce and sleep
annotations, as the closed loop makes them, then one deliberate compile of
a function never called before, inside a program span (``repro/planted``)
within the window.  The persistent compilation cache is left off, so that
the planted compile is a real backend compile.  The capture uses the
options of ``bench/trace.py::WindowTrace``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.trace import WINDOW
    from repro import obs
    from repro.core import svd

    rng = np.random.default_rng(16)
    a = np.triu(rng.standard_normal((16, 16))).astype(np.float32)
    band = jnp.asarray(a - np.triu(a, 5))
    svd.banded_singular_values(band, bw=4).block_until_ready()   # warm
    fresh = jax.jit(lambda x: jnp.cumsum(x * 2.0) - 1.0)
    x = jnp.arange(17, dtype=jnp.float32)

    tmp = tempfile.mkdtemp(prefix="band16-scoped-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            for i in range(2):
                if i:
                    with jax.profiler.TraceAnnotation("bench/sleep"):
                        time.sleep(0.01)
                with jax.profiler.TraceAnnotation("bench/reduce"):
                    svd.banded_singular_values(band, bw=4).block_until_ready()
            with jax.profiler.TraceAnnotation("bench/reduce"):
                with obs.span("planted"):
                    fresh(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes on "
          f"{jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
