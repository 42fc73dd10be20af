"""What the loops of the benchmark share: a run's context, its checks and
its statistics.  A loop (``bench/loops/<loop>.py``) takes a
:class:`Context` and returns an outcome dict:

- ``setup_s``: process start to the start of the window;
- ``e2e``: end-to-end metric name -> value, measured with tracing off;
- ``attempted`` / ``failed``: answers due in the window, and those that
  never came, failed, or were wrong;
- ``checks``: name -> ``{"value", "limit"}``, every number compared with
  the plain reference beside its limit; the run is correct when each
  value is at most its limit;
- ``memory_peak_bytes``: the fullest chip's peak, read after the window;
- ``trace``: the reduction of the window's profiler trace (traced runs);
- ``readings``: raw counters and host-clock readings the per-layer
  readers take apart.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


class BenchError(RuntimeError):
    """A run that has to end with a non-zero exit and no result line."""


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    t_start: float                 # perf_counter() when the process started
    devices: list
    precision: str | None = None   # the control's lower precision, if any

    @property
    def dtype(self) -> str:
        """The dtype the program is fed: the configuration's, or the
        control's lower one."""
        return self.precision or self.config["dtype"]

    @property
    def window_s(self) -> float:
        """Seconds the window runs: a traced run keeps to the traffic's
        ``trace_seconds`` so that its trace stays small."""
        if self.trace:
            return min(self.seconds, self.traffic.get("trace_seconds",
                                                      self.seconds))
        return self.seconds

    def limit(self, name: str) -> float:
        return float(self.config["limits"][name])


def jax_key(seed: int):
    """A JAX PRNG key from any whole-number seed (beyond 32 bits too)."""
    import jax
    word = int(np.random.SeedSequence(seed).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileCounter:
    """Counts the compiles (backend compiles and persistent-cache loads)
    JAX makes while it is entered: the window should make none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0

    def _listener(self, event: str, _duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._listener)


def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def passed(c: dict) -> bool:
    """True when the value is at most its limit (NaN never passes)."""
    return bool(c["value"] <= c["limit"])


def nearest_rank(values, q: float) -> float:
    """The q-quantile of the samples by nearest rank: a value that was
    measured, and infinite when enough samples are infinite."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    return float(xs[max(0, math.ceil(q * len(xs)) - 1)])
