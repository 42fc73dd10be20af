"""Hardware-aware autotuning subsystem (DESIGN.md §11).

Four modules compose the paper's §III-D tuning methodology into something
persistent and falsifiable:

* :mod:`repro.autotune.model`   — analytic stage-2 cost model + per-device
  profile table (bytes moved, launch amortization, Eq.-1 occupancy,
  VMEM-cliff feasibility);
* :mod:`repro.autotune.measure` — the one blocking/jit-warmup timing
  harness (the ``benchmarks/`` suites reuse it);
* :mod:`repro.autotune.search`  — model-pruned search: rank the full
  ``(tw, batch)`` grid by predicted cost, time only the top-K (plus
  the static default), report predicted-vs-measured error;
* :mod:`repro.autotune.cache`   — persistent JSON cache keyed by
  ``(device_kind, n, bw, dtype, compute_uv, backend)``; atomic writes,
  ``$REPRO_AUTOTUNE_CACHE``-overridable path.

Entry points: ``python -m repro.autotune --shapes n=512:bw=32 --backend
ref`` tunes and persists; ``tuning.PipelineConfig.resolve(autotune=True)``
consumes the cache (analytic defaults on a miss).
"""

from repro.autotune import cache, measure, model, search
from repro.autotune.cache import (cache_path, lookup, lookup_crossover,
                                  store, store_crossover)
from repro.autotune.measure import measure_seconds, time_stage2
from repro.autotune.model import (DeviceProfile, PROFILES, device_kind,
                                  fused_cost, pipeline_cost,
                                  predicted_crossover, profile_for,
                                  stage_cost, total_chase_cycles)
from repro.autotune.search import (Candidate, FusedCrossoverResult,
                                   SearchResult, search as run_search,
                                   search_fused_crossover)

__all__ = [
    "cache", "measure", "model", "search",
    "cache_path", "lookup", "store", "lookup_crossover", "store_crossover",
    "measure_seconds", "time_stage2",
    "DeviceProfile", "PROFILES", "device_kind", "pipeline_cost",
    "profile_for", "stage_cost", "total_chase_cycles",
    "fused_cost", "predicted_crossover",
    "Candidate", "SearchResult", "run_search",
    "FusedCrossoverResult", "search_fused_crossover",
]
