"""Quickstart: the paper's contribution in five lines, then the pipeline.

Computes all singular values of (1) a banded matrix via the memory-aware
bulge-chasing reduction (the paper's stage 2 + stage 3), (2) a dense matrix
via the full three-stage pipeline, (3) a stacked batch of matrices via
the batch-native pipeline + resolved PipelineConfig, and (4) a FULL SVD
(U, sigma, V^T) via the reflector-tape pipeline (compute_uv=True) —
validated against numpy on the spot.  Runs on CPU in seconds.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from repro.core import banded_singular_values, singular_values, svd_batched
from repro.core.tuning import ChaseConfig, PipelineConfig

# --- 1. banded matrix -> singular values (the paper's direct use case) ------
n, bw = 256, 16
rng = np.random.default_rng(0)
a = np.triu(rng.standard_normal((n, n)))
a = np.triu(a) - np.triu(a, bw + 1)                  # upper banded, bw=16

cfg = ChaseConfig.resolve(n, bw, jnp.float64)
print(f"banded {n}x{n}, bandwidth {bw}: tilewidth={cfg.tw}, "
      f"max concurrent sweeps={cfg.max_sweeps}")

sigma = banded_singular_values(jnp.asarray(a), bw=bw, tw=cfg.tw, backend="ref")
ref = np.linalg.svd(a, compute_uv=False)
err = np.max(np.abs(np.asarray(sigma) - ref)) / ref[0]
print(f"sigma[0..4] = {np.asarray(sigma[:5]).round(4)}")
print(f"max rel err vs LAPACK: {err:.2e}")
assert err < 1e-10

# --- 2. dense matrix -> three-stage pipeline ---------------------------------
m = 128
d = rng.standard_normal((m, m))
sigma2 = singular_values(jnp.asarray(d), bw=16, tw=8, backend="ref")
ref2 = np.linalg.svd(d, compute_uv=False)
err2 = np.max(np.abs(np.asarray(sigma2) - ref2)) / ref2[0]
print(f"dense {m}x{m} three-stage pipeline: max rel err {err2:.2e}")
assert err2 < 1e-10

# --- 3. batched: a stack of matrices through one fused wavefront -------------
# Small matrices cannot fill the machine alone (paper Eq. 1); a (B, n, n)
# stack shares one wavefront clock, so every chase cycle is one fused kernel
# call over all B*G windows.  PipelineConfig resolves every knob (tilewidth,
# backend, bucket size) once; it is the one argument every layer accepts.
B, k = 8, 64
cfg = PipelineConfig.resolve(bw=8, dtype=jnp.float64, n=k)
print(f"batched {B}x{k}x{k}: config {cfg}")
stack = rng.standard_normal((B, k, k))
sigma3 = np.asarray(svd_batched(jnp.asarray(stack), config=cfg))
err3 = max(np.max(np.abs(sigma3[b] - np.linalg.svd(stack[b], compute_uv=False)))
           / sigma3[b][0] for b in range(B))
print(f"batch of {B}: max rel err vs LAPACK {err3:.2e}")
assert err3 < 1e-10

# --- 4. full SVD: U, sigma, V^T via the reflector tape (compute_uv=True) ----
# The paper computes values only (vector accumulation is its §VII future
# work); with compute_uv=True stages 1-2 record every Householder reflector
# into a static-shape tape, replayed into U/V^T with the chase's own
# wavefront batching (DESIGN.md §8).  sigma is bit-identical to case 3.
u, sigma4, vt = svd_batched(jnp.asarray(stack), config=cfg, compute_uv=True)
u, sigma4, vt = np.asarray(u), np.asarray(sigma4), np.asarray(vt)
recon = max(np.abs(u[b] @ np.diag(sigma4[b]) @ vt[b] - stack[b]).max()
            for b in range(B))
orth = max(np.abs(u[b].T @ u[b] - np.eye(k)).max() for b in range(B))
print(f"full SVD: max recon err {recon:.2e}, max |U^T U - I| {orth:.2e}, "
      f"sigma bit-identical: {np.array_equal(sigma3, sigma4)}")
assert recon < 1e-10 and orth < 1e-12
assert np.array_equal(sigma3, sigma4)

# --- 5. hardware-aware autotuning (DESIGN.md §11) ----------------------------
# The closed-form defaults above are a guess about this host; the autotuner
# measures the truth.  The analytic cost model ranks the (tw, batch)
# grid, only the top-K (plus the static default) are timed, and the winner is
# persisted to a JSON cache keyed by (device, n, bw, dtype, uv, backend) —
# which resolve(autotune=True) then consults.  CLI equivalent:
#   python -m repro.autotune --shapes n=64:bw=8 --backend ref
import os
import tempfile
from repro.autotune import cache as at_cache, model as at_model, run_search

cache_file = os.path.join(tempfile.mkdtemp(), "autotune.json")
res = run_search(64, 8, backend="ref", top_k=2, iters=1)
print(res.table())
at_cache.store(res.to_entry(), device_kind=at_model.device_kind(), n=64,
               bw=8, dtype="float32", compute_uv=False, backend="ref",
               path=cache_file)
tuned = PipelineConfig.resolve(n=64, bw=8, backend="ref", autotune=True,
                               autotune_cache=cache_file)
assert tuned.tw == res.best.tw
assert res.best.measured_s <= res.default.measured_s   # beats or ties default
print(f"tuned config for n=64, bw=8 on this host: tw={tuned.tw} "
      f"max_batch={tuned.max_batch}")
print("OK")

# --- 6. async serving: concurrent requests -> micro-batched buckets ----------
# (DESIGN.md §12)  Callers from any thread (or asyncio task) submit and get a
# future; the engine aggregates concurrent same-shape requests into one
# batched pipeline call per bucket — the batch axis of section 3, fed by
# traffic instead of one caller.  Deadlines, per-request error surfacing, and
# multi-device dispatch (REPRO_SERVE_MESH) ride along; eng.metrics counts
# queue depth, batch-fill ratio, and bucket hit-rate.
import threading
from repro.serve import AsyncSVDEngine, SVDRequest

serve_cfg = PipelineConfig.resolve(bw=4, tw=2, backend="ref",
                                   dtype=np.float64, max_batch=4)
futs, futs_lock = {}, threading.Lock()
with AsyncSVDEngine(serve_cfg, batch_window_s=0.005) as eng:
    def client(t, k=24):
        for j in range(3):
            uid = t * 3 + j
            f = eng.submit(SVDRequest(
                uid=uid, matrix=rng.standard_normal((k, k)), bw=4))
            with futs_lock:
                futs[uid] = f
    threads = [threading.Thread(target=client, args=(t,)) for t in range(4)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    done = {uid: f.result() for uid, f in futs.items()}
worst = max(np.abs(r.sigma - np.linalg.svd(r.matrix, compute_uv=False)).max()
            for r in done.values())
snap = eng.metrics.snapshot()
print(f"async serve: {len(done)} concurrent requests in "
      f"{snap['batches']} batched calls "
      f"(fill={snap['batch_fill_ratio']:.2f}), max err {worst:.2e}")
assert len(done) == 12 and worst < 1e-10
assert snap["completed"] == 12 and snap["failed"] == 0
print("OK")

# --- 7. fused small-n tier: the whole pipeline as ONE dispatch ---------------
# (DESIGN.md §13)  Below the fused crossover the staged pipeline's per-stage
# dispatches are pure overhead on a VMEM-resident problem: backend
# "fused_small" runs band reduction, the whole bulge chase, and the Sturm
# bisection in a single kernel dispatch per (B, n, n) stack.  The serve
# engines route n <= fused_n_max buckets there automatically (tuned via
# `python -m repro.autotune --fused-crossover`); metrics attribute every
# dispatch per tier.
fcfg = PipelineConfig.resolve(bw=8, dtype=jnp.float64, n=k,
                              backend="fused_small")
sigma8 = np.asarray(svd_batched(jnp.asarray(stack), config=fcfg))
print(f"fused_small tier: max |sigma - staged| = "
      f"{np.abs(sigma8 - sigma3).max():.2e}")
assert np.abs(sigma8 - sigma3).max() < 1e-12

with AsyncSVDEngine(serve_cfg, batch_window_s=0.005) as eng:
    f = eng.submit(SVDRequest(uid=0, matrix=rng.standard_normal((24, 24)),
                              bw=4))
    f.result()
snap = eng.metrics.snapshot()
tier = next(iter(snap["bucket_tiers"].values()))
print(f"serve routing: n=24 bucket -> tier={tier['tier']!r} "
      f"(backend={tier['backend']}), fused batches = "
      f"{snap['tiers']['fused']['batches']}")
assert tier["tier"] == "fused" and snap["tiers"]["fused"]["batches"] >= 1
print("OK")

# --- 8. divide-and-conquer stage 3: the large-n end (DESIGN.md §14) ----------
# The Sturm bisection's critical path grows like n (every sweep is a
# sequential depth-2n recurrence); Cuppen's D&C replaces it with log2(n/32)
# secular merge levels whose deflated blocks are skipped at run time, so past
# the measured crossover (~2048 on a CPU host, fp64) it wins outright —
# stage3="auto" resolves the choice per problem through the autotune cache
# (`python -m repro.autotune --stage3-crossover`).  This section times both
# solvers on one n=4096 bidiagonal, so it takes ~a minute; everything above
# runs in seconds.
import time
from repro.core.bidiag_dc import bidiag_dc_singular_values
from repro.core.bidiag_svd import bidiag_singular_values

n9 = 4096
d9 = jnp.asarray(rng.standard_normal(n9))
e9 = jnp.asarray(rng.standard_normal(n9))     # e[0] unused: e[i] = B[i-1,i]

auto9 = PipelineConfig.resolve(bw=32, dtype=jnp.float64, stage3="auto")
print(f"stage3='auto' resolves: n=256 -> {auto9.stage3_for(256)!r}, "
      f"n={n9} -> {auto9.stage3_for(n9)!r}")

sig_bi = jax.block_until_ready(bidiag_singular_values(d9, e9))   # + compile
sig_dc = jax.block_until_ready(bidiag_dc_singular_values(d9, e9))
t0 = time.perf_counter()
jax.block_until_ready(bidiag_singular_values(d9, e9))
t_bi = time.perf_counter() - t0
t0 = time.perf_counter()
jax.block_until_ready(bidiag_dc_singular_values(d9, e9))
t_dc = time.perf_counter() - t0
agree9 = float(jnp.max(jnp.abs(sig_dc - sig_bi)) / sig_bi[0])
print(f"stage 3 at n={n9}: bisect {t_bi:.2f}s, dc {t_dc:.2f}s "
      f"({t_bi / t_dc:.2f}x), sigma agreement {agree9:.1e}")
assert agree9 < 1e-12
print("OK")

# --- 9. fault tolerance: injected faults, absorbed (DESIGN.md §15) ----------
# A serving tier that only works when nothing fails is a benchmark, not a
# service.  Inject a deterministic fault plan — the FIRST dispatch raises,
# and the next result comes back NaN-poisoned — and watch the fabric absorb
# both: the dispatch error retries with backoff, the NaN trips the
# numerical-health guard (NumericalFault), is retried once, and the request
# is re-served on the degraded ref tier if the poison persists.  Every
# caller still gets the correct spectrum; nothing surfaces as an error.
from repro.serve import FaultPlan, RetryPolicy, SVDEngine

plan = FaultPlan(seed=7, dispatch_errors_at=(0,), nan_at=(1, 2))
eng10 = SVDEngine(backend="ref",
                  faults=plan,
                  retry=RetryPolicy(backoff_base_s=1e-3, backoff_max_s=1e-2))
mats10 = [rng.standard_normal((24, 24)) for _ in range(3)]
for i, m in enumerate(mats10):
    eng10.submit(SVDRequest(uid=i, matrix=m, bw=4))
done10 = eng10.run()

for r in done10:
    assert r.error is None, r.error            # zero client-visible failures
    ref10 = np.linalg.svd(r.matrix, compute_uv=False)
    assert np.abs(np.asarray(r.sigma) - ref10).max() < 1e-10 * ref10[0]

health = eng10.metrics.health()
snap10 = eng10.metrics.snapshot()
print(f"injected: {plan.snapshot()['dispatch_error']} dispatch error(s), "
      f"{plan.snapshot()['nan']} NaN corruption(s)")
print(f"absorbed: retried={snap10['retried']} degraded={snap10['degraded']} "
      f"(degraded-ref batches = "
      f"{snap10['tiers'].get('degraded-ref', {}).get('batches', 0)})")
print(f"health: status={health['status']!r} "
      f"client_error_rate={health['client_error_rate']:.2f} — every sigma "
      f"correct")
assert health["client_error_rate"] == 0.0
assert snap10["retried"] + snap10["degraded"] >= 1
print("OK")

# --- 10. tracing: what did the host do in one banded call? ------------------
# (DESIGN.md §16)  Pass a Tracer into any core.svd entry point to record
# its host spans (config, pack, one stage2 per tile-width stage, extract,
# stage3) with the compiles counted under each.  The spans are always on as
# `repro/<name>` profiler annotations; the tracer only records them, and
# the call runs the same executables — sigma is bit-identical.  Device time
# per stage comes from a profiler trace, by the repro.* named scopes.
from repro.core.svd import banded_singular_values
from repro.obs import Tracer

tr = Tracer("quickstart")
band11 = np.triu(rng.standard_normal((4, 40, 40)))
band11 = jnp.asarray(band11 - np.triu(band11, 5))
cfg11 = PipelineConfig.resolve(n=40, bw=4, backend="ref", dtype=np.float64)
sig11 = banded_singular_values(band11, config=cfg11, trace=tr)
np.testing.assert_array_equal(
    np.asarray(sig11), np.asarray(banded_singular_values(band11,
                                                         config=cfg11)))

(root11,) = tr.roots
print("\nspans of one traced banded_singular_values call (a first call's "
      "compiles are counted under the spans that triggered them):")
print(tr.format(min_ms=0.0))
assert [c.name for c in root11.children] == [
    "config", "pack", "stage2", "extract", "stage3"]
print("OK")

# --- 11. multi-host serving: router + two local worker processes -------------
# (DESIGN.md §17)  The serve tier across PROCESS boundaries: SVDRouter owns
# admission and pins each shape-bucket to one worker host (rendezvous
# hashing keeps micro-batching intact); each worker is a real subprocess
# running its own AsyncSVDEngine, speaking the stdlib-socket wire protocol.
# A dropped host is quarantined and its in-flight work requeued — zero
# client-visible failures is the design contract, CI-gated with a SIGKILL.
from repro.serve import SVDRouter
from repro.serve.worker import spawn_worker_process

router = SVDRouter()
procs = [spawn_worker_process(router.address, f"w{i}", backend="ref")
         for i in range(2)]
try:
    assert router.wait_for_hosts(2, timeout=240)
    mats12 = [rng.standard_normal((16, 16)) for _ in range(6)]
    futs12 = [router.submit(SVDRequest(uid=i, matrix=m, bw=4))
              for i, m in enumerate(mats12)]
    for m, f in zip(mats12, futs12):
        ref = np.linalg.svd(m, compute_uv=False)
        np.testing.assert_allclose(f.result(timeout=300).sigma, ref,
                                   atol=1e-12 * ref[0])
    fleet = router.fleet()
    per_host = {h: row["completed"]
                for h, row in fleet["router"]["hosts"].items()}
    print(f"\nserved {fleet['router']['completed']} requests across "
          f"{len(fleet['alive_hosts'])} worker processes: {per_host}")
    print(f"fleet merged latency p99 = "
          f"{fleet['latency']['merged_summary']['p99_ms']:.1f} ms "
          f"(per-host histograms folded via StreamingHistogram.merged)")
    assert sum(per_host.values()) == 6
finally:
    router.stop()
    for p in procs:
        p.wait(timeout=30)
print("OK")
