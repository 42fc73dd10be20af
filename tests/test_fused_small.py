"""Fused small-n SVD tier (DESIGN.md §13): numerics, routing, tuning.

Layers under test:

  1. kernel numerics — fused sigma vs the staged pipeline, the dense
     reference oracle, and LAPACK, across n (1 .. 256), bw edges (bw
     clamped from 0; bw = n-1), and both dtypes;
  2. compute_uv — exact reconstruction A = U diag(s) V^T and orthogonality
     from the fused reduction + one batched bidiag_svd;
  3. backend registry — "fused_small" is a complete backend; the Pallas
     kernel in interpret mode is BIT-IDENTICAL to the jnp twin (shared
     reduction body);
  4. VMEM budget — infeasible n fails loudly at config resolution;
  5. crossover tuning — model prediction, measured search (injected
     timer), cache round-trip;
  6. serve routing — both engines route n <= crossover buckets to the
     fused tier, attribute dispatches per tier, honor pins and the tuned
     cache, and fall back to staged above the crossover;
  7. hypothesis-randomized property sweep (skips without the optional dep).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import reference, tuning
from repro.core import svd as svdmod
from repro.core.bidiag_svd import bidiag_singular_values
from repro.kernels import fused_small, ops
from repro.kernels import ref as kref
from repro.autotune import cache as at_cache
from repro.autotune import model as at_model
from repro.autotune import search as at_search
from repro.serve import AsyncSVDEngine, SVDEngine, SVDRequest


def dense(n, batch=1, seed=0, dtype=np.float64):
    a = np.random.default_rng(seed).standard_normal((batch, n, n))
    return a.astype(dtype)


def lapack_sigma(a):
    return np.linalg.svd(np.asarray(a, np.float64), compute_uv=False)


# ---------------------------------------------------------------------------
# 1. values numerics: fused vs staged vs oracle vs LAPACK
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 16, 64])
@pytest.mark.parametrize("bw", [0, 1, 4, "full"])
def test_fused_values_match_staged_and_lapack(n, bw):
    bw = (n - 1) if bw == "full" else bw       # bw=n-1 edge; bw=0 clamps to 1
    a = dense(n, batch=3, seed=n * 31 + max(bw, 0))
    sig = np.asarray(kref.fused_small_svd_ref(jnp.asarray(a), bw=bw))
    s0 = lapack_sigma(a)
    tol = 1e-12 * max(1.0, float(s0.max()))
    np.testing.assert_allclose(sig, s0, atol=tol)
    # vs the STAGED pipeline at the same (clamped) bandwidth
    bw_eff = fused_small.effective_bw(n, bw)
    if n >= 2:
        staged = np.asarray(svdmod.svd_batched(
            jnp.asarray(a), bw=bw_eff, backend="ref"))
        np.testing.assert_allclose(sig, staged, atol=tol)


def test_fused_values_n256():
    n, bw = 256, 16
    a = dense(n, batch=2, seed=7)
    sig = np.asarray(kref.fused_small_svd_ref(jnp.asarray(a), bw=bw))
    s0 = lapack_sigma(a)
    np.testing.assert_allclose(sig, s0, atol=1e-12 * s0.max())


def test_fused_matches_dense_reference_oracle():
    """On a banded input (in-kernel stage 1 is a no-op) the fused reduction
    reproduces reference.py's loop-nest oracle: same |bidiagonal| entries,
    same sigma.  The fused phase 2 is ONE SBR stage at tw = bw - 1, exactly
    the oracle's single-stage plan."""
    n, bw = 24, 5
    rng = np.random.default_rng(3)
    a = np.triu(rng.standard_normal((n, n)))
    a = np.triu(a) - np.triu(a, bw + 1)
    d_ref, e_ref, _ = reference.bidiagonalize_dense_ref(a.copy(), bw, bw - 1)
    _, _, _, d, e = fused_small._reduce_single(jnp.asarray(a), bw=bw,
                                               compute_uv=False)
    d, e = d[0], e[0]                   # (1, n) kernel rows -> (n,)
    np.testing.assert_allclose(np.abs(np.asarray(d)), np.abs(d_ref),
                               atol=1e-10)
    np.testing.assert_allclose(np.abs(np.asarray(e))[1:], np.abs(e_ref),
                               atol=1e-10)
    sig = np.asarray(bidiag_singular_values(d[None], e[None]))[0]
    np.testing.assert_allclose(sig, lapack_sigma(a[None])[0],
                               atol=1e-12 * sig.max())


def test_fused_banded_input_noop_stage1():
    """Already-banded inputs pass through the in-kernel stage 1 as exact
    no-ops (tau = 0 on zero tails): fused banded == staged banded."""
    n, bw = 20, 4
    rng = np.random.default_rng(5)
    a = np.triu(rng.standard_normal((2, n, n)))
    a = np.triu(a) - np.triu(a, bw + 1)
    sig = np.asarray(kref.fused_small_svd_ref(jnp.asarray(a), bw=bw))
    staged = np.asarray(svdmod.banded_singular_values(
        jnp.asarray(a), bw=bw, backend="ref"))
    np.testing.assert_allclose(sig, staged, atol=1e-12 * staged.max())
    np.testing.assert_allclose(sig, lapack_sigma(a),
                               atol=1e-12 * staged.max())


@pytest.mark.parametrize("dtype,tol", [(np.float32, 5e-4), (np.float64, 1e-12)])
def test_fused_values_dtypes(dtype, tol):
    n, bw = 32, 8
    a = dense(n, batch=2, seed=11, dtype=dtype)
    sig = np.asarray(kref.fused_small_svd_ref(jnp.asarray(a), bw=bw))
    assert sig.dtype == dtype
    s0 = lapack_sigma(a)
    np.testing.assert_allclose(sig, s0, atol=tol * s0.max())


# ---------------------------------------------------------------------------
# 2. compute_uv: reconstruction + orthogonality, sigma unchanged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bw", [(2, 1), (16, 4), (33, 7)])
def test_fused_uv_reconstruction(n, bw):
    a = dense(n, batch=2, seed=n)
    cfg = tuning.PipelineConfig.resolve(bw=bw, dtype=np.float64, n=n,
                                        backend="fused_small",
                                        compute_uv=True)
    u, sig, vt = svdmod.svd(jnp.asarray(a), config=cfg, compute_uv=True)
    u, sig, vt = np.asarray(u), np.asarray(sig), np.asarray(vt)
    smax = max(1.0, float(sig.max()))
    for i in range(len(a)):
        np.testing.assert_allclose(u[i] @ (sig[i][:, None] * vt[i]), a[i],
                                   atol=1e-11 * smax)
        np.testing.assert_allclose(u[i] @ u[i].T, np.eye(n), atol=1e-11)
        np.testing.assert_allclose(vt[i] @ vt[i].T, np.eye(n), atol=1e-11)
    np.testing.assert_allclose(sig, lapack_sigma(a), atol=1e-12 * smax)


def test_fused_uv_sigma_matches_values_mode():
    n, bw = 16, 4
    a = jnp.asarray(dense(n, batch=2, seed=2))
    sig_v = np.asarray(kref.fused_small_svd_ref(a, bw=bw))
    cfg = tuning.PipelineConfig.resolve(bw=bw, dtype=np.float64, n=n,
                                        backend="fused_small",
                                        compute_uv=True)
    _, sig_uv, _ = svdmod.svd(a, config=cfg, compute_uv=True)
    np.testing.assert_allclose(sig_v, np.asarray(sig_uv),
                               atol=1e-13 * max(1.0, float(sig_v.max())))


# ---------------------------------------------------------------------------
# 3. registry + Pallas interpret twin
# ---------------------------------------------------------------------------

def test_fused_small_is_complete_backend():
    assert "fused_small" in ops.backend_names()
    for op in ("chase_cycle", "hh_block_apply", "tape_apply", "fused_svd"):
        assert ops._impl(op, "fused_small") is not None


def test_ops_fused_svd_backends_agree():
    a = jnp.asarray(dense(12, batch=2, seed=9))
    s_ref = np.asarray(ops.fused_svd(a, bw=3, backend="ref"))
    s_fsd = np.asarray(ops.fused_svd(a, bw=3, backend="fused_small"))
    np.testing.assert_array_equal(s_ref, s_fsd)   # same impl off-TPU


@pytest.mark.parametrize("compute_uv", [False, True])
def test_pallas_interpret_bit_identical_to_twin(compute_uv):
    """The Pallas kernel and the jnp twin share the reduction body — in
    interpret mode the outputs are bit-identical, not merely close."""
    n, bw = 8, 3
    a = jnp.asarray(dense(n, batch=2, seed=1))
    if compute_uv:
        d_p, e_p, u_p, vt_p = fused_small.fused_small_svd_pallas(
            a, bw=bw, compute_uv=True, interpret=True)
        red = jax.vmap(lambda m: fused_small._reduce_single(
            m, bw=bw, compute_uv=True))
        _, u_r, v_r, d_r, e_r = red(a)
        d_r, e_r = d_r[:, 0], e_r[:, 0]     # (B, 1, n) kernel rows
        np.testing.assert_array_equal(np.asarray(d_p), np.asarray(d_r))
        np.testing.assert_array_equal(np.asarray(e_p), np.asarray(e_r))
        np.testing.assert_array_equal(np.asarray(u_p), np.asarray(u_r))
        np.testing.assert_array_equal(np.asarray(vt_p),
                                      np.asarray(jnp.swapaxes(v_r, -1, -2)))
    else:
        s_p = fused_small.fused_small_svd_pallas(a, bw=bw, interpret=True)
        s_r = kref.fused_small_svd_ref(a, bw=bw)
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))


# ---------------------------------------------------------------------------
# 4. VMEM budget
# ---------------------------------------------------------------------------

def test_fused_vmem_budget():
    assert tuning.fused_working_set_bytes(64, np.float32) == \
        2 * 64 * 64 * 4 + 12 * 64 * 4
    assert tuning.fused_working_set_bytes(64, np.float32, compute_uv=True) \
        > 2 * tuning.fused_working_set_bytes(64, np.float32)
    tuning.check_fused_vmem_budget(256, np.float32)
    with pytest.raises(ValueError, match="staged"):
        tuning.check_fused_vmem_budget(4096, np.float32)
    # resolution-time enforcement for fused_small configs
    with pytest.raises(ValueError):
        tuning.PipelineConfig.resolve(bw=32, dtype=np.float32, n=4096,
                                      backend="fused_small")
    cfg = tuning.PipelineConfig.resolve(bw=32, dtype=np.float32, n=256,
                                        backend="fused_small")
    assert cfg.backend == "fused_small"


# ---------------------------------------------------------------------------
# 5. crossover: model, search, cache
# ---------------------------------------------------------------------------

def test_model_fused_cost_and_crossover():
    c16 = at_model.fused_cost(16, 8, dtype=np.float64)
    c256 = at_model.fused_cost(256, 8, dtype=np.float64)
    assert 0 < c16.seconds < c256.seconds
    # uv triples the in-kernel cycle work; at n large enough for the cycle
    # term to dominate the Sturm solve, the uv figure must exceed values.
    assert (at_model.fused_cost(256, 8, compute_uv=True).seconds
            > c256.seconds)
    x = at_model.predicted_crossover(8, dtype=np.float64)
    assert x >= 16                               # fused must win the tiny end


def test_search_fused_crossover_injected():
    def fake(n, fused):                          # fused wins up to n=32
        return (1e-3 if fused else 2e-3) if n <= 32 else (2e-3 if fused
                                                          else 1e-3)
    res = at_search.search_fused_crossover(8, ns=(16, 32, 64), batch=4,
                                           measure_fn=fake)
    assert res.fused_n_max == 32
    assert [p[0] for p in res.points] == [16, 32, 64]
    entry = res.to_entry()
    assert entry["fused_n_max"] == 32 and entry["schema"] == 1
    assert "fused crossover" in res.table()


def test_crossover_cache_roundtrip(tmp_path):
    path = str(tmp_path / "cache.json")
    kw = dict(device_kind="cpu", dtype="float64", compute_uv=False)
    assert at_cache.lookup_crossover(**kw, path=path) is None
    at_cache.store_crossover({"fused_n_max": 48}, **kw, bw=8, path=path)
    assert at_cache.lookup_crossover(**kw, bw=8, path=path) == 48
    # no wide entry yet: a different bw misses the specific key AND the wide
    assert at_cache.lookup_crossover(**kw, bw=16, path=path) is None
    at_cache.store_crossover({"fused_n_max": 96}, **kw, path=path)
    assert at_cache.lookup_crossover(**kw, bw=16, path=path) == 96
    assert at_cache.lookup_crossover(**kw, bw=8, path=path) == 48  # specific
    # corrupt entries read as a miss, never as a crossover
    at_cache.store_crossover({"fused_n_max": 7}, **kw, bw=4, path=path)
    doc = at_cache.load(path)
    doc["entries"][at_cache.crossover_key(**kw, bw=4)] = {"fused_n_max": "x"}
    import json
    with open(path, "w") as f:
        json.dump(doc, f)
    assert at_cache.lookup_crossover(**kw, bw=4, path=path) == 96  # wide


# ---------------------------------------------------------------------------
# 6. serve routing + per-tier metrics attribution
# ---------------------------------------------------------------------------

def _engine(**kw):
    return SVDEngine(tuning.PipelineConfig.resolve(bw=8, dtype=np.float64),
                     **kw)


def test_engine_routes_small_buckets_fused():
    eng = _engine()
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(SVDRequest(uid=i, matrix=rng.standard_normal((16, 16)),
                              bw=8))
    done = eng.run()
    assert all(r.error is None for r in done)
    snap = eng.metrics.snapshot()
    assert snap["tiers"]["fused"]["batches"] >= 1
    assert all(v["tier"] == "fused" and v["backend"] == "fused_small"
               for v in snap["bucket_tiers"].values())
    # sigma identical to a fused-disabled engine
    eng0 = _engine(fused_n_max=0)
    m = rng.standard_normal((16, 16))
    r0 = SVDRequest(uid=0, matrix=m.copy(), bw=8)
    r1 = SVDRequest(uid=0, matrix=m.copy(), bw=8)
    eng0.submit(r0); eng0.run()
    eng1 = _engine(); eng1.submit(r1); eng1.run()
    np.testing.assert_allclose(r0.sigma, r1.sigma, atol=1e-12)
    snap0 = eng0.metrics.snapshot()
    assert "fused" not in snap0["tiers"]
    assert all(v["tier"] == "staged" for v in snap0["bucket_tiers"].values())


def test_engine_pinned_crossover_splits_tiers():
    eng = _engine(fused_n_max=32)
    rng = np.random.default_rng(1)
    for i, n in enumerate([16, 16, 48, 48]):
        eng.submit(SVDRequest(uid=i, matrix=rng.standard_normal((n, n)),
                              bw=8))
    done = eng.run()
    assert all(r.error is None for r in done)
    snap = eng.metrics.snapshot()
    tiers = {v["n"]: v["tier"] for v in snap["bucket_tiers"].values()}
    assert tiers == {16: "fused", 48: "staged"}
    assert snap["tiers"]["fused"]["batches"] >= 1
    assert snap["tiers"]["staged"]["batches"] >= 1
    # per-tier slots sum to the global dispatch counters
    assert (sum(t["served_slots"] for t in snap["tiers"].values())
            == snap["served_slots"])
    assert (sum(t["padded_slots"] for t in snap["tiers"].values())
            == snap["padded_slots"])


def test_engine_honors_tuned_crossover(tmp_path):
    path = str(tmp_path / "cache.json")
    at_cache.store_crossover(
        {"fused_n_max": 20}, device_kind=at_model.device_kind(),
        dtype="float64", compute_uv=False, path=path)
    eng = _engine(autotune=True, autotune_cache=path)
    rng = np.random.default_rng(2)
    for i, n in enumerate([16, 24]):
        eng.submit(SVDRequest(uid=i, matrix=rng.standard_normal((n, n)),
                              bw=8))
    eng.run()
    tiers = {v["n"]: v["tier"]
             for v in eng.metrics.snapshot()["bucket_tiers"].values()}
    assert tiers == {16: "fused", 24: "staged"}     # 20 from the cache
    # autotune off: the static default (256) routes both fused
    eng2 = _engine()
    assert eng2._fused_n_max_for((16, 8, "float64", False, False)) \
        == tuning.DEFAULT_FUSED_CROSSOVER


def test_engine_fused_vmem_fallback_to_staged():
    """n under the pinned crossover but over the fused VMEM budget must be
    served (staged), not failed."""
    eng = _engine(fused_n_max=10_000)
    big = 4096
    assert pytest.raises(
        ValueError, tuning.check_fused_vmem_budget, big, np.float64)
    key = (big, 8, "float64", False, False)
    cfg = eng._cfg_for(key)
    assert cfg.backend != "fused_small"
    snap = eng.metrics.snapshot()
    # n=4096 sits past the stage-3 D&C crossover, so the staged fallback
    # is attributed to the "staged-dc" tier (DESIGN.md §14).
    from repro.serve import bucket_key_str
    assert snap["bucket_tiers"][bucket_key_str(key)]["tier"] == "staged-dc"


def test_async_engine_fused_roundtrip():
    eng = AsyncSVDEngine(tuning.PipelineConfig.resolve(bw=8,
                                                       dtype=np.float64),
                         fused_n_max=32, batch_window_s=0.0)
    eng.start()
    try:
        rng = np.random.default_rng(3)
        mats = [rng.standard_normal((16, 16)) for _ in range(4)]
        futs = [eng.submit(SVDRequest(uid=i, matrix=m, bw=8))
                for i, m in enumerate(mats)]
        for f, m in zip(futs, mats):
            r = f.result(timeout=60)
            assert r.error is None
            np.testing.assert_allclose(r.sigma, lapack_sigma(m[None])[0],
                                       atol=1e-11)
    finally:
        eng.stop()
    snap = eng.metrics.snapshot()
    assert snap["tiers"]["fused"]["batches"] >= 1
    assert all(v["tier"] == "fused"
               for v in snap["bucket_tiers"].values())


# ---------------------------------------------------------------------------
# 7. hypothesis-randomized property sweep (skips without the optional dep)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(1, 40), st.integers(0, 10), st.integers(0, 2**31 - 1))
def test_fused_property_randomized(n, bw, seed):
    a = dense(n, batch=1, seed=seed)
    sig = np.asarray(kref.fused_small_svd_ref(jnp.asarray(a), bw=bw))
    s0 = lapack_sigma(a)
    np.testing.assert_allclose(sig, s0, atol=1e-11 * max(1.0, s0.max()))
    assert np.all(np.diff(sig[0]) <= 1e-12)       # descending
