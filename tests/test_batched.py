"""Batch-native pipeline tests: scheduling invariants, (B, ...) equivalence
against the sequential dense oracle and the per-matrix path, the unified
PipelineConfig/backend-registry layer, and the serve-layer bucketed path.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import band as bandmod
from repro.core import bidiag_svd
from repro.core import bulge_chasing as bc
from repro.core import tuning
from repro.core import svd as svdmod
from repro.core.stage1 import band_reduce
from repro.core.tuning import PipelineConfig
from repro.kernels import ops
from repro.serve import SVDEngine, SVDRequest


def banded_random(n, bw, seed, dtype=np.float64):
    rng = np.random.default_rng(seed)
    a = np.triu(rng.standard_normal((n, n)))
    return (np.triu(a) - np.triu(a, bw + 1)).astype(dtype)


# ---------------------------------------------------------------------------
# Scheduling invariants (paper §III-A dependency analysis, deterministic)
# ---------------------------------------------------------------------------

# (n, b_in, tw) stage shapes: small mixed shapes; the paper's n = 1024,
# b_in = 32 at tw in {31, 16, 8, 1}; tw = b_in - 1 and tw = 1 at several
# b_in; n <= b_in + 1 (no sweep, or sweeps of one cycle); odd n; and the
# stages of the f32 default plans of bw = 64 and bw = 128 (tw = 32).
SCHED_CASES = [(16, 2, 1), (24, 4, 2), (32, 8, 4), (33, 7, 6), (48, 5, 2),
               (57, 9, 4), (100, 16, 8), (8, 3, 1), (200, 32, 16),
               (1024, 32, 31), (1024, 32, 16), (1024, 32, 8), (1024, 32, 1),
               (40, 4, 3), (40, 4, 1), (64, 12, 11), (64, 12, 1),
               (90, 20, 19), (90, 20, 1),
               (5, 4, 3), (4, 4, 2), (3, 6, 5), (2, 2, 1), (129, 128, 127),
               (37, 7, 6), (101, 10, 3), (63, 5, 4), (257, 64, 63),
               (1024, 64, 32), (1024, 128, 32), (1024, 96, 32),
               (512, 128, 32)]


def sweep_cycles(n, b_in, tw):
    """All (sweep, local cycle) pairs of one stage, from the definition."""
    b_out = b_in - tw
    return [(R, j) for R in range(max(n - 1 - b_out, 0))
            for j in range((n - 1 - R - b_out) // b_in + 1)]


@pytest.mark.parametrize("n,b_in,tw", SCHED_CASES)
def test_stage_schedule_executes_every_cycle_once(n, b_in, tw):
    """The wavefront schedule is a partition of the sequential cycle list:
    each (R, j) runs in exactly one (global cycle, slot), the sweep's first
    cycle is flagged, and the last global cycle still has work — the
    property the resident kernel, the streamed stage, the replay and the
    sharded stage all loop on."""
    nsweeps, total, G = bc.stage_schedule(n, b_in, tw)
    expected = sweep_cycles(n, b_in, tw)
    assert nsweeps == max(n - 1 - (b_in - tw), 0)
    seen = []
    g = np.arange(G)
    for t in range(total):
        R, j, _, active, is_first = map(
            np.asarray, bc.chase_cycle_indices(t, g, n, b_in, tw))
        assert active.any() or t < total - 1      # no idle trailing cycle
        assert (is_first == (j == 0))[active].all()
        seen += [(int(r), int(jj)) for r, jj in zip(R[active], j[active])]
    assert sorted(seen) == expected, (n, b_in, tw)
    assert len(seen) == len(set(seen))


@pytest.mark.parametrize("n,b_in,tw", SCHED_CASES)
def test_wavefront_windows_pairwise_disjoint(n, b_in, tw):
    """Every global cycle: all active slots own pairwise-disjoint windows
    (pivot stride >= window width W), so the fused scatter is race-free."""
    nsweeps, total, G = bc.stage_schedule(n, b_in, tw)
    if nsweeps == 0:
        return
    W = b_in + tw + 1
    g = np.arange(G)
    for t in range(total):
        _, _, p, active, _ = bc.chase_cycle_indices(t, g, n, b_in, tw)
        ps = np.sort(np.asarray(p)[np.asarray(active)])
        if len(ps) > 1:
            assert (np.diff(ps) >= W).all(), (t, ps, W)


@pytest.mark.parametrize("n", [8, 16, 33, 57, 100, 200])
@pytest.mark.parametrize("b_in", [2, 4, 8, 16])
def test_stage_schedule_concurrency_matches_tuning(n, b_in):
    """stage_schedule's wavefront width == tuning.max_concurrent_sweeps."""
    for tw in {1, max(1, b_in // 2), b_in - 1}:
        if tw < 1:
            continue
        _, _, conc = bc.stage_schedule(n, b_in, tw)
        assert conc == tuning.max_concurrent_sweeps(n, b_in)


def test_stage_plan_is_tw_schedule():
    for bw in range(2, 40):
        for tw in (1, 3, 8, 31):
            assert list(tuning.stage_plan(bw, tw)) == bc.tw_schedule(bw, tw)


# ---------------------------------------------------------------------------
# Batched band storage
# ---------------------------------------------------------------------------

def test_batched_pack_unpack_roundtrip():
    n, bw, tw, B = 20, 5, 2, 3
    mats = np.stack([banded_random(n, bw, s) for s in range(B)])
    packed = bandmod.pack(jnp.asarray(mats), bw, tw)
    assert packed.shape == (B, bandmod.band_height(bw, tw), n)
    back = np.asarray(bandmod.unpack(packed, bw, tw, n))
    np.testing.assert_array_equal(back, mats)
    # batched path == per-matrix path, bit-exact
    for b in range(B):
        one = bandmod.pack(jnp.asarray(mats[b]), bw, tw)
        np.testing.assert_array_equal(np.asarray(packed[b]), np.asarray(one))
    widths = np.asarray(bandmod.bandwidth_of(jnp.asarray(mats)))
    assert widths.shape == (B,) and (widths <= bw).all()


# ---------------------------------------------------------------------------
# Batched wavefront stage vs looped vs sequential dense oracle
# ---------------------------------------------------------------------------

def test_batched_stage_equals_looped_and_oracle():
    n, bw, tw, B = 33, 7, 3, 5
    mats = np.stack([banded_random(n, bw, 10 + s) for s in range(B)])
    packed = bandmod.pack(jnp.asarray(mats), bw, tw)
    out = bc.reduce_stage_packed(packed, n=n, b_in=bw, tw=tw, backend="ref")
    for b in range(B):
        looped = bc.reduce_stage_packed(packed[b], n=n, b_in=bw, tw=tw,
                                        backend="ref")
        np.testing.assert_array_equal(np.asarray(out[b]), np.asarray(looped))
        ref = bc.reduce_stage_dense_ref(mats[b], bw, tw)
        dense = np.asarray(bandmod.unpack(out[b], bw, tw, n))
        np.testing.assert_allclose(dense, ref, atol=1e-11)


def test_batched_bidiagonalize_matches_dense_oracle():
    n, bw, tw, B = 28, 6, 2, 4
    mats = np.stack([banded_random(n, bw, 20 + s) for s in range(B)])
    d, e = bc.bidiagonalize(jnp.asarray(mats), bw=bw, tw=tw, backend="ref")
    assert d.shape == (B, n) and e.shape == (B, n)
    for b in range(B):
        dref, eref, _ = bc.bidiagonalize_dense_ref(mats[b], bw, tw)
        np.testing.assert_allclose(np.asarray(d[b]), dref, atol=1e-10)
        np.testing.assert_allclose(np.asarray(e[b])[1:], eref, atol=1e-10)


def test_batched_band_reduce_structure_and_sigma():
    n, nb, B = 40, 8, 3
    mats = np.random.default_rng(1).standard_normal((B, n, n))
    out = np.asarray(band_reduce(jnp.asarray(mats), nb=nb))
    assert out.shape == (B, n, n)
    for b in range(B):
        assert np.abs(np.tril(out[b], -1)).max() == 0.0
        assert np.abs(np.triu(out[b], nb + 1)).max() == 0.0
        s0 = np.linalg.svd(mats[b], compute_uv=False)
        s1 = np.linalg.svd(out[b], compute_uv=False)
        np.testing.assert_allclose(s1, s0, atol=1e-12 * s0[0])


def test_batched_bidiag_singular_values():
    n, B = 24, 4
    rng = np.random.default_rng(2)
    d = rng.standard_normal((B, n))
    e = rng.standard_normal((B, n))
    e[:, 0] = 0.0
    sig = np.asarray(bidiag_svd.bidiag_singular_values(jnp.asarray(d),
                                                       jnp.asarray(e)))
    assert sig.shape == (B, n)
    for b in range(B):
        Bmat = np.diag(d[b]) + np.diag(e[b][1:], 1)
        s_ref = np.linalg.svd(Bmat, compute_uv=False)
        np.testing.assert_allclose(sig[b], s_ref, atol=1e-12 * max(1, s_ref[0]))


# ---------------------------------------------------------------------------
# Acceptance sweep: batched == per-matrix, B in {1, 3, 8}, fp32/fp64,
# two (n, bw) shapes, ref + pallas(interpret) backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.float64, 1e-10)])
@pytest.mark.parametrize("n,bw", [(24, 4), (32, 8)])
def test_batched_matches_per_matrix(n, bw, dtype, tol, backend):
    tw = max(1, bw // 2)
    rng = np.random.default_rng(n * 10 + bw)
    mats = rng.standard_normal((8, n, n)).astype(np.float64)
    stacked = jnp.asarray(mats, dtype)
    per = np.stack([
        np.asarray(svdmod.singular_values(stacked[b], bw=bw, tw=tw,
                                          backend=backend), np.float64)
        for b in range(8)])
    smax = max(1.0, per.max())
    # fp64 oracle: the batched path must stay within oracle tolerance of LAPACK
    if dtype == jnp.float64:
        oracle = np.stack([np.linalg.svd(mats[b], compute_uv=False)
                           for b in range(8)])
        np.testing.assert_allclose(per, oracle, atol=1e-10 * smax)
    for B in (1, 3, 8):
        sig = np.asarray(
            svdmod.batched_singular_values(stacked[:B], bw=bw, tw=tw,
                                           backend=backend), np.float64)
        assert sig.shape == (B, n)
        np.testing.assert_allclose(sig, per[:B], atol=tol * smax)


def test_svd_batched_config_entry_point():
    n, bw, B = 24, 4, 3
    mats = np.random.default_rng(3).standard_normal((B, n, n))
    cfg = PipelineConfig.resolve(bw=bw, tw=2, backend="ref",
                                 dtype=np.float64, n=n)
    sig = np.asarray(svdmod.svd_batched(jnp.asarray(mats), config=cfg))
    legacy = np.asarray(svdmod.batched_singular_values(
        jnp.asarray(mats), bw=bw, tw=2, backend="ref"))
    np.testing.assert_array_equal(sig, legacy)


# ---------------------------------------------------------------------------
# PipelineConfig + backend registry
# ---------------------------------------------------------------------------

def test_multi_leading_batch_axes():
    """The (..., n, n) contract holds beyond one batch axis (e.g. stacked
    scan-layer weights (L, B, n, n))."""
    mats = np.random.default_rng(5).standard_normal((2, 3, 16, 16))
    sig = np.asarray(svdmod.singular_values(jnp.asarray(mats), bw=4, tw=2,
                                            backend="ref"))
    assert sig.shape == (2, 3, 16)
    for i in range(2):
        for j in range(3):
            s0 = np.linalg.svd(mats[i, j], compute_uv=False)
            np.testing.assert_allclose(sig[i, j], s0, atol=1e-10 * s0[0])


def test_config_conflicts_raise():
    cfg = PipelineConfig.resolve(bw=8, tw=4, backend="ref", dtype=np.float64)
    mats = jnp.zeros((1, 16, 16), jnp.float64)
    with pytest.raises(ValueError, match="conflicts"):
        svdmod.batched_singular_values(mats, bw=16, config=cfg)
    with pytest.raises(ValueError, match="conflicts"):
        svdmod.batched_singular_values(mats, tw=2, config=cfg)
    with pytest.raises(ValueError, match="conflicts"):
        svdmod.batched_singular_values(mats, backend="pallas", config=cfg)
    with pytest.raises(ValueError, match="conflicts"):
        svdmod.batched_singular_values(mats.astype(jnp.float32), config=cfg)
    # matching kwargs are fine
    svdmod.batched_singular_values(mats, bw=8, tw=4, backend="ref", config=cfg)


def test_config_cache_key_ignores_max_batch():
    """Configs differing only in serve-side bucket sizing must not recompile
    the numeric pipeline (kernel() normalization)."""
    import dataclasses
    cfg1 = PipelineConfig.resolve(bw=4, tw=2, backend="ref", dtype=np.float64)
    cfg2 = dataclasses.replace(cfg1, max_batch=cfg1.max_batch + 7)
    assert cfg1.kernel() == cfg2.kernel()
    mats = jnp.asarray(np.random.default_rng(8).standard_normal((2, 12, 12)))
    s1_ = svdmod.svd_batched(mats, config=cfg1)
    misses0 = svdmod._three_stage._cache_size()
    s2_ = svdmod.svd_batched(mats, config=cfg2)
    assert svdmod._three_stage._cache_size() == misses0   # no new trace
    np.testing.assert_array_equal(np.asarray(s1_), np.asarray(s2_))


def test_stage1_config_backend_routes_pallas():
    """A resolved pallas config drives stage 1 through the WY kernel too —
    bit-exact vs the ref backend, including batched (vmapped pallas_call)."""
    rng = np.random.default_rng(7)
    a = rng.standard_normal((2, 32, 32))
    cfg = PipelineConfig.resolve(bw=8, backend="pallas", interpret=True,
                                 dtype=np.float64)
    b_ref = np.asarray(band_reduce(jnp.asarray(a), nb=8, backend="ref"))
    b_cfg = np.asarray(band_reduce(jnp.asarray(a), nb=8, config=cfg))
    np.testing.assert_array_equal(b_cfg, b_ref)
    # explicit backend kwarg wins over the config
    b_exp = np.asarray(band_reduce(jnp.asarray(a), nb=8, backend="ref",
                                   config=cfg))
    np.testing.assert_array_equal(b_exp, b_ref)


def test_pipeline_config_resolution():
    cfg = PipelineConfig.resolve(bw=16, dtype=jnp.float32)
    assert cfg.backend in ops.backend_names()          # never "auto"
    assert cfg.tw == tuning.default_tilewidth(16, jnp.float32)
    assert cfg.plan == tuning.stage_plan(cfg.bw, cfg.tw)
    assert cfg.dtype == "float32"
    assert hash(cfg) == hash(PipelineConfig.resolve(bw=16, dtype=jnp.float32))
    # per-stage view agrees with the legacy ChaseConfig
    ch = cfg.chase(256)
    assert ch.tw == cfg.tw and ch.b_in == cfg.bw
    # explicit tw is clamped to the band
    assert PipelineConfig.resolve(bw=4, tw=99).tw == 3


def test_registry_resolution_and_errors():
    name, interp = ops.resolve_backend("auto")
    assert name in ops.backend_names()
    assert {"ref", "pallas"} <= set(ops.backend_names())
    with pytest.raises(ValueError):
        ops.resolve_backend("nope")
    with pytest.raises(ValueError):
        ops.chase_cycle(jnp.zeros((1, 8, 6)), jnp.zeros((1,), bool),
                        b_in=3, tw=2, backend="nope")


@pytest.fixture
def on_tpu(monkeypatch):
    """Resolve backends as on a TPU host (resolution only: nothing runs)."""
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")


def test_float64_on_tpu_resolves_to_ref(on_tpu):
    """Pallas on TPU has no float64: "auto" picks ref for 64-bit data by
    platform, and an explicit Pallas backend refuses it at resolution."""
    assert ops.resolve_backend("auto", dtype=jnp.float32) == ("pallas", False)
    assert ops.resolve_backend("auto", dtype=np.float64)[0] == "ref"
    cfg = PipelineConfig.resolve(bw=16, dtype=np.float64, n=64)
    assert cfg.backend == "ref" and cfg.dtype == "float64"
    for backend in ("pallas", "fused_small"):
        with pytest.raises(ValueError, match="float64"):
            PipelineConfig.resolve(bw=16, backend=backend, dtype=np.float64)
    # interpret mode runs the kernel body as XLA ops, which have float64
    assert PipelineConfig.resolve(bw=16, backend="pallas", interpret=True,
                                  dtype=np.float64).backend == "pallas"


def test_engine_float64_bucket_on_tpu_takes_ref_tier(on_tpu):
    """An "auto" engine on a TPU resolves float64 buckets to the ref
    backend up front, never by catching a failed Pallas compile."""
    eng = SVDEngine(backend="auto")
    f32 = eng._cfg_for((64, 8, "float32", False, False))
    f64 = eng._cfg_for((64, 8, "float64", False, False))
    big = eng._cfg_for((512, 16, "float64", False, True))
    assert f32.backend == "fused_small" and not f32.interpret
    assert f64.backend == "ref" and big.backend == "ref"
    tiers = eng.metrics.snapshot()["bucket_tiers"]
    assert {row["backend"] for row in tiers.values()} == {"fused_small",
                                                          "ref"}
    # an engine pinned to Pallas fails such a bucket with a clear error
    pinned = SVDEngine(PipelineConfig.resolve(bw=8, dtype=jnp.float32))
    with pytest.raises(ValueError, match="float64"):
        pinned._cfg_for((512, 8, "float64", False, False))


def test_default_bucket_batch_fills_wavefront():
    for n, bw in [(24, 4), (32, 8), (256, 32), (4096, 32)]:
        B = tuning.default_bucket_batch(n, bw)
        assert 1 <= B <= 64
        # batching must reach the occupancy target a single matrix may miss
        assert B * tuning.max_concurrent_sweeps(n, bw) >= 16 or B == 64
    # big matrices already saturate: no batching needed
    assert tuning.default_bucket_batch(100_000, 32) == 1


# ---------------------------------------------------------------------------
# Serve layer: bucketed path == direct batched calls
# ---------------------------------------------------------------------------

def test_serve_bucketed_matches_direct_batched():
    rng = np.random.default_rng(4)
    small = rng.standard_normal((5, 24, 24))           # bucket (24, 4, f64)
    large = rng.standard_normal((3, 32, 32))           # bucket (32, 8, f64)
    eng = SVDEngine(PipelineConfig.resolve(bw=4, tw=2, backend="ref",
                                           dtype=np.float64, max_batch=4))
    uid = 0
    for m in small:
        eng.submit(SVDRequest(uid=uid, matrix=m, bw=4)); uid += 1
    for m in large:
        eng.submit(SVDRequest(uid=uid, matrix=m, bw=8)); uid += 1
    done = eng.run()
    assert len(done) == 8 and all(r.done for r in done)
    assert eng.calls == 3                   # ceil(5/4) + ceil(3/4) flushes
    assert eng.pending() == 0
    by_uid = {r.uid: r for r in done}
    direct_small = np.asarray(svdmod.batched_singular_values(
        jnp.asarray(small), bw=4, tw=2, backend="ref"))
    direct_large = np.asarray(svdmod.batched_singular_values(
        jnp.asarray(large), bw=8, tw=2, backend="ref"))
    for i in range(5):
        np.testing.assert_allclose(by_uid[i].sigma, direct_small[i],
                                   rtol=0, atol=1e-12)
    for i in range(3):
        np.testing.assert_allclose(by_uid[5 + i].sigma, direct_large[i],
                                   rtol=0, atol=1e-12)
    # and against the fp64 oracle
    for i in range(5):
        s0 = np.linalg.svd(small[i], compute_uv=False)
        np.testing.assert_allclose(by_uid[i].sigma, s0, atol=1e-10 * s0[0])


def test_serve_banded_requests():
    n, bw = 32, 6
    mats = [banded_random(n, bw, 30 + s) for s in range(3)]
    eng = SVDEngine(PipelineConfig.resolve(bw=bw, tw=3, backend="ref",
                                           dtype=np.float64, max_batch=4))
    for i, m in enumerate(mats):
        eng.submit(SVDRequest(uid=i, matrix=m, bw=bw, banded=True))
    done = eng.run()
    assert len(done) == 3
    for r in done:
        s0 = np.linalg.svd(mats[r.uid], compute_uv=False)
        np.testing.assert_allclose(r.sigma, s0, atol=1e-10 * s0[0])
