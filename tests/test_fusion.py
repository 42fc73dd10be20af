"""Cycle-fused chase super-steps (DESIGN.md §9).

Covers the fused stage-2 stack end to end:

  1. the generalized wavefront schedule: every (sweep, local cycle) executes
     exactly once at any fuse depth, and the super-cycle count matches the
     closed form (window disjointness itself is asserted exhaustively next
     to the K=1 proof in tests/test_batched.py);
  2. fused-vs-unfused equivalence of the stage output AND the reflector
     tape — same reflectors in the same per-sweep order — for
     K in {1, 2, 4} x both backends x batched/unbatched x tape on/off;
  3. full-SVD equivalence: sigma bit-identical, U/V^T within fp64 noise,
     for fused configs through the public ``svd_batched`` surface;
  4. the VMEM performance model: monotonicity in fuse depth, the K=1
     fallback, and ``PipelineConfig`` fuse resolution;
  5. a hypothesis-randomized property sweep (skips without the optional
     dependency).
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import band as bandmod
from repro.core import bulge_chasing as bc
from repro.core import svd as svdmod
from repro.core import tuning
from repro.core.tuning import PipelineConfig


def banded_random(n, bw, seed, lead=()):
    rng = np.random.default_rng(seed)
    a = np.triu(rng.standard_normal(lead + (n, n)))
    return np.triu(a) - np.triu(a, bw + 1)


def sweep_cycles(n, b_in, tw):
    """All (sweep, local cycle) pairs of one stage, from the definition."""
    b_out = b_in - tw
    return [(R, j) for R in range(max(n - 1 - b_out, 0))
            for j in range((n - 1 - R - b_out) // b_in + 1)]


def tape_at(tv, tt, n, b_in, tw, fuse, R, j):
    """(v pair, tau pair) of sweep R's local cycle j in a fuse-K tape."""
    sep = tuning.sweep_separation(fuse)
    ts, g, i = sep * R + j // fuse, (j // fuse) // sep, j % fuse
    if fuse == 1:
        return tv[ts, g], tt[ts, g]
    return tv[ts, g, i], tt[ts, g, i]


# ---------------------------------------------------------------------------
# 1. generalized schedule
# ---------------------------------------------------------------------------

SCHED_CASES = [(16, 2, 1), (24, 4, 2), (32, 8, 4), (33, 7, 6), (48, 5, 2),
               (57, 9, 4), (100, 16, 8), (8, 3, 1)]


@pytest.mark.parametrize("fuse", [1, 2, 4, 8])
@pytest.mark.parametrize("n,b_in,tw", SCHED_CASES)
def test_fused_schedule_executes_every_cycle_once(n, b_in, tw, fuse):
    """The super-step schedule is a partition of the sequential cycle list:
    each (R, j) appears in exactly one (super-cycle, slot, fused index)."""
    nsweeps, total, G = bc.stage_schedule(n, b_in, tw, fuse)
    expected = sweep_cycles(n, b_in, tw)
    assert nsweeps == max(n - 1 - (b_in - tw), 0)
    seen = []
    g = np.arange(G)
    for t in range(total):
        R, j, p, active, is_first = bc.chase_cycle_indices(t, g, n, b_in, tw,
                                                           fuse)
        R, j, p = map(np.asarray, (R, j, p))
        for s in range(G):
            if not np.asarray(active)[s]:
                continue
            assert bool(np.asarray(is_first)[s]) == (j[s] == 0)
            for i in range(fuse):
                if p[s] + i * b_in <= n - 1:
                    seen.append((int(R[s]), int(j[s]) + i))
    assert sorted(seen) == expected, (n, b_in, tw, fuse)
    assert len(seen) == len(set(seen))


@pytest.mark.parametrize("fuse", [2, 4, 8])
def test_fused_schedule_shrinks_supercycles_and_slots(fuse):
    n, b_in, tw = 1024, 32, 8
    _, t1, g1 = bc.stage_schedule(n, b_in, tw, 1)
    _, tk, gk = bc.stage_schedule(n, b_in, tw, fuse)
    assert tk < t1                      # fewer kernel launches
    assert gk <= g1                     # no dead wavefront slots
    sep = tuning.sweep_separation(fuse)
    nsweeps = n - 1 - (b_in - tw)
    jmax_last = (n - 1 - (nsweeps - 1) - (b_in - tw)) // b_in
    assert tk == sep * (nsweeps - 1) + -(-(jmax_last + 1) // fuse)


# ---------------------------------------------------------------------------
# 2. fused == unfused: stage output and reflector tape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("tape", [False, True])
def test_fused_stage_matches_unfused(backend, batched, tape):
    n, bw, tw = 26, 5, 2
    lead = (3,) if batched else ()
    mats = banded_random(n, bw, seed=7, lead=lead)
    packed = bandmod.pack(jnp.asarray(mats), bw, tw)
    kw = dict(n=n, b_in=bw, tw=tw, backend=backend)
    if tape:
        base, v1, t1 = bc.reduce_stage_packed(packed, tape=True, **kw)
    else:
        # values only: the streamed stage itself, which the resident path
        # (tests/test_resident_stage.py) would otherwise replace for 32-bit
        # Pallas data, so the K = 1 and super-step kernels stay covered
        base = bc._reduce_stage_streamed(packed, **kw)
    for K in (1, 2, 4):
        if tape:
            out, vK, tK = bc.reduce_stage_packed(packed, tape=True, fuse=K,
                                                 **kw)
        else:
            out = bc._reduce_stage_streamed(packed, fuse=K, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=0, atol=1e-12)
        if not tape:
            continue
        # same reflectors in the same per-sweep order
        v1n, t1n = np.asarray(v1), np.asarray(t1)
        vKn, tKn = np.asarray(vK), np.asarray(tK)
        if not batched:
            v1n, t1n, vKn, tKn = (x[None] for x in (v1n, t1n, vKn, tKn))
        for R, j in sweep_cycles(n, bw, tw):
            for b in range(v1n.shape[0]):
                va, ta = tape_at(v1n[b], t1n[b], n, bw, tw, 1, R, j)
                vb, tb = tape_at(vKn[b], tKn[b], n, bw, tw, K, R, j)
                np.testing.assert_allclose(vb, va, rtol=0, atol=1e-12)
                np.testing.assert_allclose(tb, ta, rtol=0, atol=1e-12)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_fused_bidiagonalize_matches(backend):
    """Full bw -> 1 reduction (multi-stage plan) is fuse-invariant."""
    n, bw, tw = 30, 6, 3
    a = jnp.asarray(banded_random(n, bw, seed=1))
    d0, e0 = bc.bidiagonalize(a, bw=bw, tw=tw, backend=backend)
    for K in (2, 4):
        dK, eK = bc.bidiagonalize(a, bw=bw, tw=tw, backend=backend, fuse=K)
        np.testing.assert_allclose(np.asarray(dK), np.asarray(d0),
                                   rtol=0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(eK), np.asarray(e0),
                                   rtol=0, atol=1e-12)


# ---------------------------------------------------------------------------
# 3. full SVD through the public surface
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_fused_full_svd_matches(backend):
    B, n, bw, tw = 2, 20, 4, 2
    mats = np.random.default_rng(5).standard_normal((B, n, n))
    cfg1 = PipelineConfig.resolve(bw=bw, tw=tw, backend=backend,
                                  dtype=np.float64, n=n)
    u1, s1, vt1 = svdmod.svd_batched(jnp.asarray(mats), config=cfg1,
                                     compute_uv=True)
    for K in (2, 4):
        cfgK = dataclasses.replace(cfg1, fuse=K)
        uK, sK, vtK = svdmod.svd_batched(jnp.asarray(mats), config=cfgK,
                                         compute_uv=True)
        # every cycle applies the same reflector to the same values, so the
        # spectra agree to the last few ulps (bit-identity across fuse
        # depths is not promised — they are different compiled programs)
        np.testing.assert_allclose(np.asarray(sK), np.asarray(s1),
                                   rtol=0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(uK), np.asarray(u1),
                                   rtol=0, atol=1e-12)
        np.testing.assert_allclose(np.asarray(vtK), np.asarray(vt1),
                                   rtol=0, atol=1e-12)
        # and the reconstruction holds on its own
        recon = np.einsum("bij,bj,bjk->bik", np.asarray(uK), np.asarray(sK),
                          np.asarray(vtK))
        assert np.abs(recon - mats).max() < 1e-10 * np.asarray(sK).max()


def test_fused_values_only_matches_batched_surface():
    B, n, bw = 3, 24, 4
    mats = np.random.default_rng(9).standard_normal((B, n, n))
    cfg1 = PipelineConfig.resolve(bw=bw, tw=2, backend="ref",
                                  dtype=np.float64, n=n)
    s1 = svdmod.svd_batched(jnp.asarray(mats), config=cfg1)
    s4 = svdmod.svd_batched(jnp.asarray(mats),
                            config=dataclasses.replace(cfg1, fuse=4))
    np.testing.assert_allclose(np.asarray(s4), np.asarray(s1),
                               rtol=0, atol=1e-12)


def test_serve_engine_forwards_fuse():
    """The serve layer must run bucket flushes at the configured fuse depth
    (regression: _cfg_for used to rebuild bucket configs without fuse)."""
    from repro.serve import SVDEngine, SVDRequest

    eng = SVDEngine(PipelineConfig.resolve(bw=4, tw=2, backend="ref",
                                           dtype=np.float64, max_batch=4,
                                           fuse=4))
    mats = np.random.default_rng(6).standard_normal((3, 20, 20))
    for i, m in enumerate(mats):
        eng.submit(SVDRequest(uid=i, matrix=m, bw=4))
    assert eng._cfg_for(next(iter(eng.buckets))).fuse == 4
    for r in eng.run():
        s0 = np.linalg.svd(mats[r.uid], compute_uv=False)
        np.testing.assert_allclose(r.sigma, s0, atol=1e-10 * s0[0])


# ---------------------------------------------------------------------------
# 4. VMEM performance model + config resolution
# ---------------------------------------------------------------------------

def test_vmem_model_monotone_in_fuse():
    for b_in, tw in [(32, 8), (64, 16), (8, 3), (2, 1)]:
        sizes = [tuning.vmem_working_set_bytes(b_in, tw, jnp.float32, fuse=k)
                 for k in range(1, 17)]
        assert all(a < b for a, b in zip(sizes, sizes[1:])), (b_in, tw)
        # the tape adds output blocks on top, never subtracts
        taped = [tuning.vmem_working_set_bytes(b_in, tw, jnp.float32, fuse=k,
                                               tape=True)
                 for k in range(1, 17)]
        assert all(t > s for s, t in zip(sizes, taped))
    # wider precision costs more VMEM for the same window
    assert (tuning.vmem_working_set_bytes(32, 8, jnp.float64, fuse=4) >
            tuning.vmem_working_set_bytes(32, 8, jnp.float32, fuse=4))


def test_default_fuse_depth_budget_and_fallback():
    # a tiny budget always falls back to K = 1 (the pre-rolled-window path)
    assert tuning.default_fuse_depth(32, 8, jnp.float32, budget_bytes=1) == 1
    # a huge budget saturates the cap
    assert tuning.default_fuse_depth(32, 8, jnp.float32,
                                     budget_bytes=1 << 40, cap=8) == 8
    # the chosen depth actually fits, and depth+1 would not
    for b_in, tw, budget in [(32, 8, 200_000), (64, 16, 600_000),
                             (128, 32, 400_000)]:
        k = tuning.default_fuse_depth(b_in, tw, jnp.float32,
                                      budget_bytes=budget, cap=16)
        assert tuning.vmem_working_set_bytes(b_in, tw, jnp.float32,
                                             fuse=k) <= budget or k == 1
        if k < 16:
            assert tuning.vmem_working_set_bytes(
                b_in, tw, jnp.float32, fuse=k + 1) > budget
    # monotone: a bigger band never earns a deeper default fuse
    ks = [tuning.default_fuse_depth(b, b // 4, jnp.float32, cap=16)
          for b in (16, 32, 64, 128, 256)]
    assert all(a >= b for a, b in zip(ks, ks[1:]))


def test_pipeline_config_fuse_resolution():
    cfg = PipelineConfig.resolve(bw=16, tw=8, backend="ref",
                                 dtype=np.float64)
    assert cfg.fuse == 1                       # conservative default
    auto = PipelineConfig.resolve(bw=16, tw=8, backend="ref",
                                  dtype=np.float64, fuse=None)
    assert auto.fuse == tuning.default_fuse_depth(16, 8, jnp.float64)
    assert PipelineConfig.resolve(bw=16, backend="ref", fuse=0).fuse == 1
    # fuse is part of the kernel cache key (it changes the traced pipeline)
    assert cfg.kernel() != dataclasses.replace(cfg, fuse=4).kernel()


def test_tight_fused_wavefront_bound_is_sufficient():
    """max_concurrent_sweeps(fuse, tw) >= every slot index the schedule
    ever populates (the tight duration-based bound, not the stride one)."""
    for n, b_in, tw in SCHED_CASES:
        for fuse in (2, 4, 8):
            G = tuning.max_concurrent_sweeps(n, b_in, fuse, tw)
            sep = tuning.sweep_separation(fuse)
            jmax0 = max((n - 1 - (b_in - tw)) // b_in, 0)
            dur0 = -(-(jmax0 + 1) // fuse)
            assert G == max(1, (dur0 - 1) // sep + 1)
            # exhaustive: the hosting rule never needs a slot >= G
            nsweeps, total, _ = bc.stage_schedule(n, b_in, tw, fuse)
            for t in range(total):
                for R in range(nsweeps):
                    js = t - sep * R
                    if 0 <= js < dur0 and R + (b_in - tw) + js * fuse * b_in <= n - 1:
                        assert js // sep < G, (n, b_in, tw, fuse, t, R)


# ---------------------------------------------------------------------------
# 5. hypothesis-randomized property sweep
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(8, 36), st.integers(2, 7), st.data(),
       st.sampled_from([2, 3, 4, 8]), st.booleans())
def test_fused_stage_matches_unfused_randomized(n, bw, data, fuse, batched):
    tw = data.draw(st.integers(1, bw - 1), label="tw")
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    lead = (2,) if batched else ()
    mats = banded_random(n, bw, seed=seed, lead=lead)
    packed = bandmod.pack(jnp.asarray(mats), bw, tw)
    base = bc._reduce_stage_streamed(packed, n=n, b_in=bw, tw=tw,
                                     backend="ref")
    out = bc._reduce_stage_streamed(packed, n=n, b_in=bw, tw=tw,
                                    backend="ref", fuse=fuse)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=0, atol=1e-12)
