"""Reflector-tape pipeline tests: full SVD (U, sigma, V^T) through every layer.

Verified against the fp64 dense oracle (``bidiagonalize_dense_ref_uv``) and
first principles:

  1. chase-tape replay reproduces the oracle's transforms (U^T A V bidiagonal,
     matching the packed chase's (d, e));
  2. vector properties of the public surface — reconstruction
     ``||U S V^T - A||``, orthogonality ``||U^T U - I||`` / ``||V^T V - I||``
     — across dtypes, batch shapes, and both backends (ref + pallas
     interpret), with sigma BIT-identical to the values-only path;
  3. stage-3 inverse iteration (``bidiag_svd``) in isolation;
  4. the serve engine's compute_uv buckets;
  5. the n = 1 / bw = 0 degenerate edge (regression, satellite);
  6. hypothesis-randomized property sweep (skips without the optional dep).
"""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bulge_chasing as bc
from repro.core import bidiag_svd as s3
from repro.core import transforms
from repro.core import svd as svdmod
from repro.core.tuning import PipelineConfig


def banded_random(n, bw, seed):
    rng = np.random.default_rng(seed)
    a = np.triu(rng.standard_normal((n, n)))
    return np.triu(a) - np.triu(a, bw + 1)


def check_svd(a, u, s, vt, tol):
    """Reconstruction + orthogonality + descending order, all in fp64."""
    n = a.shape[-1]
    a, u, s, vt = (np.asarray(x, np.float64) for x in (a, u, s, vt))
    scale = max(1.0, float(np.max(s)))
    recon = np.abs(np.einsum("...ij,...j,...jk->...ik", u, s, vt) - a).max()
    eye = np.eye(n)
    uerr = np.abs(np.einsum("...ji,...jk->...ik", u, u) - eye).max()
    verr = np.abs(np.einsum("...ij,...kj->...ik", vt, vt) - eye).max()
    assert recon < tol * scale, ("reconstruction", recon)
    assert uerr < tol, ("U orthogonality", uerr)
    assert verr < tol, ("V orthogonality", verr)
    assert np.all(np.diff(s, axis=-1) <= 1e-12 * scale), "sigma not descending"


# ---------------------------------------------------------------------------
# 1. chase-tape replay == dense oracle transforms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bw,tw", [(36, 6, 2), (24, 5, 3), (33, 7, 6)])
def test_chase_tape_replay_matches_oracle(n, bw, tw):
    a = banded_random(n, bw, seed=n + bw)
    d, e, tapes = bc.bidiagonalize(jnp.asarray(a), bw=bw, tw=tw,
                                   backend="ref", tape=True)
    u, vt = transforms.accumulate_transforms(n, chase_tapes=tapes,
                                             dtype=jnp.float64)
    u, vt = np.asarray(u), np.asarray(vt)
    B = u.T @ a @ vt.T
    np.testing.assert_allclose(np.diag(B), np.asarray(d), atol=1e-11)
    np.testing.assert_allclose(np.diag(B, 1), np.asarray(e)[1:], atol=1e-11)
    off = B - np.diag(np.diag(B)) - np.diag(np.diag(B, 1), 1)
    assert np.abs(off).max() < 1e-11
    assert np.abs(u.T @ u - np.eye(n)).max() < 1e-12
    assert np.abs(vt @ vt.T - np.eye(n)).max() < 1e-12
    # the oracle agrees on the bidiagonal itself
    dref, eref, _, _ = bc.bidiagonalize_dense_ref_uv(a, bw, tw)
    np.testing.assert_allclose(np.abs(np.asarray(d)), np.abs(dref), atol=1e-10)


def test_tape_mode_leaves_band_arithmetic_untouched():
    """(d, e) must be BIT-identical with and without the tape."""
    n, bw, tw = 40, 6, 2
    a = jnp.asarray(banded_random(n, bw, 3))
    d0, e0 = bc.bidiagonalize(a, bw=bw, tw=tw, backend="ref")
    d1, e1, _ = bc.bidiagonalize(a, bw=bw, tw=tw, backend="ref", tape=True)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert np.array_equal(np.asarray(e0), np.asarray(e1))


# ---------------------------------------------------------------------------
# 2. public surface: svd / svd_batched across dtypes, batches, backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float64, 1e-10),
                                       (jnp.float32, 5e-4)])
def test_svd_dense_roundtrip(backend, dtype, tol):
    n, bw, tw = 32, 8, 4
    a = np.random.default_rng(11).standard_normal((n, n))
    aj = jnp.asarray(a, dtype)
    u, s, vt = svdmod.svd(aj, bw=bw, tw=tw, backend=backend)
    check_svd(np.asarray(aj), u, s, vt, tol)
    # sigma bit-identical to the values-only path
    s_only = svdmod.singular_values(aj, bw=bw, tw=tw, backend=backend)
    assert np.array_equal(np.asarray(s), np.asarray(s_only))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_svd_batched_roundtrip(backend):
    B, n, bw, tw = 3, 24, 6, 3
    mats = np.random.default_rng(2).standard_normal((B, n, n))
    cfg = PipelineConfig.resolve(bw=bw, tw=tw, backend=backend,
                                 dtype=np.float64, n=n)
    u, s, vt = svdmod.svd_batched(jnp.asarray(mats), config=cfg,
                                  compute_uv=True)
    check_svd(mats, u, s, vt, 1e-10)
    for b in range(B):
        s0 = np.linalg.svd(mats[b], compute_uv=False)
        np.testing.assert_allclose(np.asarray(s)[b], s0, atol=1e-9 * s0[0])
    # batched sigma bit-identical to the values-only batched path
    s_only = svdmod.svd_batched(jnp.asarray(mats), config=cfg)
    assert np.array_equal(np.asarray(s), np.asarray(s_only))
    # config-default threading: compute_uv=True in the config alone suffices
    import dataclasses
    cfg_uv = dataclasses.replace(cfg, compute_uv=True)
    res = svdmod.svd_batched(jnp.asarray(mats), config=cfg_uv)
    assert isinstance(res, tuple) and len(res) == 3


def test_banded_svd_roundtrip():
    n, bw, tw = 40, 6, 2
    a = banded_random(n, bw, 9)
    u, s, vt = svdmod.banded_svd(jnp.asarray(a), bw=bw, tw=tw, backend="ref")
    check_svd(a, u, s, vt, 1e-10)
    s0 = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s0, atol=1e-9 * s0[0])


# ---------------------------------------------------------------------------
# 3. stage-3 vectors in isolation
# ---------------------------------------------------------------------------

def test_bidiag_svd_stage3():
    n = 24
    rng = np.random.default_rng(4)
    d = rng.standard_normal(n)
    e = np.concatenate([[0.0], rng.standard_normal(n - 1)])
    B = np.diag(d) + np.diag(e[1:], 1)
    u, s, vt = s3.bidiag_svd(jnp.asarray(d), jnp.asarray(e))
    check_svd(B, u, s, vt, 1e-10)
    # values bit-identical to the bisection entry point
    s_only = s3.bidiag_singular_values(jnp.asarray(d), jnp.asarray(e))
    assert np.array_equal(np.asarray(s), np.asarray(s_only))
    # batched stacking vmaps
    ds = jnp.asarray(np.stack([d, 2 * d]))
    es = jnp.asarray(np.stack([e, e]))
    ub, sb, vtb = s3.bidiag_svd(ds, es)
    assert ub.shape == (2, n, n) and sb.shape == (2, n)
    np.testing.assert_allclose(np.asarray(sb)[0], np.asarray(s), atol=0)


def test_svd_degenerate_spectra():
    """Repeated/clustered sigma: inverse iteration alone gives non-orthogonal
    vectors inside a cluster — the stein-style reorthogonalization +
    u = Bv/||Bv|| re-pairing must recover a valid SVD."""
    rng = np.random.default_rng(1)
    q, _ = np.linalg.qr(rng.standard_normal((8, 8)))
    lowrank = rng.standard_normal((8, 3)) @ rng.standard_normal((3, 8))
    cases = [
        ("identity", np.eye(8)),
        ("orthogonal", q),                       # all sigma = 1
        ("repeated", np.diag([3.0, 2.0, 2.0, 1.0])),
        ("near-degenerate", np.diag([1.0, 1.0 + 1e-9, 0.5, 0.3])),
        ("rank-deficient", lowrank),             # sigma = 0 cluster
        ("zero", np.zeros((6, 6))),
    ]
    for name, a in cases:
        n = a.shape[0]
        bw = max(2, n // 4)
        u, s, vt = svdmod.svd(jnp.asarray(a), bw=bw, tw=max(1, bw // 2),
                              backend="ref")
        check_svd(a, u, s, vt, 1e-10)
        s0 = np.linalg.svd(a, compute_uv=False)
        np.testing.assert_allclose(np.asarray(s), s0, atol=1e-9 * max(s0[0], 1),
                                   err_msg=name)


# ---------------------------------------------------------------------------
# 4. serve engine compute_uv buckets
# ---------------------------------------------------------------------------

def test_engine_compute_uv_bucketing():
    from repro.serve.engine import SVDEngine, SVDRequest
    rng = np.random.default_rng(8)
    eng = SVDEngine(PipelineConfig.resolve(bw=6, tw=2, backend="ref",
                                           dtype=np.float64))
    mats = [rng.standard_normal((20, 20)) for _ in range(6)]
    for i, m in enumerate(mats):
        eng.submit(SVDRequest(uid=i, matrix=m, bw=6, compute_uv=(i % 2 == 0)))
    done = eng.run()
    assert len(done) == 6
    for r in done:
        s0 = np.linalg.svd(mats[r.uid], compute_uv=False)
        np.testing.assert_allclose(r.sigma, s0, atol=1e-8 * s0[0])
        if r.compute_uv:
            check_svd(mats[r.uid], r.u, r.sigma, r.vt, 1e-9)
        else:
            assert r.u is None and r.vt is None


# ---------------------------------------------------------------------------
# 5. degenerate edges: n = 1 and bw = 0  (regression, satellite)
# ---------------------------------------------------------------------------

def test_degenerate_n1_and_bw0():
    # gk_offdiag (2n-1,) fast path
    z = s3.gk_offdiag(jnp.asarray([3.0]), jnp.asarray([0.0]))
    assert z.shape == (1,) and float(z[0]) == 3.0
    np.testing.assert_allclose(
        np.asarray(s3.bidiag_singular_values(jnp.asarray([-2.0]),
                                             jnp.asarray([0.0]))), [2.0])
    # singular_values / svd_batched on 1x1 problems
    np.testing.assert_allclose(
        np.asarray(svdmod.singular_values(jnp.asarray([[-4.0]]))), [4.0])
    stack = jnp.asarray(np.array([[[2.0]], [[-5.0]]]))
    np.testing.assert_allclose(np.asarray(svdmod.svd_batched(stack)),
                               [[2.0], [5.0]])
    u, s, vt = svdmod.svd_batched(stack, compute_uv=True)
    np.testing.assert_allclose(
        np.asarray(u) * np.asarray(s)[..., None] * np.asarray(vt),
        np.asarray(stack))
    # bw = 0 resolves to a working (clamped) config
    cfg = PipelineConfig.resolve(bw=0, dtype=np.float64, n=4)
    assert cfg.bw >= 1
    a = np.random.default_rng(0).standard_normal((4, 4))
    s0 = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(
        np.asarray(svdmod.singular_values(jnp.asarray(a), config=cfg)),
        s0, atol=1e-10 * s0[0])
    u4, s4, vt4 = svdmod.svd(jnp.asarray(a), config=cfg)
    check_svd(a, u4, s4, vt4, 1e-10)


# ---------------------------------------------------------------------------
# 6. hypothesis-randomized property sweep (optional dep; skip-shim otherwise)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(8, 40), st.integers(2, 8), st.integers(1, 5),
       st.integers(0, 2**31 - 1))
def test_svd_property_randomized(n, bw, tw, seed):
    bw = min(bw, n - 2)
    if bw < 2:
        return
    tw = min(tw, bw - 1)
    a = np.random.default_rng(seed).standard_normal((n, n))
    u, s, vt = svdmod.svd(jnp.asarray(a), bw=bw, tw=tw, backend="ref")
    check_svd(a, u, s, vt, 1e-9)
    s0 = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s0, atol=1e-9 * max(s0[0], 1))


# ---------------------------------------------------------------------------
# 7. tiny vectors: every recorded reflector is orthogonal (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _graded_band(n, bw, case):
    """A float32 band whose reflectors meet vectors with squared norms near
    float32's underflow: "bottom_edge" scales the last six rows by 2^-62,
    as the trailing rows of a random triangular band become near-singular;
    "tiny" scales the whole band."""
    a = banded_random(n, bw, 3)
    if case == "bottom_edge":
        a[n - 6:] *= 2.0 ** -62
    elif case == "tiny":
        a *= 2.0 ** -62
    return jnp.asarray(a, jnp.float32)


@pytest.mark.parametrize("case", ["plain", "bottom_edge", "tiny"])
@pytest.mark.parametrize("path", ["ref", "streamed", "resident"])
def test_chase_tape_reflectors_are_orthogonal(path, case, monkeypatch):
    """tau * ||v||^2 = 2 for every reflector with tau != 0 on the chase tape,
    to 4 ulps of 2 (the float32 rounding of v's entries, tau and the sum),
    and every entry of such a reflector at a row or column past n - 1 is
    exactly zero, so the replay may drop those rows — on each stage-2 path
    that records a tape: the ref backend, the streamed Pallas stage and the
    band-resident Pallas stage."""
    n, bw, tw = 48, 8, 7
    a = _graded_band(n, bw, case)
    backend = "ref" if path == "ref" else "pallas"
    if path == "streamed":
        monkeypatch.setattr(bc, "stage_path", lambda *_, **__: "streamed")
        monkeypatch.setattr(bc, "reduce_stage_packed",
                            bc._reduce_stage_streamed)
    assert bc.stage_path(a.dtype, n=n, b_in=bw, tw=tw, backend=backend,
                         tape=True) == ("resident" if path == "resident"
                                        else "streamed")
    _, _, tapes = bc.bidiagonalize(a, bw=bw, tw=tw, backend=backend,
                                   tape=True)
    eps = float(np.finfo(np.float32).eps)
    for tape in tapes:
        v = np.asarray(tape.v, np.float64)
        tau = np.asarray(tape.tau, np.float64)
        live = tau != 0
        assert live.any()
        drift = np.abs(tau * np.sum(v * v, axis=-1) - 2.0)[live]
        assert drift.max() <= 8 * eps, (case, drift.max())
        T, G = v.shape[:2]
        t, g = np.meshgrid(np.arange(T), np.arange(G), indexing="ij")
        _, _, p, _, _ = bc.chase_cycle_indices(t, g, n, tape.b_in, tape.tw)
        index = p[..., None] + np.arange(tape.tw + 1)       # (T, G, k)
        past = np.broadcast_to((index > n - 1)[:, :, None], v.shape)
        assert np.all(v[past & live[..., None]] == 0)


def _n512_band():
    """The band of ``bench/loops/closed_single.py::make_pool`` at n = 512,
    bw = 32 from PRNGKey(0): its left reflector at pivot 507 meets a column
    of entries near 1e-20 (the trailing rows of a random triangular band
    are near-singular)."""
    import jax
    n, bw = 512, 32
    a = jax.random.normal(jax.random.PRNGKey(0), (1, n, n), jnp.float32)
    i = jnp.arange(n)
    keep = (i[None, :] >= i[:, None]) & (i[None, :] <= i[:, None] + bw)
    return jnp.where(keep, a, 0)[0]


def _full_svd_case(case):
    """(A, U, sigma, V^T) of one float32 full SVD on the default
    configuration, through the entry point the case names."""
    if case == "banded_n512":
        a = _n512_band()
        return (a,) + svdmod.banded_svd(a, bw=32)
    if case == "fused_n48_tiny":
        a = jnp.asarray(_graded_band(48, 8, "tiny"))
        return (a,) + svdmod.svd(a, bw=8, backend="fused_small")
    a = np.random.default_rng(5).standard_normal((264, 264)) * 2.0 ** -62
    if case == "dense_n264_tiny":
        a = jnp.asarray(a, jnp.float32)
        return (a,) + svdmod.svd(a)
    from repro.serve.engine import SVDEngine, SVDRequest
    eng = SVDEngine(PipelineConfig.resolve(bw=32, dtype=np.float32))
    eng.submit(SVDRequest(uid=0, matrix=a.astype(np.float32), bw=32,
                          compute_uv=True))
    (r,) = eng.run()
    return a.astype(np.float32), r.u, r.sigma, r.vt


@pytest.mark.parametrize("case", ["banded_n512", "dense_n264_tiny",
                                  "served_n264_tiny", "fused_n48_tiny"])
def test_full_svd_factors_are_orthogonal(case):
    """Float32 full SVDs whose reductions meet tiny vectors: three on the
    staged path (n > 256: chase tape, and stage 1's for a dense input) and
    one on the fused tier.  Limits: sigma within 2e-5 of float64 LAPACK's,
    normwise, as the benchmark's (the float32 chase reads ~3e-7); residual
    and orthogonality (Frobenius, over sqrt(n)) within 1e-4, about fifty
    times what these float32 reductions read (~2e-6) and two hundred times
    below the 0.022 that U read at n = 512 when a reflector over
    underflowed squares was not orthogonal."""
    a, u, s, vt = _full_svd_case(case)
    a, u, s, vt = (np.asarray(x, np.float64) for x in (a, u, s, vt))
    n = a.shape[-1]
    ref = np.linalg.svd(a, compute_uv=False)
    assert np.max(np.abs(s - ref)) / ref[0] <= 2e-5
    assert np.linalg.norm(a - (u * s) @ vt) / np.linalg.norm(a) <= 1e-4
    eye = np.eye(n)
    assert np.linalg.norm(u.T @ u - eye) / np.sqrt(n) <= 1e-4
    assert np.linalg.norm(vt @ vt.T - eye) / np.sqrt(n) <= 1e-4
