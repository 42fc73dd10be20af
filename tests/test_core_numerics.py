"""Unit + property tests for the core numerics: Householder reflectors,
packed band storage, and the Golub-Kahan stage-3 bisection."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import band as bandmod
from repro.core import householder as hh
from repro.core.bidiag_svd import bidiag_singular_values, sturm_count, gk_offdiag


# ---------------------------------------------------------------------------
# Householder
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(2, 24), st.integers(0, 2**31 - 1))
def test_reflector_annihilates(L, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(L))
    v, tau, beta = hh.make_reflector(x)
    y = hh.apply_left(v, tau, x[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(beta), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(y[1:]), 0, atol=1e-12 * float(jnp.abs(x).max()))
    # norm preserved (orthogonality)
    np.testing.assert_allclose(float(jnp.linalg.norm(y)), float(jnp.linalg.norm(x)),
                               rtol=1e-12)
    assert float(v[0]) == 1.0


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 16))
def test_reflector_zero_tail_is_identity(L):
    x = jnp.zeros(L).at[0].set(3.5)
    v, tau, beta = hh.make_reflector(x)
    assert float(tau) == 0.0 and float(beta) == 3.5


def test_reflector_matrix_orthogonal():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(9))
    v, tau, _ = hh.make_reflector(x)
    q = hh.reflector_matrix(v, tau)
    np.testing.assert_allclose(np.asarray(q @ q.T), np.eye(9), atol=1e-12)


def test_reflector_bf16_tolerates_low_precision():
    x = jnp.asarray(np.random.default_rng(1).standard_normal(16), jnp.bfloat16)
    v, tau, beta = hh.make_reflector(x)
    y = hh.apply_left(v, tau, x[:, None])[:, 0]
    assert abs(float(y[0]) - float(beta)) < 0.05
    assert float(jnp.max(jnp.abs(y[1:]))) < 0.05


# ---------------------------------------------------------------------------
# Band storage
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(4, 40), st.integers(1, 8), st.integers(0, 4),
       st.integers(0, 2**31 - 1))
def test_pack_unpack_roundtrip(n, bw, tw, seed):
    bw = min(bw, n - 1)
    rng = np.random.default_rng(seed)
    a = np.triu(rng.standard_normal((n, n)))
    a = np.triu(a) - np.triu(a, bw + 1)          # upper banded, bandwidth bw
    packed = bandmod.pack(jnp.asarray(a), bw, tw)
    assert packed.shape == (bandmod.band_height(bw, tw), n)
    back = bandmod.unpack(packed, bw, tw, n)
    np.testing.assert_allclose(np.asarray(back), a, atol=0)


def test_bandwidth_of():
    a = np.zeros((8, 8))
    a[0, 3] = 1.0
    assert int(bandmod.bandwidth_of(jnp.asarray(a))) == 3


def test_band_diag_helpers():
    n, bw, tw = 10, 3, 1
    a = np.triu(np.random.default_rng(2).standard_normal((n, n)))
    a = np.triu(a) - np.triu(a, bw + 1)
    packed = bandmod.pack(jnp.asarray(a), bw, tw)
    d = bandmod.band_extract_diag(packed, tw, 0, n)
    e = bandmod.band_extract_diag(packed, tw, 1, n)
    np.testing.assert_allclose(np.asarray(d), np.diag(a))
    np.testing.assert_allclose(np.asarray(e)[1:], np.diag(a, 1))


# ---------------------------------------------------------------------------
# Stage 3 (Golub-Kahan bisection)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(2, 60), st.integers(0, 2**31 - 1))
def test_bidiag_singular_values_match_lapack(n, seed):
    rng = np.random.default_rng(seed)
    d = rng.standard_normal(n)
    e = rng.standard_normal(n)
    e[0] = 0.0
    B = np.diag(d) + np.diag(e[1:], 1)
    s_ref = np.linalg.svd(B, compute_uv=False)
    s = np.asarray(bidiag_singular_values(jnp.asarray(d), jnp.asarray(e)))
    np.testing.assert_allclose(s, s_ref, atol=1e-12 * max(1.0, s_ref[0]))


def test_sturm_count_monotone_and_bounded():
    rng = np.random.default_rng(3)
    d, e = rng.standard_normal(20), rng.standard_normal(20)
    e[0] = 0
    z = gk_offdiag(jnp.asarray(d), jnp.asarray(e))
    lams = jnp.linspace(0.01, 10.0, 17)
    counts = np.asarray(jax.vmap(lambda l: sturm_count(z, l))(lams))
    assert (np.diff(counts) >= 0).all()
    assert counts[-1] <= 40


def test_bidiag_sv_fp32():
    rng = np.random.default_rng(4)
    n = 48
    d = rng.standard_normal(n).astype(np.float32)
    e = rng.standard_normal(n).astype(np.float32)
    e[0] = 0
    B = np.diag(d.astype(np.float64)) + np.diag(e[1:].astype(np.float64), 1)
    s_ref = np.linalg.svd(B, compute_uv=False)
    s = np.asarray(bidiag_singular_values(jnp.asarray(d), jnp.asarray(e)))
    np.testing.assert_allclose(s, s_ref, rtol=2e-5, atol=2e-6 * s_ref[0])


def test_sigma_error_is_normwise_against_fp64():
    from repro.core.reference import sigma_error
    rng = np.random.default_rng(5)
    a = rng.standard_normal((32, 32))
    s = np.linalg.svd(a, compute_uv=False)
    assert sigma_error(s, a) <= 1e-14
    bumped = s.copy()
    bumped[-1] += 1e-3 * s[0]        # an error on the smallest sigma counts
    np.testing.assert_allclose(sigma_error(bumped, a), 1e-3, rtol=1e-9)
    # float32 inputs are judged against the fp64 spectrum of that input
    a32 = a.astype(np.float32)
    s32 = np.linalg.svd(a32, compute_uv=False)
    assert 0 < sigma_error(s32, a32) <= 10 * 32 * np.finfo(np.float32).eps


@pytest.mark.parametrize("entry,backend", [
    ("singular_values", "ref"), ("singular_values", "pallas"),
    ("svd", "ref"), ("banded_svd", "ref"), ("svd", "fused_small")])
def test_pipeline_dots_ask_for_full_precision(entry, backend):
    """Every dot the pipeline issues carries HIGHEST precision itself: a
    TPU runs an f32 dot as one bf16 pass by default, and no caller should
    have to wrap the pipeline in a precision context to get f32 answers."""
    from repro.core import svd as svdmod
    fn = getattr(svdmod, entry)
    a = jax.ShapeDtypeStruct((2, 24, 24), jnp.float32)
    text = jax.jit(lambda m: fn(m, bw=4, backend=backend)).lower(a).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots
    assert all("HIGHEST" in ln for ln in dots), [
        ln for ln in dots if "HIGHEST" not in ln][:3]
