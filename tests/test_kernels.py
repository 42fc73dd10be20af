"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ref as kref
from repro.kernels import ops
from repro.kernels.bulge_chase import chase_cycle_pallas
from repro.kernels.hh_apply import hh_block_apply_pallas

CHASE_SHAPES = [(4, 2, 3), (6, 2, 4), (8, 3, 5), (12, 4, 3), (16, 8, 2),
                (32, 8, 2), (5, 4, 6), (2, 1, 8)]
DTYPES = [(jnp.float32, 3e-5), (jnp.float64, 1e-12), (jnp.bfloat16, 8e-2)]


@pytest.mark.parametrize("b_in,tw,G", CHASE_SHAPES)
@pytest.mark.parametrize("dtype,tol", DTYPES)
def test_chase_kernel_matches_ref(b_in, tw, G, dtype, tol):
    H, W = b_in + 2 * tw + 1, b_in + tw + 1
    rng = np.random.default_rng(b_in * 1000 + tw)
    win = jnp.asarray(rng.standard_normal((G, H, W)), dtype)
    first = jnp.asarray([i % 2 == 0 for i in range(G)])
    a = kref.chase_cycle_ref(win, first, b_in=b_in, tw=tw)
    b = chase_cycle_pallas(win, first, b_in=b_in, tw=tw, interpret=True)
    scale = max(1.0, float(jnp.max(jnp.abs(a)).astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(b, np.float64), np.asarray(a, np.float64),
                               atol=tol * scale)


@pytest.mark.parametrize("b_in,tw", [(6, 2), (12, 4)])
def test_chase_kernel_zero_window_noop(b_in, tw):
    """Padding semantics: all-zero windows must stay exactly zero."""
    H, W = b_in + 2 * tw + 1, b_in + tw + 1
    win = jnp.zeros((3, H, W), jnp.float32)
    first = jnp.asarray([True, False, True])
    out = chase_cycle_pallas(win, first, b_in=b_in, tw=tw, interpret=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


WY_SHAPES = [(64, 8, 100), (128, 16, 64), (33, 4, 7), (256, 32, 512), (16, 1, 5)]


@pytest.mark.parametrize("m,k,n", WY_SHAPES)
@pytest.mark.parametrize("dtype,tol", DTYPES)
def test_wy_kernel_matches_ref(m, k, n, dtype, tol):
    rng = np.random.default_rng(m + k + n)
    v = np.tril(rng.standard_normal((m, k)), -1)
    v[np.arange(k), np.arange(k)] = 1.0
    t = np.triu(rng.standard_normal((k, k))) * 0.2
    c = rng.standard_normal((m, n))
    v, t, c = (jnp.asarray(x, dtype) for x in (v, t, c))
    a = kref.hh_block_apply_ref(v, t, c)
    b = hh_block_apply_pallas(v, t, c, interpret=True, block_cols=64)
    scale = max(1.0, float(jnp.max(jnp.abs(a)).astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(b, np.float64), np.asarray(a, np.float64),
                               atol=tol * scale * max(1, k // 4))


def test_ops_dispatch_ref_equals_pallas():
    b_in, tw, G = 8, 3, 4
    H, W = b_in + 2 * tw + 1, b_in + tw + 1
    rng = np.random.default_rng(0)
    win = jnp.asarray(rng.standard_normal((G, H, W)), jnp.float32)
    first = jnp.zeros((G,), bool)
    a = ops.chase_cycle(win, first, b_in=b_in, tw=tw, backend="ref")
    b = ops.chase_cycle(win, first, b_in=b_in, tw=tw, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_ops_unknown_backend_raises():
    with pytest.raises(ValueError):
        ops.chase_cycle(jnp.zeros((1, 8, 6)), jnp.zeros((1,), bool),
                        b_in=3, tw=2, backend="nope")


# ---------------------------------------------------------------------------
# stage-1 pallas integration
# ---------------------------------------------------------------------------

def test_stage1_pallas_backend_bit_exact():
    from repro.core.stage1 import band_reduce
    rng = np.random.default_rng(7)
    a = rng.standard_normal((48, 48))
    b_ref = np.asarray(band_reduce(jnp.asarray(a), nb=8, backend="ref"))
    b_pal = np.asarray(band_reduce(jnp.asarray(a), nb=8, backend="pallas"))
    np.testing.assert_array_equal(b_pal, b_ref)
