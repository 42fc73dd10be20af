"""The resident chase-tape replay kernel (DESIGN.md §8), in interpret mode.

``transforms.replay_chase`` replays a Pallas, 32-bit stage tape whose
128-lane accumulator stripe fits fast memory as ONE ``replay_stage``
kernel that keeps each column stripe of U^T and V^T in VMEM for the whole
stage.  Covered here:

  1. the resident replay equals the streamed ``ref`` replay within float32
     rounding, for one and two matrices, single- and multi-stage tile-width
     plans and pivots whose rows pass n - 1, and with the accumulator cut
     into several column stripes; in float64 the kernel's arithmetic agrees
     to a few ulps;
  2. the stripe width and its VMEM count (``tuning.replay_stripe_cols``);
  3. the path is chosen from the input alone: Pallas with float32 or
     bfloat16 data is resident, the ref backend and float64 are streamed;
  4. the ``replay_chase`` span's ``path`` attribute, the per-path replay
     counter and its Prometheus line say which path ran.

Interpret mode steps the whole T x G loop on the CPU, so the sizes stay
small.
"""

import re

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import bulge_chasing as bc
from repro.core import svd as svdmod
from repro.core import transforms, tuning
from repro.core.tuning import PipelineConfig
from repro.kernels import hh_apply


def banded(n, bw, seed, lead=(), dtype=np.float32):
    rng = np.random.default_rng(seed)
    a = np.triu(rng.standard_normal(lead + (n, n)))
    return jnp.asarray((a - np.triu(a, bw + 1)).astype(dtype))


def _tapes(a, bw, tw):
    """The chase tapes of ``a`` (ref backend), flattened to one batch axis."""
    _, _, tapes = bc.bidiagonalize(a, bw=bw, tw=tw, backend="ref", tape=True)
    lead = a.shape[:-2]
    b = int(np.prod(lead)) if lead else 1
    return [(t, t.v.reshape((b,) + t.v.shape[len(lead):]),
             t.tau.reshape((b,) + t.tau.shape[len(lead):])) for t in tapes]


def _replay(a, bw, tw, run):
    """Replay every stage tape of ``a`` from identity with ``run(ut, vt,
    tape, tape_v, tape_tau)``; returns (U^T, V^T) as float64 arrays."""
    n = a.shape[-1]
    b = int(np.prod(a.shape[:-2])) if a.ndim > 2 else 1
    ut = vt = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), (b, n, n))
    for tape, tv, tt in _tapes(a, bw, tw):
        ut, vt = run(ut, vt, tape, tv, tt)
    return np.asarray(ut, np.float64), np.asarray(vt, np.float64)


def _streamed(ut, vt, tape, tv, tt):
    return transforms._replay_chase_streamed(ut, vt, tv, tt, n=tape.n,
                                             b_in=tape.b_in, tw=tape.tw)


_F32_TOL = 2e-5   # float32 rounding of a few hundred rank-1 updates of O(1)


# (n, bw, tw): one stage to bandwidth 1; n = 37 with tw = 6 puts the last
# pivots' rows past n - 1; the plan 8 -> 5 -> 2 -> 1 at n = 40; the plan
# 5 -> 3 -> 1 at n = 26; a wide-block plan 12 -> 1 at n = 50
CASES = [(36, 6, 5), (37, 7, 6), (40, 8, 3), (26, 5, 2), (50, 12, 11)]


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("n,bw,tw", CASES)
def test_resident_replay_matches_streamed(n, bw, tw, batch):
    """replay_chase on the resident path against the streamed ref replay,
    every stage of the plan, float32."""
    lead = (batch,) if batch > 1 else ()
    a = banded(n, bw, seed=n + batch, lead=lead)
    cfg = PipelineConfig.resolve(n=n, bw=bw, tw=tw, backend="pallas",
                                 dtype=jnp.float32)

    def resident(ut, vt, tape, tv, tt):
        assert transforms.replay_path(
            ut.dtype, n=tape.n, b_in=tape.b_in, tw=tape.tw,
            config=cfg) == "resident"
        return transforms.replay_chase(ut, vt, tv, tt, n=tape.n,
                                       b_in=tape.b_in, tw=tape.tw,
                                       config=cfg)

    got = _replay(a, bw, tw, resident)
    want = _replay(a, bw, tw, _streamed)
    for x, y in zip(got, want):
        assert x.shape == y.shape == (batch, n, n)
        np.testing.assert_allclose(x, y, rtol=0, atol=_F32_TOL)


@pytest.mark.parametrize("n,bw,tw", [(37, 7, 6), (40, 8, 3)])
def test_resident_replay_kernel_float64(n, bw, tw):
    """The kernel's arithmetic, run in float64 in interpret mode, agrees with
    the streamed replay to a few ulps."""
    a = banded(n, bw, seed=n, lead=(2,), dtype=np.float64)

    def kernel(ut, vt, tape, tv, tt):
        return hh_apply.replay_stage_pallas(ut, vt, tv, tt, n=tape.n,
                                            b_in=tape.b_in, tw=tape.tw,
                                            interpret=True)

    got = _replay(a, bw, tw, kernel)
    want = _replay(a, bw, tw, _streamed)
    for x, y in zip(got, want):
        np.testing.assert_allclose(x, y, rtol=0, atol=1e-13)


@pytest.mark.parametrize("stripes", [2, 3])
def test_resident_replay_several_stripes(stripes):
    """A budget that admits only a narrower stripe cuts the accumulators into
    several column stripes, which replay independently to the same result."""
    n, bw, tw = 300, 4, 3
    width = -(-n // 128) * 128                      # 384: three lane tiles
    cols = width // 3 if stripes == 3 else 256
    budget = tuning.resident_replay_bytes(n, bw, tw, cols)
    got_cols = tuning.replay_stripe_cols(n, bw, tw, budget_bytes=budget)
    assert got_cols == cols and -(-n // got_cols) == stripes
    a = banded(n, bw, seed=stripes)

    def resident(ut, vt, tape, tv, tt):
        return hh_apply.replay_stage_pallas(ut, vt, tv, tt, n=tape.n,
                                            b_in=tape.b_in, tw=tape.tw,
                                            stripe_cols=got_cols,
                                            interpret=True)

    got = _replay(a, bw, tw, resident)
    want = _replay(a, bw, tw, _streamed)
    for x, y in zip(got, want):
        np.testing.assert_allclose(x, y, rtol=0, atol=_F32_TOL)


def test_replay_stripe_width_and_bytes():
    """At n = 1024, bw = 32 one stripe holds all 1024 columns (one grid step
    per side) and a tape block four cycles of G = 12 slots; the count is
    the stripe plus two tape buffers; a 128-lane stripe keeps n up to about
    29k resident (the paper's range reaches 32k); stripes are balanced."""
    b_in, tw = 32, 31
    assert tuning.replay_layout(1024, b_in, tw) == (1064, 40, 128, 4)
    assert tuning.replay_stripe_cols(1024, b_in, tw) == 1024
    assert tuning.resident_replay_bytes(1024, b_in, tw, 1024) == 4 * (
        1064 * 1024 + 2 * (tuning.REPLAY_CHUNK // 4) * 40 * 128)
    # G = 44 at n = 4096: two cycles a block; G = 88 at n = 8192: one
    assert tuning.replay_layout(4096, b_in, tw)[3] == 2
    assert tuning.replay_layout(8192, b_in, tw)[3] == 1
    assert tuning.replay_stripe_cols(29000, b_in, tw) == 128
    assert tuning.replay_stripe_cols(30000, b_in, tw) == 0
    # n = 2048: 1920 columns fit, so two stripes of 1024, not 1920 + 128
    assert tuning.replay_stripe_cols(2048, b_in, tw) == 1024
    assert tuning.replay_stripe_cols(1024, b_in, tw, budget_bytes=0) == 0


@pytest.mark.parametrize("case,kw,want", [
    ("float32", dict(), "resident"),
    ("bfloat16", dict(dtype=jnp.bfloat16), "resident"),
    ("ref", dict(backend="ref"), "streamed"),
    ("float64", dict(dtype=jnp.float64), "streamed"),
    ("over_budget", dict(n=40000), "streamed"),
])
def test_replay_path_selection(case, kw, want):
    args = dict(dtype=jnp.float32, n=1024, b_in=32, tw=31, backend="pallas")
    args.update(kw)
    dtype = args.pop("dtype")
    assert transforms.replay_path(dtype, **args) == want, case


def _replay_spans(tracer):
    return [sp for root in tracer.roots for sp in root.find("replay_chase")]


@pytest.mark.parametrize("kind,want", [("float32", "resident"),
                                       ("bfloat16", "resident"),
                                       ("ref", "streamed")])
def test_replay_path_is_reported(kind, want):
    """banded_svd's replay_chase spans carry the path, the per-path counter
    counts one replay per stage, and the Prometheus text exposes it."""
    n, bw, tw = 20, 4, 3
    a = banded(n, bw, seed=5)
    backend = "ref" if kind == "ref" else "pallas"
    if kind == "bfloat16":
        a = a.astype(jnp.bfloat16)
    before = obs.replay_stage_counts()
    tr = obs.Tracer(kind)
    u, s, vt = svdmod.banded_svd(a, bw=bw, tw=tw, backend=backend, trace=tr)
    spans = _replay_spans(tr)
    assert spans and all(sp.attrs["path"] == want for sp in spans)
    assert all(sp.attrs["tape_bytes"] > 0 for sp in spans)
    after = obs.replay_stage_counts()
    other = "streamed" if want == "resident" else "resident"
    assert after.get(want, 0) == before.get(want, 0) + len(spans)
    assert after.get(other, 0) == before.get(other, 0)
    text = obs.render_compile_metrics()
    assert "# TYPE repro_replay_stages_total counter" in text
    assert re.search(rf'^repro_replay_stages_total\{{path="{want}"\}} '
                     rf'{after[want]}$', text, re.M)
    a64 = np.asarray(a, np.float64)
    u, s, vt = (np.asarray(x, np.float64) for x in (u, s, vt))
    tol = 5e-2 if kind == "bfloat16" else 1e-4
    assert np.linalg.norm(a64 - (u * s) @ vt) / np.linalg.norm(a64) < tol
