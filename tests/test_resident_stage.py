"""The band-resident stage-2 kernel (DESIGN.md §9), in interpret mode.

``reduce_stage_packed`` runs a Pallas, 32-bit stage whose band fits fast
memory as ONE ``chase_stage`` kernel that keeps each matrix's band in VMEM
and runs the whole wavefront loop inside, recording the reflector tape
when one is asked for.  Covered here:

  1. the resident stage equals the streamed stage bit for bit, for one
     and several matrices, single- and multi-stage tile-width plans, and
     sizes whose last sweeps' windows run off the band — at fixed shapes
     and at hypothesis-drawn ones; so does its tape, and the full SVD
     built on it;
  2. the path is chosen from the input alone: the ref backend, bf16 or
     float64 data and an over-budget band (counted with the tape's staging
     slots when a tape is recorded) keep the streamed path;
  3. the ``stage2`` span's ``path`` attribute, the per-path stage counter
     and its Prometheus line say which path ran.

Interpret mode steps the whole T x G wavefront loop on the CPU, so the
sizes stay small.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import band as bandmod
from repro.core import bulge_chasing as bc
from repro.core import svd as svdmod
from repro.core import tuning
from repro.core.tuning import PipelineConfig


def banded_f32(n, bw, seed, lead=()):
    rng = np.random.default_rng(seed)
    a = np.triu(rng.standard_normal(lead + (n, n)))
    return jnp.asarray((a - np.triu(a, bw + 1)).astype(np.float32))


# (n, b_in, tw): b_out = 1 in one stage; a first stage of a two-stage plan;
# n = 37 leaves the last sweeps' windows past column n - 1 (n + W - 1 > n);
# (26, 5, 2) the first stage of the plan 5 -> 3 -> 1; then shapes from the
# range n 8-36, b_in 2-7, tw 1 .. b_in - 1, down to a single sweep (n = 8,
# b_in = 3, tw = 2 has n - 1 - b_out = 6 sweeps of one or two cycles).
STAGES = [(32, 6, 5), (40, 8, 3), (37, 7, 6), (26, 5, 2),
          (8, 3, 2), (13, 2, 1), (19, 7, 1), (23, 4, 3), (29, 6, 2),
          (36, 7, 4)]


def _assert_stage_matches(packed, n, b_in, tw):
    kw = dict(n=n, b_in=b_in, tw=tw, backend="pallas")
    assert bc.stage_path(packed.dtype, **kw) == "resident"
    out = np.asarray(bc.reduce_stage_packed(packed, **kw))
    ref = np.asarray(bc._reduce_stage_streamed(packed, **kw))
    assert out.shape == ref.shape == packed.shape
    np.testing.assert_array_equal(out, ref)
    # the stage did reduce the band: rows past b_out + tw hold zeros only
    assert not np.any(out[..., tw + (b_in - tw) + 1:, :n])


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("n,b_in,tw", STAGES)
def test_resident_stage_matches_streamed_bitwise(n, b_in, tw, batch):
    lead = (batch,) if batch > 1 else ()
    _assert_stage_matches(
        bandmod.pack(banded_f32(n, b_in, seed=n + batch, lead=lead),
                     b_in, tw), n, b_in, tw)


@settings(max_examples=4, deadline=None)
@given(st.integers(8, 36), st.integers(2, 7), st.data(), st.booleans())
def test_resident_stage_matches_streamed_randomized(n, b_in, data, batched):
    tw = data.draw(st.integers(1, b_in - 1), label="tw")
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    lead = (2,) if batched else ()
    _assert_stage_matches(
        bandmod.pack(banded_f32(n, b_in, seed=seed, lead=lead), b_in, tw),
        n, b_in, tw)


def test_resident_stage_passes_wide_storage_through():
    """Storage wider than the columns a window can touch (n + W) keeps its
    extra columns, as on the streamed path."""
    n, b_in, tw = 24, 5, 4
    rows, _ = tuning.resident_band_layout(n, b_in, tw)
    packed = bandmod.pad_columns(
        bandmod.pack(banded_f32(n, b_in, seed=9), b_in, tw), rows + 5 - n)
    packed = packed.at[..., rows:].set(7.0)
    kw = dict(n=n, b_in=b_in, tw=tw, backend="pallas")
    out = np.asarray(bc.reduce_stage_packed(packed, **kw))
    assert out.shape == packed.shape
    np.testing.assert_array_equal(
        out, np.asarray(bc._reduce_stage_streamed(packed, **kw)))
    assert np.all(out[..., rows:] == 7.0)


@pytest.mark.parametrize("batch", [1, 3])
def test_resident_plan_matches_streamed_bitwise(batch):
    """Every stage of a three-stage tile-width plan (bw 9 -> 6 -> 3 -> 1)."""
    n, bw, tw = 30, 9, 3
    lead = (batch,) if batch > 1 else ()
    plan = tuning.stage_plan(bw, tw)
    assert len(plan) == 3
    res = strm = bandmod.pack(banded_f32(n, bw, seed=4, lead=lead), bw, tw)
    tw_cur = tw
    for b_in, twi in plan:
        start = tw_cur - twi
        h = b_in + 2 * twi + 1
        res = jax.lax.slice_in_dim(res, start, start + h, axis=-2)
        strm = jax.lax.slice_in_dim(strm, start, start + h, axis=-2)
        kw = dict(n=n, b_in=b_in, tw=twi, backend="pallas")
        res = bc.reduce_stage_packed(res, **kw)
        strm = bc._reduce_stage_streamed(strm, **kw)
        np.testing.assert_array_equal(np.asarray(res), np.asarray(strm))
        tw_cur = twi
    d, e = bc.bidiagonalize(banded_f32(n, bw, seed=4, lead=lead), bw=bw,
                            tw=tw, backend="pallas")
    np.testing.assert_array_equal(np.asarray(d),
                                  np.asarray(bandmod.band_extract_diag(
                                      strm, tw_cur, 0, n)))
    np.testing.assert_array_equal(np.asarray(e),
                                  np.asarray(bandmod.band_extract_diag(
                                      strm, tw_cur, 1, n)))


def _assert_tape_stage_matches(res, strm, n, b_in, tw):
    """The band and tau bit for bit, v bit for bit wherever tau != 0, and
    v = tau = 0 on every inactive wavefront slot."""
    (out, v, tau), (ref, rv, rtau) = (tuple(np.asarray(x) for x in r)
                                      for r in (res, strm))
    assert v.shape == rv.shape and tau.shape == rtau.shape
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(tau, rtau)
    live = rtau != 0
    assert live.any()
    np.testing.assert_array_equal(v[live], rv[live])
    t, g = np.meshgrid(*map(np.arange, tau.shape[-3:-1]), indexing="ij")
    idle = ~np.asarray(bc.chase_cycle_indices(t, g, n, b_in, tw)[3])
    assert idle.any()
    assert not v[..., idle, :, :].any() and not tau[..., idle, :].any()


# (19, 7, 1) is left out: its plan has six stages, and its first stage is
# already in test_resident_stage_matches_streamed_bitwise
@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("n,bw,tw", [s for s in STAGES[1:] if s != (19, 7, 1)])
def test_resident_tape_matches_streamed_bitwise(n, bw, tw, batch):
    """The resident stage's tape against the streamed stage's, for every
    stage of the plan ``stage_plan(bw, tw)``: one stage whose last windows
    run off the band (n = 37), the plan bw 8 -> 5 -> 2 -> 1 at n = 40,
    tw = 3, and the plans of the shapes after it in ``STAGES``."""
    lead = (batch,) if batch > 1 else ()
    cur = bandmod.pack(banded_f32(n, bw, seed=n + batch, lead=lead), bw, tw)
    plan = tuning.stage_plan(bw, tw)
    tw_cur = tw
    for b_in, twi in plan:
        start = tw_cur - twi
        cur = jax.lax.slice_in_dim(cur, start, start + b_in + 2 * twi + 1,
                                   axis=-2)
        kw = dict(n=n, b_in=b_in, tw=twi, backend="pallas", tape=True)
        assert bc.stage_path(cur.dtype, **kw) == "resident"
        res = bc.reduce_stage_packed(cur, **kw)
        strm = bc._reduce_stage_streamed(cur, **kw)
        assert res[1].shape[:len(lead)] == lead
        _assert_tape_stage_matches(res, strm, n, b_in, twi)
        cur, tw_cur = strm[0], twi


def test_resident_tape_stage_without_cycles():
    """n = 2 at b_in = 2 has no sweep: the band passes through and the tape
    is empty, in the streamed stage's shapes."""
    packed = bandmod.pack(banded_f32(2, 2, seed=1), 2, 1)
    kw = dict(n=2, b_in=2, tw=1, backend="pallas", tape=True)
    assert bc.stage_path(packed.dtype, **kw) == "resident"
    res = bc.reduce_stage_packed(packed, **kw)
    strm = bc._reduce_stage_streamed(packed, **kw)
    for x, y in zip(res, strm):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("batch", [1, 3])
def test_resident_tape_svd_matches_streamed_bitwise(batch, monkeypatch):
    """banded_svd on the resident tape stage gives the same U, sigma and
    V^T bits as with every stage forced onto the streamed path, for one
    matrix and a batch: both paths record the one tape layout the replay
    reads."""
    n, bw, tw = 24, 6, 3
    lead = (batch,) if batch > 1 else ()
    a = banded_f32(n, bw, seed=8, lead=lead)
    cfg = PipelineConfig.resolve(n=n, bw=bw, tw=tw, backend="pallas",
                                 dtype=jnp.float32)
    tr = obs.Tracer("resident")
    res = svdmod.banded_svd(a, config=cfg, trace=tr)
    assert {sp.attrs["path"] for sp in _stage2_spans(tr)} == {"resident"}
    monkeypatch.setattr(bc, "stage_path", lambda *_, **__: "streamed")
    monkeypatch.setattr(bc, "reduce_stage_packed", bc._reduce_stage_streamed)
    strm = svdmod.banded_svd(a, config=cfg)
    for x, y in zip(res, strm):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case,kw,want", [
    ("resident", dict(), "resident"),
    ("tape", dict(tape=True), "resident"),
    ("ref", dict(backend="ref"), "streamed"),
    ("bf16", dict(dtype=jnp.bfloat16), "streamed"),
    ("float64", dict(dtype=jnp.float64), "streamed"),
    ("over_budget", dict(n=40000), "streamed"),
    ("tape_bf16", dict(tape=True, dtype=jnp.bfloat16), "streamed"),
    ("tape_float64", dict(tape=True, dtype=jnp.float64), "streamed"),
    ("tape_over_budget", dict(tape=True, n=40000), "streamed"),
])
def test_stage_path_selection(case, kw, want):
    args = dict(dtype=jnp.float32, n=1024, b_in=32, tw=31, backend="pallas")
    args.update(kw)
    dtype = args.pop("dtype")
    assert bc.stage_path(dtype, **args) == want, case


def test_resident_budget_counts_the_band():
    """resident_band_bytes grows with n by one padded band row per column,
    and the largest n it admits at the f32 default plan is in the paper's
    range (n up to 32k)."""
    b_in, tw = 32, 31
    small = tuning.resident_band_bytes(1024, b_in, tw)
    rows, lanes = tuning.resident_band_layout(1024, b_in, tw)
    assert rows >= 1024 + b_in + tw + 1 and rows % 8 == 0 and lanes == 128
    assert small >= rows * lanes * 4
    assert (tuning.resident_band_bytes(1032, b_in, tw) - small
            == 8 * lanes * 4)
    assert tuning.resident_band_bytes(32000, b_in, tw) <= \
        tuning.VMEM_BUDGET_BYTES < tuning.resident_band_bytes(33000, b_in, tw)


def test_resident_budget_counts_the_tape_staging():
    """With a tape, resident_band_bytes adds exactly the two staging slots
    at their tiled size (2G rows, v and tau in one lane-padded row), and a
    band that fits only without them takes the streamed path with a
    tape."""
    b_in, tw = 32, 31
    for n in (1024, 4096):
        rows = -(-2 * tuning.max_concurrent_sweeps(n, b_in) // 8) * 8
        extra = (tuning.resident_band_bytes(n, b_in, tw, tape=True)
                 - tuning.resident_band_bytes(n, b_in, tw))
        assert extra == 2 * rows * tuning.tape_stage_lanes(tw) * 4
    # n = 1024: G = 12, so two slots of 24 rows by 128 lanes
    assert (tuning.resident_band_bytes(1024, b_in, tw, tape=True)
            - tuning.resident_band_bytes(1024, b_in, tw)) == 2 * 24 * 128 * 4
    lo, hi = 1024, 1 << 16
    while lo < hi:                   # the largest band that fits, no tape
        mid = (lo + hi + 1) // 2
        fits = (tuning.resident_band_bytes(mid, b_in, tw)
                <= tuning.VMEM_BUDGET_BYTES)
        lo, hi = (mid, hi) if fits else (lo, mid - 1)
    kw = dict(n=lo, b_in=b_in, tw=tw, backend="pallas")
    assert bc.stage_path(jnp.float32, **kw) == "resident"
    assert bc.stage_path(jnp.float32, tape=True, **kw) == "streamed"


def _stage2_spans(tracer):
    return [sp for root in tracer.roots for sp in root.find("stage2")]


def test_values_path_reports_resident():
    n, bw = 24, 6
    a = banded_f32(n, bw, seed=3)
    before = obs.chase_stage_counts()
    tr = obs.Tracer("values")
    sig = svdmod.banded_singular_values(a, bw=bw, tw=3, backend="pallas",
                                        trace=tr)
    spans = _stage2_spans(tr)
    assert [sp.attrs["path"] for sp in spans] == ["resident", "resident"]
    after = obs.chase_stage_counts()
    assert after.get("resident", 0) == before.get("resident", 0) + 2
    assert after.get("streamed", 0) == before.get("streamed", 0)
    assert re.search(r'^repro_chase_stages_total\{path="resident"\} \d+$',
                     obs.render_compile_metrics(), re.M)
    ref = np.linalg.svd(np.asarray(a, np.float64), compute_uv=False)
    np.testing.assert_allclose(np.asarray(sig), ref, atol=1e-4 * ref[0])


def test_tape_path_reports_resident():
    """banded_svd's tape stages take the resident kernel too."""
    n, bw = 20, 4
    a = banded_f32(n, bw, seed=5)
    before = obs.chase_stage_counts()
    tr = obs.Tracer("tape")
    svdmod.banded_svd(a, bw=bw, tw=3, backend="pallas", trace=tr)
    spans = _stage2_spans(tr)
    assert spans and all(sp.attrs["path"] == "resident" for sp in spans)
    assert all(sp.attrs["tape"] for sp in spans)
    after = obs.chase_stage_counts()
    assert after.get("resident", 0) == before.get("resident", 0) + len(spans)
    assert after.get("streamed", 0) == before.get("streamed", 0)
    assert re.search(r'^repro_chase_stages_total\{path="resident"\} \d+$',
                     obs.render_compile_metrics(), re.M)


@pytest.mark.parametrize("kind", ["tape", "ref"])
def test_other_paths_report_streamed(kind):
    n, bw = 20, 4
    a = banded_f32(n, bw, seed=5)
    before = obs.chase_stage_counts()
    tr = obs.Tracer(kind)
    if kind == "tape":
        # a bf16 band keeps the tape on the streamed path
        svdmod.banded_svd(a.astype(jnp.bfloat16), bw=bw, tw=3,
                          backend="pallas", trace=tr)
    else:
        svdmod.banded_singular_values(a, bw=bw, tw=3, backend="ref",
                                      trace=tr)
    spans = _stage2_spans(tr)
    assert spans and all(sp.attrs["path"] == "streamed" for sp in spans)
    after = obs.chase_stage_counts()
    assert after.get("streamed", 0) == before.get("streamed", 0) + len(spans)
    assert after.get("resident", 0) == before.get("resident", 0)
    assert re.search(r'^repro_chase_stages_total\{path="streamed"\} \d+$',
                     obs.render_compile_metrics(), re.M)


def test_resident_stage_carries_stage2_scope():
    """The resident kernel's ops sit under the repro.stage2 scope, as the
    streamed stage's do, so a device trace still splits stage-2 time."""
    n, bw = 24, 6
    cfg = PipelineConfig.resolve(n=n, bw=bw, tw=3, backend="pallas",
                                 dtype=jnp.float32)
    a = banded_f32(n, bw, seed=6)
    text = jax.jit(lambda x: svdmod.banded_singular_values(
        x, config=cfg)).lower(a).compile().as_text()
    assert re.search(r'op_name="[^"]*repro\.stage2', text)
