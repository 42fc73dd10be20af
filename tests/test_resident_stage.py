"""The band-resident stage-2 kernel (DESIGN.md §9), in interpret mode.

``reduce_stage_packed`` runs a values-only, Pallas, 32-bit stage whose band
fits fast memory as ONE ``chase_stage`` kernel that keeps each matrix's
band in VMEM and runs the whole wavefront loop inside.  Covered here:

  1. the resident stage equals the streamed K = 1 stage bit for bit, for
     one and several matrices, single- and multi-stage tile-width plans,
     and sizes whose last sweeps' windows run off the band;
  2. the path is chosen from the input alone: a tape, the ref backend,
     bf16 or float64 data and an over-budget band keep the streamed path;
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
# n = 37 leaves the last sweeps' windows past column n - 1 (n + W - 1 > n).
STAGES = [(32, 6, 5), (40, 8, 3), (37, 7, 6)]


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("n,b_in,tw", STAGES)
def test_resident_stage_matches_streamed_bitwise(n, b_in, tw, batch):
    lead = (batch,) if batch > 1 else ()
    packed = bandmod.pack(banded_f32(n, b_in, seed=n + batch, lead=lead),
                          b_in, tw)
    kw = dict(n=n, b_in=b_in, tw=tw, backend="pallas")
    assert bc.stage_path(packed.dtype, **kw) == "resident"
    out = np.asarray(bc.reduce_stage_packed(packed, **kw))
    ref = np.asarray(bc._reduce_stage_streamed(packed, fuse=1, **kw))
    assert out.shape == ref.shape == packed.shape
    np.testing.assert_array_equal(out, ref)
    # the stage did reduce the band: rows past b_out + tw hold zeros only
    assert not np.any(out[..., tw + (b_in - tw) + 1:, :n])


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
        strm = bc._reduce_stage_streamed(strm, fuse=1, **kw)
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


def test_resident_stage_ignores_fuse():
    """The resident path is chosen whatever ``fuse`` says, and matches the
    streamed super-step output at every depth."""
    n, b_in, tw = 34, 6, 3
    packed = bandmod.pack(banded_f32(n, b_in, seed=2), b_in, tw)
    kw = dict(n=n, b_in=b_in, tw=tw, backend="pallas")
    base = np.asarray(bc.reduce_stage_packed(packed, **kw))
    for k in (2, 4):
        np.testing.assert_array_equal(
            np.asarray(bc.reduce_stage_packed(packed, fuse=k, **kw)), base)
        np.testing.assert_array_equal(
            np.asarray(bc._reduce_stage_streamed(packed, fuse=k, **kw)), base)


@pytest.mark.parametrize("case,kw,want", [
    ("resident", dict(), "resident"),
    ("tape", dict(tape=True), "streamed"),
    ("ref", dict(backend="ref"), "streamed"),
    ("bf16", dict(dtype=jnp.bfloat16), "streamed"),
    ("float64", dict(dtype=jnp.float64), "streamed"),
    ("over_budget", dict(n=40000), "streamed"),
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


@pytest.mark.parametrize("kind", ["tape", "ref"])
def test_other_paths_report_streamed(kind):
    n, bw = 20, 4
    a = banded_f32(n, bw, seed=5)
    before = obs.chase_stage_counts()
    tr = obs.Tracer(kind)
    if kind == "tape":
        svdmod.banded_svd(a, bw=bw, tw=3, backend="pallas", trace=tr)
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
