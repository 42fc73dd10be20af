"""The ``band1k_uv`` cell's loop, readers and byte count, on the CPU.

The loop runs as a chip run drives it (the look for a chip is skipped), on
a small band of the cell's own configuration.  The program comes out
correct; an altered U, and a U that is not orthogonal though it multiplies
back to A, come out not correct.  Each reader of the cell returns None on
a run without what it reads, and its number on a run that has it.
"""

import json
import os
import time

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench import work_uv
from bench.harness import Context, passed

ROOT = bench_run.ROOT
READERS = ("stage2_device_s.band_uv", "replay_device_s.band_uv",
           "stage3_device_s.band_uv", "replay_hbm_share.band_uv",
           "device_idle.band_uv")


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def uv_ctx(precision=None):
    config = dict(_load("bench/configs/band1k_bw32_uv.json"), n=64, bw=8)
    return Context(workload="band_uv_small", seed=2**40 + 7, seconds=0.3,
                   trace=False, config=config,
                   traffic=_load("bench/traffic/closed_uv.json"),
                   t_start=time.perf_counter(), devices=jax.devices(),
                   precision=precision)


def correct(ctx):
    out = bench_run.measure(ctx, "closed_uv")
    return all(passed(c) for c in out["checks"].values()), out


def test_program_is_correct_and_control_is_not():
    ok, out = correct(uv_ctx())
    assert ok, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"sigma_err", "uv_resid", "uv_orth"}
    readings = out["readings"]
    assert readings["reductions"] == out["attempted"]
    # one stage-2 tape per reduction, and no stage-1 tape for a band
    assert set(readings["tape_bytes_total"]) == {"stage2"}
    assert readings["tape_bytes_total"]["stage2"] % readings["reductions"] == 0
    ok, out = correct(uv_ctx("bfloat16"))
    assert not ok
    for name in ("sigma_err", "uv_resid", "uv_orth"):
        assert out["checks"][name]["value"] > 10 * out["checks"][name]["limit"]


def test_altered_u_is_not_correct(monkeypatch):
    from repro.core import svd
    orig = svd.banded_svd

    def altered(a, **kw):
        u, s, vt = orig(a, **kw)
        return u.at[:, 0].multiply(1.01), s, vt

    monkeypatch.setattr(svd, "banded_svd", altered)
    ok, out = correct(uv_ctx())
    assert not ok and out["failed"] == out["attempted"]
    assert not passed(out["checks"]["uv_orth"])


def test_u_not_orthogonal_with_a_good_residual_is_not_correct(monkeypatch):
    """U G and G^-1-compensated V^T, with G = I + 0.05 e_0 e_1^T: A is
    rebuilt to rounding and sigma is untouched, as in the fault where a
    reflector was not orthogonal; only the orthogonality check sees it."""
    from repro.core import svd
    orig = svd.banded_svd

    def skewed(a, **kw):
        u, s, vt = orig(a, **kw)
        eps = 0.05
        u = u.at[:, 1].add(eps * u[:, 0])
        vt = vt.at[0].add(-eps * (s[1] / s[0]) * vt[1])
        return u, s, vt

    monkeypatch.setattr(svd, "banded_svd", skewed)
    ok, out = correct(uv_ctx())
    assert not ok and out["failed"] == out["attempted"]
    assert passed(out["checks"]["sigma_err"])
    assert passed(out["checks"]["uv_resid"])
    assert not passed(out["checks"]["uv_orth"])


def _run(trace):
    return {"trace": trace, "readings": {"reductions": 2},
            "config": {"n": 1024, "bw": 32, "dtype": "float32"},
            "peak": {"hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("metric", READERS)
def test_reader_is_none_without_what_it_reads(metric):
    read = bench_run.reader(ROOT, metric)
    assert read(_run(None)) is None
    if "device_s" in metric:
        # a trace of a program without the stage scopes
        assert read(_run({"busy_s": 3.0, "window_s": 4.0,
                          "scope_s": {"unscoped": 3.0}})) is None


@pytest.mark.parametrize("metric,scope", [
    ("stage2_device_s.band_uv", "repro.stage2"),
    ("replay_device_s.band_uv", "repro.replay"),
    ("stage3_device_s.band_uv", "repro.stage3")])
def test_scope_reader_gives_seconds_per_reduction(metric, scope):
    trace = {"busy_s": 3.0, "window_s": 4.0,
             "scope_s": {scope: 1.5, "unscoped": 1.5}}
    assert bench_run.reader(ROOT, metric)(_run(trace)) == pytest.approx(0.75)


def test_replay_share_and_idle_from_a_trace():
    trace = {"busy_s": 3.0, "window_s": 4.0, "scope_s": {}}
    share = bench_run.reader(ROOT, "replay_hbm_share.band_uv")(_run(trace))
    least_s = 8_845_520_376 / 819e9
    assert share == pytest.approx(100.0 * least_s / 1.5)
    idle = bench_run.reader(ROOT, "device_idle.band_uv")(_run(trace))
    assert idle == pytest.approx(25.0)


def test_replay_bytes_at_n1024_bw32():
    assert work_uv.replay_bytes(1024, 32) == 8_845_520_376
    assert work_uv.replay_bytes(1024, 32, "bfloat16") == 8_845_520_376 // 2
    assert np.isclose(work_uv.replay_bytes(1024, 32) / 819e9, 0.0108,
                      atol=1e-4)
