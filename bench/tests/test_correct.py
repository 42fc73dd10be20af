"""``correct`` at a size a test run holds, on the CPU.

The loops of the benchmark run as a chip run drives them (the look for a
chip is skipped), on a small band and a small served mix.  The program as
its configuration states it comes out correct; the control (the same
program fed bfloat16 data) and each fault planted under the timed path
come out not correct:

- an answer altered where it is produced;
- half of each batch left out (its rows never computed);
- an answer that never comes (one request's result is dropped);
- a kernel that fails, so that the engine answers, rightly, on its
  degraded fallback instead of the timed path.
"""

import json
import os
import time

import jax
import pytest

from bench import run as bench_run
from bench.harness import Context, passed

ROOT = bench_run.ROOT


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def band_ctx(precision=None):
    config = dict(_load("bench/configs/band1k_bw32.json"), n=64, bw=8)
    return Context(workload="band_small", seed=2**40 + 5, seconds=0.3,
                   trace=False, config=config,
                   traffic=_load("bench/traffic/closed_values.json"),
                   t_start=time.perf_counter(), devices=jax.devices(),
                   precision=precision)


def serve_ctx(precision=None, rate=40.0, sizes=None, uv_share=0.5):
    traffic = dict(_load("bench/traffic/small_steady.json"), rate=rate,
                   sizes=sizes or [{"n": 16, "bw": 4, "weight": 0.5},
                                   {"n": 32, "bw": 8, "weight": 0.5}],
                   compute_uv_share=uv_share)
    return Context(workload="serve_small", seed=12345678901, seconds=0.5,
                   trace=False, config=_load("bench/configs/serve_f32.json"),
                   traffic=traffic, t_start=time.perf_counter(),
                   devices=jax.devices(), precision=precision)


def correct(ctx, loop):
    out = bench_run.measure(ctx, loop)
    return all(passed(c) for c in out["checks"].values()), out


@pytest.mark.parametrize("ctx_fn,loop", [(band_ctx, "closed_single"),
                                         (serve_ctx, "open_poisson")])
def test_program_is_correct_and_control_is_not(ctx_fn, loop):
    ok, out = correct(ctx_fn(), loop)
    assert ok, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    ok, out = correct(ctx_fn("bfloat16"), loop)
    assert not ok
    assert out["checks"]["sigma_err"]["value"] > \
        100 * out["checks"]["sigma_err"]["limit"]


def test_band_answer_altered_is_not_correct(monkeypatch):
    from repro.core import svd
    orig = svd.banded_singular_values
    monkeypatch.setattr(svd, "banded_singular_values",
                        lambda a, **kw: orig(a, **kw).at[0].multiply(1.001))
    ok, out = correct(band_ctx(), "closed_single")
    assert not ok and out["failed"] == out["attempted"]


def test_served_answer_altered_is_not_correct(monkeypatch):
    from repro.core import svd
    orig = svd.svd_batched
    monkeypatch.setattr(svd, "svd_batched", lambda m, *a, **kw: orig(
        m, *a, **kw).at[:, 0].multiply(1.001))
    ok, out = correct(serve_ctx(), "open_poisson")
    assert not ok and out["failed"] > 0


def test_half_of_each_batch_left_out_is_not_correct(monkeypatch):
    from repro.core import svd
    orig = svd.svd_batched

    def half(m, *a, **kw):
        sig = orig(m[: m.shape[0] // 2], *a, **kw)
        return jax.numpy.concatenate(
            [sig, jax.numpy.zeros((m.shape[0] - sig.shape[0],) + sig.shape[1:],
                                  sig.dtype)])

    monkeypatch.setattr(svd, "svd_batched", half)
    ok, out = correct(serve_ctx(rate=200.0, sizes=[{"n": 16, "bw": 4,
                                                     "weight": 1.0}]),
                      "open_poisson")
    assert not ok and out["failed"] > 0


def test_answer_that_never_comes_is_not_correct(monkeypatch):
    from bench.loops import open_poisson
    from repro.serve import engine
    monkeypatch.setattr(open_poisson, "GRACE_S", 2.0)
    orig = engine.SVDEngine._deliver

    def drop_one(self, key, reqs, *a, **kw):
        return orig(self, key, [r for r in reqs if r.uid != 3], *a, **kw)

    monkeypatch.setattr(engine.SVDEngine, "_deliver", drop_one)
    ok, out = correct(serve_ctx(), "open_poisson")
    assert not ok
    assert out["checks"]["missing"]["value"] > 0


@pytest.mark.parametrize("uv_share", [0.0, 0.5])
def test_served_on_the_degraded_fallback_is_not_correct(monkeypatch,
                                                        uv_share):
    from repro.serve import engine
    orig = engine.SVDEngine._pipeline_call

    def kernel_fails(self, key, cfg, mats, **kw):
        if cfg.backend != "ref":
            raise RuntimeError("planted kernel failure")
        return orig(self, key, cfg, mats, **kw)

    monkeypatch.setattr(engine.SVDEngine, "_pipeline_call", kernel_fails)
    ok, out = correct(serve_ctx(uv_share=uv_share), "open_poisson")
    assert not ok and out["failed"] == 0
    assert out["checks"]["degraded"]["value"] > 0
    assert passed(out["checks"]["sigma_err"]) and passed(out["checks"]["missing"])
