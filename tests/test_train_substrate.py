"""Training-substrate tests: optimizer, data determinism, checkpoint
atomicity/restore, crash-restart bit-exactness, straggler detection,
spectral monitor, compression error feedback."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import smoke_of
from repro.models import build
from repro.train import (AdamWConfig, DataConfig, FailureInjector,
                         StragglerMonitor, Trainer, batch_at, checkpoint,
                         run_with_restarts)
from repro.train.optimizer import cosine_lr, global_norm
from repro.train.spectral import SpectralMonitor, SpectralMonitorConfig, spectral_metrics


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_cosine_schedule_shape():
    cfg = AdamWConfig(peak_lr=1.0, min_lr=0.1, warmup_steps=10, total_steps=100)
    lrs = [float(cosine_lr(cfg, jnp.asarray(s))) for s in [0, 5, 10, 55, 100]]
    assert lrs[0] == 0.0 and abs(lrs[2] - 1.0) < 1e-6
    assert lrs[1] == pytest.approx(0.5)
    assert 0.1 < lrs[3] < 1.0
    assert lrs[4] == pytest.approx(0.1, abs=1e-6)


def test_training_reduces_loss():
    cfg = smoke_of("granite-3-2b")
    model = build(cfg)
    tr = Trainer(model, AdamWConfig(peak_lr=3e-3, warmup_steps=2,
                                    total_steps=100, clip_norm=1.0))
    state = tr.init_state(jax.random.PRNGKey(0))
    step = jax.jit(tr.make_train_step())
    dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4, seed=3)
    batch = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}
    first = None
    for t in range(20):                      # overfit one batch
        state, m = step(state, batch)
        first = first if first is not None else float(m["loss"])
    assert float(m["loss"]) < first - 0.5


def test_grad_accumulation_matches_full_batch():
    cfg = smoke_of("granite-3-2b")
    model = build(cfg)
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=5)
    batch = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}
    opt = AdamWConfig(peak_lr=1e-3, warmup_steps=0, total_steps=10, clip_norm=0)
    params = Trainer(model, opt).init_state(jax.random.PRNGKey(0))["params"]
    grads = []
    for accum in (1, 2):
        tr = Trainer(model, opt, accum=accum)
        _, _, g = jax.jit(lambda p, b, tr=tr: tr._grads(p, b))(params, batch)
        grads.append(g)
    scale = max(float(global_norm(grads[0])), 1.0)
    for x, y in zip(jax.tree_util.tree_leaves(grads[0]),
                    jax.tree_util.tree_leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   atol=1e-5 * scale)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_is_pure_function_of_step():
    dc = DataConfig(vocab=1000, seq_len=64, global_batch=8, seed=11)
    b1, b2 = batch_at(dc, 42), batch_at(dc, 42)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    b3 = batch_at(dc, 43)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_host_slice_partitions():
    from repro.train.data import host_slice
    dc = DataConfig(vocab=100, seq_len=8, global_batch=8, seed=0)
    full = batch_at(dc, 0)
    parts = [host_slice(full, h, 4) for h in range(4)]
    glued = np.concatenate([p["tokens"] for p in parts])
    np.testing.assert_array_equal(glued, full["tokens"])


def test_prefetcher_orders_steps():
    from repro.train.data import Prefetcher
    dc = DataConfig(vocab=50, seq_len=4, global_batch=2, seed=1)
    pf = Prefetcher(dc, start_step=5)
    try:
        s0, b0 = pf.next()
        s1, _ = pf.next()
        assert (s0, s1) == (5, 6)
        ref = batch_at(dc, 5)
        np.testing.assert_array_equal(np.asarray(b0["tokens"]), ref["tokens"])
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_keep(tmp_path):
    state = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.asarray(7)}}
    for s in (1, 2, 3, 4):
        checkpoint.save(str(tmp_path), s, state, keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 4
    assert sorted(checkpoint._complete_steps(str(tmp_path))) == [3, 4]
    out = checkpoint.restore(str(tmp_path), 4, state)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(state["a"]))
    assert int(out["b"]["c"]) == 7


def test_incomplete_checkpoint_ignored(tmp_path):
    state = {"x": jnp.ones(3)}
    checkpoint.save(str(tmp_path), 1, state)
    # fake a torn write: directory without DONE
    os.makedirs(tmp_path / "step_00000002")
    np.savez(tmp_path / "step_00000002" / "state.npz", x=np.ones(3))
    assert checkpoint.latest_step(str(tmp_path)) == 1


def test_restart_bit_exact(tmp_path):
    """Crash at step 7 -> restore -> final params identical to a clean run."""
    cfg = smoke_of("granite-3-2b")
    model = build(cfg)
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=2, seed=9)
    opt = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=20)

    def driver(ckpt_dir, injector):
        tr = Trainer(model, opt)
        jstep = jax.jit(tr.make_train_step())

        def make_state():
            return tr.init_state(jax.random.PRNGKey(0))

        def restore_state(step, template):
            return checkpoint.restore(ckpt_dir, step, template)

        def step_fn(step, state):
            batch = {k: jnp.asarray(v) for k, v in batch_at(dc, step).items()}
            return jstep(state, batch)

        return run_with_restarts(
            total_steps=12, ckpt_dir=ckpt_dir, make_state=make_state,
            restore_state=restore_state, step_fn=step_fn, save_every=5,
            injector=injector)

    clean, _, r0 = driver(str(tmp_path / "clean"), FailureInjector())
    crash, _, r1 = driver(str(tmp_path / "crash"), FailureInjector(fail_at=(7,)))
    assert r0 == 0 and r1 == 1
    for x, y in zip(jax.tree_util.tree_leaves(clean["params"]),
                    jax.tree_util.tree_leaves(crash["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_async_checkpointer(tmp_path):
    from repro.train.checkpoint import AsyncCheckpointer
    ac = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in range(4):
        ac.submit(s, {"w": jnp.full((4,), float(s))})
    ac.close()
    last = checkpoint.latest_step(str(tmp_path))
    assert last is not None
    out = checkpoint.restore(str(tmp_path), last, {"w": jnp.zeros(4)})
    np.testing.assert_array_equal(np.asarray(out["w"]), np.full(4, float(last)))


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_straggler_monitor_flags():
    mon = StragglerMonitor(threshold=2.0)
    for s in range(10):
        mon.record(s, 1.0)
    assert mon.record(10, 5.0) is True
    assert mon.flagged == [10]
    assert mon.record(11, 1.1) is False


# ---------------------------------------------------------------------------
# spectral monitor (the paper's kernel in the training loop)
# ---------------------------------------------------------------------------

def test_spectral_monitor_and_metrics():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((48, 48))
    params = {"layer": {"w": jnp.asarray(w)}, "bias": jnp.zeros(8)}
    mon = SpectralMonitor(SpectralMonitorConfig(every=5, size=48, bw=8,
                                                backend="ref"))
    assert mon.maybe_refresh(0, params)
    assert not mon.maybe_refresh(3, params)
    assert mon.maybe_refresh(5, params)
    sig = mon.sigma_tree["layer"]["w"]
    s_ref = np.linalg.svd(w, compute_uv=False)
    np.testing.assert_allclose(np.asarray(sig), s_ref, atol=1e-8 * s_ref[0])
    sm = mon.sigma_max_tree()
    assert float(sm["layer"]["w"]) == pytest.approx(s_ref[0], rel=1e-9)
    assert sm["bias"] is None
    m = spectral_metrics(jnp.asarray(s_ref))
    assert m["stable_rank"] > 1.0
    mets = mon.metrics()
    assert any("sigma_max" in k for k in mets)


def _compress_loop(g, rank, iters):
    import functools
    from jax.sharding import PartitionSpec as P
    from repro.parallel.compression import (CompressionConfig,
                                            compression_init,
                                            compress_and_sync)
    cfgc = CompressionConfig(rank=rank, min_dim=16)
    state = compression_init(cfgc, {"w": g})
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    fn = functools.partial(compress_and_sync, cfg=cfgc, axis_names=("data",))
    shfn = jax.shard_map(fn, mesh=mesh,
                         in_specs=({"w": P()}, {"w": {"q": P(), "err": P("data")}}),
                         out_specs=({"w": P()}, {"w": {"q": P(), "err": P("data")}},
                                    P()),
                         check_vma=False)
    total = jnp.zeros_like(g)
    for _ in range(iters):
        ghat, state, stats = shfn({"w": g}, state)
        total = total + ghat["w"]
    return total / iters, stats


def test_compression_recovers_low_rank_gradient():
    """Warm-started subspace iteration locks onto a rank-4 gradient: the
    reconstruction becomes near-exact and the telescoped EF residual -> 0."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((64, 4)) @ rng.standard_normal((4, 96)),
                    jnp.float32)
    avg, stats = _compress_loop(g, rank=4, iters=8)
    rel = float(jnp.linalg.norm(avg - g) / jnp.linalg.norm(g))
    assert rel < 1e-3, rel
    assert stats["compression_ratio"] > 5


def test_compression_error_feedback_telescopes():
    """Full-rank (white-noise) gradient: the time-averaged compressed signal
    still drifts toward g (EF telescoping), even though per-step rank-4
    capture is small."""
    rng = np.random.default_rng(2)
    g = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    avg4, _ = _compress_loop(g, rank=4, iters=4)
    avg12, _ = _compress_loop(g, rank=4, iters=12)
    rel4 = float(jnp.linalg.norm(avg4 - g) / jnp.linalg.norm(g))
    rel12 = float(jnp.linalg.norm(avg12 - g) / jnp.linalg.norm(g))
    assert rel12 < rel4 < 1.0
