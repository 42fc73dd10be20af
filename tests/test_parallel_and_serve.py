"""Sharding rules, roofline HLO walker, serving engine, and subprocess
integration tests (sharded trainer on 8 fake devices; one real dry-run cell
with the 512-device production mesh)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import smoke_of
from repro.models import build
from repro.parallel.sharding import AxisRules, _SINGLE, _MULTI
from repro.roofline.hlo_parse import parse_module
from repro.serve import Engine, Request, ServeConfig


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_rules_spec_resolution():
    r = AxisRules(_SINGLE)
    assert r.spec(("batch", None, None)) == P(("data",), None, None)
    assert r.spec((None, "model_out")) == P(None, "model")
    # duplicate physical axis is dropped on second use
    assert r.spec(("heads", "kv_heads")) == P("model", None)
    # unknown logical name -> replicated
    assert r.spec(("nope",)) == P(None)


def test_multipod_rules_batch_axes():
    r = AxisRules(_MULTI)
    assert r.spec(("batch",)) == P(("pod", "data"))


def test_prune_spec_divisibility():
    from repro.launch.dryrun import _prune_spec
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    class FakeMesh:
        shape = {"model": 16, "data": 4}
    spec = _prune_spec(P("model", "data", None), (32, 9, 7), FakeMesh())
    assert spec == P("model", None, None)      # 9 % 4 != 0 -> dropped


# ---------------------------------------------------------------------------
# loop-aware HLO walker
# ---------------------------------------------------------------------------

FAKE_HLO = """\
HloModule test

%body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %lhs = f32[8,4]{1,0} parameter(1)
  %rhs = f32[4,16]{1,0} parameter(2)
  %dot.1 = f32[8,16]{1,0} dot(%lhs, %rhs), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), channel_id=1, replica_groups=[2,4]<=[8]
}

%cond.1 (p2: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]) parameter(0)
  %c = s32[] constant(5)
}

ENTRY %main (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %t = (s32[], f32[8,16]) tuple(%a)
  %w = (s32[], f32[8,16]) while(%t), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  %ag = f32[8,16]{1,0} all-gather(%a), channel_id=2, replica_groups=[1,8]<=[8], dimensions={0}
}
"""


def test_parser_scales_loops_and_collectives():
    mc = parse_module(FAKE_HLO)
    assert mc.n_while == 1
    # dot flops: 2*8*16*4 = 1024, x5 trips
    assert mc.dot_flops == pytest.approx(1024 * 5)
    # all-reduce: 8*16*4B * 2*(4-1)/4 factor, x5
    assert mc.coll_bytes["all-reduce"] == pytest.approx(512 * 1.5 * 5)
    # all-gather: result 512B, operand 512/8, receives (8-1) shards
    assert mc.coll_bytes["all-gather"] == pytest.approx(512 / 8 * 7)
    assert mc.coll_counts["all-reduce"] == 5
    assert mc.coll_counts["all-gather"] == 1


def test_parser_fusion_bodies_keep_flops_drop_bytes():
    hlo = """\
HloModule t

%fused_computation (p0: f32[4,4], p1: f32[4,4]) -> f32[4,4] {
  %p0 = f32[4,4]{1,0} parameter(0)
  %p1 = f32[4,4]{1,0} parameter(1)
  %dot.9 = f32[4,4]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x = f32[4,4]{1,0} parameter(0)
  %f = f32[4,4]{1,0} fusion(%x, %x), kind=kOutput, calls=%fused_computation
}
"""
    mc = parse_module(hlo)
    assert mc.dot_flops == pytest.approx(2 * 4 * 4 * 4)
    # bytes: only the fusion op at the call site (result 64B + operands 2x64B)
    assert mc.hbm_bytes == pytest.approx(64 * 3)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_engine_matches_offline_decode():
    cfg = smoke_of("granite-3-2b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    req = Request(uid=1, prompt=[5, 7, 9], max_new_tokens=5)
    eng = Engine(model, params, ServeConfig(max_batch=2, max_seq=32))
    eng.submit(req)
    eng.run()
    # offline reference, batch 1
    caches = model.init_caches(1, 32)
    step = jax.jit(model.decode_step)
    toks, out, cur, k, t = [5, 7, 9], [], 5, 1, 0
    while len(out) < 5:
        logits, caches = step(params, jnp.asarray([[cur]], jnp.int32), caches,
                              jnp.asarray([t]))
        t += 1
        if k < len(toks):
            cur = toks[k]
            k += 1
            continue
        cur = int(jnp.argmax(logits[0, 0, : cfg.vocab]))
        out.append(cur)
    assert req.output == out


def test_engine_continuous_batching_refills():
    cfg = smoke_of("rwkv6-1.6b")          # state-cache arch (attention-free)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(2))
    eng = Engine(model, params, ServeConfig(max_batch=2, max_seq=24))
    rng = np.random.default_rng(0)
    for uid in range(5):
        eng.submit(Request(uid=uid,
                           prompt=list(map(int, rng.integers(1, cfg.vocab, 3))),
                           max_new_tokens=4))
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.output) == 4 for r in done)


# ---------------------------------------------------------------------------
# subprocess integration: sharded trainer + production-mesh dry-run
# ---------------------------------------------------------------------------

@pytest.mark.distributed
def test_sharded_train_step_8dev(subproc):
    code = """
import jax, jax.numpy as jnp
from repro.configs.base import smoke_of
from repro.models import build
from repro.train import Trainer, AdamWConfig
from repro.train.data import DataConfig, batch_at
from repro.parallel.sharding import AxisRules, _SINGLE
from repro.configs.shapes import SUITES
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
rules = AxisRules(_SINGLE, mesh=mesh)
cfg = smoke_of("llama3-8b")
model = build(cfg)
tr = Trainer(model, AdamWConfig(warmup_steps=2, total_steps=20), mesh=mesh, rules=rules)
with mesh:
    state = tr.init_state(jax.random.PRNGKey(0))
    step = tr.jit_train_step(SUITES["train_4k"], state)
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=1)
    for t in range(2):
        batch = {k: jnp.asarray(v) for k, v in batch_at(dc, t).items()}
        state, m = step(state, batch)
assert float(m["loss"]) > 0
print("SHARDED_OK", float(m["loss"]))
"""
    r = subproc(code, devices=8, timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.distributed
def test_compressed_train_step_8dev(subproc):
    code = """
import jax, jax.numpy as jnp, re
from repro.configs.base import smoke_of
from repro.models import build
from repro.train import Trainer, AdamWConfig
from repro.train.data import DataConfig, batch_at
from repro.parallel.sharding import AxisRules, _SINGLE
from repro.parallel.compression import CompressionConfig
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
rules = AxisRules(_SINGLE, mesh=mesh)
cfg = smoke_of("llama3-8b")
model = build(cfg)
tr = Trainer(model, AdamWConfig(warmup_steps=2, total_steps=20), mesh=mesh,
             rules=rules, compression=CompressionConfig(rank=4, min_dim=32))
with mesh:
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=1)
    batch = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}
    step = jax.jit(tr.make_train_step())
    state, m = step(state, batch)
    txt = jax.jit(tr.make_train_step()).lower(state, batch).compile().as_text()
# no full-weight-gradient all-reduce: stacked layer grads f32[2,64,...] and
# embed grads must never cross DP at full size
big = [l for l in txt.splitlines() if "all-reduce(" in l
       and ("f32[2,64,160]" in l or "f32[2,64,320]" in l or "f32[512,64]" in l)]
assert not big, big[:2]
assert float(m["compression_ratio"]) > 3, m["compression_ratio"]
print("COMPRESS_OK", float(m["compression_ratio"]))
"""
    r = subproc(code, devices=8, timeout=600)
    assert "COMPRESS_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.distributed
def test_dryrun_cell_production_mesh(subproc):
    """One real cell through the actual 512-device dry-run path."""
    code = """
import repro.launch.dryrun as dr
import tempfile
out = dr.run_cell("rwkv6-1.6b", "long_500k", "multi", force=True,
                  out_dir=tempfile.mkdtemp())
assert out["status"] == "ok", out
assert out["chips"] == 512
assert out["t_memory"] > 0
print("DRYRUN_OK", out["bottleneck"])
"""
    r = subproc(code, timeout=900)
    assert "DRYRUN_OK" in r.stdout, (r.stdout[-500:], r.stderr[-2000:])


def test_engine_whisper_cross_attention():
    """Enc-dec serving: per-request frames fill the cross-KV cache."""
    cfg = smoke_of("whisper-medium")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(3))
    eng = Engine(model, params, ServeConfig(max_batch=2, max_seq=24))
    rng = np.random.default_rng(1)
    frames = [rng.standard_normal((cfg.enc_seq, cfg.d_model)).astype("f")
              for _ in range(2)]
    for uid in range(2):
        eng.submit(Request(uid=uid, prompt=[3, 5], max_new_tokens=4,
                           frames=frames[uid]))
    done = eng.run()
    assert len(done) == 2 and all(len(r.output) == 4 for r in done)
    # different audio must generally produce different continuations
    # (not guaranteed, but with random weights collisions are ~impossible)
    assert done[0].output != done[1].output


@pytest.mark.distributed
def test_elastic_reshard_restore(subproc, tmp_path):
    """Checkpoint written on 1 device restores onto an 8-device mesh with
    explicit shardings and continues training (elastic scaling)."""
    import jax.numpy as jnp2
    from repro.train import AdamWConfig, Trainer, checkpoint
    from repro.train.data import DataConfig, batch_at
    cfg = smoke_of("granite-3-2b")
    model = build(cfg)
    tr = Trainer(model, AdamWConfig(warmup_steps=1, total_steps=10))
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=4)
    step = jax.jit(tr.make_train_step())
    batch = {k: jnp2.asarray(v) for k, v in batch_at(dc, 0).items()}
    state, m0 = step(state, batch)
    checkpoint.save(str(tmp_path), 1, state)
    code = f"""
import jax, jax.numpy as jnp
from repro.configs.base import smoke_of
from repro.models import build
from repro.train import Trainer, AdamWConfig, checkpoint
from repro.train.data import DataConfig, batch_at
from repro.parallel.sharding import AxisRules, _SINGLE, param_shardings
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
rules = AxisRules(_SINGLE, mesh=mesh)
cfg = smoke_of("granite-3-2b")
model = build(cfg)
tr = Trainer(model, AdamWConfig(warmup_steps=1, total_steps=10), mesh=mesh, rules=rules)
with mesh:
    template = tr.init_state(jax.random.PRNGKey(0))
    shardings = tr.state_shardings(template)
    state = checkpoint.restore({str(tmp_path)!r}, 1, template, shardings)
    dc = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=4)
    batch = {{k: jnp.asarray(v) for k, v in batch_at(dc, 1).items()}}
    step = tr.jit_train_step()
    state, m = step(state, batch)
print("ELASTIC_OK", float(m["loss"]))
"""
    r = subproc(code, devices=8, timeout=600)
    assert "ELASTIC_OK" in r.stdout, (r.stdout[-400:], r.stderr[-2000:])


@pytest.mark.distributed
def test_distributed_halo_chase_8dev(subproc):
    """Beyond-paper: single-matrix bulge chase sharded column-wise over 8
    devices with collective_permute halo exchange — bit-exact vs local."""
    code = """
import numpy as np, jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from repro.core import band as bandmod, bulge_chasing as bc
from repro.core.distributed import reduce_stage_sharded, bidiagonalize_sharded
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
n, bw, tw = 96, 8, 3
a = np.triu(rng.standard_normal((n, n))); a = np.triu(a) - np.triu(a, bw+1)
w = bw + tw + 1
ncols = -(-(n + w) // 8) * 8
packed = bandmod.pad_columns(bandmod.pack(jnp.asarray(a), bw, tw), ncols - n)
out_sh = reduce_stage_sharded(packed, n=n, b_in=bw, tw=tw, mesh=mesh)
ref = bc.reduce_stage_packed(bandmod.pack(jnp.asarray(a), bw, tw), n=n, b_in=bw, tw=tw, backend="ref")
err = float(jnp.max(jnp.abs(out_sh[:, :n] - ref[:, :n])))
assert err < 1e-11, err
d, e = bidiagonalize_sharded(jnp.asarray(a), bw=bw, tw=tw, mesh=mesh)
B = np.diag(np.asarray(d)) + np.diag(np.asarray(e)[1:], 1)
s0 = np.linalg.svd(a, compute_uv=False); s1 = np.linalg.svd(B, compute_uv=False)
assert np.abs(s0 - s1).max() / s0[0] < 1e-11
print("DIST_CHASE_OK", err)
"""
    r = subproc(code, devices=8, timeout=600)
    assert "DIST_CHASE_OK" in r.stdout, (r.stdout[-400:], r.stderr[-2000:])
