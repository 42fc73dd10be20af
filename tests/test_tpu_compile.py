"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, scatters, 1-D
layouts, 64-bit indices.  Each test here lowers one Pallas kernel — or the
whole jitted pipeline — for one chip of a described ``v5e:2x2`` topology
and compiles it with the TPU compiler, which runs without a chip.  Nothing
executes, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the tests of this file
must be collected identically by every worker of a parallel run.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bulge_chasing as bc
from repro.core import svd, transforms, tuning
from repro.kernels import bulge_chase, fused_small, hh_apply


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001 — any refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache.
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cached)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(params=[False, True], ids=["x32", "x64"])
def x64(request):
    """Run the compile with jax_enable_x64 off and on: kernel operands and
    indices must stay 32-bit either way."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", request.param)
    yield request.param
    jax.config.update("jax_enable_x64", prev)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# b_in = 32 with tw = 16 (a two-stage plan's first stage), and b_in = tw + 1
# (a stage that ends at bandwidth 1; (32, 31) is the f32 default plan at
# bw = 32); G up to the wavefront of n ~ 8k; the first stages of the f32
# default plans at bw = 64 and bw = 128 (tw = 32), which the streamed path
# runs for bands over the resident budget.
@pytest.mark.parametrize("b_in,tw,g", [(32, 16, 200), (17, 16, 300),
                                       (32, 31, 140), (64, 32, 100),
                                       (128, 32, 50)])
@pytest.mark.parametrize("tape", [False, True], ids=["values", "tape"])
def test_chase_kernel_compiles(one_chip, x64, b_in, tw, g, tape):
    h, w = b_in + 2 * tw + 1, b_in + tw + 1
    _compile(lambda win, first: bulge_chase.chase_cycle_pallas(
                 win, first, b_in=b_in, tw=tw, with_tape=tape),
             _spec(one_chip, (g, h, w)), _spec(one_chip, (g,), jnp.bool_))


def _largest_resident_n(b_in, tw):
    lo, hi = 1, 1 << 20
    while lo < hi:
        mid = (lo + hi + 1) // 2
        fits = (tuning.resident_band_bytes(mid, b_in, tw)
                <= tuning.VMEM_BUDGET_BYTES)
        lo, hi = (mid, hi) if fits else (lo, mid - 1)
    return lo


# the benchmark's stage (n = 1024, the f32 default plan at bw = 32), the
# largest band the resident path admits at that plan, and the stages of the
# f32 default plans at bw = 64 (64 -> 32 -> 1) and bw = 128 (128 -> 96 ->
# 64 -> 32 -> 1) at n = 1024
@pytest.mark.parametrize("n,b_in,tw", [(1024, 32, 31),
                                       (_largest_resident_n(32, 31), 32, 31),
                                       (1024, 64, 32), (1024, 96, 32),
                                       (1024, 128, 32)])
def test_chase_stage_kernel_compiles(one_chip, x64, n, b_in, tw):
    assert tuning.resident_band_bytes(n, b_in, tw) <= tuning.VMEM_BUDGET_BYTES
    compiled = _compile(lambda band: bulge_chase.chase_stage_pallas(
                            band, n=n, b_in=b_in, tw=tw),
                        _spec(one_chip, (1, b_in + 2 * tw + 1, n)))
    assert "tpu_custom_call" in compiled.as_text()


# the same stages recording the reflector tape: the per-cycle DMA of a
# staging slot to the dynamic tape row (b, t) is what Mosaic must accept
@pytest.mark.parametrize("n,b_in,tw", [(1024, 32, 31), (4096, 32, 31),
                                       (1024, 64, 32), (1024, 96, 32),
                                       (1024, 128, 32)])
def test_chase_stage_tape_kernel_compiles(one_chip, x64, n, b_in, tw):
    assert (tuning.resident_band_bytes(n, b_in, tw, tape=True)
            <= tuning.VMEM_BUDGET_BYTES)
    compiled = _compile(lambda band: bulge_chase.chase_stage_pallas(
                            band, n=n, b_in=b_in, tw=tw, with_tape=True),
                        _spec(one_chip, (1, b_in + 2 * tw + 1, n)))
    assert "tpu_custom_call" in compiled.as_text()


def test_uv_pipeline_compiles(one_chip, x64):
    """The full banded SVD the chip runs at n = 1024, bw = 32: its stage 2
    is the band-resident ``chase_stage`` kernel recording the tape, and no
    streamed ``chase_cycle`` is left in it; its replay is the resident
    ``replay_stage`` kernel, and no ``tape_apply`` is left in it."""
    n = 1024
    cfg = tuning.PipelineConfig.resolve(bw=32, backend="pallas",
                                        interpret=False, dtype=jnp.float32,
                                        n=n).kernel()
    assert bc.stage_path(jnp.float32, n=n, b_in=32, tw=cfg.tw, config=cfg,
                         tape=True) == "resident"
    assert transforms.replay_path(jnp.float32, n=n, b_in=32, tw=cfg.tw,
                                  config=cfg) == "resident"
    text = _compile(lambda a: svd._uv_pipeline(a, config=cfg, banded=True),
                    _spec(one_chip, (n, n))).as_text()
    # (``chase_cycle_indices``, the replay's schedule, stays in op metadata)
    assert "chase_stage" in text and not re.search(r"chase_cycle\b", text)
    assert "replay_stage" in text and "tape_apply" not in text


# the benchmark's replay (n = 1024, the f32 default plan at bw = 32: one
# stripe a side), two matrices, and n = 4096 (five 896-column stripes)
@pytest.mark.parametrize("n,b_in,tw,batch", [(1024, 32, 31, 1),
                                             (1024, 32, 31, 2),
                                             (4096, 32, 31, 1)])
def test_replay_stage_kernel_compiles(one_chip, x64, n, b_in, tw, batch):
    _, T, G = bc.stage_schedule(n, b_in, tw)
    acc = _spec(one_chip, (batch, n, n))
    compiled = _compile(lambda ut, vt, v, tau: hh_apply.replay_stage_pallas(
                            ut, vt, v, tau, n=n, b_in=b_in, tw=tw),
                        acc, acc, _spec(one_chip, (batch, T, G, 2, tw + 1)),
                        _spec(one_chip, (batch, T, G, 2)))
    assert "replay_stage" in compiled.as_text()


# stage-1 panel replay at m = 4096 (k = nb = 32) and chase-tape replay
# (k = 1 over tw + 1 rows, one slot per wavefront window).
@pytest.mark.parametrize("s,m,k,w", [(1, 4096, 32, 4096),
                                     (300, 17, 1, 4096)])
def test_tape_apply_compiles(one_chip, x64, s, m, k, w):
    _compile(hh_apply.tape_apply_pallas, _spec(one_chip, (s, m, k)),
             _spec(one_chip, (s, k, k)), _spec(one_chip, (s, m, w)))


@pytest.mark.parametrize("n", [32, 64, 128, tuning.DEFAULT_FUSED_CROSSOVER])
@pytest.mark.parametrize("compute_uv", [False, True], ids=["values", "uv"])
def test_fused_small_kernel_compiles(one_chip, n, compute_uv):
    _compile(lambda a: fused_small.fused_small_svd_pallas(
                 a, bw=16, compute_uv=compute_uv),
             _spec(one_chip, (8, n, n)))


@pytest.mark.parametrize("n,batch", [(4096, 1), (256, 8)])
def test_pallas_pipeline_compiles(one_chip, n, batch):
    """The values pipeline the chip runs (stage 1 -> chase -> bisection),
    with the compiled Pallas kernels in it, not interpret mode; its stage 2
    is the band-resident ``chase_stage`` kernel."""
    cfg = tuning.PipelineConfig.resolve(bw=32, backend="pallas",
                                        interpret=False, dtype=jnp.float32,
                                        n=n).kernel()
    shape = (n, n) if batch == 1 else (batch, n, n)
    compiled = svd._three_stage.lower(_spec(one_chip, shape),
                                      config=cfg).compile()
    # the stage-1 WY apply and the chase
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert bc.stage_path(jnp.float32, n=n, b_in=32, tw=cfg.tw,
                         config=cfg) == "resident"
    assert "chase_stage" in compiled.as_text()
