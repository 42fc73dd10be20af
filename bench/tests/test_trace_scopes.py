"""Self-check of ``bench/scopes.py``: ``scope_s`` and ``idle_by_span``,
the split of device busy time by the program's stage scopes and of device
idle time by the host span it fell in.

``band16_scoped.xplane.pb`` was recorded on one TPU v5 lite by
``bench/fixtures/record_band16_scoped.py``: two n = 16, bw = 4 band
reductions of the program with its ``repro/`` spans and ``repro.*``
scopes, then one planted compile inside a ``repro/planted`` span.
``band16.reduced.json`` pins what ``bench/trace.py::reduce_xplane`` gives
on the older ``band16.xplane.pb``, a trace of a program without spans or
scopes.
"""

import json
import os

import pytest

from bench import run as bench_run
from bench import scopes, trace

FIXTURES = os.path.join(bench_run.ROOT, "bench", "fixtures")
SCOPED = os.path.join(FIXTURES, "band16_scoped.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return dict(trace.reduce_xplane(SCOPED), **scopes.reduce_scopes(SCOPED))


def test_innermost_cover_splits_windows_by_the_latest_start():
    spans = [(0, 100, "outer"), (20, 40, "inner"), (60, 70, "inner2")]
    got = scopes.innermost_cover(spans, [(10, 30), (35, 65), (90, 120)],
                                default="none")
    assert got == {"outer": 10 + 20 + 10, "inner": 10 + 5, "inner2": 5,
                   "none": 20}
    assert scopes.innermost_cover(spans, [(0, 100)]) == {
        "outer": 70, "inner": 20, "inner2": 10}


_HLO = """HloModule m

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %scatter_fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kCustom, calls=%fc
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fc2, metadata={op_name="jit(f)/jit(repro.stage2)/while/body/jit(repro.stage3)/add"}
  ROOT %while.1 = f32[4]{0} while(f32[4]{0} %fusion.3), condition=%cond, body=%body, metadata={op_name="jit(f)/jit(repro.stage2)/while"}
}
"""


def test_hlo_scopes_take_the_innermost_scope_or_the_callers():
    got = scopes.hlo_scopes(_HLO)
    assert got == {"fusion.3": "repro.stage3", "while.1": "repro.stage2",
                   # no metadata: the while whose body holds it
                   "scatter_fusion.1": "repro.stage2", "p": "repro.stage2"}
    # a module whose scoped instructions share one scope lends it to the
    # instructions the compiler left without metadata
    one = _HLO.replace("jit(repro.stage3)/", "")
    assert set(scopes.hlo_scopes(one).values()) == {"repro.stage2"}
    assert "a" in scopes.hlo_scopes(one)


def test_scope_s_sums_to_the_chips_op_time(scoped):
    assert sum(scoped["scope_s"].values()) == pytest.approx(
        scoped["busy_s"], rel=1e-9)
    assert {"repro.stage2", "repro.stage3"} <= set(scoped["scope_s"])
    assert scoped["scope_s"]["repro.stage2"] > scoped["scope_s"].get(
        scopes.UNSCOPED, 0.0)


def test_idle_by_span_sums_to_the_window_less_busy(scoped):
    assert sum(scoped["idle_by_span"].values()) == pytest.approx(
        scoped["window_s"] - scoped["busy_s"], rel=1e-9)
    assert {"repro/banded_singular_values", "repro/pack", "repro/stage2",
            "repro/extract", "repro/stage3"} <= set(scoped["idle_by_span"])
    # the 10 ms sleep between the reductions is the harness's
    assert scoped["idle_by_span"][scopes.HARNESS] >= 0.010


def test_planted_compile_gap_is_named_by_a_compile_annotation(scoped):
    idle = scoped["idle_by_span"]
    compile_s = sum(s for name, s in idle.items()
                    if name.startswith(scopes.COMPILE_SPANS))
    longest = max(idle, key=idle.get)
    assert longest.startswith(scopes.COMPILE_SPANS), idle
    assert compile_s > idle.get("repro/planted", 0.0)


def test_existing_keys_unchanged_on_band16():
    with open(os.path.join(FIXTURES, "band16.reduced.json")) as f:
        before = json.load(f)
    old = os.path.join(FIXTURES, "band16.xplane.pb")
    got = trace.reduce_xplane(old)
    assert list(got) == list(before)
    for key, value in before.items():
        assert json.dumps(got[key]) == json.dumps(value), key
    # a program without spans or scopes: everything unscoped / harness
    split = scopes.reduce_scopes(old)
    assert split["scope_s"] == {scopes.UNSCOPED: pytest.approx(got["busy_s"])}
    assert list(split["idle_by_span"]) == [scopes.HARNESS]
    assert split["idle_by_span"][scopes.HARNESS] == pytest.approx(
        got["window_s"] - got["busy_s"])
