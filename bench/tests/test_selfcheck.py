"""Self-check of the benchmark's yardstick, on the CPU.

    python -m pytest bench/tests -q

Covers the byte count of the chase, the trace reduction on a small
recorded trace, discovery of a configuration, a traffic mix and a metric
by name from files added in a temporary checkout, the shape of
``BENCHMARK.json``, and that a run without a TPU (or without the program)
fails with no result line.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench import trace, work

ROOT = bench_run.ROOT
FIXTURE = os.path.join(ROOT, "bench", "fixtures", "band16.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_chase_bytes_at_n1024_bw32():
    assert work.total_chase_cycles(1024, 32, 31) == 16863
    assert work.chase_window(32, 31) == (95, 64)
    assert work.chase_bytes(1024, 32) == 820_216_320
    assert work.chase_bytes(1024, 32, "bfloat16") == 820_216_320 // 2


def test_op_class_reads_hlo_text():
    assert trace.op_class(
        '%custom-call.3 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), '
        'custom_call_target="tpu_custom_call"') == ("pallas kernel",
                                                    "custom-call.3")
    assert trace.op_class("%gather_fusion.2 = f32[12,95,64]{2,1,0} fusion("
                          "f32[1024,1024]{1,0} %a), kind=kLoop") == (
        "gather", "gather_fusion.2")
    assert trace.op_class("%scatter.1 = f32[4]{0} scatter(f32[4]{0} %a)")[0] \
        == "scatter"
    assert trace.op_class("%while.7 = (s32[]) while((s32[]) %t), "
                          "body=%b")[0] == "control"
    assert trace.op_class("%add.1 = f32[4]{0} add(f32[4]{0} %a)") == (
        "other", "add.1")


def test_union_and_idle():
    assert trace.union([(5, 9), (0, 2), (1, 3), (8, 10)]) == [(0, 3), (5, 10)]
    assert trace.idle_percent({"busy_s": 1.0, "window_s": 4.0}) == 75.0
    assert trace.idle_percent(None) is None


def test_trace_reduction_on_recorded_fixture():
    """A trace of two n = 16, bw = 4 band reductions on one TPU v5 lite, with the
    harness's window, reduce and sleep annotations."""
    got = trace.reduce_xplane(FIXTURE)
    assert got["window_s"] == pytest.approx(0.044065032, abs=1e-9)
    assert got["busy_s"] == pytest.approx(0.005057606, abs=1e-9)
    assert len(got["device_ops"]) == 10
    assert got["device_ops"][:3] == [["scatter:fusion.51", 0.001411999],
                                     ["other:dynamic_slice.17", 0.00078734],
                                     ["gather:fusion.49", 0.000278658]]
    assert ["pallas kernel:chase_cycle_pallas.11", 0.00010903] \
        in got["device_ops"]
    assert set(got["classes"]) >= {"scatter", "gather", "pallas kernel"}
    # the 10 ms sleep between the reductions is the longest idle gap
    assert got["idle_gaps"][0][0] == "bench/sleep"
    assert got["idle_gaps"][0][1] == pytest.approx(0.011087785, abs=1e-9)
    assert {name for name, _s in got["idle_gaps"][1:]} == {"bench/reduce"}


def test_schedule_orders_one_set_of_gaps_by_the_seed():
    import numpy as np
    from bench.loops import open_poisson
    traffic = {"rate": 50, "schedule_seed": 7,
               "sizes": [{"n": 16, "bw": 4, "weight": 1.0}]}
    a, kinds = open_poisson.schedule(traffic, 2.0, np.random.default_rng(1))
    b, _ = open_poisson.schedule(traffic, 2.0,
                                 np.random.default_rng(2**40 + 3))
    assert len(a) == len(kinds) == 100
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(b, prepend=0.0)))
    assert a[-1] == pytest.approx(b[-1]) and a[-1] < 2.0
    assert np.all(np.diff(a) >= 0)


def _snap(tiers, backends, degraded=0, quarantined=()):
    return {"degraded": degraded, "retried": 0, "sharded_retries": 0,
            "quarantined_buckets": list(quarantined),
            "tiers": {t: {"batches": b} for t, b in tiers.items()},
            "bucket_tiers": {f"k{i}": {"backend": be}
                             for i, be in enumerate(backends)}}


def test_path_checks_read_the_window_off_the_engine_counters():
    from bench.loops import open_poisson
    config = {"path": {"tiers": ["fused", "staged"],
                       "backends": ["fused_small", "pallas"]}}
    start = _snap({"fused": 2}, ["fused_small"])
    good = open_poisson.path_checks(
        config, start, _snap({"fused": 9, "staged": 4},
                             ["fused_small", "pallas"]))
    assert {k: c["value"] for k, c in good.items()} == {
        "degraded": 0, "retried": 0, "quarantined": 0,
        "sharded_retries": 0, "off_path": 0}
    bad = open_poisson.path_checks(
        config, start, _snap({"fused": 9, "degraded-ref": 3},
                             ["fused_small", "ref"], degraded=3,
                             quarantined=["k1"]))
    assert {k: c["value"] for k, c in bad.items()} == {
        "degraded": 3, "retried": 0, "quarantined": 1,
        "sharded_retries": 0, "off_path": 4}
    assert all(c["limit"] == 0 for c in bad.values())


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_discovery_by_name_from_added_files(tmp_path):
    root = str(tmp_path)
    bench = {
        "workloads": [{"name": "cellx", "config": "confx", "traffic": "mixx",
                       "chips": 1, "why": "w"}],
        "configs": [{"name": "confx", "source": "s",
                     "file": "bench/configs/confx.json", "reduced": [],
                     "why": "w"}],
        "end_to_end": [{"name": "setup_s"}, {"name": "rate_x",
                                             "workloads": ["cellx"]},
                       {"name": "other", "workloads": ["celly"]}],
        "per_layer": [{"name": "metricx.cell", "moves": "rate_x",
                       "workloads": ["cellx"]},
                      {"name": "metricy", "moves": "other",
                       "workloads": ["celly"]},
                      {"name": "metricz", "moves": "other",
                       "workloads": ["cellx"]}],
    }
    _write(os.path.join(root, "BENCHMARK.json"), json.dumps(bench))
    _write(os.path.join(root, "bench/configs/confx.json"), '{"n": 7}')
    _write(os.path.join(root, "bench/traffic/mixx.json"), '{"loop": "l"}')
    _write(os.path.join(root, "bench/metrics/metricx.cell.py"),
           "def read(run):\n    return run['readings']['x'] * 2\n")
    got_bench, cell, config, traffic = bench_run.cell_spec(root, "cellx")
    assert cell["name"] == "cellx"
    assert config == {"n": 7} and traffic == {"loop": "l"}
    e2e, layer = bench_run.cell_metrics(got_bench, "cellx")
    assert [m["name"] for m in e2e] == ["setup_s", "rate_x"]
    assert [m["name"] for m in layer] == ["metricx.cell", "metricz"]
    assert bench_run.reader(root, "metricx.cell")({"readings": {"x": 4}}) == 8
    with pytest.raises(bench_run.BenchError):
        bench_run.cell_spec(root, "nosuchcell")


def test_benchmark_json_names_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in bench[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for cell in bench["workloads"]:
        spec = bench_run.cell_spec(ROOT, cell["name"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "loops",
                                           spec[3]["loop"] + ".py"))
        reported, layer = bench_run.cell_metrics(bench, cell["name"])
        assert len(reported) >= 2 and layer
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert callable(bench_run.reader(ROOT, m["name"]))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "band1k_values",
         "--seed", "5000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_tpu_fails_with_no_result_line():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_without_the_program_fails_with_no_result_line(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
