#!/usr/bin/env python3
"""Run one benchmark cell once; print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell names its configuration (``configs[].file``) and its
traffic mix (``bench/traffic/<traffic>.json``), the mix names its loop
(``bench/loops/<loop>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
entries; it edits none.

``--trace 0`` prints the cell's end-to-end metrics, measured with the
profiler off.  ``--trace 1`` captures the window with ``jax.profiler`` and
prints the cell's per-layer metrics, the device's busy and window seconds,
and a breakdown of device ops and idle gaps.  Both compare every answer
with the float64 reference and print each compared number beside its
limit, last on standard error and under ``checks`` in the result line.

The run fails, with no result line, when the program's sources are not
beside it, when JAX finds no TPU or fewer chips than the cell asks for,
and when the chip's ``device_kind`` is not in ``bench/peaks.json``.
JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, or else in ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
SRC = os.path.join(ROOT, "src")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.harness import BenchError, Context, passed  # noqa: E402


def load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as exc:
        raise BenchError(f"cannot read {path}: {exc}") from None


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"no {name} at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"BENCHMARK.json has no {what} named {name!r}")


def cell_spec(root: str, workload: str):
    """(benchmark, cell, configuration, traffic mix) of one cell, by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The cell's end-to-end metrics and the per-layer metrics it reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    layer = [m for m in bench["per_layer"] if cell in m["workloads"]]
    return e2e, layer


def reader(root: str, metric: str):
    """The ``read(run) -> float | None`` of one per-layer metric."""
    return load_module(os.path.join(root, "bench", "metrics", metric + ".py"),
                       f"bench_metric_{metric.replace('.', '_')}").read


def enable_compile_cache(jax) -> str:
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(jax, chips: int, peaks: dict):
    """The chips to run on and the peaks of their kind."""
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise BenchError(f"JAX found no usable backend: {exc}") from None
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devices, peaks["devices"][kind]


def open_cell(workload: str):
    """Everything a run of one cell needs before its loop starts: (benchmark,
    cell, configuration, traffic, chips, their peaks, compile-cache path).
    Imports JAX, so it starts the TPU runtime; raises BenchError where a run
    has to stop."""
    bench, cell, config, traffic = cell_spec(ROOT, workload)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise BenchError(f"the program's sources are not at {SRC}")
    sys.path.insert(0, SRC)
    import jax
    cache = enable_compile_cache(jax)
    devices, peak = require_chips(jax, cell["chips"],
                                  load_json(os.path.join(BENCH, "peaks.json")))
    return (bench, cell, config, traffic, devices[:cell["chips"]], peak,
            cache)


def measure(ctx: Context, loop_name: str) -> dict:
    """Run the cell's loop, ``bench/loops/<loop_name>.py``."""
    if not os.path.isfile(os.path.join(BENCH, "loops", loop_name + ".py")):
        raise BenchError(f"no loop {loop_name!r} under bench/loops")
    return importlib.import_module(f"bench.loops.{loop_name}").run(ctx)


def result_line(bench, cell, ctx, out, devices, peak) -> dict:
    e2e, layer = cell_metrics(bench, cell["name"])
    values = dict(out["e2e"], setup_s=out["setup_s"])
    metrics = {}
    if ctx.trace:
        run = dict(out, config=ctx.config, traffic=ctx.traffic, peak=peak)
        for m in layer:
            v = reader(ROOT, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": all(passed(c) for c in out["checks"].values()),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device}
    if ctx.trace:
        tr = out["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic, devices, peak, cache = open_cell(
            args.workload)
        ctx = Context(workload=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      config=config, traffic=traffic, t_start=T_START,
                      devices=devices)
        out = measure(ctx, traffic["loop"])
        line = result_line(bench, cell, ctx, out, devices, peak)
    except BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    print(f"# compile cache {cache}; readings "
          f"{json.dumps(out['readings'], default=str)[:4000]}",
          file=sys.stderr)
    if out["trace"]:
        tr = out["trace"]
        print(f"# device seconds per op class {tr['classes']}; executables "
              f"[name, runs, seconds] {tr['modules']}; device ops from "
              f"{tr['ops_from_s']} s to {tr['ops_until_s']} s of a "
              f"{tr['window_s']} s window", file=sys.stderr)
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if passed(c) else 'FAILED'}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
