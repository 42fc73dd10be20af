"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig6,vectors] [--smoke]
                                          [--list] [--json PATH]
                                          [--compare BASELINE.json]

``--only`` takes a comma-separated list of EXACT suite names (``--only
kernels_bench`` no longer also pulls in every suite containing the
substring); ``--list`` prints the registered suites; ``--smoke`` runs tiny
shapes — suites that support it are called with ``run(smoke=True)``, the
rest are skipped with a comment row (used as the non-blocking CI perf
probe).  Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH``
additionally writes the same results machine-readably, grouped per suite
plus host metadata (device_kind, device count, dtype defaults) so
baselines and autotune caches are comparable across hosts (the committed
``BENCH_stage2.json`` baseline and the CI workflow artifact are produced
this way).

``--compare BASELINE.json`` is the regression gate: rows are matched by
name against a previously committed ``--json`` report and the run FAILS
(exit 1) when any matched row regresses ``us_per_call`` by more than
``--compare-threshold`` percent (``--compare-warn-only`` downgrades the
failure to a warning — how CI runs it until the noise floor is known).
Rows present on only one side are reported but never fail the gate, and a
baseline recorded on different hardware (device_kind mismatch) downgrades
to warn-only automatically — cross-host numbers are not comparable.

The roofline tables (EXPERIMENTS.md §Roofline) come from the dry-run
artifacts instead: ``python -m repro.roofline.report`` after ``python -m
repro.launch.dryrun``.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

SUITES = ["accuracy", "hyperparams", "occupancy", "scaling", "precision",
          "kernels_bench", "batched", "vectors", "fused_small",
          "serve_load", "stage3"]


def _supports_smoke(fn) -> bool:
    try:
        return "smoke" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def _cpu_model() -> str:
    """Host CPU identity.  ``device_kind`` is just "cpu" on EVERY CPU host,
    so CPU wall-clock baselines need the actual part number to know whether
    they are comparable (a TPU kind like "tpu v5e" already carries it)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.processor() or ""


def _flat_rows(report: dict) -> dict[str, float]:
    """{row name: us_per_call} across every suite of a --json report."""
    flat = {}
    for suite in report.get("suites", {}).values():
        for r in suite.get("rows", []):
            flat[r["name"]] = float(r["us_per_call"])
    return flat


def compare_reports(baseline: dict, current: dict, *,
                    threshold_pct: float) -> tuple[list[str], list[str]]:
    """Match rows by name; return (report lines, failing row names).

    A row fails when its ``us_per_call`` regressed more than
    ``threshold_pct`` percent over the baseline.  Unmatched rows (renamed
    suites, new benchmarks) are listed but never fail.
    """
    base, cur = _flat_rows(baseline), _flat_rows(current)
    lines, failures = [], []
    for name in sorted(set(base) & set(cur)):
        old, new = base[name], cur[name]
        pct = 100.0 * (new - old) / old if old > 0 else 0.0
        verdict = "ok"
        if pct > threshold_pct:
            verdict = f"REGRESSION (> {threshold_pct:g}%)"
            failures.append(name)
        lines.append(f"# compare: {name}: {old:.1f} -> {new:.1f} us "
                     f"({pct:+.1f}%) {verdict}")
    for name in sorted(set(base) - set(cur)):
        lines.append(f"# compare: {name}: only in baseline (skipped)")
    for name in sorted(set(cur) - set(base)):
        lines.append(f"# compare: {name}: new row (no baseline)")
    return lines, failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated exact suite names (see --list)")
    ap.add_argument("--exclude", default="", metavar="NAMES",
                    help="comma-separated exact suite names to skip (e.g. a "
                         "suite a dedicated CI step already runs)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; suites without a smoke mode are skipped")
    ap.add_argument("--list", action="store_true", dest="list_suites",
                    help="print registered suite names and exit")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write per-suite results as JSON to PATH")
    ap.add_argument("--compare", default="", metavar="BASELINE",
                    help="baseline --json report; fail on us_per_call "
                         "regressions beyond --compare-threshold")
    ap.add_argument("--compare-threshold", type=float, default=25.0,
                    metavar="PCT", help="max tolerated regression, percent "
                                        "(default: 25)")
    ap.add_argument("--compare-warn-only", action="store_true",
                    help="report regressions but always exit 0")
    args = ap.parse_args(argv)
    if args.list_suites:
        for name in SUITES:
            print(name)
        return
    selected = SUITES
    if args.only:
        wanted = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(wanted) - set(SUITES))
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; registered: {SUITES}")
        selected = [s for s in SUITES if s in wanted]
    if args.exclude:
        excl = [s.strip() for s in args.exclude.split(",") if s.strip()]
        unknown = sorted(set(excl) - set(SUITES))
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; registered: {SUITES}")
        selected = [s for s in selected if s not in excl]
    print("name,us_per_call,derived")
    from repro.autotune.model import device_kind

    report = {
        "smoke": args.smoke,
        "backend": jax.devices()[0].platform,
        # Host identity: what makes perf baselines (and autotune cache
        # entries, which share the device_kind key axis — hence the same
        # normalization) comparable.
        "device_kind": device_kind(),
        "cpu_model": _cpu_model(),
        "device_count": jax.device_count(),
        "x64": bool(jax.config.jax_enable_x64),
        "default_dtype": str(jnp.zeros(()).dtype),
        "jax": jax.__version__,
        "python": platform.python_version(),
        "machine": platform.machine(),
        "suites": {},
    }
    for mod_name in selected:
        t0 = time.time()
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        if args.smoke and not _supports_smoke(mod.run):
            print(f"# {mod_name} skipped (no smoke mode)", flush=True)
            continue
        lines = mod.run(smoke=True) if args.smoke else mod.run()
        for line in lines:
            print(line, flush=True)
        elapsed = time.time() - t0
        print(f"# {mod_name} done in {elapsed:.1f}s", flush=True)
        report["suites"][mod_name] = {
            "elapsed_s": round(elapsed, 1),
            "rows": [_parse_row(l) for l in lines],
        }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# json written to {args.json}", flush=True)
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        warn_only = args.compare_warn_only
        base_kind = baseline.get("device_kind", "")
        mismatch = ""
        if base_kind != report["device_kind"]:
            # A pre-metadata baseline (no device_kind) is just as
            # non-comparable as a different device: downgrade either way
            # so the gate never blocks on numbers from an unknown host.
            mismatch = (f"baseline device_kind {base_kind!r} vs host "
                        f"{report['device_kind']!r}" if base_kind
                        else "baseline has no device_kind (pre-metadata "
                             "schema)")
        elif (base_kind == "cpu"
              and baseline.get("cpu_model", "") != report["cpu_model"]):
            # "cpu" matches on every CPU host; wall-clock between different
            # parts is not comparable, so the CPU identity is the model
            # string.  Re-baseline from a CI runner's uploaded
            # bench_smoke.json artifact to arm the gate on that hardware.
            mismatch = (f"baseline cpu_model "
                        f"{baseline.get('cpu_model', '')!r} vs host "
                        f"{report['cpu_model']!r}")
        if mismatch:
            print(f"# compare: {mismatch}; cross-host numbers are not "
                  f"comparable -> warn-only", flush=True)
            warn_only = True
        lines, failures = compare_reports(
            baseline, report, threshold_pct=args.compare_threshold)
        for line in lines:
            print(line, flush=True)
        if failures:
            print(f"# compare: {len(failures)} row(s) regressed beyond "
                  f"{args.compare_threshold:g}% vs {args.compare}",
                  flush=True)
            if not warn_only:
                sys.exit(1)
        else:
            print(f"# compare: no regression beyond "
                  f"{args.compare_threshold:g}% vs {args.compare}",
                  flush=True)


if __name__ == "__main__":
    main()
