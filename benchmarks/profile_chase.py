"""Device-time breakdown of the stage-2 bulge chase on a TPU.

    PYTHONPATH=src python -m benchmarks.profile_chase \\
        [--out chiprun_out/profile_chase]

A seeded random upper-banded f32 matrix (n = 1024, bw = 32, the banded
chase of ``chip_smoke.py``) goes through
``banded_singular_values`` (stage 2 + bisection) under one ``jax.jit``, so
the whole call is one executable whose optimized HLO is known.  One call
warms up, one steady call runs under ``jax.profiler.trace``.  Every device
op of the trace is then classed by what its HLO instruction does: the
Pallas chase kernel (``tpu_custom_call``), an element gather, a scatter,
a dynamic-update-slice, other.  A fusion takes the class of the ops it
fuses.  ``while`` ops contain their body's ops and are reported on their
own, outside the class sums.

Prints the wall time of the traced call, device busy time and span of
the executable (idle share = 1 - busy / span), time per class and the top
ops.  Writes the same as JSON to ``<out>/profile.json`` and keeps the
trace under ``<out>/trace``.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import svd, tuning

N, BW, SEED = 1024, 32, 0

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def hlo_classes(text: str) -> dict[str, str]:
    """Instruction name -> class, for every instruction of an optimized
    HLO module's text."""
    comps: dict[str, list[tuple[str, str, str]]] = {}
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith("HloModule"):   # a computation header
            cur = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append((m.group(1), m.group(2), line))

    memo: dict[str, set[str]] = {}

    def opcodes(comp: str) -> set[str]:
        if comp not in memo:
            memo[comp] = set()
            found = set()
            for _name, op, line in comps.get(comp, ()):
                found.add("tpu_custom_call" if "tpu_custom_call" in line
                          else op)
                if op in ("fusion", "call"):
                    for callee in _CALLS.findall(line):
                        found |= opcodes(callee)
            memo[comp] = found
        return memo[comp]

    def classify(op: str, line: str) -> str:
        if op == "while":
            return "while"
        ops = {op}
        if "tpu_custom_call" in line:
            ops.add("tpu_custom_call")
        if op in ("fusion", "call"):
            for callee in _CALLS.findall(line):
                ops |= opcodes(callee)
        for name, cls in (("tpu_custom_call", "pallas kernel"),
                          ("scatter", "scatter"), ("gather", "gather"),
                          ("dynamic-update-slice", "dynamic-update-slice")):
            if name in ops:
                return cls
        return "other"

    return {name: classify(op, line)
            for instrs in comps.values() for name, op, line in instrs}


def _union(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def device_breakdown(trace_dir: str, classes: dict[str, str]) -> dict:
    """Read the newest xplane under ``trace_dir``: busy time and span of
    the device's executables, and op time per class and per op."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    prof = jax.profiler.ProfileData.from_file(path)
    modules, ops = [], []
    for plane in prof.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.duration_ns, ev.name)
                   for ev in line.events]
            if line.name == "XLA Modules":
                modules += evs
            elif line.name == "XLA Ops":
                ops += evs
    if not modules:
        raise RuntimeError(f"no TPU executables in {path}")
    span = (max(s + d for s, d, _ in modules) - min(s for s, _, _ in modules))
    busy = _union((s, s + d) for s, d, _ in modules)
    per_class = collections.Counter()
    per_op = collections.Counter()
    count = collections.Counter()
    for _s, d, name in ops:
        m = re.match(r"%?([\w.\-]+)", name)
        instr = m.group(1) if m else name
        cls = classes.get(instr, "other")
        per_op[(cls, instr)] += d
        count[(cls, instr)] += 1
        if cls != "while":
            per_class[cls] += d
    return dict(
        busy_s=busy / 1e9, span_s=span / 1e9,
        idle_share=1.0 - busy / span if span else 0.0,
        modules={n: d / 1e9 for _s, d, n in modules},
        classes={k: v / 1e9 for k, v in per_class.most_common()},
        top_ops=[dict(cls=cls, op=op, seconds=ns / 1e9, calls=count[(cls, op)])
                 for (cls, op), ns in per_op.most_common(12)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/profile_chase")
    args = ap.parse_args(argv)

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("profile_chase: needs a TPU (JAX's default backend "
                         f"is {jax.devices()[0].platform!r})")
    os.makedirs(args.out, exist_ok=True)
    rng = np.random.default_rng(SEED)
    dense = np.triu(rng.standard_normal((N, N)))
    band = (dense - np.triu(dense, BW + 1)).astype(np.float32)
    a = jax.device_put(band)
    cfg = tuning.PipelineConfig.resolve(bw=BW, n=N,
                                        dtype=jnp.float32).kernel()
    compiled = jax.jit(lambda m: svd.banded_singular_values(
        m, config=cfg)).lower(a).compile()
    compiled(a).block_until_ready()                           # warm-up
    trace_dir = os.path.join(args.out, "trace")
    with jax.profiler.trace(trace_dir):
        t0 = time.perf_counter()
        compiled(a).block_until_ready()
        wall = time.perf_counter() - t0
    report = device_breakdown(trace_dir, hlo_classes(compiled.as_text()))
    report.update(config=repr(cfg), wall_s=wall)
    print(f"n={N} bw={BW} tw={cfg.tw}: traced call {wall:.4f}s wall; "
          f"device busy {report['busy_s']:.4f}s of {report['span_s']:.4f}s "
          f"span (idle {report['idle_share']:.2%})")
    for cls, sec in report["classes"].items():
        print(f"  {cls:22s} {sec:10.4f}s")
    for row in report["top_ops"]:
        print(f"    {row['seconds']:10.4f}s x{row['calls']:6d} "
              f"[{row['cls']}] {row['op']}")
    with open(os.path.join(args.out, "profile.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
