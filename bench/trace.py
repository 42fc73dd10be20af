"""From a profiler trace of the measured window to device numbers.

A traced run captures the window with ``jax.profiler`` (the Python tracer
off, so that only the runtime's and the benchmark's own annotations land
on the host's lines) and reduces the ``.xplane.pb`` it writes to:

- ``window_s``: the length of the harness's ``bench/window`` annotation;
- ``busy_s``: the union of the intervals in which an ``XLA Ops`` event ran
  on a chip, clipped to the window and averaged over the chips used;
- ``device_ops``: the ten ops with the most device time, each named by its
  class and HLO instruction (control flow such as a ``while``, which
  contains the ops of its body, is left out of this list);
- ``idle_gaps``: the longest gaps between device ops inside the window,
  each named by the harness or engine span the host was in meanwhile;
- ``modules``: the ten executables with the most device time, from the
  ``XLA Modules`` events that lie wholly inside the window on chip 0, each
  as ``[name, runs, seconds]``.

The op classes are those of ``benchmarks/profile_chase.py``: an op takes
the class of what its optimized HLO instruction does, a fusion the class
of the ops it fuses (Pallas kernel, gather, scatter, dynamic-update-slice,
control flow, other).  The HLO comes from the trace itself: the capture
asks the profiler for each module's HLO proto, which the ``/host:metadata``
plane carries, and an op belongs to the ``XLA Modules`` event that
encloses it on the device's line.  An op whose module's HLO is not in the
trace is classed from its own HLO text alone.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench/window"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# Host annotations that only say "waiting": a gap is named after a more
# specific span when the host was in one for at least half of the gap.
_WAITING = ("bench/sleep", "bench/await")


_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_CLASSES = (("tpu_custom_call", "pallas kernel"), ("scatter", "scatter"),
            ("gather", "gather"),
            ("dynamic-update-slice", "dynamic-update-slice"))


def hlo_classes(text: str) -> dict[str, str]:
    """Instruction name -> class, for every instruction of an optimized
    HLO module's text (a fusion or call takes the class of what it calls;
    ``while`` and ``conditional`` are ``control``)."""
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
        if op in ("while", "conditional"):
            return "control"
        ops = {op}
        if "tpu_custom_call" in line:
            ops.add("tpu_custom_call")
        if op in ("fusion", "call"):
            for callee in _CALLS.findall(line):
                ops |= opcodes(callee)
        for name, cls in _CLASSES:
            if name in ops:
                return cls
        return "other"

    return {name: classify(op, line)
            for instrs in comps.values() for name, op, line in instrs}


def _fields(buf: bytes):
    """(field number, value) pairs of one protobuf message, decoded from
    the wire format: varints as ints, length-delimited fields as bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not handled")
        yield number, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def module_hlo(path: str) -> dict[str, str]:
    """Module name (as the ``XLA Modules`` events give it) -> optimized HLO
    text, from the HLO protos of the trace's ``/host:metadata`` plane:
    XSpace.planes(1) -> XPlane.event_metadata(4) -> XEventMetadata.name(2)
    and .stats(5) -> XStat.bytes_value(6) = HloProto, whose field 1 is the
    HloModuleProto."""
    from jax._src.lib import xla_client
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        fields = list(_fields(plane))
        if (2, b"/host:metadata") not in fields:
            continue
        for number, entry in fields:
            if number != 4:
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            names = [v for n, v in meta if n == 2]
            for n, stat in meta:
                proto = dict(_fields(stat)).get(6) if n == 5 else None
                if proto and names:
                    module = dict(_fields(proto)).get(1)
                    if module:
                        out[names[0].decode()] = xla_client._xla.HloModule \
                            .from_serialized_hlo_module_proto(module) \
                            .to_string()
    return out


def op_class(text: str) -> tuple[str, str]:
    """(class, instruction name) of one ``XLA Ops`` event's HLO text."""
    m = _INSTR.match(text)
    instr, opcode = (m.group(1), m.group(2)) if m else (text.split(" ")[0], "")
    if opcode == "custom-call" and "tpu_custom_call" in text:
        return "pallas kernel", instr
    if opcode in ("while", "conditional", "call"):
        return "control", instr
    for key in ("scatter", "gather", "dynamic-update-slice"):
        if opcode == key or key in instr:
            return key, instr
    if opcode.startswith("copy") or instr.startswith("copy"):
        return "copy", instr
    return "other", instr


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted, disjoint intervals covering ``intervals``."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: int, e: int, lo: int, hi: int):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_xplane(path: str, chips: int = 1) -> dict:
    """Reduce one ``.xplane.pb`` to the numbers above, plus ``classes``
    (device seconds per op class on chip 0) and ``ops_from_s`` /
    ``ops_until_s`` (the first and last device op of chip 0, in seconds from
    the window's start: a capture that dropped events shows as a last op
    well before the window's end); raises ValueError when it holds no
    ``bench/window`` annotation or no device ops."""
    import jax
    prof = jax.profiler.ProfileData.from_file(path)
    host: list[tuple[int, int, str]] = []
    devices: dict[int, list[tuple[int, int, str]]] = {}
    modules: list[tuple[int, int, str]] = []
    for plane in prof.planes:
        m = _DEVICE.match(plane.name)
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                          ev.name) for ev in line.events]
        elif m and int(m.group(1)) < chips:
            dev = int(m.group(1))
            ops = devices.setdefault(dev, [])
            for line in plane.lines:
                evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                        ev.name) for ev in line.events]
                if line.name == "XLA Ops":
                    ops += evs
                elif line.name == "XLA Modules" and dev == 0:
                    modules += evs
    windows = [(s, e) for s, e, name in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = windows[0]
    if not any(devices.values()):
        raise ValueError(f"no device ops of chips 0..{chips - 1} in {path}")

    hlo = module_hlo(path)
    modules.sort()
    starts = [s for s, _e, _n in modules]
    memo: dict[str, dict[str, str]] = {}

    def classify(s: int, text: str) -> tuple[str, str]:
        fallback, instr = op_class(text)
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or modules[i][1] < s or modules[i][2] not in hlo:
            return fallback, instr
        name = modules[i][2]
        if name not in memo:
            memo[name] = hlo_classes(hlo[name])
        return memo[name].get(instr, fallback), instr

    runs: collections.Counter = collections.Counter()
    run_ns: collections.Counter = collections.Counter()
    for s, e, name in modules:
        if lo <= s and e <= hi:
            runs[name] += 1
            run_ns[name] += e - s

    busy = []
    per_op: collections.Counter = collections.Counter()
    per_class: collections.Counter = collections.Counter()
    gaps: list[tuple[int, int]] = []
    spans0: list[tuple[int, int]] = []
    for dev, ops in sorted(devices.items()):
        clipped = [c for s, e, _ in ops if (c := _clip(s, e, lo, hi))]
        spans = union(clipped)
        busy.append(sum(e - s for s, e in spans))
        if dev == 0:
            spans0 = spans
            for s, e, text in ops:
                c = _clip(s, e, lo, hi)
                if not c:
                    continue
                cls, instr = classify(s, text)
                if cls != "control":
                    per_op[f"{cls}:{instr}"] += c[1] - c[0]
                    per_class[cls] += c[1] - c[0]
            edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    busy_ns = sum(busy) / max(len(devices), 1)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "device_ops": [[name, ns / 1e9]
                       for name, ns in per_op.most_common(10)],
        "idle_gaps": [[host_span(host, s, e), (e - s) / 1e9]
                      for s, e in longest],
        "classes": {k: v / 1e9 for k, v in per_class.most_common()},
        "modules": [[name, runs[name], ns / 1e9]
                    for name, ns in run_ns.most_common(10)],
        "ops_from_s": (min(s for s, _e in spans0) - lo) / 1e9 if spans0 else None,
        "ops_until_s": (max(e for _s, e in spans0) - lo) / 1e9 if spans0 else None,
    }


def idle_percent(trace: dict | None) -> float | None:
    """1 - busy / window of a reduced trace, in percent (None untraced)."""
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def host_span(host, s: int, e: int) -> str:
    """Name of the host annotation the host was in during [s, e): a
    specific one (submitting, dispatching, reducing) when it covers at
    least half of the gap, else the one covering most of it, leaving out
    the window itself; ``"none"`` when no annotation of the benchmark or
    the engine overlaps it."""
    cover: collections.Counter = collections.Counter()
    for hs, he, name in host:
        if not name.startswith(("bench/", "serve/")) or name == WINDOW:
            continue
        c = _clip(hs, he, s, e)
        if c:
            cover[name] += c[1] - c[0]
    specific = [(n, ns) for n, ns in cover.most_common() if n not in _WAITING]
    if specific and 2 * specific[0][1] >= e - s:
        return specific[0][0]
    return cover.most_common(1)[0][0] if cover else "none"


class WindowTrace:
    """Profiler capture of the window, in a temporary directory that is
    removed once it is read.  Disabled, every method is a no-op."""

    def __init__(self, enabled: bool, chips: int = 1):
        self.enabled = enabled
        self.chips = chips
        self.dir = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = True
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> dict | None:
        """Stop the capture and return its reduction (None when disabled)."""
        if not self.enabled:
            return None
        import jax
        try:
            jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise ValueError(f"the profiler wrote no trace under {self.dir}")
            return reduce_xplane(max(paths, key=os.path.getmtime), self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
