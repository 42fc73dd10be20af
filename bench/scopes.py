"""The program's stage scopes and spans in a profiler trace of the window.

``reduce_scopes`` reads the same ``.xplane.pb`` as
``bench/trace.py::reduce_xplane`` and gives two more splits of chip 0's
window:

- ``scope_s``: busy seconds by the program's stage scope, the innermost
  ``repro.*`` component of the ``op_name`` that each op's HLO instruction
  carries (``repro.stage2``, ``repro.stage3``, ...); ``unscoped`` for ops
  without one.  Where ops overlap, the one that started last (the
  innermost) takes the time, so the values sum to chip 0's busy time;
- ``idle_by_span``: idle seconds, split at each instant by the innermost
  host event among the program's ``repro/`` spans and JAX's compile and
  lowering annotations (:data:`COMPILE_SPANS`); an instant inside none of
  them is ``harness``.  The values sum to the window less chip 0's busy
  time; every ``repro/`` span seen in the window has an entry, 0 where no
  idle time fell inside it.

A program without spans or scopes gives everything to ``unscoped`` and
``harness``.  The benchmark's loops do not call this yet: the readers of
per-layer metrics see only what ``reduce_xplane`` returns.
"""

from __future__ import annotations

import bisect
import collections
import heapq
import re

from bench.trace import (_CALLS, _COMP, _DEVICE, _INSTR, WINDOW, _clip,
                         module_hlo, union)

# Host annotations of the program's spans, and those JAX puts around
# lowering and compiling a jitted function (``profiler.annotate_function``
# in ``jax/_src/interpreters/pxla.py`` and ``jax/_src/compiler.py``).
PROGRAM_SPAN = "repro/"
COMPILE_SPANS = ("backend_compile", "lower_sharding_computation",
                 "lower_parallel_callable")
HARNESS = "harness"
UNSCOPED = "unscoped"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(?:jit\()?(repro\.[A-Za-z0-9_]+)\)?(?=/|$)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> program scope, for the instructions of an HLO
    module's text that have one.  An instruction's scope is the innermost
    ``repro.*`` component of its ``op_name`` metadata.  The compiler leaves
    some instructions without metadata (a scatter fusion it emits itself,
    the ``while`` of a loop); such an instruction takes the scope of the
    instruction whose called computation holds it, and else the module's
    scope when every scoped instruction of the module has the same one (a
    stage compiled as its own executable)."""
    own: dict[str, str] = {}
    comp_of: dict[str, str] = {}
    caller: dict[str, str] = {}
    cur = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and not line.startswith("HloModule"):
            cur = m.group(1)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if not m or cur is None:
            continue
        name = m.group(1)
        comp_of[name] = cur
        branches = [c.strip().lstrip("%") for group in _BRANCHES.findall(line)
                    for c in group.split(",")]
        for callee in _CALLS.findall(line) + branches:
            caller.setdefault(callee, name)
        op_name = _OP_NAME.search(line)
        scopes = _SCOPE.findall(op_name.group(1)) if op_name else None
        if scopes:
            own[name] = scopes[-1]
    module = set(own.values())
    fallback = module.pop() if len(module) == 1 else None

    def scope(name: str, depth: int = 0):
        if name in own:
            return own[name]
        parent = caller.get(comp_of.get(name))
        if parent is not None and depth < 64:
            return scope(parent, depth + 1)
        return fallback

    return {name: s for name in comp_of if (s := scope(name))}


def innermost_cover(intervals, windows, default=None):
    """Nanoseconds of ``windows`` (sorted, disjoint ``(start, end)``) by the
    key of the interval covering each instant: of ``intervals``
    (``(start, end, key)``) the one that started last wins; an instant no
    interval covers goes to ``default``, or is left out when that is None."""
    evs = sorted(intervals)
    pts = sorted({x for s, e, _k in evs for x in (s, e)}
                 | {x for s, e in windows for x in (s, e)})
    out: collections.Counter = collections.Counter()
    heap: list = []
    i = w = 0
    for a, b in zip(pts, pts[1:]):
        while i < len(evs) and evs[i][0] <= a:
            heapq.heappush(heap, (-evs[i][0], i, evs[i][1], evs[i][2]))
            i += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        while w < len(windows) and windows[w][1] <= a:
            w += 1
        if w == len(windows):
            break
        if windows[w][0] > a:
            continue
        key = heap[0][3] if heap else default
        if key is not None:
            out[key] += b - a
    return out


def reduce_scopes(path: str) -> dict:
    """``scope_s`` and ``idle_by_span`` of one ``.xplane.pb`` (chip 0, the
    ``bench/window`` annotation); raises ValueError when it holds no window
    or no device ops of chip 0."""
    import jax
    prof = jax.profiler.ProfileData.from_file(path)
    host: list[tuple[int, int, str]] = []
    ops: list[tuple[int, int, str]] = []
    modules: list[tuple[int, int, str]] = []
    for plane in prof.planes:
        m = _DEVICE.match(plane.name)
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                          ev.name) for ev in line.events]
        elif m and int(m.group(1)) == 0:
            for line in plane.lines:
                evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                        ev.name) for ev in line.events]
                if line.name == "XLA Ops":
                    ops += evs
                elif line.name == "XLA Modules":
                    modules += evs
    windows = [(s, e) for s, e, name in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = windows[0]
    if not ops:
        raise ValueError(f"no device ops of chip 0 in {path}")

    hlo = module_hlo(path)
    modules.sort()
    starts = [s for s, _e, _n in modules]
    memo: dict[str, dict[str, str]] = {}

    def scope_of(s: int, text: str) -> str:
        m = _INSTR.match(text)
        instr = m.group(1) if m else text.split(" ")[0]
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and modules[i][1] >= s and modules[i][2] in hlo:
            name = modules[i][2]
            if name not in memo:
                memo[name] = hlo_scopes(hlo[name])
            return memo[name].get(instr, UNSCOPED)
        return hlo_scopes(text).get(instr, UNSCOPED)

    scoped = innermost_cover(
        [(s, e, scope_of(s, text)) for s, e, text in ops
         if _clip(s, e, lo, hi)], [(lo, hi)])
    spans = union([c for s, e, _t in ops if (c := _clip(s, e, lo, hi))])
    edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    marks = [(s, e, name) for s, e, name in host
             if name.startswith(PROGRAM_SPAN) or name.startswith(COMPILE_SPANS)]
    idle = {name: 0 for s, e, name in marks
            if name.startswith(PROGRAM_SPAN) and _clip(s, e, lo, hi)}
    idle.update(innermost_cover(marks, gaps, default=HARNESS))
    return {
        "scope_s": {k: v / 1e9 for k, v in scoped.most_common()},
        "idle_by_span": {k: v / 1e9 for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
    }
