"""Program spans for the SVD pipeline (DESIGN.md §16).

A span marks one layer boundary of the program on the host: an entry
point, the configuration, packing, one stage, validation, a serve
dispatch.  Spans are always on and path-neutral: opening one never changes
what runs, only what is recorded about it.

* Every span opens a ``jax.profiler.TraceAnnotation`` named
  ``repro/<name>``.  A profiler capture puts these on its own clock, the
  clock of the device planes, so every device gap lines up with the span
  the host was in.  Attributes go into the annotation only while a capture
  is running; otherwise the annotation costs one native call.
* A thread-local stack of the open spans is kept always (a list push and
  pop).  The compile counter below reads its top.
* A span is recorded into a :class:`Tracer` only when one is active: passed
  as ``trace=`` to a ``core.svd`` entry point, given to a serve engine as
  ``tracer=``, activated with :func:`activated` or installed process-wide
  with :func:`install`.  A recorded span's duration is host time; device
  time comes from the profiler trace, by the ``repro.*`` named scopes that
  each stage's jitted body opens.
* Inside jit tracing a span is a shared no-op object: the host times of
  symbolic values mean nothing, and the named scopes carry the stage names
  into each op's HLO ``op_name`` instead.

Stage scopes: :func:`scope` traces a stage's jitted body as a nested
jitted call named ``repro.<stage>`` (``stage1``, ``stage2``, ``stage3``,
``replay``, ``compose``, ``fused``).  The name lands in the ``op_name``
metadata of every op of the stage (``.../jit(repro.stage3)/...``),
whether the stage runs as its own executable or is inlined into a larger
one, which is how a profiler trace's device time is split by stage.  A ``jax.named_scope``
would put the same name into ``op_name`` only: JAX's persistent
compilation cache hashes the module without its debug information, so a
build without the scopes and one with them share cache entries, and a
cache hit returns an executable whose ops carry no stage name.  The nested
call adds a ``repro.<stage>`` function to the module itself, which the
cache key sees; XLA inlines it, so the compiled code is unchanged.

Compile counter: one ``jax.monitoring`` listener, registered when this
module is imported, counts JAX's ``backend_compile_duration`` events (one
per executable compiled or loaded from the persistent cache) and its
``cache_retrieval_time_sec`` events (the loads among them) against the
innermost span open on the compiling thread.  The counts are on each
recorded span (``compiles``, ``cache_loads``), in its JSONL export, and
process-wide in :func:`compile_counts`, which ``obs.prom`` renders as
``repro_compiles_total{span=...}``.  Beside it, :func:`chase_stage_counts`
(``repro_chase_stages_total{path=...}``) counts stage-2 stages by chase
path, :func:`replay_stage_counts` (``repro_replay_stages_total{path=...}``)
chase-tape replays by replay path, and :func:`tape_bytes`
(``repro_tape_bytes_total{stage=...}``) the bytes of reflector tape
recorded.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import itertools
import threading
import time
from typing import Any, Optional

import jax

__all__ = [
    "Span",
    "Tracer",
    "current",
    "activated",
    "install",
    "span",
    "compile_counts",
    "count_chase_stage",
    "chase_stage_counts",
    "count_replay_stage",
    "replay_stage_counts",
    "count_tape_bytes",
    "tape_bytes",
    "scope",
]

PREFIX = "repro/"            # profiler annotation name = PREFIX + span name
SCOPE_PREFIX = "repro."      # named scope of a stage's jitted body
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
NO_SPAN = "none"             # compile counter key outside every span

_ids = itertools.count(1)
_local = threading.local()
_capturing = jax.profiler.TraceAnnotation.is_enabled   # a capture is running


def _stack() -> list:
    """The calling thread's open spans, innermost last."""
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _host_clean() -> bool:
    """True when we are NOT inside jax tracing (host spans are meaningful)."""
    return jax.core.trace_ctx.is_top_level()


class Span:
    """One program span.  Use as a context manager (from :func:`span` or
    :meth:`Tracer.span`); closing it records its host duration, tags an
    error, and hands it to its tracer, if it has one."""

    __slots__ = ("name", "attrs", "children", "span_id", "parent_id",
                 "thread", "t0", "dur_s", "compiles", "cache_loads",
                 "_tracer", "_annotation")

    def __init__(self, name: str, attrs: dict[str, Any],
                 tracer: Optional["Tracer"] = None) -> None:
        self.name = name
        self.attrs = attrs
        self.children: list[Span] = []
        self.span_id = next(_ids)
        self.parent_id: Optional[int] = None
        self.thread = threading.get_ident()
        self.t0 = 0.0
        self.dur_s = 0.0
        self.compiles = 0
        self.cache_loads = 0
        self._tracer = tracer
        self._annotation = None

    def set(self, **attrs: Any) -> "Span":
        """Attach/overwrite attributes mid-span."""
        self.attrs.update(attrs)
        if self._annotation is not None and _capturing():
            self._annotation.set_metadata(**attrs)
        return self

    def __enter__(self) -> "Span":
        _stack().append(self)
        name = PREFIX + self.name
        self._annotation = (jax.profiler.TraceAnnotation(name, **self.attrs)
                            if self.attrs and _capturing()
                            else jax.profiler.TraceAnnotation(name))
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur_s = time.perf_counter() - self.t0
        self._annotation.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:          # defensive: unwind mis-nested exits
            stack.remove(self)
        tracer = self._tracer
        if tracer is not None:
            if exc_type is not None:
                self.attrs["error"] = repr(exc)
            parent = next((s for s in reversed(stack)
                           if s._tracer is tracer), None)
            tracer._record(self, parent)
        return False                 # never swallow exceptions

    # ------------------------------------------------------------------

    def total_child_seconds(self) -> float:
        return sum(c.dur_s for c in self.children)

    def find(self, name: str) -> list["Span"]:
        """All descendants (and self) whose name matches, pre-order."""
        out = [self] if self.name == name else []
        for c in self.children:
            out.extend(c.find(name))
        return out

    def to_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "t0": self.t0,
            "dur_s": self.dur_s,
            "thread": self.thread,
            "compiles": self.compiles,
            "cache_loads": self.cache_loads,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def format(self, indent: int = 0, *, min_ms: float = 0.0) -> str:
        """Human-readable tree: name, duration, compiles, attrs."""
        pad = "  " * indent
        attrs = " ".join(f"{k}={v}" for k, v in self.attrs.items())
        line = f"{pad}{self.name:<24s} {self.dur_s * 1e3:9.3f} ms"
        if self.compiles:
            line += f"  compiles={self.compiles}"
        if attrs:
            line += f"  [{attrs}]"
        lines = [line]
        for c in self.children:
            if c.dur_s * 1e3 >= min_ms:
                lines.append(c.format(indent + 1, min_ms=min_ms))
        return "\n".join(lines)


class _NullSpan:
    """Shared no-op span, returned while jax is tracing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects span trees (thread-safe) and optionally streams each
    closed span as one JSONL line.

    ``tracer.roots`` holds completed top-level spans: one tree per traced
    entry-point call, plus one per span opened with no recorded span of
    this tracer open on its thread (a serve dispatcher thread, say).
    """

    def __init__(self, name: str = "trace",
                 jsonl: Optional[str] = None) -> None:
        self.name = name
        self.roots: list[Span] = []
        self._lock = threading.Lock()
        self._jsonl_file = None
        if jsonl is not None:
            from .export import JsonlExporter
            self._jsonl_file = JsonlExporter(jsonl)

    def _record(self, sp: Span, parent: Optional[Span]) -> None:
        if parent is not None:
            sp.parent_id = parent.span_id
            parent.children.append(sp)
        else:
            with self._lock:
                self.roots.append(sp)
        if self._jsonl_file is not None:
            self._jsonl_file.write_span(sp)

    def span(self, name: str, **attrs: Any):
        """A span recorded into this tracer, whatever the ambient one."""
        if not _host_clean():
            return _NULL_SPAN
        return Span(name, attrs, self)

    def format(self, *, min_ms: float = 0.0) -> str:
        with self._lock:
            roots = list(self.roots)
        return "\n".join(r.format(min_ms=min_ms) for r in roots)

    def close(self) -> None:
        if self._jsonl_file is not None:
            self._jsonl_file.close()


# ----------------------------------------------------------------------
# ambient ("current") tracer plumbing

_current: contextvars.ContextVar[Optional[Tracer]] = contextvars.ContextVar(
    "repro_obs_tracer", default=None)
_global: Optional[Tracer] = None


def current() -> Optional[Tracer]:
    """The active tracer: context-local first, process-global fallback."""
    tr = _current.get()
    return tr if tr is not None else _global


@contextlib.contextmanager
def activated(tracer: Optional[Tracer]):
    """Make ``tracer`` the ambient tracer within this context (and
    thread).  ``activated(None)`` is a no-op passthrough."""
    if tracer is None:
        yield None
        return
    token = _current.set(tracer)
    try:
        yield tracer
    finally:
        _current.reset(token)


def install(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Set (or clear, with None) the process-global fallback tracer —
    visible to ALL threads, unlike :func:`activated`.  Returns the
    previous global."""
    global _global
    prev, _global = _global, tracer
    return prev


def span(name: str, **attrs: Any):
    """A program span ``repro/<name>``, recorded into the ambient tracer
    when one is active; the shared no-op span while jax is tracing."""
    if not _host_clean():
        return _NULL_SPAN
    return Span(name, attrs, current())


def scope(stage: str):
    """Decorator: trace the function's body as a nested jitted call named
    ``repro.<stage>``.  Put it under ``jax.jit`` (whose static arguments are
    then bound here as Python values); positional arguments are the
    stage's arrays."""
    name = SCOPE_PREFIX + stage

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            body = functools.partial(fn, **kwargs)
            body.__name__ = name
            return jax.jit(body)(*args)
        return scoped
    return wrap


# ----------------------------------------------------------------------
# compile counter

_compile_lock = threading.Lock()
_compiles: collections.Counter = collections.Counter()
_cache_loads: collections.Counter = collections.Counter()


def _on_duration_event(event: str, _secs: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        field, counter = "compiles", _compiles
    elif event == CACHE_LOAD_EVENT:
        field, counter = "cache_loads", _cache_loads
    else:
        return
    stack = _stack()
    if stack:
        top = stack[-1]
        setattr(top, field, getattr(top, field) + 1)
    with _compile_lock:
        counter[stack[-1].name if stack else NO_SPAN] += 1


def compile_counts() -> dict[str, dict[str, int]]:
    """Process-wide counts by innermost span name (``"none"`` outside every
    span): ``{"compiles": {...}, "cache_loads": {...}}``, where a compile
    is an executable the backend compiled or loaded from the persistent
    cache, and a cache load is one of the latter."""
    with _compile_lock:
        return {"compiles": dict(_compiles),
                "cache_loads": dict(_cache_loads)}


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


# ----------------------------------------------------------------------
# stage-2 path counter

_chase_stages: collections.Counter = collections.Counter()


def count_chase_stage(path: str) -> None:
    """Count one stage-2 stage of the tile-width plan by the chase path that
    runs it (``"resident"`` or ``"streamed"``, DESIGN.md §9).  Counted where
    the path is chosen: once per call where the pipeline runs eagerly, once
    per trace inside a jitted pipeline."""
    with _compile_lock:
        _chase_stages[path] += 1


def chase_stage_counts() -> dict[str, int]:
    """Process-wide stage-2 stage counts by chase path."""
    with _compile_lock:
        return dict(_chase_stages)


# ----------------------------------------------------------------------
# tape-replay path counter

_replay_stages: collections.Counter = collections.Counter()


def count_replay_stage(path: str) -> None:
    """Count one chase stage's tape replay by the path that runs it
    (``"resident"`` or ``"streamed"``, DESIGN.md §8); counted as
    :func:`count_chase_stage` counts."""
    with _compile_lock:
        _replay_stages[path] += 1


def replay_stage_counts() -> dict[str, int]:
    """Process-wide chase-tape replays by replay path."""
    with _compile_lock:
        return dict(_replay_stages)


# ----------------------------------------------------------------------
# reflector-tape counter

_tape_bytes: collections.Counter = collections.Counter()


def count_tape_bytes(stage: str, nbytes: int) -> None:
    """Count ``nbytes`` of reflector tape recorded by ``stage`` (``"stage1"``
    or ``"stage2"``, DESIGN.md §8), computed from the tape's static shapes
    where it is made; counted as :func:`count_chase_stage` counts."""
    with _compile_lock:
        _tape_bytes[stage] += int(nbytes)


def tape_bytes() -> dict[str, int]:
    """Process-wide bytes of reflector tape recorded, by stage."""
    with _compile_lock:
        return dict(_tape_bytes)
