"""Structured span tracer for the SVD pipeline (DESIGN.md §16).

A :class:`Span` is a named, attributed interval on the host monotonic
clock (``time.perf_counter``).  Spans nest per-thread (a thread-local
stack), carry arbitrary key/value attributes (``n``, ``bw``, ``dtype``,
``fuse``, ``backend``, ``tier``, ...), and — critically for an async
device runtime — **fence** at close: any JAX arrays registered on the
span are ``block_until_ready``'d before the closing timestamp is taken,
so device work launched inside the span is actually attributed to it
instead of leaking into whichever span happens to call ``np.asarray``
first.

Two integration rules keep the tracer zero-cost and jit-safe:

* **No ambient tracer → no-op.**  Instrumented code calls
  :func:`repro.obs.span`, which returns a singleton null context when no
  tracer is active.  Production paths pay one dict lookup.
* **Inside jit tracing → no-op.**  Host spans make no sense while JAX is
  abstractly tracing a function (the "times" would be trace times of
  symbolic values).  :func:`span` checks ``jax.core.trace_ctx.is_top_level()``
  and degrades to the null span under tracing; device-side attribution
  inside jitted code uses ``jax.named_scope`` instead (§16).

Compile-vs-run attribution: JAX hides compilation inside the first call
of a jitted function.  :meth:`Tracer.jit_call` splits it — the first
dispatch per (name, static args, input avals) lowers and compiles under
an explicit ``<name>/compile`` child span, then executes the compiled
object under ``<name>/run``.  The compiled executable is memoized on the
tracer because (measured on jax 0.4.37) the AOT ``lower().compile()``
path does NOT populate the regular jit call cache — without the memo a
traced run would compile everything twice.

Each span also opens a ``jax.profiler.TraceAnnotation`` for its
duration, so host spans line up with device profiler traces when a
``jax.profiler.trace`` capture is active (DESIGN.md §16).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time
from typing import Any, Callable, Optional

import jax

__all__ = [
    "Span",
    "Tracer",
    "current",
    "activated",
    "install",
    "span",
    "traced_jit_call",
]

_ids = itertools.count(1)


def _host_clean() -> bool:
    """True when we are NOT inside jax tracing (host spans are meaningful)."""
    return jax.core.trace_ctx.is_top_level()


class Span:
    """One timed interval.  Use via ``tracer.span(...)`` as a context
    manager; closing fences registered device values, records duration,
    tags errors, and attaches the span to its parent (or the tracer's
    root list)."""

    __slots__ = ("name", "attrs", "children", "span_id", "parent_id",
                 "thread", "t0", "dur_s", "_tracer", "_fence",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str,
                 attrs: dict[str, Any]) -> None:
        self.name = name
        self.attrs = attrs
        self.children: list[Span] = []
        self.span_id = next(_ids)
        self.parent_id: Optional[int] = None
        self.thread = threading.get_ident()
        self.t0 = 0.0
        self.dur_s = 0.0
        self._tracer = tracer
        self._fence: list[Any] = []
        self._annotation = None

    def set(self, **attrs: Any) -> "Span":
        """Attach/overwrite attributes mid-span."""
        self.attrs.update(attrs)
        return self

    def fence(self, value: Any) -> Any:
        """Register a (pytree of) JAX array(s) to block on at span close,
        so its device work is attributed to THIS span.  Returns value."""
        if value is not None:
            self._fence.append(value)
        return value

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        if stack:
            self.parent_id = stack[-1].span_id
        stack.append(self)
        try:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        except Exception:
            self._annotation = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._fence and exc_type is None:
                jax.block_until_ready(self._fence)
        except Exception:
            pass
        self.dur_s = time.perf_counter() - self.t0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:
                pass
        if exc_type is not None:
            self.attrs["error"] = repr(exc)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:          # defensive: unwind mis-nested exits
            stack.remove(self)
        parent = stack[-1] if stack else None
        self._tracer._record(self, parent)
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
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def format(self, indent: int = 0, *, min_ms: float = 0.0) -> str:
        """Human-readable tree: name, duration, attrs — one line per span."""
        pad = "  " * indent
        attrs = " ".join(f"{k}={v}" for k, v in self.attrs.items())
        line = f"{pad}{self.name:<24s} {self.dur_s * 1e3:9.3f} ms"
        if attrs:
            line += f"  [{attrs}]"
        lines = [line]
        for c in self.children:
            if c.dur_s * 1e3 >= min_ms:
                lines.append(c.format(indent + 1, min_ms=min_ms))
        return "\n".join(lines)


class _NullSpan:
    """Shared no-op span: returned when no tracer is active or jax is
    tracing.  Every method is a cheap no-op so instrumented code never
    branches on tracer presence."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs):
        return self

    def fence(self, value):
        return value


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects span trees (thread-safe) and optionally streams each
    closed span as one JSONL line.

    ``tracer.roots`` holds completed top-level spans (one tree per
    traced entry-point call, plus one per spans opened on threads with
    an empty stack — e.g. serve dispatcher threads).
    """

    def __init__(self, name: str = "trace",
                 jsonl: Optional[str] = None) -> None:
        self.name = name
        self.roots: list[Span] = []
        self._local = threading.local()
        self._lock = threading.Lock()
        self._compiled: dict[Any, Any] = {}   # AOT executable memo
        self._jsonl_path = jsonl
        self._jsonl_file = None
        if jsonl is not None:
            from .export import JsonlExporter
            self._jsonl_file = JsonlExporter(jsonl)

    # ------------------------------------------------------------------

    def _stack(self) -> list[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, sp: Span, parent: Optional[Span]) -> None:
        if parent is not None:
            parent.children.append(sp)
        else:
            with self._lock:
                self.roots.append(sp)
        if self._jsonl_file is not None:
            self._jsonl_file.write_span(sp)

    def span(self, name: str, **attrs: Any):
        """Open a child span of the current thread's innermost span (or a
        new root).  Returns the no-op span while jax is tracing."""
        if not _host_clean():
            return _NULL_SPAN
        return Span(self, name, attrs)

    # ------------------------------------------------------------------
    # compile-vs-run attribution

    @staticmethod
    def _aval_key(x: Any):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return ("aval", tuple(x.shape), str(x.dtype))
        if isinstance(x, (tuple, list)):
            return ("seq", tuple(Tracer._aval_key(v) for v in x))
        return ("lit", x)

    def jit_call(self, name: str, fn: Callable, *args: Any,
                 **static_kwargs: Any) -> Any:
        """Call a jitted ``fn(*args, **static_kwargs)`` with compile/run
        split.  First dispatch per (name, statics, arg avals) lowers and
        compiles under a ``<name>/compile`` child span and memoizes the
        executable (jax's AOT cache is separate from the call cache);
        later dispatches run the memoized executable directly.  Falls
        back to a plain call when ``fn`` has no AOT path.
        """
        if not _host_clean():
            return fn(*args, **static_kwargs)
        try:
            key = (name, tuple(sorted(static_kwargs.items(), key=str)),
                   tuple(self._aval_key(a) for a in args))
            hash(key)
        except TypeError:
            return fn(*args, **static_kwargs)
        compiled = self._compiled.get(key)
        if compiled is None:
            lower = getattr(fn, "lower", None)
            if lower is None:
                # Not a jit entry point — run plainly, mark the parent.
                stack = self._stack()
                if stack:
                    stack[-1].set(compile="unsplit")
                return fn(*args, **static_kwargs)
            try:
                with self.span(f"{name}/compile"):
                    compiled = lower(*args, **static_kwargs).compile()
            except Exception:
                return fn(*args, **static_kwargs)
            self._compiled[key] = compiled
            with self.span(f"{name}/run") as sp:
                return sp.fence(compiled(*args))
        return compiled(*args)

    # ------------------------------------------------------------------

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
    """Module-level convenience: a span on the ambient tracer, or the
    shared no-op span when none is active (or jax is tracing)."""
    tr = current()
    if tr is None:
        return _NULL_SPAN
    return tr.span(name, **attrs)


def traced_jit_call(name: str, fn: Callable, *args: Any,
                    **static_kwargs: Any) -> Any:
    """Module-level convenience: compile/run-split call on the ambient
    tracer, or a plain call when none is active."""
    tr = current()
    if tr is None:
        return fn(*args, **static_kwargs)
    return tr.jit_call(name, fn, *args, **static_kwargs)
