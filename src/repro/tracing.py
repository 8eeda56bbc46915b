"""Named spans over the serving path, on the JAX profiler.

``span("decode", live=8)`` is a ``jax.profiler.TraceAnnotation`` named
``rgl.decode``: with a profiler session open (``jax.profiler.start_trace``)
it lands on the host plane of the same trace as the device's programs, on
the trace's clock, with its arguments as event stats; with none open it
costs about a microsecond.  There is no switch and no recorder: whoever
opens a profiler session reads the spans from its trace.

Two more things ride on the spans:

* :func:`compiles` counts XLA backend compiles by the innermost ``rgl.``
  span open on the compiling thread (``""`` outside any span), from a
  ``jax.monitoring`` listener registered at import: which step recompiled.
* Every garbage collection runs inside an ``rgl.gc`` span (a
  ``gc.callbacks`` hook), so a collection that stalls the host is named in
  the trace like any other span.

Span names, nesting and the metric each feeds are listed in ``PERF.md``.
"""
from __future__ import annotations

import gc
import threading

import jax

PREFIX = "rgl."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_Annotation = jax.profiler.TraceAnnotation
_enter, _exit = _Annotation.__enter__, _Annotation.__exit__


class _Stack(threading.local):
    def __init__(self):
        self.names: list = []


_open = _Stack()  # the open spans of each thread, innermost last
_compiles: dict = {}
_lock = threading.Lock()


class _Span(_Annotation):
    __slots__ = ("_name",)

    def __init__(self, name: str, args: dict):
        _Annotation.__init__(self, PREFIX + name, **args)
        self._name = name

    def __enter__(self):
        _open.names.append(self._name)
        _enter(self)
        return self

    def __exit__(self, *exc):
        _exit(self, *exc)
        _open.names.pop()


def span(name: str, **args) -> _Annotation:
    """A ``TraceAnnotation`` named ``rgl.<name>`` with ``args`` as its event
    stats, and the innermost span of its thread while it is open."""
    return _Span(name, args)


def compiles() -> dict:
    """Backend compiles since import, by the innermost open span's name."""
    with _lock:
        return dict(_compiles)


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    names = _open.names
    key = names[-1] if names else ""
    with _lock:
        _compiles[key] = _compiles.get(key, 0) + 1


_gc_span = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = _Annotation(PREFIX + "gc", generation=info["generation"])
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


jax.monitoring.register_event_duration_secs_listener(_on_duration)
gc.callbacks.append(_on_gc)
