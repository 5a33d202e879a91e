"""Host spans and counters of the fused engine, on the profiler's clock.

``span(name, **attrs)`` always opens a ``jax.profiler.TraceAnnotation``:
under ``jax.profiler`` the span lands in the trace beside the device's
ops, with its attributes as event stats; with no profiler running it
costs one annotation enter.  A :class:`Recorder` keeps the spans and
counters in memory as well, for the parts of a run no profiler covers
(set-up: the engine build, the program loads)::

    with tracing.Recorder() as rec:
        eng = FusedEngine(...)
        wq = eng.sgd_epoch(wq, lr, key, batch, steps)
    rec.seconds("vfb2.engine.build"), rec.load_s()

While a recorder is active it also listens to JAX's compile events (the
jaxpr trace, the lowering, the backend compile or persistent-cache load)
and counts each under the program named by the enclosing
``vfb2.dispatch`` span.  Nothing here is switched by an environment
variable or a config field: the recorder is on only inside ``with``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Optional

import jax

#: the span around one call of a built engine program
DISPATCH = "vfb2.dispatch"
#: JAX's compile events, and the counter each is booked under
LOAD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}
#: the counters whose seconds make up a program's load; a cache load is
#: timed inside ``jax.compile`` and is not added again
LOAD_PARTS = ("jax.trace", "jax.lower", "jax.compile")
#: the events JAX also announces at their start (a scalar event): they
#: nest (a jitted function traced inside another's trace or lowering),
#: and only the outermost is counted, so no second is counted twice
_NESTING = tuple(e for e, part in LOAD_EVENTS.items() if part in LOAD_PARTS)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    parent: Optional[int]   # index in Recorder.spans of the enclosing span
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_active: Optional["Recorder"] = None
_local = threading.local()


def _open_spans() -> list:
    """This thread's open recorded spans, innermost last:
    ``(index, name, attrs)``."""
    if not hasattr(_local, "open"):
        _local.open = []
    return _local.open


def _program() -> Optional[str]:
    for _, name, attrs in reversed(_open_spans()):
        if name == DISPATCH:
            return attrs.get("program")
    return None


@contextlib.contextmanager
def span(name: str, **attrs):
    """A host span: a profiler trace annotation, and a record in the
    active :class:`Recorder` if there is one."""
    rec = _active
    with jax.profiler.TraceAnnotation(name, **attrs):
        if rec is None:
            yield
        else:
            with rec._record(name, attrs):
                yield


def count(name: str, n=1) -> None:
    """Add ``n`` to the active recorder's counter ``name``, booked to the
    program of the enclosing ``vfb2.dispatch`` span (None outside one)."""
    rec = _active
    if rec is not None:
        key = (name, _program())
        with rec._lock:
            rec.counters[key] = rec.counters.get(key, 0) + n


class Recorder:
    """Spans and counters kept in memory while the ``with`` block runs.

    ``spans`` is in the order the spans opened; ``counters`` maps
    ``(name, program)`` to a total.  JAX's compile events are counted as
    ``<part>`` (how many) and ``<part>_s`` (seconds), ``<part>`` one of
    the values of :data:`LOAD_EVENTS`.  One recorder is active at a
    time."""

    def __init__(self):
        self.spans: list = []
        self.counters: dict = {}
        self._lock = threading.Lock()

    def __enter__(self):
        global _active
        if _active is not None:
            raise RuntimeError("a tracing.Recorder is already active")
        _active = self
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        global _active
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_scalar_listener(self._on_start)
        _active = None
        return False

    @contextlib.contextmanager
    def _record(self, name, attrs):
        stack = _open_spans()
        with self._lock:
            index = len(self.spans)
            self.spans.append(None)
        parent = stack[-1][0] if stack else None
        stack.append((index, name, attrs))
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            end = time.perf_counter_ns()
            stack.pop()
            self.spans[index] = Span(name, start, end, parent, dict(attrs))

    def _on_start(self, event, value, **_):
        if event in _NESTING:
            _local.load_depth = getattr(_local, "load_depth", 0) + 1

    def _on_duration(self, event, duration, **_):
        part = LOAD_EVENTS.get(event)
        if part is None:
            return
        if event in _NESTING:
            _local.load_depth = max(0, getattr(_local, "load_depth", 1) - 1)
            if _local.load_depth:
                return
        count(part)
        count(part + "_s", duration)

    def seconds(self, name: str) -> float:
        """Seconds of every closed span called ``name``, added up."""
        return sum(s.seconds for s in self.spans
                   if s is not None and s.name == name)

    def total(self, name: str, program=...):
        """Counter ``name`` over every program, or for one ``program``."""
        return sum(v for (n, p), v in self.counters.items()
                   if n == name and (program is ... or p == program))

    def load_s(self, program=...) -> float:
        """Seconds spent making programs runnable: jaxpr trace, lowering,
        backend compile or persistent-cache load."""
        return sum(self.total(part + "_s", program) for part in LOAD_PARTS)
