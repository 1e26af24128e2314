"""Spans, counters and compile time of the program, on the profiler's clock.

Two levels of labels, one vocabulary:

  * named scopes (``TEACHER``, ``STUDENT``, ``GENERATOR``, ``LOSS``) label
    device work: ``jax.named_scope`` inside the jitted stage-2 steps puts
    them into each op's ``op_name`` at compile time, at no run-time cost;
  * ``span(name, **attrs)`` labels host work: a
    ``jax.profiler.TraceAnnotation`` (so the span lands in a profiler
    trace's host plane, on the same clock as the device ops) plus a
    record in a bounded in-memory ring. Spans are coarse (a phase, a
    chunk, an epoch of the python driver) and never go inside jitted
    code.

``count(name, n)`` keeps process-wide counters. ``keep_program`` keeps
what it takes to give a jitted program's compiled HLO text later
(``program_text``): a profiler trace names device ops by HLO instruction
and carries no ``op_name``, so a trace reduction maps instructions to
scopes through that text, after the measured window. One ``jax.monitoring``
listener, registered when this module is imported, charges JAX's
compile events (trace, lowering, backend compile or persistent-cache
load) to the innermost span open on the compiling thread, or to
``(none)``. ``snapshot()`` is the one exporter: a plain dict an operator
``json.dump``s; ``reset()`` clears it.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax

TEACHER, STUDENT, GENERATOR, LOSS = "teacher", "student", "generator", "loss"
SCOPES = (TEACHER, STUDENT, GENERATOR, LOSS)
NO_SPAN = "(none)"
RING_SIZE = 4096

# JAX 0.9's compile stages, each a timed event. Trace events nest (an
# inner jit is traced inside its caller), so a stage is charged only the
# time no event already charged covers. The persistent-cache load runs
# inside the backend compile event and is reported apart, not added.
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/core/compile/backend_compile_duration": "backend_s"}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_RECENT = 64        # top-level compile intervals kept to detect nesting


def _compile_entry() -> dict:
    return {"seconds": 0.0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_load_s": 0.0, "compiles": 0, "cache_hits": 0,
            "cache_misses": 0}


class _Recorder:
    """The process's spans, counters and compile table."""

    def __init__(self):
        self.lock = threading.Lock()
        self.local = threading.local()
        self.reset()

    def reset(self):
        with self.lock:
            self.ring = collections.deque(maxlen=RING_SIZE)
            self.counters: dict = {}
            self.compile: dict = {}
            self.compile_total = 0.0
            self.recent: list = []
            self.programs: dict = {}

    def stack(self) -> list:
        s = getattr(self.local, "stack", None)
        if s is None:
            s = self.local.stack = []
        return s

    def innermost(self) -> str:
        s = self.stack()
        return s[-1] if s else NO_SPAN

    def charge(self, field: str, value):
        """Add ``value`` (seconds, or 1 event) to ``field`` of the
        innermost span's compile entry."""
        with self.lock:
            e = self.compile.setdefault(self.innermost(), _compile_entry())
            e[field] += value
            if field in _STAGES.values():
                e["seconds"] += value
                self.compile_total += value
                e["compiles"] += field == "backend_s"

    def on_span(self, event, start, end, **_):
        field = _STAGES.get(event)
        if field is None:
            return
        # nested events end first: those inside [start, end] are charged
        # already, so this one gets only the rest
        with self.lock:
            inner = [iv for iv in self.recent
                     if iv[0] >= start and iv[1] <= end]
            self.recent = [iv for iv in self.recent if iv not in inner]
            self.recent = (self.recent + [(start, end)])[-_RECENT:]
        covered = sum(e - s for s, e in inner)
        self.charge(field, max(end - start - covered, 0.0))

    def on_duration(self, event, seconds, **_):
        if event == _CACHE_LOAD:
            self.charge("cache_load_s", seconds)

    def on_event(self, event, **_):
        field = _CACHE_EVENTS.get(event)
        if field is not None:
            self.charge(field, 1)


_REC = _Recorder()
jax.monitoring.register_event_time_span_listener(_REC.on_span)
jax.monitoring.register_event_duration_secs_listener(_REC.on_duration)
jax.monitoring.register_event_listener(_REC.on_event)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Label the host work inside: a profiler ``TraceAnnotation`` with
    ``attrs`` as its stats, and a record ``(name, start_ns, end_ns,
    parent, attrs)`` in the ring, ``parent`` being the innermost span
    open on this thread when it opened. Host-side only: never inside
    jitted code."""
    stack = _REC.stack()
    parent = stack[-1] if stack else None
    compiled = _REC.compile_total
    stack.append(name)
    with jax.profiler.TraceAnnotation(name, **attrs):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            stack.pop()
            with _REC.lock:
                _REC.ring.append((name, t0, t1, parent, attrs, compiled))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    with _REC.lock:
        _REC.counters[name] = _REC.counters.get(name, 0) + n


def keep_program(name: str, fn, *args) -> None:
    """Keep, the first time ``name`` is seen, the jitted ``fn`` and the
    shapes, dtypes and shardings of ``args``, for ``program_text``."""
    if name in _REC.programs:
        return
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=getattr(a, "sharding", None)), args)
    with _REC.lock:
        _REC.programs[name] = [fn, shapes, None]


def program_text(name: str):
    """The compiled HLO text of the program kept as ``name``, or None.
    Lowers and compiles it again the first time (a persistent-cache hit
    where the cache is on): call it after the measured window."""
    kept = _REC.programs.get(name)
    if kept is None:
        return None
    if kept[2] is None:
        fn, shapes, _ = kept
        kept[2] = fn.lower(*shapes).compile().as_text()
    return kept[2]


def snapshot() -> dict:
    """Everything recorded since start-up or ``reset()``, as plain data:

    ``spans``: the ring's records, oldest first, each with
    ``compile_s_at_start``, the compile seconds the process had recorded
    when the span opened; ``counters``; ``compile``: per innermost span
    name (``(none)`` outside any), ``seconds`` of trace, lowering and
    backend compile (``trace_s``, ``lower_s``, ``backend_s``; the cache
    load ``cache_load_s`` is part of ``backend_s``), ``compiles``,
    ``cache_hits``, ``cache_misses``; ``compile_s``: their total."""
    with _REC.lock:
        ring = list(_REC.ring)
        return {
            "spans": [{"name": n, "start_ns": t0, "end_ns": t1,
                       "parent": p, "attrs": dict(a),
                       "compile_s_at_start": c}
                      for n, t0, t1, p, a, c in ring],
            "counters": dict(_REC.counters),
            "compile": {k: dict(v) for k, v in _REC.compile.items()},
            "compile_s": _REC.compile_total}


def reset() -> None:
    """Clear spans, counters, the compile table and kept programs."""
    _REC.reset()


__all__ = ["TEACHER", "STUDENT", "GENERATOR", "LOSS", "SCOPES", "NO_SPAN",
           "RING_SIZE", "span", "count", "keep_program", "program_text",
           "snapshot", "reset"]
