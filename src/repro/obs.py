"""Spans on the profiler's clock.

    from repro import obs

    with obs.span("sink.stage", ds=name, bytes=n) as sp:
        ...
        sp.set(pinned_bytes=m)        # attributes known only at the end

A span is on while a JAX profiler session traces this process
(``jax.profiler.start_trace``, or a capture through the profiler server).
It then enters a ``jax.profiler.TraceAnnotation`` with the span's name and
attributes, so the span lands in the same trace as the device operations,
on one clock, from whatever thread it runs on; and it appends a
:class:`Span` record to a bounded in-memory list that :func:`spans` reads.
Records past :data:`MAX_SPANS` are counted (:func:`dropped`), not kept.
The first span of a new ``start_trace`` session drops the records of the
previous one.

With no session active a span checks ``TraceAnnotation.is_enabled()`` and
returns a shared no-op context. Until something else has imported JAX,
tracing is off and this module does not import it: staging and SAVIME run
without JAX.

Counts ride on spans as attributes; the components' ``stats`` stay the
cumulative counters.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import NamedTuple, Optional

MAX_SPANS = 1 << 16          # records kept per profiler session


class Span(NamedTuple):
    """One finished span."""
    name: str
    id: int
    parent: Optional[int]    # id of the enclosing span on the same thread
    thread: str
    t0: float                # time.perf_counter() at entry
    t1: float                # ... and at exit
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Log:
    """The records of one profiler session."""

    _GUARDED_BY = {"records": "_lock", "dropped": "_lock"}

    def __init__(self, session):
        self.session = session   # held, so its identity is never reused
        self.records: list[Span] = []
        self.dropped = 0
        self._lock = threading.Lock()

    def add(self, rec: Span) -> None:
        with self._lock:
            if len(self.records) < MAX_SPANS:
                self.records.append(rec)
            else:
                self.dropped += 1

    def snapshot(self) -> tuple[list[Span], int]:
        with self._lock:
            return list(self.records), self.dropped


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NO_SPAN = _NoSpan()
_ids = itertools.count(1)
_local = threading.local()
_log_lock = threading.Lock()
_log = _Log(None)
_annotation = None           # jax.profiler.TraceAnnotation once JAX is in


def _trace_annotation():
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def _profiler_session():
    """The session ``jax.profiler.start_trace`` opened, or None (none, or a
    capture through the profiler server)."""
    state = getattr(sys.modules.get("jax._src.profiler"), "_profile_state",
                    None)
    return getattr(state, "profile_session", None)


def _log_of(session) -> _Log:
    global _log
    log = _log
    if log.session is session:
        return log
    with _log_lock:
        if _log.session is not session:
            _log = _Log(session)
        return _log


def _stack() -> list[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "attrs", "_ann", "_log", "_id", "_parent", "_t0")

    def __init__(self, annotation, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self._ann = annotation(name, **attrs)

    def set(self, **attrs) -> None:
        """Attach attributes known only once the work is done."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "_Span":
        self._log = _log_of(_profiler_session())
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _stack().pop()
        self._log.add(Span(self.name, self._id, self._parent,
                           threading.current_thread().name, self._t0, t1,
                           self.attrs))
        return False


def span(name: str, **attrs):
    """A context manager marking one piece of work (see the module doc).
    Attribute values are strings, numbers or booleans."""
    annotation = _annotation or _trace_annotation()
    if annotation is None or not annotation.is_enabled():
        return _NO_SPAN
    return _Span(annotation, name, attrs)


def _read() -> tuple[list[Span], int]:
    log = _log
    current = _profiler_session()
    if current is not None and current is not log.session:
        return [], 0             # a new session in which no span ran yet
    return log.snapshot()


def spans(name: Optional[str] = None) -> list[Span]:
    """The records of the active profiler session or, once it has stopped,
    of the last session in which a span ran; only those named ``name`` if
    given."""
    records, _ = _read()
    return records if name is None else [r for r in records
                                         if r.name == name]


def dropped() -> int:
    """Records of the session :func:`spans` reads that the bound dropped."""
    return _read()[1]
