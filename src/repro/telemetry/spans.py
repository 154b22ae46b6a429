"""Host spans and counters of the served path, recorded while the JAX
profiler records.

Recording turns on with a profiler session (``jax.profiler.trace``,
``start_trace``, TensorBoard's capture) and off with its end; there is no
flag of its own.  While it records, :func:`span` enters a
``jax.profiler.TraceAnnotation``, so the span lies in the profiler's host
plane on the same clock as the device ops, and keeps a record of it in
memory.  With no session running, :func:`span` returns a shared no-op and
:func:`add` returns at once, so an instrumented call site costs one check.

A record is a list ``[name, t0, t1, parent, args]``: ``t0`` and ``t1`` on
``time.perf_counter`` (``t1`` is None while the span is open), ``parent``
the index in :func:`records` of the span it opened inside, or -1, and
``args`` the keyword arguments it opened with plus its counters.  Counters
are inclusive, as a span's time is: :func:`add` adds to the innermost open
span and to every span it opened inside.  Every XLA compilation (a
persistent-cache read included) adds 1 to ``compiles``.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.monitoring

# The one use of a private JAX name: the profiler's own on/off test.
from jax._src.lib import _profiler

__all__ = ["span", "add", "records", "dropped", "clear", "MAX_RECORDS"]

MAX_RECORDS = 1 << 20
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_recording = _profiler.TraceMe.is_enabled
_records: list = []
_dropped = 0
_lock = threading.Lock()   # spans of several threads share the list


class _Off:
    """The span of a call site while nothing records."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, typ, val, tb) -> None:
        return None


_NOOP = _Off()


class _Stack(threading.local):
    def __init__(self):
        self.open: list = []   # (index, args) of the thread's open spans


_stack = _Stack()


class _Span:
    __slots__ = ("rec", "ann")

    def __init__(self, rec: list):
        self.rec = rec
        self.ann = jax.profiler.TraceAnnotation(rec[0])

    def __enter__(self) -> dict:
        open_, rec = _stack.open, self.rec
        rec[3] = open_[-1][0] if open_ else -1
        with _lock:
            open_.append((len(_records), rec[4]))
            _records.append(rec)
        self.ann.__enter__()
        rec[1] = time.perf_counter()
        return rec[4]

    def __exit__(self, *exc) -> None:
        self.rec[2] = time.perf_counter()
        self.ann.__exit__(*exc)
        _stack.open.pop()


def span(name: str, **args):
    """A context manager over one span of host work.

    While the profiler records, it yields the span's ``args`` dict, where
    the caller may set what it learns inside the span; otherwise it yields
    None and records nothing.  Past :data:`MAX_RECORDS` records, spans are
    counted by :func:`dropped` and not kept.
    """
    global _dropped
    if not _recording():
        return _NOOP
    if len(_records) >= MAX_RECORDS:
        with _lock:
            _dropped += 1
        return _NOOP
    return _Span([name, 0.0, None, -1, args])


def add(key: str, n: int = 1) -> None:
    """Add ``n`` to counter ``key`` of the innermost open span and of the
    spans it opened inside; does nothing while nothing records."""
    if not _recording():
        return
    for _, a in _stack.open:
        a[key] = a.get(key, 0) + n


def records() -> list:
    """The records kept since the last :func:`clear`, in opening order."""
    return _records


def dropped() -> int:
    """Spans not kept since the last :func:`clear`, the list being full."""
    return _dropped


def clear() -> None:
    """Forget every record; call it with no span open."""
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        add("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
