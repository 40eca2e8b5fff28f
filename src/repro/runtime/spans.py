"""Host spans and counters of the program: a flight recorder.

``span(name, **attrs)`` times a block of host code with
``time.perf_counter_ns()``, notes the enclosing span (per thread) as its
parent, and keeps the record in a bounded in-memory ring. It also enters a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so while
the profiler runs the span lies on the host plane of the device trace, on
the trace's own clock (a host clock stamped here cannot be laid onto the
trace afterwards: the trace's times count from the start of its session).

``begin`` / ``end`` record an async span that opens in one call and closes
in another (a request's wait in the queue); it nests nothing and is not
annotated. ``count`` adds to a per-process integer counter. A listener on
JAX's compile event counts every top-level program XLA compiles (or fetches
from the persistent compile cache) as ``compile.<name>``:
``compile.engine_prefill`` for a new prompt length. A jit called inside
another's trace is inlined into it and not counted.

Recording is always on and writes nothing out: ``records()``,
``counters()`` and ``reset()`` are the readers.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

import jax

__all__ = ["RING", "begin", "count", "counters", "end", "records", "reset",
           "span"]

RING = 65536                      # records kept; the oldest drop out first

_records: collections.deque = collections.deque(maxlen=RING)
_counters: collections.Counter = collections.Counter()
_ids = itertools.count(1)
_local = threading.local()
_on = True                        # off only to measure the recorder's cost
_clock = time.perf_counter_ns


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class span:
    """``with span("engine.step", engine=1): ...`` records one span."""

    __slots__ = ("name", "attrs", "_id", "_parent", "_t0", "_ann")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self._id = None

    def __enter__(self):
        if not _on:
            return self
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc):
        if self._id is None:
            return False
        t1 = _clock()
        self._ann.__exit__(*exc)
        _stack().pop()
        _records.append((self._id, self.name, self._t0, t1, self._parent,
                         self.attrs))
        self._id = None
        return False


def begin(name: str, **attrs):
    """Open an async span; returns the token :func:`end` closes (None while
    recording is off)."""
    if not _on:
        return None
    stack = _stack()
    return (next(_ids), name, _clock(), stack[-1] if stack else None, attrs)


def end(token) -> None:
    """Close the async span ``token`` opened."""
    if token is None:
        return
    sid, name, t0, parent, attrs = token
    _records.append((sid, name, t0, _clock(), parent, attrs))


def count(name: str, n: int = 1) -> None:
    if _on:
        _counters[name] += n


def records() -> list[dict]:
    """The ring's spans in the order they closed: ``id``, ``name``,
    ``start_ns``, ``end_ns``, ``parent`` (an id or None) and ``attrs``."""
    return [{"id": i, "name": n, "start_ns": t0, "end_ns": t1, "parent": p,
             "attrs": a} for i, n, t0, t1, p, a in list(_records)]


def counters() -> dict[str, int]:
    return dict(_counters)


def reset() -> None:
    """Drop every span and counter (span ids keep counting)."""
    _records.clear()
    _counters.clear()


def _compiled(event: str, _secs: float, fun_name: str = "?", **_kw):
    # fun_name is the program's "jit(<function name>)"
    if event == "/jax/core/compile/backend_compile_duration":
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        count(f"compile.{fun_name}")


jax.monitoring.register_event_duration_secs_listener(_compiled)
