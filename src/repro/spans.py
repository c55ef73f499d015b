"""Spans of the serving path on the host clock, and each engine call's
compile wait.

``with span(name):`` times a stretch of host code with two reads of
``time.monotonic_ns()`` and adds the duration to the ``Collector`` of
the engine call the thread works for: ``collect()`` opens one on the
calling thread, ``attach(collector)`` lends it to a worker thread.  On a
thread that works for no call a span only times, and ``span.seconds``
is there for its caller (the gateway's delivery).

Spans time the host and sync nothing: device work still in flight when
a span closes is charged to the span that later waits for it.

Compile wait: a ``jax.monitoring`` listener, registered once when this
module is imported, adds the durations of tracing, lowering to MLIR and
backend compilation (or loading from the persistent cache) to the
collector of the thread that compiles, while a span is open on it.
``with compile_wait():`` does the same for a thread that blocks on a
compile started on another (``kernels/ahead.wait``).  Compiles on other
threads, such as the compile-ahead pool or a client's verification, are
charged to no call.  A compile event nested in another of the three (an inner
jit traced inside an outer one) is counted once, in the outer.

``count(name, n)`` adds ``n`` to a count of the engine call, read back
as ``Collector.count(name)``.

``with recording(capacity) as rec:`` keeps the last ``capacity`` closed
spans in ``rec`` as ``(name, parent, call id, thread name, start,
end)``, stamps in ``time.monotonic_ns()``; ``wall_ns`` maps a stamp onto
``time.time_ns()``, the clock of a profiler trace's start.  With no
recording, a span costs its two clock reads and one addition.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax

#: key of the compile wait in a collector's sums
COMPILE = "compile"
_COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})
_IDS = itertools.count(1)

Record = Tuple[str, Optional[str], Optional[int], str, int, int]
_RECORDER: Optional[Deque[Record]] = None


class Collector:
    """Nanoseconds by span name, and ``COMPILE``, over every thread that
    works for one engine call.  Each thread adds to a dict of its own, so
    no lock is needed; read the sums once those threads are done."""

    def __init__(self):
        self.id = next(_IDS)
        self._parts: List[Dict[str, int]] = []

    def _part(self) -> Dict[str, int]:
        part: Dict[str, int] = {}
        self._parts.append(part)
        return part

    def seconds(self, name: str) -> float:
        return sum(p.get(name, 0) for p in self._parts) / 1e9

    def count(self, name: str) -> int:
        """The sum of ``count(name, n)`` over the call's threads."""
        return sum(p.get(name, 0) for p in self._parts)


class _State(threading.local):
    def __init__(self):
        self.col: Optional[Collector] = None
        self.part: Optional[Dict[str, int]] = None
        self.stack: List[str] = []       # names of the open spans
        self.compiling = 0               # open compile events


_STATE = _State()


class span:
    """``with span(name) as s:`` times the block; ``s.seconds`` after."""
    __slots__ = ("name", "start", "end")

    def __init__(self, name: str):
        self.name = name
        self.start = self.end = 0

    def __enter__(self) -> "span":
        _STATE.stack.append(self.name)
        self.start = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end = end = time.monotonic_ns()
        st = _STATE
        st.stack.pop()
        if st.part is not None:
            st.part[self.name] = st.part.get(self.name, 0) + end - self.start
        if _RECORDER is not None:
            _RECORDER.append((self.name, st.stack[-1] if st.stack else None,
                              st.col.id if st.col is not None else None,
                              threading.current_thread().name, self.start,
                              end))

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


def collector() -> Optional[Collector]:
    """The collector of the engine call this thread works for."""
    return _STATE.col


@contextlib.contextmanager
def attach(col: Optional[Collector]):
    """This thread works for ``col``'s engine call inside the block."""
    st = _STATE
    if st.col is col:                    # already, or no call to work for
        yield
        return
    saved = st.col, st.part
    st.col, st.part = col, col._part()
    try:
        yield
    finally:
        st.col, st.part = saved


@contextlib.contextmanager
def collect():
    """A new engine call's collector, attached to this thread."""
    col = Collector()
    with attach(col):
        yield col


def _charge_compile(ns: int) -> None:
    """Charge ``ns`` of compile wait to this thread's engine call, if a
    span is open on it."""
    st = _STATE
    if st.part is not None and st.stack:
        st.part[COMPILE] = st.part.get(COMPILE, 0) + ns


@contextlib.contextmanager
def compile_wait():
    """The block's time is compile wait (a wait on another thread's
    compile)."""
    t = time.monotonic_ns()
    try:
        yield
    finally:
        _charge_compile(time.monotonic_ns() - t)


def _on_start(event: str, _value, **_) -> None:
    # JAX records each compile event's start as a scalar
    if event in _COMPILE_EVENTS:
        _STATE.compiling += 1


def _on_duration(event: str, secs: float, **_) -> None:
    if event not in _COMPILE_EVENTS:
        return
    st = _STATE
    if st.compiling:
        st.compiling -= 1
        if st.compiling:
            return                       # nested: the outer event counts
    _charge_compile(int(secs * 1e9))


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def recording(capacity: int):
    """Keep the last ``capacity`` closed spans, of every thread."""
    global _RECORDER
    saved, _RECORDER = _RECORDER, collections.deque(maxlen=capacity)
    try:
        yield _RECORDER
    finally:
        _RECORDER = saved


def wall_ns(t: int) -> int:
    """The ``time.time_ns()`` of the ``time.monotonic_ns()`` stamp ``t``."""
    return t + time.time_ns() - time.monotonic_ns()


def count(name: str, n: int) -> None:
    """Add ``n`` to the count ``name`` of this thread's engine call, if it
    works for one."""
    part = _STATE.part
    if part is not None:
        part[name] = part.get(name, 0) + n
