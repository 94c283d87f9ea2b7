"""Host spans and compile counts of the engine's host side.

``span(name, **ids)`` times one stage of a job on the host.  It enters a
``jax.profiler.TraceAnnotation``, so under ``jax.profiler.trace`` the
span lands on the profiler's host plane, on the clock of the device
ops, and it adds its ``perf_counter`` duration to the :class:`Record`
of the outermost span open on its thread.  The outermost span (the
engine's ``sim/run`` or ``sim/run_batch``) opens the record; every span
inside it carries the outermost span's ids (run number, seed), so the
spans of one job share an identifier in the trace.  With no profiler
running, a span costs two clock reads and an inactive ``TraceMe``.

One listener of JAX's monitoring events counts backend compiles (each
XLA compilation or persistent-cache load, eager ops' included) and
persistent compile-cache hits.  An event that arrives while a span is
open is attributed to the innermost open span of the record;
:func:`count_backend_compiles` counts compiles over any block of code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Iterator, List, Optional

import jax
from jax import monitoring

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Record:
    """What one outermost span saw.  ``seconds``: host time of each of
    its direct child spans, by name, in the order they opened (a span
    nested deeper counts inside its parent's time).  ``compiles`` and
    ``cache_hits``: backend compiles and persistent-cache hits by the
    innermost span open when each arrived.  ``counts``: counters of the
    job's simulated state that its caller copied out, by name."""
    name: str
    ids: dict
    seconds: dict = dataclasses.field(default_factory=dict)
    compiles: dict = dataclasses.field(default_factory=dict)
    cache_hits: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)


_tls = threading.local()          # .stack: [(span name, Record)] open
_listen_lock = threading.Lock()
_listening = False
_counters: List[List[int]] = []   # cells of open count_backend_compiles
_last: Optional[Record] = None


def _stack() -> list:
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def _attribute(field: str) -> None:
    stack = _stack()
    if stack:
        name, rec = stack[-1]
        counts = getattr(rec, field)
        counts[name] = counts.get(name, 0) + 1


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        for cell in _counters:
            cell[0] += 1
        _attribute("compiles")


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        _attribute("cache_hits")


def _listen() -> None:
    """Register the one pair of listeners, on first use."""
    global _listening
    with _listen_lock:
        if not _listening:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _listening = True


@contextlib.contextmanager
def span(name: str, **ids) -> Iterator[Record]:
    """Time the block as stage ``name``; yields the outermost span's
    :class:`Record`.  ``ids`` (ints or strings) apply to an outermost
    span; a span inside one takes its ids."""
    global _last
    _listen()
    stack = _stack()
    rec = stack[0][1] if stack else Record(name, dict(ids))
    with jax.profiler.TraceAnnotation(name, **rec.ids):
        stack.append((name, rec))
        t0 = time.perf_counter()
        try:
            yield rec
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            if len(stack) == 1:
                rec.seconds[name] = rec.seconds.get(name, 0.0) + dt
            elif not stack:
                _last = rec


def last() -> Optional[Record]:
    """The record of the most recent outermost span that closed in this
    process, for a reader that holds no result of the job."""
    return _last


@contextlib.contextmanager
def count_backend_compiles() -> Iterator[List[int]]:
    """Yields a one-cell list accumulating the backend compiles that
    happen while the block runs."""
    _listen()
    cell = [0]
    _counters.append(cell)
    try:
        yield cell
    finally:
        # by identity: two cells holding the same count compare equal
        _counters[:] = [c for c in _counters if c is not cell]
