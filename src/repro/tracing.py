"""In-program spans and a compile counter, on the host's
``time.perf_counter`` clock.

``span(name)`` times a step of the program as ``Span(name, start_s,
end_s, span_id, parent_id)``; the parent is the span open on the same
thread. Spans are kept in memory, the newest ``MAX_SPANS`` of them:
``spans()`` reads them and ``reset()`` clears them. Recording is always
on: a span costs two clock reads and a deque append, a few microseconds
against steps of milliseconds, and adds no wait or copy to the step it
times.

``COMPILES`` counts the programs JAX compiles (or fetches from its
persistent cache) and their seconds. Each compile is also kept as a
``jax.compile`` span under the span open on the compiling thread, so a
step that recompiles shows as such.

``time.perf_counter`` is the clock a profiler trace's host times are
mapped from, so the spans can be laid over a device trace.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

import jax

CLOCK = time.perf_counter
MAX_SPANS = 1 << 16
COMPILE = "jax.compile"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    name: str
    start_s: float
    end_s: float
    span_id: int
    parent_id: int | None


_spans: deque = deque(maxlen=MAX_SPANS)
_ids = itertools.count(1)
_local = threading.local()


def spans() -> list[Span]:
    """The kept spans, in the order they ended."""
    return list(_spans)


def reset() -> None:
    _spans.clear()


def _open() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span(name):`` records one span over the block."""

    __slots__ = ("name", "start", "span_id", "parent_id")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _open()
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(_ids)
        stack.append(self.span_id)
        self.start = CLOCK()
        return self

    def __exit__(self, *exc):
        end = CLOCK()
        _open().pop()
        _spans.append(Span(self.name, self.start, end, self.span_id,
                           self.parent_id))
        return False


class CompileCounter:
    """Programs JAX compiled (or fetched from the persistent cache), their
    seconds, and the persistent-cache hits among them, since import."""

    def __init__(self):
        self.programs, self.seconds, self.cache_hits = 0, 0.0, 0
        self.lock = threading.Lock()

    def report(self) -> str:
        return (f"{self.seconds:.1f} s over {self.programs} programs "
                f"({self.cache_hits} persistent-cache hits)")


COMPILES = CompileCounter()


def _on_duration(event: str, duration: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    with COMPILES.lock:
        COMPILES.programs += 1
        COMPILES.seconds += duration
    end = CLOCK()
    stack = _open()
    _spans.append(Span(COMPILE, end - duration, end, next(_ids),
                       stack[-1] if stack else None))


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        with COMPILES.lock:
            COMPILES.cache_hits += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
