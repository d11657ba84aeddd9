"""Host spans of the program's calls, kept in memory and marked in traces.

``span(name, **attrs)`` times one host call boundary -- a session call, its
phases, a CSR build -- and does two things with it:

* it enters ``jax.profiler.TraceAnnotation("spinner/" + name)``, so in any
  profiler trace the span sits on the same clock as the device's ``XLA
  Ops`` and an idle gap can be put down to what the host was doing;
* it records a ``Span`` into a bounded in-memory ring, so an operator can
  read the last calls' phases without a profiler (``spans``, ``self_ns``).

Parent links follow a ``contextvars.ContextVar``: a span opened inside
another is its child, and every span under one top-level call shares that
call's ``call_id``.  A new thread starts outside every span.  Recording is
always on and costs one ``perf_counter_ns`` pair, one annotation and one
deque append per span; spans sit only at call boundaries that last
milliseconds or more, never per LPA iteration (the loop runs on device).

The device side carries ``jax.named_scope`` names (``lpa/gather``,
``lpa/scatter``, ``lpa/noise``, ``lpa/propose``, ``lpa/migrate``,
``lpa/halt``, ``lpa/exchange``, ``delta/merge``): metadata of the compiled
program's ops, which a device trace reports beside each op.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import time
from typing import Iterator, List, NamedTuple, Optional

import jax

PREFIX = "spinner/"
CAPACITY = 4096


class Span(NamedTuple):
    """One finished span; times are ``time.perf_counter_ns`` readings."""

    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    call_id: int
    attrs: dict

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
# (span_id, call_id) of the innermost open span of this context
_open: contextvars.ContextVar = contextvars.ContextVar("spinner_span",
                                                       default=None)


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[dict]:
    """Time the ``with`` body as span ``name``.  Yields the span's attrs
    dict, so the body can add what it learns (iterations, bytes, ...);
    the span is recorded when the body ends, also when it raises."""
    parent = _open.get()
    span_id = next(_ids)
    call_id = span_id if parent is None else parent[1]
    token = _open.set((span_id, call_id))
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield attrs
    finally:
        end = time.perf_counter_ns()
        _open.reset(token)
        _ring.append(Span(name, start, end, span_id,
                          None if parent is None else parent[0], call_id,
                          attrs))


def spans(name: Optional[str] = None) -> List[Span]:
    """The recorded spans (the last ``CAPACITY``), oldest start first;
    only those called ``name`` where given."""
    out = [s for s in list(_ring) if name is None or s.name == name]
    return sorted(out, key=lambda s: (s.start_ns, s.span_id))


def children(parent: Span) -> List[Span]:
    """The recorded spans opened directly inside ``parent``."""
    return [s for s in spans() if s.parent_id == parent.span_id]


def self_ns(parent: Span) -> int:
    """``parent``'s duration less the part its children cover."""
    covered, reach = 0, parent.start_ns
    for s in children(parent):
        lo, hi = max(s.start_ns, reach), min(s.end_ns, parent.end_ns)
        if hi > lo:
            covered += hi - lo
            reach = hi
    return parent.duration_ns - covered


def clear() -> None:
    """Forget every recorded span."""
    _ring.clear()
