"""Host spans of the program.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler session
is active it is written to the host plane of the same trace that holds
the device's operations, so a reader can say what the host was doing
while the device sat idle; with no session active it costs about a
microsecond.  It is always on.  Each span that ends also leaves its
interval on ``time.perf_counter``'s clock in ``RECORD``, the latest
``RECORD.maxlen`` of them, for a reader that has no trace to read.

``SPANS`` names every span the program emits, and ``span`` refuses any
other name, so a reader that looks spans up by these names cannot go
silent after a rename.  Spans stay on the host: a ``jax.named_scope``
inside jitted code lands only in HLO metadata, which the TPU's trace
events do not carry.
"""
from __future__ import annotations

import collections
import time

import jax

SPANS = (
    # InfluenceService answering one batch (core/service.answer_batch):
    "service.prepare",    # query arrays built and copied to the device
    "service.dispatch",   # solve and epilogue enqueued (jit cache lookup)
    "service.wait",       # until the epilogue's outputs are ready
    "service.answers",    # per-query fetches, certificate, Answer objects
)

RECORD: collections.deque = collections.deque(maxlen=1 << 16)
"""(name, start, end) of the latest spans, in seconds of
``time.perf_counter``, oldest first."""


class _Span:
    __slots__ = ("name", "_annotation", "_start")

    def __init__(self, name: str):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._start = time.perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        RECORD.append((self.name, self._start, time.perf_counter()))


def span(name: str) -> _Span:
    """The span ``name`` (one of ``SPANS``), to enter with ``with``."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return _Span(name)
