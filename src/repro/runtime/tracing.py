"""Spans of the serving path, kept in memory on a clock the caller gives.

A :class:`SpanLog` records ``(name, step, t0, t1, info)`` for every span
opened through :meth:`SpanLog.span`, and opens a
``jax.profiler.TraceAnnotation`` of the same name around it, so that in a
profile the span sits on the host plane, on the device trace's clock.

:class:`~repro.runtime.serve.BatchingEngine` numbers its batches from 1 and
opens, per batch, a ``serve.step`` span whose ``info`` holds the request ids
it served (``rids``), the real width (``width``) and the width the model ran
at (``executed``); the batch's children ``serve.stack``, ``serve.call`` and
``serve.split`` carry the same step id.  ``launch/serve.py: build_model``
opens ``build.init`` and ``build.plan``, outside any batch (step ``None``).
Readers take :attr:`SpanLog.spans` as it is; nothing is written out.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Iterator, NamedTuple

import jax

__all__ = ["Span", "SpanLog"]


class Span(NamedTuple):
    name: str
    step: int | None
    t0: float
    t1: float
    info: dict


class SpanLog:
    """In-memory spans on ``clock``, in the order they closed."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self.clock = clock
        self.spans: list[Span] = []

    @contextmanager
    def span(self, name: str, step: int | None = None) -> Iterator[dict]:
        """Times the block as span ``name`` of batch ``step``; yields the
        span's ``info`` dict, which the block may fill."""
        info: dict = {}
        with jax.profiler.TraceAnnotation(name):
            t0 = self.clock()
            try:
                yield info
            finally:
                self.spans.append(Span(name, step, t0, self.clock(), info))
