"""The serving path's one timing helper.

A :class:`Span` times one block of per-batch work in two places at once: a
``jax.profiler.TraceAnnotation`` puts it on the profiler's host plane, on the
same clock as the device trace and tagged with the batch's dispatch sequence
number (``batch=<seq>``), and ``time.perf_counter`` times it, adding the
microseconds to a scalar ``EngineStats`` counter. With the profiler off an
annotation costs about a microsecond, so spans go on batches, never on
single requests.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Span:
    """``with Span(name, batch=seq, stats=s, counter="x_us") as sp:``

    ``name=None`` times without an annotation (a window that holds many
    batches); ``counter=None`` annotates and times but adds to no counter. A
    block that raises adds nothing to the counter. After the block, ``t0``
    is its start on the ``perf_counter`` clock and ``s`` its seconds."""

    __slots__ = ("_ann", "_stats", "_counter", "t0", "s")

    def __init__(self, name: str | None = None, *, batch: int = -1,
                 stats=None, counter: str | None = None):
        self._ann = (None if name is None
                     else TraceAnnotation(name, batch=batch))
        self._stats = stats
        self._counter = counter
        self.t0 = 0.0
        self.s = 0.0

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.s = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._counter is not None and exc_type is None:
            setattr(self._stats, self._counter,
                    getattr(self._stats, self._counter) + self.s * 1e6)
