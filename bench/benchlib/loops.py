"""The load generator: drives the engine through ``submit`` / ``drain`` /
``result`` for the measured window, on the bench's own clock.

Open loop: every request is due at a time fixed before the window starts
(``traffic.arrival_times``), whether or not the engine has kept up; its
latency runs from that due time to the collection of its answer, so a stall
also delays every request that falls due during it.

Each call into the engine sits in a ``jax.profiler.TraceAnnotation`` span
(``bench.submit``, ``bench.drain``, ``bench.result``, ``bench.sleep``, all
inside ``bench.window``), so a traced run can say what the host was doing
in each gap between device operations.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

GRACE_S = 60.0  # how long past the window's close an answer is waited for


@dataclasses.dataclass
class Window:
    """What one measured window did, request by request (times in s from
    the window's start; NaN where a request was never submitted/answered)."""

    due: np.ndarray  # when each request was due
    submit: np.ndarray
    done: np.ndarray  # when its answer was collected
    answers: list  # what ``engine.result`` gave (None: never answered)
    in_window: np.ndarray  # bool: counted by the end-to-end metrics
    stats_before: dict
    stats_after: dict

    @property
    def stats(self) -> dict:
        """Engine counters accumulated over the window."""
        return {k: self.stats_after[k] - self.stats_before[k]
                for k in self.stats_after}


def _collect(engine, outstanding: dict, answers: list, done: np.ndarray,
             t: float) -> None:
    for rid in list(outstanding):
        r = engine.result(rid)
        if r is not None:
            i = outstanding.pop(rid)
            answers[i] = r
            done[i] = t


def warm(system, queries: np.ndarray, *, batch: int, drain_chunk: int) -> None:
    """Send ``drain_chunk`` full batches through submit / drain / result,
    so the first batches of the window compile nothing (the engine's own
    warm-up calls the search directly, not the way a request goes)."""
    engine = system.engine
    rids = [engine.submit(q) for q in queries[:batch * drain_chunk]]
    while engine.pending_requests:
        engine.drain(max_dispatches=drain_chunk)
    for rid in rids:
        engine.result(rid)


def open_loop(system, queries: np.ndarray, due: np.ndarray, *, seconds: float,
              drain_chunk: int) -> Window:
    """Request ``i`` sends ``queries[i]`` at ``due[i]``."""
    engine = system.engine
    n = len(due)
    submit = np.full(n, np.nan)
    done = np.full(n, np.nan)
    answers: list = [None] * n
    outstanding: dict = {}
    before = system.stats()
    clock = time.perf_counter
    i = 0
    with TraceAnnotation("bench.window"):
        t0 = clock()
        while i < n or outstanding:
            now = clock() - t0
            if now > seconds + GRACE_S:
                break
            if i < n and due[i] <= now:
                with TraceAnnotation("bench.submit"):
                    while i < n and due[i] <= now:
                        rid = engine.submit(queries[i])
                        submit[i] = clock() - t0
                        outstanding[rid] = i
                        i += 1
            if engine.pending_requests:
                with TraceAnnotation("bench.drain"):
                    engine.drain(max_dispatches=drain_chunk)
            if outstanding:
                with TraceAnnotation("bench.result"):
                    _collect(engine, outstanding, answers, done, clock() - t0)
                if not engine.pending_requests and i >= n:
                    break  # nothing queued: what is outstanding never comes
            elif i < n:
                with TraceAnnotation("bench.sleep"):
                    time.sleep(min(max(due[i] - (clock() - t0), 0.0), 1e-3))
    return Window(due=due, submit=submit, done=done, answers=answers,
                  in_window=due < seconds, stats_before=before,
                  stats_after=system.stats())
