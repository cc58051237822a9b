"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and draws, from the run's seed, which corpus rows the queries come from and
when each request is due.

Arrivals are Poisson at ``rate_qps``, the arrival maths of the program's
``serving/traffic.py``. One change keeps runs steady: the gaps are the
exponential distribution's stratified quantiles, the same set for every
seed, put in a seeded order. So every seed offers the same work in the
window and only its order differs.

Queries are ``fresh``: every query comes from its own corpus row, drawn
uniformly without replacement, so no two requests repeat.
"""
from __future__ import annotations

import numpy as np


class TrafficError(ValueError):
    pass


def arrival_times(arrivals: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of every request of the window."""
    if arrivals["kind"] != "poisson":
        raise TrafficError(f"arrivals kind {arrivals['kind']!r} is not poisson")
    rate = float(arrivals["rate_qps"])
    if rate <= 0 or seconds <= 0:
        raise TrafficError("rate_qps and seconds must be positive")
    n = max(1, int(round(seconds * rate)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng.shuffle(gaps)
    return np.cumsum(gaps)


def query_rows(queries: dict, n: int, corpus_size: int,
               rng: np.random.Generator) -> np.ndarray:
    """The corpus row each of the ``n`` requests makes its query from."""
    if queries["kind"] != "fresh":
        raise TrafficError(f"query kind {queries['kind']!r} is not fresh")
    if n > corpus_size:
        raise TrafficError(f"{n} fresh queries from {corpus_size} rows")
    return rng.choice(corpus_size, n, replace=False)
