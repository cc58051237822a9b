"""The seeded corpus and queries, made on the device.

A copy of the program's generator (``data/synthetic.py``:
``_retrieval_corpus`` and ``retrieval_queries``), kept here so that a change
to the program cannot move the yardstick. Passages are unit vectors drawn
from a mixture of Gaussians (one mode per ``points_per_mode`` passages,
``spread`` around it); a query is a passage plus Gaussian noise of scale
``query_noise``, renormalised.

Every stream hangs off ``--seed``: the same seed gives the same corpus,
queries and arrivals.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CORPUS, QUERY_NOISE, BUILD = 0, 1, 2  # fold_in tags of the seed's key streams


def key(seed: int, stream: int) -> jax.Array:
    """The seed's key for one stream (``PRNGKey`` takes 64-bit seeds)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), stream)


def _normalize(x):
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(n, 1e-12)


# One jit, so the draw, the mode gather and the normalisation do not each
# hold a full (N, d) buffer.
@partial(jax.jit, static_argnames=("n", "dim", "n_modes"))
def _corpus(k, spread, *, n: int, dim: int, n_modes: int):
    k1, k2, k3 = jax.random.split(k, 3)
    modes = jax.random.normal(k1, (n_modes, dim))
    assign = jax.random.randint(k2, (n,), 0, n_modes)
    pts = modes[assign] + spread * jax.random.normal(k3, (n, dim))
    return _normalize(pts)


def corpus(seed: int, config: dict) -> jax.Array:
    """(N, d) float32 unit rows on the device."""
    n, dim = int(config["corpus_size"]), int(config["dim"])
    g = config["generator"]
    n_modes = max(16, n // int(g["points_per_mode"]))
    return _corpus(key(seed, CORPUS), jnp.float32(g["spread"]), n=n, dim=dim,
                   n_modes=n_modes)


@jax.jit
def _queries(k, rows_table, rows, noise):
    q = rows_table[rows] + noise * jax.random.normal(
        k, (rows.shape[0], rows_table.shape[1]))
    return _normalize(q)


def queries(seed: int, config: dict, corpus_rows: jax.Array,
            rows: np.ndarray) -> np.ndarray:
    """Host float32 queries (len(rows), d): ``corpus_rows[rows]`` plus noise."""
    q = _queries(key(seed, QUERY_NOISE), corpus_rows,
                 jnp.asarray(rows, jnp.int32),
                 jnp.float32(config["generator"]["query_noise"]))
    return np.asarray(q)
