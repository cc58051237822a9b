"""Plain reference for a corpus searched by inner product of unit vectors:
exact search over every row, with no index, no codes and no kernels.

It takes the seeded corpus and queries the bench made, and nothing the
program made. Float32 matrix products run at ``Precision.HIGHEST`` (on a
TPU the default float32 product is one bfloat16 pass). Queries go in
blocks of ``QUERY_BLOCK`` rows and the corpus in blocks of
``CORPUS_BLOCK``, so the (queries x corpus) score matrix never exists
whole, and every call has the same shapes.

``control_search`` is the same search one precision below the
configuration's float32: bfloat16 operands, float32 accumulation. Served in
the program's place it has to come out as not correct.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
CORPUS_BLOCK = 1 << 16
HIGHEST = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnames=("k", "dtype"))
def _topk_block(corpus, q, *, k: int, dtype):
    n, d = corpus.shape
    blk = min(CORPUS_BLOCK, n)
    n_blk = -(-n // blk)
    pad = n_blk * blk - n
    x = jnp.pad(corpus, ((0, pad), (0, 0))).reshape(n_blk, blk, d)
    prec = HIGHEST if dtype == jnp.float32 else None
    qd = q.astype(dtype)

    def body(carry, xb_i):
        best_s, best_i = carry
        xb, i = xb_i
        s = jnp.einsum("qd,nd->qn", qd, xb.astype(dtype), precision=prec,
                       preferred_element_type=jnp.float32)
        ids = i * blk + jnp.arange(blk, dtype=jnp.int32)
        s = jnp.where(ids < n, s, -jnp.inf)
        ts, ti = jax.lax.top_k(s, k)
        cs = jnp.concatenate([best_s, ts], axis=1)
        ci = jnp.concatenate([best_i, ids[ti]], axis=1)
        ms, mi = jax.lax.top_k(cs, k)
        return (ms, jnp.take_along_axis(ci, mi, axis=1)), None

    init = (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    (s, i), _ = jax.lax.scan(body, init, (x, jnp.arange(n_blk, dtype=jnp.int32)))
    return i, s


@jax.jit
def _scores_block(corpus, q, ids):
    rows = corpus[jnp.clip(ids, 0, corpus.shape[0] - 1)]  # (Q, k, d)
    return jnp.einsum("qd,qkd->qk", q, rows, precision=HIGHEST)


def _blocks(queries: np.ndarray, *extra):
    """Pad to whole QUERY_BLOCKs so every call compiles once."""
    n = len(queries)
    for s in range(0, n, QUERY_BLOCK):
        m = min(QUERY_BLOCK, n - s)
        yield s, m, [np.pad(a[s:s + m], [(0, QUERY_BLOCK - m)] + [(0, 0)] * (a.ndim - 1))
                     for a in (queries, *extra)]


def exact_topk(corpus, queries: np.ndarray, k: int):
    """Exact top-k rows by float32 inner product: ((Q, k) ids, (Q, k) scores)."""
    ids = np.zeros((len(queries), k), np.int64)
    scores = np.zeros((len(queries), k), np.float32)
    for s, m, (q,) in _blocks(queries):
        i, sc = _topk_block(corpus, jnp.asarray(q), k=k, dtype=jnp.float32)
        ids[s:s + m] = np.asarray(i)[:m]
        scores[s:s + m] = np.asarray(sc)[:m]
    return ids, scores


class Answer:
    """A batch of answers, shaped as the engine takes a search's result."""

    def __init__(self, ids, scores):
        self.ids, self.scores = ids, scores


def control_search(corpus):
    """The control as a search function ``(q, k) -> Answer`` over device
    query batches, to serve in the program's place."""
    def search(q, k):
        ids, scores = _topk_block(corpus, q, k=k, dtype=jnp.bfloat16)
        return Answer(ids, scores)
    return search


def scores_of(corpus, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Float32 inner product of each query with each of its (Q, k) rows."""
    out = np.zeros(ids.shape, np.float32)
    for s, m, (q, i) in _blocks(queries, ids.astype(np.int32)):
        out[s:s + m] = np.asarray(_scores_block(corpus, jnp.asarray(q),
                                                jnp.asarray(i)))[:m]
    return out
