"""Flat (exact brute-force) search — the quality upper bound (paper Table 2).

Chunked over the corpus so the (B, N) score matrix never materialises; the
running top-k merge is the same pattern the ``flat_topk`` Pallas kernel fuses
on TPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core_model import TopK
from ..utils import merge_topk


@partial(jax.jit, static_argnames=("k", "chunk"))
def flat_search(
    embs: jnp.ndarray, queries: jnp.ndarray, *, k: int, chunk: int = 8192
) -> TopK:
    n, d = embs.shape
    b = queries.shape[0]
    pad = (-n) % chunk
    ep = jnp.pad(embs, ((0, pad), (0, 0)))
    n_chunks = ep.shape[0] // chunk
    ec = ep.reshape(n_chunks, chunk, d)

    def body(carry, args):
        ids, scores = carry  # (B, k) running top-k
        chunk_embs, chunk_start = args
        # HIGHEST: the TPU's default f32 matmul is one bf16 pass, and this
        # is the exact reference recall is measured against.
        s = jnp.dot(
            queries, chunk_embs.T, precision=jax.lax.Precision.HIGHEST
        )  # (B, chunk)
        cand_ids = chunk_start + jnp.arange(chunk, dtype=jnp.int32)
        cand_ids = jnp.where(cand_ids < n, cand_ids, -1)
        s = jnp.where(cand_ids[None, :] < 0, -jnp.inf, s)
        top_s, top_i = jax.lax.top_k(s, min(k, chunk))
        top_ids = cand_ids[top_i]
        all_ids = jnp.concatenate([ids, top_ids], axis=-1)
        all_s = jnp.concatenate([scores, top_s], axis=-1)
        m_s, m_i = jax.lax.top_k(all_s, k)
        m_ids = jnp.take_along_axis(all_ids, m_i, axis=-1)
        return (m_ids, m_s), None

    init = (
        jnp.full((b, k), -1, dtype=jnp.int32),
        jnp.full((b, k), -jnp.inf, dtype=jnp.float32),
    )
    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    (ids, scores), _ = jax.lax.scan(body, init, (ec, starts))
    return TopK(ids=ids, scores=scores)
