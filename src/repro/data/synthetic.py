"""Deterministic synthetic data generators for every model family.

Real MS MARCO / Wiki-21M embeddings are not available offline; the retrieval
generators produce mixture-of-Gaussians corpora (dense-retrieval embeddings
are strongly clustered — the regime LIDER exploits) and queries that are
perturbed corpus points with known relevant sets, so recall/MRR metrics are
meaningful. ``load_embeddings`` accepts a ``.npy`` drop-in to run the same
benchmarks on real embeddings.

Everything is keyed by (seed, step) — ``batch_at(step)`` is a pure function,
which is what makes restart replay exact (fault_tolerance contract).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.utils import l2_normalize


def load_embeddings(path: str) -> jnp.ndarray:
    return l2_normalize(jnp.asarray(np.load(path), dtype=jnp.float32))


def retrieval_corpus(
    seed: int, n: int, dim: int, *, n_modes: int | None = None, spread: float = 0.35
) -> jnp.ndarray:
    """Clustered unit-norm corpus (N, d). ~256 points/mode approximates the
    local neighborhood density of real passage-embedding spaces."""
    return _retrieval_corpus(
        seed, n=n, dim=dim, n_modes=n_modes or max(16, n // 256), spread=spread
    )


# One jit: generated eagerly, the draw, the mode gather, their sum and the
# normalisation would each hold a full (N, d) f32 buffer at once.
@partial(jax.jit, static_argnames=("n", "dim", "n_modes"))
def _retrieval_corpus(seed, *, n: int, dim: int, n_modes: int, spread: float):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    modes = jax.random.normal(k1, (n_modes, dim))
    assign = jax.random.randint(k2, (n,), 0, n_modes)
    pts = modes[assign] + spread * jax.random.normal(k3, (n, dim))
    return l2_normalize(pts)


def retrieval_queries(
    seed: int, corpus: jnp.ndarray, n_queries: int, *, noise: float = 0.08
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Queries near known corpus points -> (queries (Q,d), seed ids (Q,))."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed ^ 0x5EED))
    ids = jax.random.choice(k1, corpus.shape[0], (n_queries,), replace=False)
    q = corpus[ids] + noise * jax.random.normal(k2, (n_queries, corpus.shape[1]))
    return l2_normalize(q), ids


def lm_batch(seed: int, step: int, *, batch: int, seq: int, vocab: int) -> dict:
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    tokens = jax.random.randint(key, (batch, seq + 1), 0, vocab)
    return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


def recsys_batch(seed: int, step: int, *, kind: str, batch: int, cfg) -> dict:
    key = jax.random.fold_in(jax.random.PRNGKey(seed + 17), step)
    ks = jax.random.split(key, 6)
    if kind == "sasrec":
        return {
            "seq": jax.random.randint(ks[0], (batch, cfg.seq_len), 1, cfg.item_vocab),
            "pos": jax.random.randint(ks[1], (batch, cfg.seq_len), 1, cfg.item_vocab),
            "neg": jax.random.randint(ks[2], (batch, cfg.seq_len), 1, cfg.item_vocab),
        }
    if kind == "two_tower":
        return {
            "user_fields": jax.random.randint(
                ks[0], (batch, cfg.n_user_fields), 0, cfg.field_vocab
            ),
            "item_fields": jnp.concatenate(
                [
                    jax.random.randint(ks[1], (batch, 1), 0, cfg.item_vocab),
                    jax.random.randint(
                        ks[2], (batch, cfg.n_item_fields - 1), 0, cfg.field_vocab
                    ),
                ],
                axis=1,
            ),
        }
    if kind == "din":
        return {
            "history": jax.random.randint(
                ks[0], (batch, cfg.seq_len), 0, cfg.item_vocab
            ),
            "target": jax.random.randint(ks[1], (batch,), 0, cfg.item_vocab),
            "label": jax.random.bernoulli(ks[2], 0.5, (batch,)).astype(jnp.float32),
        }
    if kind == "xdeepfm":
        return {
            "fields": jax.random.randint(
                ks[0], (batch, cfg.n_sparse), 0, cfg.field_vocab
            ),
            "label": jax.random.bernoulli(ks[1], 0.5, (batch,)).astype(jnp.float32),
        }
    raise ValueError(kind)


def random_graph(
    seed: int, n_nodes: int, n_edges: int, d_feat: int, n_classes: int
) -> dict:
    """Random sparse graph with CSR arrays (for the neighbour sampler)."""
    key = jax.random.PRNGKey(seed + 31)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    src = jax.random.randint(k1, (n_edges,), 0, n_nodes)
    dst = jax.random.randint(k2, (n_edges,), 0, n_nodes)
    feat = jax.random.normal(k3, (n_nodes, d_feat))
    labels = jax.random.randint(k4, (n_nodes,), 0, n_classes)
    # CSR by src (for sampling): sort edges by src.
    order = jnp.argsort(src)
    src_s, dst_s = src[order], dst[order]
    counts = jnp.bincount(src_s, length=n_nodes)
    indptr = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])
    return {
        "node_feat": feat,
        "edge_index": jnp.stack([src, dst]).astype(jnp.int32),
        "labels": labels,
        "indptr": indptr.astype(jnp.int32),
        "indices": dst_s.astype(jnp.int32),
    }


def molecule_batch(
    seed: int, step: int, *, n_graphs: int, nodes_per: int, edges_per: int, d_feat: int
) -> dict:
    key = jax.random.fold_in(jax.random.PRNGKey(seed + 47), step)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    n = n_graphs * nodes_per
    e = n_graphs * edges_per
    base = jnp.repeat(jnp.arange(n_graphs) * nodes_per, edges_per)
    src = jax.random.randint(k1, (e,), 0, nodes_per) + base
    dst = jax.random.randint(k2, (e,), 0, nodes_per) + base
    return {
        "node_feat": jax.random.normal(k3, (n, d_feat)),
        "edge_index": jnp.stack([src, dst]).astype(jnp.int32),
        "edge_feat": jax.random.normal(k5, (e, 4)),
        "graph_ids": jnp.repeat(jnp.arange(n_graphs), nodes_per).astype(jnp.int32),
        "n_graphs": n_graphs,
        "graph_targets": jax.random.normal(k4, (n_graphs,)),
    }
