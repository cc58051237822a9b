"""Faults planted in the layers that decide which passages an answer holds,
ahead of the float32 rescore. A planted fault still returns real passages
with their true scores, so ``score_err`` cannot see it: the recall floor of
``check.judge`` has to. They set the upper readings of that floor
(``bench/control.py --plant``, PERF.md) and ``bench/tests`` sees each one
come out not correct.

  routing   every routed cluster id moves to the next cluster id, so each
            query searches clusters that are not its nearest;
  sketch    the sketch pre-filter keeps candidates at fixed random
            positions instead of the best by Hamming distance;
  codes     the code pass scores a table of zeros, so its provisional
            top-k' is decided by the tie-break alone.

Each replaces one name in the program's ``repro.core.lider`` while it is
planted and clears JAX's caches on the way in and out, so the query path is
traced again with the fault and again without it.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

KINDS = ("routing", "sketch", "codes")


def _routing(lider):
    orig = lider.route_queries

    def route_queries(params, queries, **kw):
        out = orig(params, queries, **kw)
        ids = jnp.where(out.ids >= 0, (out.ids + 1) % params.n_clusters, -1)
        return out._replace(ids=ids)
    return "route_queries", route_queries


def _sketch(lider):
    def sketch_topk_op(sketches, row_ids, queries, *, k, out_ids=None, **_):
        ids = row_ids if out_ids is None else out_ids
        keep = jax.random.permutation(jax.random.PRNGKey(0), ids.shape[-1])[:k]
        surv = ids[:, keep]
        return surv, jnp.zeros(surv.shape, jnp.float32)
    return "sketch_topk_op", sketch_topk_op


def _codes(lider):
    orig = lider.verify_topk_op

    def verify_topk_op(embs, row_ids, queries, *, scales=None, **kw):
        if scales is not None:  # the code pass; the rescore passes no scales
            embs = jnp.zeros_like(embs)
        return orig(embs, row_ids, queries, scales=scales, **kw)
    return "verify_topk_op", verify_topk_op


_MAKERS = {"routing": _routing, "sketch": _sketch, "codes": _codes}


@contextlib.contextmanager
def planted(kind: str | None):
    """Plant fault ``kind`` in the program for the body (None: none)."""
    if kind is None:
        yield
        return
    from repro.core import lider

    name, replacement = _MAKERS[kind](lider)
    orig = getattr(lider, name)
    setattr(lider, name, replacement)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(lider, name, orig)
        jax.clear_caches()
