"""Binary-sketch pre-filter tier (DESIGN.md §Binary sketch tier).

Oracle discipline mirrors the quantized tiers: the packed representation
round-trips exactly, the Pallas pre-filter is bit-identical to the
natural-order NumPy/JAX Hamming oracle across bank liveness patterns, the
sketch table stays byte-exact through upsert and checkpoint, and the full
sketch -> int4/int8 -> rescore ladder is bit-identical to the unfiltered
search at a covering ``sketch_factor``.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, lider, update
from repro.core.utils import recall_at_k
from repro.kernels import ref
from repro.kernels.fused_verify import sketch_prefilter
from repro.kernels.quant import (
    SKETCH_WORD_BITS,
    sketch_rows,
    sketch_width,
    unpack_sketch,
)
from repro.training import checkpoint

CFG = lider.LiderConfig(
    n_clusters=32, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10
)


def _cfg(storage_dtype, **kw):
    return dataclasses.replace(CFG, storage_dtype=storage_dtype, **kw)


# ---------------------------------------------------------------------------
# Packing: round-trip + padding conventions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [1, 31, 32, 33, 64, 96, 100])
def test_sketch_pack_unpack_roundtrip(d):
    """Deterministic round-trip at the width edge cases (the hypothesis
    sweep below explores the space when the optional dep is present)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(17, d)).astype(np.float32)
    x[3] = 0.0  # all-zero (padded-slot) row
    words = sketch_rows(jnp.asarray(x))
    assert words.shape == (17, sketch_width(d))
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_sketch(words, d)), x > 0)
    # Zero rows pack to zero words; bits past d stay zero on every row (so
    # they XOR away against the identically-padded query sketch).
    np.testing.assert_array_equal(np.asarray(words[3]), 0)
    if d % SKETCH_WORD_BITS:
        full = unpack_sketch(words, sketch_width(d) * SKETCH_WORD_BITS)
        assert not np.asarray(full)[:, d:].any()


def test_sketch_pack_unpack_roundtrip_hypothesis():
    pytest.importorskip("hypothesis")  # optional dep: deterministic test above
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(1, 130), st.integers(0, 2**31 - 1))
    def inner(d, seed):
        rng = np.random.default_rng(seed)
        # signs including exact zeros (strict > 0 predicate)
        x = rng.choice([-1.0, 0.0, 1.0], size=(4, d)).astype(np.float32)
        words = sketch_rows(jnp.asarray(x))
        np.testing.assert_array_equal(
            np.asarray(unpack_sketch(words, d)), x > 0
        )

    inner()


def test_sketch_hamming_scores_are_exact():
    """ref scores == the independent NumPy bit-count Hamming, negated."""
    rng = np.random.default_rng(3)
    n, d, b, c = 40, 50, 4, 12
    embs = rng.normal(size=(n, d)).astype(np.float32)
    q = rng.normal(size=(b, d)).astype(np.float32)
    ids = rng.integers(0, n, size=(b, c)).astype(np.int32)
    table = sketch_rows(jnp.asarray(embs))
    got_ids, got_sc = ref.sketch_topk_ref(
        table, jnp.asarray(ids), jnp.asarray(q), k=c
    )
    tb, qb = embs > 0, q > 0  # unpacked bit matrices
    for i in range(b):
        for j in range(c):
            rid = int(np.asarray(got_ids)[i, j])
            if rid < 0:
                continue
            ham = int(np.sum(tb[rid] != qb[i]))
            assert float(np.asarray(got_sc)[i, j]) == -float(ham)


# ---------------------------------------------------------------------------
# Kernel vs oracle parity across bank liveness patterns
# ---------------------------------------------------------------------------


# Batch sizes: one query (one row a grid step), under one row group, a full
# sublane group, ragged, the cells' 32 (one group), two groups with padding.
BATCHES = (1, 5, 8, 13, 32, 40)


def _mask(ids, pattern, block_c):
    if pattern == "all_live":
        return ids
    if pattern == "tombstoned":  # scattered dead candidates
        return ids.at[:, ::3].set(-1)
    if pattern == "dead_block":  # one fully-dead candidate block per row
        return ids.at[:, block_c : 2 * block_c].set(-1)
    if pattern == "all_pruned_row":  # row 0 entirely dead
        return ids.at[0, :].set(-1)
    if pattern == "dead_rows_in_live_group":  # every third row dead
        return ids.at[::3, :].set(-1)
    raise ValueError(pattern)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize(
    "pattern",
    ["all_live", "tombstoned", "dead_block", "all_pruned_row",
     "dead_rows_in_live_group"],
)
def test_sketch_kernel_matches_oracle(pattern, b):
    block_c = 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    embs = jax.random.normal(k1, (64, 48))
    ids = jax.random.randint(k2, (b, 4 * block_c), 0, 64)
    q = jax.random.normal(k3, (b, 48))
    ids = _mask(ids, pattern, block_c)
    table = sketch_rows(embs)
    gi, gs = sketch_prefilter(table, ids, q, k=6, block_c=block_c, interpret=True)
    wi, ws = ref.sketch_topk_ref(table, ids, q, k=6)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    dead = {"all_pruned_row": [0], "dead_rows_in_live_group": list(range(0, b, 3))}
    for row in dead.get(pattern, []):
        assert (np.asarray(gi)[row] == -1).all()
        assert np.isneginf(np.asarray(gs)[row]).all()


@pytest.mark.parametrize("b", BATCHES)
def test_sketch_out_ids_suppression_matches_oracle(b):
    """Tombstoned candidates (``out_ids`` < 0) are suppressed identically by
    kernel and oracle — the same contract as ``verify_topk_op``."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    embs = jax.random.normal(k1, (32, 32))
    rows = jax.random.randint(k2, (b, 16), 0, 32)
    q = jax.random.normal(k3, (b, 32))
    out_ids = rows.at[:, 1::2].set(-1)  # every other candidate tombstoned
    table = sketch_rows(embs)
    gi, gs = sketch_prefilter(
        table, rows, q, k=8, out_ids=out_ids, block_c=8, interpret=True
    )
    wi, ws = ref.sketch_topk_ref(table, rows, q, k=8, out_ids=out_ids)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))
    for got, live in zip(np.asarray(gi), np.asarray(out_ids)):
        assert set(got[got >= 0].tolist()) <= set(live[live >= 0].tolist())


# ---------------------------------------------------------------------------
# Bank lifecycle: upsert / checkpoint keep sketches in lockstep with codes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sd", ["int8", "int4"])
def test_sketch_upsert_matches_full_rebuild(corpus, sd):
    """build(80%) -> upsert(20%) produces a byte-identical sketch table to
    build(100%) under frozen layer-1 (sketching is row-local, like the
    quantizers), and the table always equals re-sketching the raw rows."""
    x, _, _ = corpus
    n80 = int(x.shape[0] * 0.8)
    km = clustering.kmeans(jax.random.PRNGKey(2), x[:n80], CFG.n_clusters, iters=10)
    assignment, _ = clustering.assign_chunked(x, km.centroids)
    max_size = int(jnp.bincount(assignment, length=CFG.n_clusters).max())
    cfg = _cfg(
        sd, capacity=lider.padded_capacity(max_size, None, CFG.pad_multiple)
    )
    full = lider.build_lider(jax.random.PRNGKey(2), x, cfg, centroids=km.centroids)
    base = lider.build_lider(
        jax.random.PRNGKey(2), x[:n80], cfg, centroids=km.centroids
    )
    up, _ = update.upsert(base, x[n80:])
    assert up.bank.sketches is not None
    np.testing.assert_array_equal(
        np.asarray(up.bank.sketches), np.asarray(full.bank.sketches)
    )
    raw = (
        up.bank.rescore_embs
        if up.bank.rescore_embs is not None
        else up.bank.store.rescore
    )
    np.testing.assert_array_equal(
        np.asarray(up.bank.sketches), np.asarray(sketch_rows(jnp.asarray(raw)))
    )


def test_sketch_compaction_keeps_lockstep(corpus):
    """Compaction (threshold-0 delete) permutes sketches with the codes:
    the table still equals re-sketching the compacted raw rows."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, _cfg("int8"))
    before = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    dead = np.unique(np.asarray(before.ids)[:, :3].ravel())
    dead = jnp.asarray(dead[dead >= 0][:50], jnp.int32)
    p2, stats = update.delete(p, dead, refit_threshold=0.0)
    assert stats.n_refit > 0
    np.testing.assert_array_equal(
        np.asarray(p2.bank.sketches),
        np.asarray(sketch_rows(jnp.asarray(p2.bank.rescore_embs))),
    )


def test_checkpoint_roundtrip_preserves_sketches(tmp_path, corpus):
    x, _, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("int4"))
    checkpoint.save_index(str(tmp_path), p)
    p2 = checkpoint.load_index(str(tmp_path))
    np.testing.assert_array_equal(
        np.asarray(p.bank.sketches), np.asarray(p2.bank.sketches)
    )


def test_checkpoint_presketch_fallback_recomputes_byte_exact(tmp_path, corpus):
    """Loading a pre-sketch-era checkpoint (no ``bank__sketches.npy``)
    recomputes the table from the rescore rows — byte-exact, because the
    sketch is a pure row-local function of the raw rows."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("int8"))
    checkpoint.save_index(str(tmp_path), p)
    os.remove(os.path.join(str(tmp_path), "index", "bank__sketches.npy"))
    p2 = checkpoint.load_index(str(tmp_path))
    assert p2.bank.sketches is not None
    np.testing.assert_array_equal(
        np.asarray(p.bank.sketches), np.asarray(p2.bank.sketches)
    )
    a = lider.search_lider(p, q, k=10, n_probe=8, r0=8, sketch_factor=4)
    b = lider.search_lider(p2, q, k=10, n_probe=8, r0=8, sketch_factor=4)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


# ---------------------------------------------------------------------------
# End-to-end: covering factor is bit-identical; small factors hold recall
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sd", ["int8", "int4"])
def test_search_covering_sketch_factor_bit_identical(corpus, sd):
    """A ``sketch_factor`` covering every distinct candidate makes the
    pre-filter a no-op: ids AND scores match the unfiltered search exactly,
    on the per-query and the cluster-major spellings."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg(sd))
    base = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    cov = lider.search_lider(p, q, k=10, n_probe=8, r0=8, sketch_factor=64)
    np.testing.assert_array_equal(np.asarray(base.ids), np.asarray(cov.ids))
    np.testing.assert_array_equal(
        np.asarray(base.scores), np.asarray(cov.scores)
    )
    cm = lider.search_lider(p, q, k=10, n_probe=8, r0=8, block_q=4)
    cm_cov = lider.search_lider(
        p, q, k=10, n_probe=8, r0=8, block_q=4, sketch_factor=64
    )
    np.testing.assert_array_equal(np.asarray(cm.ids), np.asarray(cm_cov.ids))
    np.testing.assert_array_equal(
        np.asarray(cm.scores), np.asarray(cm_cov.scores)
    )


def test_sketch_float_bank_rejects_nothing_silently(corpus):
    """A float bank has no sketches; passing sketch_factor is a no-op (the
    pre-filter gates on ``bank.sketches is not None``)."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("float32"))
    assert p.bank.sketches is None
    a = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    b = lider.search_lider(p, q, k=10, n_probe=8, r0=8, sketch_factor=4)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_sketch_recall_floor(corpus):
    """Serving-grade operating point: sketch + int4 + exact rescore recalls
    within 0.02 of the plain int4 + rescore pass (the BENCH_verify gate)."""
    x, q, gt = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("int4"))
    plain = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    sk = lider.search_lider(p, q, k=10, n_probe=8, r0=8, sketch_factor=4)
    r_plain = float(recall_at_k(plain.ids, gt))
    r_sk = float(recall_at_k(sk.ids, gt))
    assert r_sk >= r_plain - 0.02, (r_sk, r_plain)
