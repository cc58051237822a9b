"""Adaptive probe pruning: margin-rule parity, candidate-subset guarantees,
speed-quality monotonicity, and the block-skipping verification kernel
(DESIGN.md §Adaptive speed-quality control plane)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lider
from repro.core.utils import l2_normalize, recall_at_k
from repro.kernels import fused_verify, ref

CFG = lider.LiderConfig(
    n_clusters=32, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=8
)


@pytest.fixture(scope="module")
def built(corpus):
    x, q, gt = corpus
    params = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    return x, q, gt, params


# ---------------------------------------------------------------------------
# Margin rule on the core search path
# ---------------------------------------------------------------------------


def test_margin_none_bit_identical(built):
    """prune_margin=None must be bit-identical to the fixed-probe search."""
    _, q, _, p = built
    base = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    off = lider.search_lider(p, q, k=10, n_probe=8, r0=8, prune_margin=None)
    np.testing.assert_array_equal(np.asarray(off.ids), np.asarray(base.ids))
    assert (
        np.asarray(off.scores).tobytes() == np.asarray(base.scores).tobytes()
    )
    routed = lider.route_queries(p, q, n_probe=8)
    routed_off = lider.route_queries(p, q, n_probe=8, prune_margin=None)
    np.testing.assert_array_equal(
        np.asarray(routed_off.ids), np.asarray(routed.ids)
    )
    assert (
        np.asarray(routed_off.scores).tobytes()
        == np.asarray(routed.scores).tobytes()
    )


def test_prune_probes_masks_only_below_margin(built):
    _, q, _, p = built
    routed = lider.route_queries(p, q, n_probe=8)
    cids = lider.prune_probes(routed.ids, routed.scores, 0.1)
    scores = np.asarray(routed.scores)
    best = scores.max(axis=-1, keepdims=True)
    kept, orig = np.asarray(cids), np.asarray(routed.ids)
    # kept slots are unchanged; masked slots are exactly those below margin
    np.testing.assert_array_equal(kept[kept >= 0], orig[kept >= 0])
    assert ((scores >= best - 0.1) == (kept >= 0)).all()
    # the per-query best probe always survives
    assert (kept.max(axis=-1) >= 0).all()


def test_pruned_results_are_subset_of_unpruned_candidates(built):
    """Every id a pruned search returns must come from a cluster the
    unpruned routing probed AND the margin rule kept."""
    x, q, _, p = built
    routed = lider.route_queries(p, q, n_probe=8)
    kept = np.asarray(lider.prune_probes(routed.ids, routed.scores, 0.05))
    out = lider.search_lider(p, q, k=10, n_probe=8, r0=8, prune_margin=0.05)
    gids = np.asarray(p.bank.gids)
    cluster_of = np.full((x.shape[0],), -1, np.int32)
    for ci in range(gids.shape[0]):
        live = gids[ci][gids[ci] >= 0]
        cluster_of[live] = ci
    ids = np.asarray(out.ids)
    for b in range(ids.shape[0]):
        kept_set = set(kept[b][kept[b] >= 0].tolist())
        for i in ids[b][ids[b] >= 0]:
            assert cluster_of[i] in kept_set


def test_incluster_prune_spelling_matches_search_lider(built):
    """Pruning inside incluster_search (cid_scores + margin) equals pruning
    at the routing layer — one candidate mask, two spellings."""
    _, q, _, p = built
    routed = lider.route_queries(p, q, n_probe=8)
    a = lider.incluster_search(
        p, q, routed.ids, k=10, r0=8, cid_scores=routed.scores,
        prune_margin=0.1,
    )
    b = lider.search_lider(p, q, k=10, n_probe=8, r0=8, prune_margin=0.1)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_recall_monotone_non_increasing_as_margin_tightens(built):
    """Tightening the margin shrinks the candidate set; recall@k must not
    improve as probes are pruned away."""
    _, q, gt, p = built
    margins = [1.0, 0.5, 0.2, 0.1, 0.05, 0.02, 0.0]
    recalls = [
        float(
            recall_at_k(
                lider.search_lider(
                    p, q, k=10, n_probe=8, r0=8, prune_margin=m
                ).ids,
                gt,
            )
        )
        for m in margins
    ]
    for wide, tight in zip(recalls, recalls[1:]):
        assert tight <= wide + 1e-9, recalls
    # sanity: an infinite margin prunes nothing ...
    none = float(
        recall_at_k(lider.search_lider(p, q, k=10, n_probe=8, r0=8).ids, gt)
    )
    assert recalls[0] == pytest.approx(none)
    # ... and a zero margin still serves the best probe per query
    assert recalls[-1] > 0


def test_with_stats_returns_pruned_mask(built):
    _, q, _, p = built
    out, pruned = lider.search_lider(
        p, q, k=10, n_probe=8, r0=8, prune_margin=0.1, with_stats=True
    )
    pruned = np.asarray(pruned)
    assert pruned.shape == (q.shape[0], 8)
    assert pruned.dtype == bool
    assert 0 < pruned.sum() < pruned.size  # something, but not everything
    _, none_pruned = lider.search_lider(
        p, q, k=10, n_probe=8, r0=8, prune_margin=None, with_stats=True
    )
    assert not np.asarray(none_pruned).any()


def test_margin_sweep_does_not_recompile(built):
    """The margin is traced: sweeping values must reuse one compilation."""
    _, q, _, p = built
    with jax.log_compiles(False):
        pass  # silence any ambient logging
    fn = lider.search_lider
    base = fn._cache_size() if hasattr(fn, "_cache_size") else None
    fn(p, q, k=10, n_probe=8, r0=8, prune_margin=0.3)
    after_first = fn._cache_size() if base is not None else None
    fn(p, q, k=10, n_probe=8, r0=8, prune_margin=0.07)
    fn(p, q, k=10, n_probe=8, r0=8, prune_margin=0.9)
    if base is not None:
        assert fn._cache_size() == after_first


# ---------------------------------------------------------------------------
# Block-skipping fused kernel on pruned inputs
# ---------------------------------------------------------------------------


def _case(seed, n, d, b, c):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    embs = jax.random.normal(k1, (n, d))
    rows = jax.random.randint(k2, (b, c), 0, n)
    q = jax.random.normal(k3, (b, d))
    return embs, rows, q


def _assert_parity(embs, rows, q, k, block_c, out_ids):
    gi, gs = fused_verify(
        embs, rows, q, k=k, out_ids=out_ids, block_c=block_c, interpret=True
    )
    wi, ws = ref.verify_topk_ref(embs, rows, q, k=k, out_ids=out_ids)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


def test_block_skip_parity_whole_blocks_pruned():
    """Fully-invalid blocks (a pruned probe's candidate span) are skipped by
    the kernel but the output must match the reference exactly."""
    embs, rows, q = _case(0, 60, 16, 3, 32)
    out_ids = rows
    # kill blocks 1 and 3 of 4 (block_c=8) on every row
    mask = jnp.arange(32) // 8
    out_ids = jnp.where((mask == 1) | (mask == 3), -1, out_ids)
    _assert_parity(embs, rows, q, k=5, block_c=8, out_ids=out_ids)


def test_block_skip_parity_mixed_blocks():
    """Blocks with a few valid candidates must still be processed."""
    embs, rows, q = _case(1, 60, 16, 2, 24)
    out_ids = rows.at[:, ::2].set(-1)  # half-dead everywhere, no dead block
    _assert_parity(embs, rows, q, k=4, block_c=8, out_ids=out_ids)
    out_ids = out_ids.at[:, 8:16].set(-1)  # now block 1 is fully dead
    _assert_parity(embs, rows, q, k=4, block_c=8, out_ids=out_ids)


def test_block_skip_all_probes_pruned_row():
    """A row whose probes were all pruned returns all (-1, -inf) — the
    edge case where every block of that row is skipped."""
    embs, rows, q = _case(2, 40, 16, 3, 16)
    out_ids = rows.at[1, :].set(-1)  # row 1: everything pruned
    gi, gs = fused_verify(
        embs, rows, q, k=4, out_ids=out_ids, block_c=4, interpret=True
    )
    assert (np.asarray(gi)[1] == -1).all()
    assert np.isneginf(np.asarray(gs)[1]).all()
    _assert_parity(embs, rows, q, k=4, block_c=4, out_ids=out_ids)


def test_block_skip_all_rows_all_pruned():
    embs, rows, q = _case(3, 30, 8, 2, 12)
    out_ids = jnp.full_like(rows, -1)
    gi, gs = fused_verify(
        embs, rows, q, k=3, out_ids=out_ids, block_c=4, interpret=True
    )
    assert (np.asarray(gi) == -1).all()
    assert np.isneginf(np.asarray(gs)).all()


@pytest.fixture(scope="module")
def small_lider():
    rng = jax.random.PRNGKey(7)
    kc, kx, kq, kb = jax.random.split(rng, 4)
    centers = jax.random.normal(kc, (16, 32))
    assign = jax.random.randint(kx, (1500,), 0, 16)
    x = l2_normalize(centers[assign] + 0.3 * jax.random.normal(kq, (1500, 32)))
    q = l2_normalize(x[:8] + 0.05 * jax.random.normal(kb, (8, 32)))
    cfg = lider.LiderConfig(
        n_clusters=16, n_probe=4, n_arrays=2, n_leaves=2, kmeans_iters=5
    )
    params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
    return params, q


def test_search_lider_pruned_fused_matches_unfused(small_lider):
    """End-to-end: fused block-skip path == materialized reference under
    pruning (the pruned probes' spans are the skipped blocks)."""
    params, q = small_lider
    kw = dict(k=10, n_probe=4, r0=8, prune_margin=0.1)
    unfused = lider.search_lider(params, q, use_fused=False, **kw)
    fused = lider.search_lider(params, q, use_fused=True, **kw)
    np.testing.assert_array_equal(
        np.asarray(fused.ids), np.asarray(unfused.ids)
    )
    np.testing.assert_allclose(
        np.asarray(fused.scores), np.asarray(unfused.scores), rtol=1e-6
    )
