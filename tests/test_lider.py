"""LIDER two-layer index: build integrity + end-to-end search quality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lider
from repro.core.utils import recall_at_k

CFG = lider.LiderConfig(
    n_clusters=64, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10
)


@pytest.fixture(scope="module")
def built(corpus):
    x, q, gt = corpus
    params = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    return x, q, gt, params


def test_build_integrity(built):
    x, _, _, p = built
    n = x.shape[0]
    gids = np.asarray(p.bank.gids)
    valid = gids[gids >= 0]
    # every point indexed exactly once (no capacity drops at default Lp)
    assert len(valid) == n
    assert len(set(valid.tolist())) == n
    assert int(p.bank.next_gid) == n
    assert (np.asarray(p.bank.tombstones) == 0).all()
    # cluster embeddings match the corpus rows
    c, lp = gids.shape
    embs = np.asarray(p.bank.embs)
    xs = np.asarray(x)
    for ci in range(0, c, 13):
        for li in range(0, lp, 17):
            g = gids[ci, li]
            if g >= 0:
                np.testing.assert_allclose(embs[ci, li], xs[g], rtol=1e-6)
    # sorted arrays are sorted with pads at the end
    keys = np.asarray(p.bank.sorted_keys)
    pos = np.asarray(p.bank.sorted_pos)
    assert (np.diff(keys.astype(np.int64), axis=-1) >= 0).all()
    sizes = np.asarray(p.bank.sizes)
    for ci in range(c):
        row_pos = pos[ci]  # (H, Lp)
        assert ((row_pos >= 0).sum(axis=-1) == sizes[ci]).all()


def test_end_to_end_recall(built):
    x, q, gt, p = built
    out = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    assert float(recall_at_k(out.ids, gt)) > 0.9


def test_no_duplicates_and_sorted(built):
    _, q, _, p = built
    out = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    ids = np.asarray(out.ids)
    scores = np.asarray(out.scores)
    assert (np.diff(scores, axis=1) <= 1e-6).all()
    for row in ids:
        v = row[row >= 0]
        assert len(set(v.tolist())) == len(v)


def test_more_probes_improve_recall(built):
    """Paper Fig. 7: recall increases with c0."""
    x, q, gt, p = built
    r1 = float(recall_at_k(lider.search_lider(p, q, k=10, n_probe=1, r0=8).ids, gt))
    r8 = float(recall_at_k(lider.search_lider(p, q, k=10, n_probe=8, r0=8).ids, gt))
    assert r8 >= r1


def test_refine_variant(built):
    x, q, gt, p = built
    out = lider.search_lider(p, q, k=10, n_probe=8, r0=8, refine=True)
    assert float(recall_at_k(out.ids, gt)) > 0.9


def test_capacity_overflow_raises_without_allow_drops(corpus):
    """Silent data loss guard: a lossy pack must raise unless the caller
    explicitly opts in — dropped passages are permanently unretrievable."""
    from repro.core.bank import CapacityOverflowError

    x, _, _ = corpus
    cfg = lider.LiderConfig(
        n_clusters=16, n_probe=4, n_arrays=2, n_leaves=2, kmeans_iters=5, capacity=64
    )
    with pytest.raises(CapacityOverflowError) as ei:
        lider.build_lider(jax.random.PRNGKey(3), x, cfg)
    assert ei.value.n_dropped > 0
    assert ei.value.capacity == 64


def test_capacity_overflow_drops_are_counted(corpus):
    x, _, _ = corpus
    cfg = lider.LiderConfig(
        n_clusters=16, n_probe=4, n_arrays=2, n_leaves=2, kmeans_iters=5,
        capacity=64, allow_drops=True,
    )
    p, stats = lider.build_lider(jax.random.PRNGKey(3), x, cfg, return_stats=True)
    gids = np.asarray(p.bank.gids)
    kept = (gids >= 0).sum()
    assert kept <= x.shape[0]
    assert p.capacity == 64
    # sizes clamped to capacity
    assert (np.asarray(p.bank.sizes) <= 64).all()
    # drop accounting: every corpus point is either packed or counted dropped
    assert stats.n_dropped == x.shape[0] - kept
    assert stats.n_indexed == kept
    assert stats.n_dropped > 0  # this config genuinely overflows


def test_no_overflow_build_reports_zero_drops(corpus):
    x, _, _ = corpus
    p, stats = lider.build_lider(
        jax.random.PRNGKey(2), x, CFG, return_stats=True
    )
    assert stats.n_dropped == 0
    assert stats.n_indexed == x.shape[0]
    assert stats.capacity == p.capacity


def test_search_takes_only_operating_point_fields(built):
    """``search_lider`` and ``make_backend("lider")`` take the fields of
    ``OperatingPoint`` as keywords; any other keyword is a TypeError."""
    from repro.serving import make_backend

    _, q, _, p = built
    with pytest.raises(TypeError, match="n_probes"):
        lider.search_lider(p, q, k=10, n_probes=8)
    with pytest.raises(TypeError, match="n_probes"):
        make_backend("lider", p, n_probes=8)


def test_route_then_incluster_equals_search(built):
    x, q, _, p = built
    routed = lider.route_queries(p, q, n_probe=8, r0_centroid=4)
    a = lider.incluster_search(p, q, routed.ids, k=10, r0=8)
    b = lider.search_lider(p, q, k=10, n_probe=8, r0=8, r0_centroid=4)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


# ---------------------------------------------------------------------------
# Candidate generation: contiguous window reads against the element gather
# ---------------------------------------------------------------------------

CAND_CFG = lider.LiderConfig(
    n_clusters=64, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10
)
CAND_K, CAND_R0 = 10, 8  # r = 80 slots of a ~100-slot cluster: windows clip
CAND_BANKS = {
    "float32": {},
    "int8-device": {"storage_dtype": "int8"},
    "int4-host-sketch": {"storage_dtype": "int4", "rescore_tier": "host"},
}


def _element_gather_candidates(bank, queries, cids, *, k, r0, refine):
    """Candidate generation as one scalar gather per candidate: each of the
    B·P·H·R ``sorted_pos`` entries read from the flattened table, then
    ``gids[flat_emb]`` for liveness. Returns ``(flat_emb, gids, start)``;
    the window read must give the same rows, and ``gids >= 0`` as its
    liveness."""
    from repro.core import lsh as lsh_lib, rescale as rescale_lib, rmi as rmi_lib

    c, h, lp = bank.sorted_keys.shape
    b, p = cids.shape
    r = min(r0 * k, lp)
    qkeys = lsh_lib.hash_vectors(bank.lsh, queries)
    safe_cid = jnp.clip(cids, 0, c - 1)
    resc = jax.tree.map(lambda leaf: leaf[safe_cid], bank.rescale)
    scaled = rescale_lib.rescale(resc, qkeys[:, None, :])
    pos = rmi_lib.predict_banked(rmi_lib.gather_banked(bank.rmi, safe_cid), scaled)
    h_idx = jnp.arange(h, dtype=jnp.int32)[None, None, :, None]
    if refine:
        w1 = min(2 * r, lp)
        start1 = jnp.clip(jnp.round(pos).astype(jnp.int32) - w1 // 2, 0, lp - w1)
        idx1 = start1[..., None] + jnp.arange(w1, dtype=jnp.int32)
        flat1 = (safe_cid[:, :, None, None] * h + h_idx) * lp + idx1
        keys_win = jnp.take(bank.sorted_keys.reshape(-1), flat1)
        qk = jnp.broadcast_to(qkeys[:, None, :], (b, p, h)).reshape(-1)
        off = jax.vmap(jnp.searchsorted)(keys_win.reshape(-1, w1), qk)
        pos = (start1 + off.reshape(b, p, h).astype(jnp.int32)).astype(jnp.float32)
    start = jnp.clip(jnp.round(pos).astype(jnp.int32) - r // 2, 0, lp - r)
    idx = start[..., None] + jnp.arange(r, dtype=jnp.int32)
    flat = (safe_cid[:, :, None, None] * h + h_idx) * lp + idx
    local_pos = jnp.take(bank.sorted_pos.reshape(-1), flat)
    valid = (local_pos >= 0) & (cids >= 0)[:, :, None, None]
    flat_emb = safe_cid[:, :, None, None] * lp + jnp.maximum(local_pos, 0)
    gids = jnp.where(valid, jnp.take(bank.gids.reshape(-1), flat_emb), -1)
    return flat_emb, gids, start


@pytest.fixture(scope="module")
def cand_banks(corpus):
    """Each bank kind fresh, and after a tombstoning delete of every 7th
    passage (no compaction: dead rows stay in their slots)."""
    import dataclasses

    from repro.core import update

    x, _, _ = corpus
    out = {}
    for kind, over in CAND_BANKS.items():
        p = lider.build_lider(
            jax.random.PRNGKey(2), x, dataclasses.replace(CAND_CFG, **over)
        )
        dead = jnp.arange(0, x.shape[0], 7, dtype=jnp.int32)
        out[kind, False] = p
        out[kind, True] = update.delete(p, dead, refit_threshold=1.0)[0]
    return out


def _probes(p, q):
    """Routed probes with the last three of every other query pruned."""
    cids = lider.route_queries(p, q, n_probe=CAND_CFG.n_probe).ids
    return cids.at[::2, -3:].set(-1)


def _funnel(p, q, cids, merge):
    """The first funnel each bank kind serves through."""
    if p.bank.rescore_tier == "host":
        out = lider.provisional_rows(
            p, q, cids, k=CAND_K, merge=merge,
            point=lider.OperatingPoint(r0=CAND_R0, sketch_factor=2),
        )
    else:
        out = lider.incluster_search(p, q, cids, k=CAND_K, r0=CAND_R0, merge=merge)
    return np.asarray(out.ids), np.asarray(out.scores)


@pytest.mark.parametrize("deleted", [False, True], ids=["fresh", "deleted"])
@pytest.mark.parametrize("merge", [True, False], ids=["merge", "pairs"])
@pytest.mark.parametrize("kind", list(CAND_BANKS))
def test_window_candidates_match_element_gather(
    cand_banks, corpus, monkeypatch, kind, merge, deleted
):
    """The window read gives the element gather's flat rows, and liveness
    from ``sorted_pos`` equals ``gids >= 0`` — with pruned probes, windows
    clipped at both ends of the table, and tombstoned rows — so the funnels
    see the same candidates and answer bit-identically."""
    _, q, _ = corpus
    p = cand_banks[kind, deleted]
    bank = p.bank
    cids = _probes(p, q)
    lp = bank.capacity
    r = min(CAND_R0 * CAND_K, lp)
    for refine in (False, True):
        want_rows, want_gids, start = _element_gather_candidates(
            bank, q, cids, k=CAND_K, r0=CAND_R0, refine=refine
        )
        rows, valid = lider._bank_candidates(
            bank, q, cids, k=CAND_K, r0=CAND_R0, refine=refine
        )
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
        np.testing.assert_array_equal(
            np.asarray(valid), np.asarray(want_gids) >= 0
        )
        live = np.broadcast_to(np.asarray(cids >= 0)[..., None], start.shape)
        st = np.asarray(start)[live]
        assert (st == 0).any() and (st == lp - r).any()
    pruned = np.asarray(cids < 0)
    assert pruned.any() and not np.asarray(valid)[pruned].any()
    if deleted:  # some windows hold rows the delete killed
        _, was_valid = lider._bank_candidates(
            cand_banks[kind, False].bank, q, cids, k=CAND_K, r0=CAND_R0,
            refine=True,
        )
        assert (np.asarray(was_valid) & ~np.asarray(valid)).any()

    got = _funnel(p, q, cids, merge)
    monkeypatch.setattr(
        lider, "_bank_candidates",
        lambda bank, queries, cids, **kw: (
            lambda fr, g, _: (fr, g >= 0)
        )(*_element_gather_candidates(bank, queries, cids, **kw)),
    )
    want = _funnel(p, q, cids, merge)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("table", ["sorted_pos", "sorted_keys", "tiled"])
def test_read_windows_matches_numpy(built, table):
    """``_read_windows`` is the per-(query, probe, array) slice of the
    (c, H, Lp) table, starts at both ends of the range included: on the
    bank's own tables (padded to whole (8, 128) tiles first) and on one
    whose c and Lp are whole tiles already."""
    if table == "tiled":
        t = jnp.asarray(
            np.random.default_rng(1).integers(-1, 1 << 20, (16, 3, 384)),
            dtype=jnp.int32,
        )
        w = 160  # 3 tiles a window, the refine window's width
    else:
        t = getattr(built[3].bank, table)
        w = min(24, t.shape[-1])
    c, h, lp = t.shape
    rng = np.random.default_rng(0)
    cids = rng.integers(0, c, (3, 5)).astype(np.int32)
    start = rng.integers(0, lp - w + 1, (3, 5, h)).astype(np.int32)
    start[0, 0], start[0, 1] = 0, lp - w
    got = lider._read_windows(t, jnp.asarray(cids), jnp.asarray(start), w)
    want = np.asarray(t)[
        cids[..., None, None],
        np.arange(h)[:, None],
        start[..., None] + np.arange(w),
    ]
    assert got.dtype == t.dtype
    np.testing.assert_array_equal(np.asarray(got), want)
