"""Tiered embedding store (DESIGN.md §Tiered embedding store): host-tier
rescore table bit-parity with the device tier across the whole index
lifecycle, cross-tier checkpointing, per-tier byte accounting, the pipelined
serving engine, and the device/host generation split."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bank as bank_lib, lider, update
from repro.core.baselines.flat import flat_search
from repro.core.bank import EmbStore, set_rescore_tier
from repro.core.utils import l2_normalize, recall_at_k
from repro.serving import RetrievalEngine, make_backend
from repro.serving.traffic import make_trace, run_open_loop
from repro.training import checkpoint

CFG = lider.LiderConfig(
    n_clusters=32, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10,
    storage_dtype="int8",
)


def _search(p, q, **kw):
    return lider.search_lider(p, q, k=10, n_probe=8, r0=8, **kw)


def _assert_bit_parity(pd, ph, q, **kw):
    a = _search(pd, q, **kw)
    b = _search(ph, q, **kw)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))


@pytest.fixture(scope="module")
def tier_pair(corpus):
    """The same int8 index on both tiers (device-built, host-converted)."""
    x, q, gt = corpus
    pd = lider.build_lider(jax.random.PRNGKey(0), x, CFG)
    ph = lider.set_rescore_tier(pd, "host")
    return x, q, gt, pd, ph


# ---------------------------------------------------------------------------
# Tier plumbing & accounting
# ---------------------------------------------------------------------------


def test_tier_properties_and_store_shape(tier_pair):
    x, _, _, pd, ph = tier_pair
    assert pd.bank.rescore_tier == "device" and ph.bank.rescore_tier == "host"
    assert ph.bank.rescore_embs is None
    assert ph.bank.store.shape == tuple(pd.bank.rescore_embs.shape)
    np.testing.assert_array_equal(
        ph.bank.store.rescore, np.asarray(pd.bank.rescore_embs)
    )
    # the synced gid copy matches the device one
    np.testing.assert_array_equal(ph.bank.store.gids, np.asarray(ph.bank.gids))


def test_nbytes_by_tier_accounting(tier_pair):
    _, _, _, pd, ph = tier_pair
    dev = pd.bank.nbytes_by_tier()
    host = ph.bank.nbytes_by_tier()
    assert dev["host"] == 0
    # moving the table off-device shifts exactly its bytes between tiers
    assert host["host"] == pd.bank.rescore_embs.size * 4
    assert dev["device"] - host["device"] == host["host"]


def test_direct_host_build_matches_conversion(corpus):
    x, q, _, = corpus
    cfg = dataclasses.replace(CFG, rescore_tier="host")
    built = lider.build_lider(jax.random.PRNGKey(0), x, cfg)
    assert built.bank.rescore_tier == "host"
    converted = lider.set_rescore_tier(
        lider.build_lider(jax.random.PRNGKey(0), x, CFG), "host"
    )
    np.testing.assert_array_equal(built.bank.store.rescore,
                                  converted.bank.store.rescore)
    _assert_bit_parity(built, converted, q)


def _bank_bytes(bank):
    """Every leaf of a host-tier bank, and its host table and gid copy."""
    leaves = {jax.tree_util.keystr(kp): np.asarray(v) for kp, v in
              jax.tree_util.tree_leaves_with_path(bank)}
    return dict(leaves, host_rows=bank.store.rescore,
                host_gids=bank.store.gids)


@pytest.fixture(scope="module")
def wide_corpus():
    """4,096 unit rows at d=256 (a mixture, like the benchmark's)."""
    kc, kx, kn = jax.random.split(jax.random.PRNGKey(5), 3)
    centers = jax.random.normal(kc, (24, 256))
    assign = jax.random.randint(kx, (4096,), 0, 24)
    return l2_normalize(
        centers[assign] + 0.35 * jax.random.normal(kn, (4096, 256)))


@pytest.mark.parametrize("chunk", [1, 3, 7, 16])
@pytest.mark.parametrize("sd", ["int8", "int4"])
def test_chunked_host_build_equals_one_shot_build(wide_corpus, monkeypatch,
                                                  sd, chunk):
    """The host-tier build gathers, encodes and copies a few clusters at a
    time; the index equals the one-shot build (the whole capacity-padded
    table gathered on the device, then moved to the host) byte for byte:
    codes, scales, sketches, every sorted table and fit, host rows, gids."""
    cfg = lider.LiderConfig(n_clusters=16, n_probe=4, n_arrays=4, n_leaves=4,
                            kmeans_iters=5, storage_dtype=sd)
    one_shot = lider.set_rescore_tier(
        lider.build_lider(jax.random.PRNGKey(1), wide_corpus, cfg), "host")
    monkeypatch.setattr(bank_lib, "ENCODE_CHUNK", chunk)
    chunked = lider.build_lider(jax.random.PRNGKey(1), wide_corpus,
                                dataclasses.replace(cfg, rescore_tier="host"))
    want, got = _bank_bytes(one_shot.bank), _bank_bytes(chunked.bank)
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_engine_serves_d4096_host_tier_like_flat_search():
    """The LLM embedder's width through build_lider -> make_backend ->
    RetrievalEngine on an int4 + sketch + host-tier index: every answered
    row carries its exact float32 score, and recall against the brute-force
    reference holds."""
    kc, kx, kn, kq = jax.random.split(jax.random.PRNGKey(6), 4)
    centers = jax.random.normal(kc, (16, 4096))
    assign = jax.random.randint(kx, (2048,), 0, 16)
    x = l2_normalize(
        centers[assign] + 0.35 * jax.random.normal(kn, (2048, 4096)))
    q = np.asarray(l2_normalize(
        x[:48] + 0.08 * jax.random.normal(kq, (48, 4096))))
    cfg = lider.LiderConfig(n_clusters=16, n_probe=4, n_arrays=4, n_leaves=4,
                            kmeans_iters=5, storage_dtype="int4",
                            rescore_tier="host")
    params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
    eng = _host_engine(params, 4096, sketch_factor=4)
    eng.warmup()
    rids = [eng.submit(v) for v in q]
    eng.drain()
    answers = [eng.result(r) for r in rids]
    ids = np.stack([a[0] for a in answers])
    scores = np.stack([a[1] for a in answers])
    assert (ids >= 0).all()
    # Each fetch returns a batch's k' = 40 rows of 4096 float32s.
    s = eng.stats
    assert s.n_host_fetches == 3
    assert s.host_fetch_bytes == s.n_host_fetches * 16 * 40 * 4096 * 4
    exact = np.einsum("bd,bkd->bk", q.astype(np.float64),
                      np.asarray(x, np.float64)[ids])
    # The benchmark's score limit for float32 rows (bench/configs).
    np.testing.assert_allclose(scores, exact, rtol=0, atol=1e-5)
    want = flat_search(x, jnp.asarray(q), k=10)
    assert float(recall_at_k(jnp.asarray(ids), want.ids)) > 0.9


def test_host_tier_requires_int8(corpus):
    x, _, _ = corpus
    cfg = dataclasses.replace(
        CFG, storage_dtype="float32", rescore_tier="host"
    )
    with pytest.raises(ValueError, match="int8"):
        lider.build_lider(jax.random.PRNGKey(0), x, cfg)
    p32 = lider.build_lider(
        jax.random.PRNGKey(0), x, dataclasses.replace(CFG, storage_dtype="float32")
    )
    with pytest.raises(ValueError, match="int8|rescore"):
        lider.set_rescore_tier(p32, "host")


def test_incluster_search_rejects_host_tier(tier_pair):
    _, q, _, _, ph = tier_pair
    cids = jnp.zeros((q.shape[0], 2), jnp.int32)
    with pytest.raises(ValueError, match="host-tier"):
        lider.incluster_search(ph, q, cids, k=10)


def test_embstore_hash_is_content_stable(tier_pair):
    """The store rides the pytree as static aux: content writes must not
    change its identity-as-aux (or every host update would recompile)."""
    _, _, _, _, ph = tier_pair
    st = ph.bank.store
    before = hash(st)
    st.write_rows(np.array([0]), st.fetch(np.array([0])))
    assert hash(st) == before
    abstract = EmbStore("host", shape=st.shape)
    assert abstract == st and hash(abstract) == hash(st)
    with pytest.raises(ValueError, match="abstract"):
        abstract.fetch(np.array([0]))


# ---------------------------------------------------------------------------
# Bit-parity across the lifecycle (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_parity_all_live(tier_pair):
    _, q, _, pd, ph = tier_pair
    _assert_bit_parity(pd, ph, q)


def test_parity_with_pruning_and_stats(tier_pair):
    _, q, _, pd, ph = tier_pair
    a, pa = _search(pd, q, prune_margin=0.1, with_stats=True)
    b, pb = _search(ph, q, prune_margin=0.1, with_stats=True)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_parity_across_lifecycle(corpus):
    """Upsert -> tombstone -> compaction, applied to both tiers in parallel:
    every stage stays bit-identical (and the host gid map stays synced)."""
    x, q, _ = corpus
    n80 = int(x.shape[0] * 0.8)
    pd = lider.build_lider(jax.random.PRNGKey(0), x[:n80], CFG)
    ph = lider.set_rescore_tier(
        lider.build_lider(jax.random.PRNGKey(0), x[:n80], CFG), "host"
    )
    # post-upsert (grows capacity -> exercises EmbStore.grow)
    pd, sd = update.upsert(pd, x[n80:])
    ph, sh = update.upsert(ph, x[n80:])
    assert sd.capacity_grew == sh.capacity_grew
    _assert_bit_parity(pd, ph, q)
    np.testing.assert_array_equal(
        ph.bank.store.rescore, np.asarray(pd.bank.rescore_embs)
    )
    # tombstoned (no compaction)
    dead = jnp.arange(50, 150, dtype=jnp.int32)
    pd, _ = update.delete(pd, dead, refit_threshold=1.0)
    ph, _ = update.delete(ph, dead, refit_threshold=1.0)
    _assert_bit_parity(pd, ph, q)
    assert not np.isin(np.asarray(_search(ph, q).ids), np.asarray(dead)).any()
    # post-compaction (threshold 0 forces it)
    pd, s1 = update.delete(pd, jnp.arange(200, 260, dtype=jnp.int32),
                           refit_threshold=0.0)
    ph, s2 = update.delete(ph, jnp.arange(200, 260, dtype=jnp.int32),
                           refit_threshold=0.0)
    assert s1.n_refit == s2.n_refit > 0
    _assert_bit_parity(pd, ph, q)
    np.testing.assert_array_equal(
        ph.bank.store.rescore, np.asarray(pd.bank.rescore_embs)
    )
    np.testing.assert_array_equal(ph.bank.store.gids, np.asarray(ph.bank.gids))


def test_growth_preserves_pre_growth_snapshot(corpus):
    """Capacity growth is copy-on-grow on the host tier: a retained
    pre-growth params snapshot keeps its own consistent store (the flat-row
    arithmetic changes with Lp, so sharing the grown table would silently
    gather wrong rows)."""
    x, q, _ = corpus
    n80 = int(x.shape[0] * 0.8)
    cfg = dataclasses.replace(CFG, rescore_tier="host")
    snap = lider.build_lider(jax.random.PRNGKey(0), x[:n80], cfg)
    before = _search(snap, q)
    grown, stats = update.upsert(snap, x[n80:])
    assert stats.capacity_grew
    assert grown.bank.store is not snap.bank.store
    assert snap.bank.store.shape[1] == snap.bank.capacity
    after = _search(snap, q)  # the old snapshot must be unaffected
    np.testing.assert_array_equal(np.asarray(before.ids), np.asarray(after.ids))
    np.testing.assert_array_equal(
        np.asarray(before.scores), np.asarray(after.scores)
    )


def test_round_trip_tier_conversion_is_lossless(tier_pair):
    _, q, _, pd, ph = tier_pair
    back = lider.set_rescore_tier(ph, "device")
    np.testing.assert_array_equal(
        np.asarray(back.bank.rescore_embs), np.asarray(pd.bank.rescore_embs)
    )
    _assert_bit_parity(pd, back, q)


# ---------------------------------------------------------------------------
# Checkpoint round-trip across tier changes
# ---------------------------------------------------------------------------


def test_checkpoint_round_trip_across_tiers(tmp_path, tier_pair):
    _, q, _, pd, ph = tier_pair
    # host-saved -> loads as host (default) and as device
    checkpoint.save_index(str(tmp_path / "h"), ph)
    as_host = checkpoint.load_index(str(tmp_path / "h"))
    as_dev = checkpoint.load_index(str(tmp_path / "h"), rescore_tier="device")
    assert as_host.bank.rescore_tier == "host"
    assert as_dev.bank.rescore_tier == "device"
    _assert_bit_parity(pd, as_host, q)
    _assert_bit_parity(pd, as_dev, q)
    # device-saved -> loads as host
    checkpoint.save_index(str(tmp_path / "d"), pd)
    cross = checkpoint.load_index(str(tmp_path / "d"), rescore_tier="host")
    assert cross.bank.rescore_tier == "host"
    _assert_bit_parity(pd, cross, q)


def test_checkpoint_rejects_host_tier_for_float(tmp_path, corpus):
    x, _, _ = corpus
    p32 = lider.build_lider(
        jax.random.PRNGKey(0), x, dataclasses.replace(CFG, storage_dtype="float32")
    )
    checkpoint.save_index(str(tmp_path), p32)
    with pytest.raises(ValueError, match="int8"):
        checkpoint.load_index(str(tmp_path), rescore_tier="host")


# ---------------------------------------------------------------------------
# Serving: pipelined drain + generation split
# ---------------------------------------------------------------------------


def _host_engine(ph, dim, **kw):
    search = make_backend("lider", None, updatable=True, n_probe=8, r0=8, **kw)
    return RetrievalEngine(search, batch_size=16, k=10, dim=dim, params=ph)


def test_engine_serves_host_tier_with_overlap(tier_pair):
    """Multi-batch drain through the double-buffered pipeline: every batch
    but the last fetches under a dispatched next batch, results match the
    serial staged search, and recall holds."""
    x, q, gt, _, ph = tier_pair
    eng = _host_engine(ph, x.shape[1])
    eng.warmup()
    qs = np.asarray(q)[:48]
    rids = [eng.submit(v) for v in qs]
    eng.drain()
    got = np.stack([eng.result(r)[0] for r in rids])
    s = eng.stats
    assert s.n_batches == 3 and s.n_host_fetches == 3
    assert s.n_overlapped_fetches == 2
    assert s.overlap_fraction == pytest.approx(2 / 3)
    assert s.host_fetch_us > 0 and s.aqt > 0
    serial = _search(ph, jnp.asarray(qs))
    np.testing.assert_array_equal(got, np.asarray(serial.ids))
    assert float(recall_at_k(jnp.asarray(got), gt[:48])) > 0.85
    # no pruning configured -> no probe stats (same contract as serial)
    assert s.n_probes_total == 0


def test_batches_in_flight_keep_their_own_host_buffers(tier_pair):
    """Under the pipelined drain (batch i+1 dispatched while batch i is in
    flight) every answer belongs to its own query: each returned row's
    score is that query's float32 inner product with the row. Queries are
    distinct corpus rows, so a batch handed another batch's queries (a
    shared host buffer refilled while its transfer was pending, seen on the
    CPU under load) answers with other queries' scores."""
    x, _, _, _, ph = tier_pair
    xs = np.asarray(x)
    eng = _host_engine(ph, xs.shape[1])
    eng.warmup()
    for start in range(0, 192, 48):  # four drains of three batches
        rows = np.arange(start, start + 48)
        rids = [eng.submit(xs[r]) for r in rows]
        eng.drain()
        for r, rid in zip(rows, rids):
            ids, scores = eng.result(rid)
            ids, scores = np.asarray(ids), np.asarray(scores)
            assert (ids >= 0).all()
            np.testing.assert_allclose(
                scores, xs[ids] @ xs[r], rtol=0, atol=1e-5, err_msg=str(r))
    assert eng.stats.n_overlapped_fetches > 0


def test_open_loop_drain_chunk_one_keeps_overlap(tier_pair):
    """Satellite regression (ROADMAP): open-loop replay with
    ``drain_chunk=1`` used to dispatch one batch per drain call, which
    collapsed the host-tier fetch overlap to zero; the driver now raises
    the chunk to the engine's pipeline depth for host-tier params."""
    x, q, _, _, ph = tier_pair
    eng = _host_engine(ph, x.shape[1])
    eng.warmup()
    pool = np.asarray(q)[:32]
    trace = make_trace(
        seed=0, n_arrivals=64, pool_size=len(pool), mean_rate=1e5,
    )
    rids = run_open_loop(eng, trace, pool, drain_chunk=1)
    assert len(rids) == 64
    assert all(eng.result(r) is not None for r in rids)
    s = eng.stats
    assert s.n_host_fetches >= 2
    assert s.overlap_fraction > 0


def test_pick_block_q_cost_model():
    """The autotuner's cost model: singleton clusters (no sharing to
    exploit) pick the shallowest rung, a hot cluster picks the deepest,
    and an empty observation window falls back to the first rung."""
    from repro.serving.engine import pick_block_q

    assert pick_block_q([np.ones(64, np.int64)], (2, 4, 8)) == 2
    assert pick_block_q([np.full(4, 128, np.int64)], (2, 4, 8)) == 8
    assert pick_block_q([], (4, 8)) == 4


def test_engine_autotunes_block_q_without_retrace(tier_pair):
    """Online block_q autotuning (staged host-tier serving): each drained
    batch's measured probe distribution re-picks the rung for the next
    dispatch, hot traffic climbs to the deepest rung, the measured sharing
    ratio lands in EngineStats, and — because every rung was pre-warmed in
    ``warmup`` and the schedule padding is fixed worst-case — the whole
    adaptation costs ZERO query-path retraces."""
    from repro.core.lider import query_path_cache_size
    from repro.serving.engine import pick_block_q

    x, q, _, _, ph = tier_pair
    ladder = (2, 4, 8)
    search = make_backend("lider", None, updatable=True, n_probe=8, r0=8)
    eng = RetrievalEngine(
        search, batch_size=16, k=10, dim=x.shape[1], params=ph,
        block_q_ladder=ladder,
    )
    eng.warmup()
    before = query_path_cache_size()
    # Hot trace: every query is a perturbation of one point, so all probes
    # concentrate on the same n_probe clusters (counts ~16 per cluster).
    rng = np.random.default_rng(0)
    hot = np.asarray(q)[:1] + 1e-3 * rng.normal(size=(48, x.shape[1]))
    hot /= np.linalg.norm(hot, axis=-1, keepdims=True)
    rids = [eng.submit(v.astype(np.float32)) for v in hot]
    eng.drain()
    assert all(eng.result(r) is not None for r in rids)
    assert query_path_cache_size() == before  # zero retraces while adapting
    s = eng.stats
    assert s.n_sched_pairs == 48 * 8
    assert 0 < s.n_sched_steps < s.n_sched_pairs
    assert s.sharing_ratio > 2.0
    assert s.n_batches == 3  # one measurement per drained batch
    assert eng._auto_block_q == 8  # hot traffic -> deepest rung...
    # ...and the live pick is exactly the cost-model argmin over the window.
    assert pick_block_q(eng._probe_counts, ladder) == 8


def test_engine_static_block_q_overrides_autotune(tier_pair):
    """A static backend ``block_q`` is an explicit operator override: the
    ladder never injects an auto rung over it (the engine still serves)."""
    x, q, _, _, ph = tier_pair
    search = make_backend(
        "lider", None, updatable=True, n_probe=8, r0=8, block_q=4
    )
    eng = RetrievalEngine(
        search, batch_size=16, k=10, dim=x.shape[1], params=ph,
        block_q_ladder=(2, 8),
    )
    eng.warmup()
    # The auto rung is suppressed — the static kwarg reaches the search
    # through the backend's own kwargs, not through an injected point.
    assert (eng._effective_point() or {}).get("block_q") is None
    assert search.static_point.get("block_q") == 4
    rids = [eng.submit(v) for v in np.asarray(q)[:16]]
    eng.drain()
    assert all(eng.result(r) is not None for r in rids)


def test_engine_host_tier_reports_pruned_probes(tier_pair):
    x, q, _, _, ph = tier_pair
    eng = _host_engine(ph, x.shape[1], prune_margin=0.1)
    rids = [eng.submit(v) for v in np.asarray(q)[:40]]
    eng.drain()
    s = eng.stats
    assert s.n_probes_total == 40 * 8
    assert 0 < s.n_probes_pruned < s.n_probes_total
    for rid in rids:
        assert eng.result(rid) is not None


def test_host_only_update_does_not_recompile(tier_pair):
    """Satellite regression: apply_updates with only host-tier content
    changes must bump the host generation alone — no device recompile, no
    device generation bump."""
    x, _, _, _, ph = tier_pair
    eng = _host_engine(ph, x.shape[1])
    eng.warmup()

    def host_only(params):
        st = params.bank.store
        st.write_rows(np.array([0]), st.fetch(np.array([0])))
        return params

    grew = eng.apply_updates(host_only)
    assert not grew
    assert eng.recompiles == 0
    assert eng.device_generation == 0
    assert eng.host_generation == 1
    assert eng.generation == 1


def test_generations_split_on_mixed_update(corpus):
    x, _, _ = corpus
    # generous capacity so the upsert cannot grow shapes
    cfg = dataclasses.replace(CFG, capacity=512)
    ph = lider.set_rescore_tier(
        lider.build_lider(jax.random.PRNGKey(0), x, cfg), "host"
    )
    eng = _host_engine(ph, x.shape[1])
    eng.warmup()
    grew = eng.apply_updates(lambda p: update.upsert(p, x[:8] + 0.01))
    assert not grew and eng.recompiles == 0
    assert eng.device_generation == 1  # codes/scales/gids changed
    assert eng.host_generation == 1  # rescore rows written in lockstep
