"""Index lifecycle: upsert/delete parity, tombstones, checkpointed serving,
capacity growth, and the serving-engine update hooks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, lider, update
from repro.core.utils import recall_at_k
from repro.serving import RetrievalEngine, make_backend
from repro.training import checkpoint

CFG = lider.LiderConfig(
    n_clusters=64, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10
)


@pytest.fixture(scope="module")
def split_indexes(corpus):
    """80/20 split sharing one set of centroids (layer-1-frozen lifecycle)."""
    x, q, gt = corpus
    n80 = int(x.shape[0] * 0.8)
    base_x, new_x = x[:n80], x[n80:]
    km = clustering.kmeans(jax.random.PRNGKey(2), base_x, CFG.n_clusters, iters=10)
    # Fix the capacity so the incremental index and the full rebuild agree on
    # shapes (the acceptance criterion's "given identical capacity").
    assignment, _ = clustering.assign_chunked(x, km.centroids)
    max_size = int(jnp.bincount(assignment, length=CFG.n_clusters).max())
    cfg = dataclasses.replace(
        CFG, capacity=lider.padded_capacity(max_size, None, CFG.pad_multiple)
    )
    full = lider.build_lider(jax.random.PRNGKey(2), x, cfg, centroids=km.centroids)
    base = lider.build_lider(jax.random.PRNGKey(2), base_x, cfg, centroids=km.centroids)
    return x, q, gt, base, new_x, full


def test_upsert_matches_full_rebuild(split_indexes):
    """build(80%) -> upsert(20%) == build(100%) — same bank, same results."""
    x, q, _, base, new_x, full = split_indexes
    up, stats = update.upsert(base, new_x)
    assert stats.n_added == new_x.shape[0]
    assert stats.n_refit >= 1
    assert not stats.capacity_grew
    for name in ("sorted_keys", "sorted_pos", "gids", "sizes"):
        np.testing.assert_array_equal(
            np.asarray(getattr(up.bank, name)),
            np.asarray(getattr(full.bank, name)),
            err_msg=name,
        )
    a = lider.search_lider(up, q, k=10, n_probe=8, r0=8)
    b = lider.search_lider(full, q, k=10, n_probe=8, r0=8)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_upsert_finds_new_passages(split_indexes):
    x, q, gt, base, new_x, _ = split_indexes
    up, _ = update.upsert(base, new_x)
    out = lider.search_lider(up, q, k=10, n_probe=8, r0=8)
    assert float(recall_at_k(out.ids, gt)) > 0.9


def test_upsert_learned_route(split_indexes):
    """The centroids-retriever route also lands every point in a cluster."""
    x, q, gt, base, new_x, _ = split_indexes
    up, stats = update.upsert(base, new_x, route="learned")
    assert stats.n_added == new_x.shape[0]
    assert int(up.bank.sizes.sum()) == int(base.bank.sizes.sum()) + new_x.shape[0]
    out = lider.search_lider(up, q, k=10, n_probe=8, r0=8)
    assert float(recall_at_k(out.ids, gt)) > 0.85


@pytest.mark.parametrize("threshold", [1.0, 0.0])
def test_deleted_ids_never_surface(corpus, threshold):
    """Tombstoned (and, at threshold 0, compacted) ids never appear."""
    x, q, _, = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    before = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    dead = np.unique(np.asarray(before.ids)[:, :3].ravel())
    dead = dead[dead >= 0]
    d, stats = update.delete(p, jnp.asarray(dead), refit_threshold=threshold)
    assert stats.n_deleted == len(dead)
    assert (stats.n_refit > 0) == (threshold == 0.0)
    after = lider.search_lider(d, q, k=10, n_probe=8, r0=8)
    assert not np.intersect1d(np.asarray(after.ids), dead).size
    # live points are still served
    ids = np.asarray(after.ids)
    assert (ids >= 0).any(axis=-1).all()


def test_delete_then_upsert_reuses_capacity(corpus):
    x, _, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    d, _ = update.delete(p, jnp.arange(100, dtype=jnp.int32), refit_threshold=0.0)
    # compaction freed the slots: same capacity can absorb 100 new rows
    up, stats = update.upsert(d, x[:100] * 0.99)
    assert int(up.bank.sizes.sum()) == x.shape[0]
    assert int(up.bank.next_gid) == x.shape[0] + 100


def test_capacity_growth_keeps_pad_multiple(corpus):
    """Overflowing one cluster grows Lp in pad_multiple steps and the grown
    index still finds the new points."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    old_cap = p.capacity
    # aim a burst at one spot: clones of one corpus vector overflow its cluster
    burst = jnp.tile(x[:1], (2 * CFG.pad_multiple + old_cap, 1))
    up, stats = update.upsert(p, burst, pad_multiple=CFG.pad_multiple)
    assert stats.capacity_grew
    assert up.capacity > old_cap
    assert up.capacity % CFG.pad_multiple == 0
    assert int(up.bank.sizes.sum()) == x.shape[0] + burst.shape[0]
    out = lider.search_lider(up, x[:1], k=10, n_probe=8, r0=8)
    new_gids = set(range(x.shape[0], x.shape[0] + burst.shape[0]))
    assert new_gids & set(np.asarray(out.ids).ravel().tolist())


def _assert_sorted_pos_live(bank, step):
    """Every ``sorted_pos >= 0`` entry points at a row whose gid is live:
    search reads candidate liveness off ``sorted_pos`` alone."""
    sp = np.asarray(bank.sorted_pos)
    gids = np.asarray(bank.gids)
    c = sp.shape[0]
    at = gids[np.arange(c)[:, None, None], np.maximum(sp, 0)]
    assert (at[sp >= 0] >= 0).all(), step
    # and every live row is still reachable from each sorted array
    assert ((sp >= 0).sum(-1) == (gids >= 0).sum(-1)[:, None]).all(), step


@pytest.mark.parametrize(
    "over",
    [{}, {"storage_dtype": "int8"},
     {"storage_dtype": "int4", "rescore_tier": "host"}],
    ids=["float32", "int8-device", "int4-host"],
)
def test_sorted_pos_points_only_at_live_rows(corpus, over):
    """The invariant holds after build, upsert, capacity growth, a
    tombstoning delete and a compacting delete."""
    x, _, _ = corpus
    cfg = dataclasses.replace(CFG, **over)
    p = lider.build_lider(jax.random.PRNGKey(2), x[:3000], cfg)
    _assert_sorted_pos_live(p.bank, "build")
    p, _ = update.upsert(p, x[3000:])
    _assert_sorted_pos_live(p.bank, "upsert")
    burst = jnp.tile(x[:1], (p.capacity + CFG.pad_multiple, 1))
    p, stats = update.upsert(p, burst, pad_multiple=CFG.pad_multiple)
    assert stats.capacity_grew
    _assert_sorted_pos_live(p.bank, "growth")
    p, stats = update.delete(
        p, jnp.arange(0, 4000, 5, dtype=jnp.int32), refit_threshold=1.0
    )
    assert stats.n_refit == 0 and int(p.bank.tombstones.sum()) == 800
    _assert_sorted_pos_live(p.bank, "tombstone")
    p, stats = update.delete(
        p, jnp.arange(1, 4000, 5, dtype=jnp.int32), refit_threshold=0.0
    )
    assert stats.n_refit > 0
    _assert_sorted_pos_live(p.bank, "compaction")


def test_checkpoint_roundtrip_bit_identical(corpus, tmp_path):
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    p, _ = update.upsert(p, x[:32] * 0.98)  # persist a *mutated* index
    checkpoint.save_index(str(tmp_path), p)
    p2 = checkpoint.load_index(str(tmp_path))
    for (path_a, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(p)[0],
        jax.tree_util.tree_flatten_with_path(p2)[0],
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=str(path_a)
        )
    before = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    after = lider.search_lider(p2, q, k=10, n_probe=8, r0=8)
    np.testing.assert_array_equal(np.asarray(before.ids), np.asarray(after.ids))
    np.testing.assert_array_equal(
        np.asarray(before.scores), np.asarray(after.scores)
    )


def test_engine_apply_updates_generations(corpus):
    """Same-shape updates bump only the generation; growth also recompiles."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, CFG)
    search = make_backend("lider", None, updatable=True, n_probe=8, r0=8)
    engine = RetrievalEngine(search, batch_size=16, k=10, dim=x.shape[1], params=p)
    engine.warmup()
    grew = engine.apply_updates(lambda pr: update.upsert(pr, x[:8] * 0.97))
    assert not grew
    assert engine.generation == 1 and engine.recompiles == 0
    burst = jnp.tile(x[:1], (p.capacity + 8, 1))
    grew = engine.apply_updates(lambda pr: update.upsert(pr, burst))
    assert grew
    assert engine.generation == 2 and engine.recompiles == 1
    rids = [engine.submit(v) for v in np.asarray(q)[:16]]
    engine.drain()
    assert all(engine.result(r) is not None for r in rids)


def test_engine_requires_params_for_updates(corpus):
    x, _, _ = corpus
    search = make_backend("flat", None, x)
    engine = RetrievalEngine(search, batch_size=8, k=5, dim=x.shape[1])
    with pytest.raises(ValueError, match="params"):
        engine.apply_updates(lambda p: p)


def test_make_backend_rejects_unknown_kwargs(corpus):
    x, _, _ = corpus
    with pytest.raises(TypeError, match="n_prove"):
        make_backend("lider", None, n_prove=8)  # typo'd n_probe
    with pytest.raises(TypeError, match="refine"):
        make_backend("flat", None, x, refine=True)
    with pytest.raises(ValueError, match="unknown backend"):
        make_backend("annoy", None)
    with pytest.raises(ValueError, match="updatable"):
        make_backend("flat", None, x, updatable=True)
    # the mplsh probe knob is spelled n_probe like every other backend
    from repro.core.baselines import build_mplsh
    mp = build_mplsh(jax.random.PRNGKey(0), x)
    make_backend("mplsh", mp, x, n_probe=4)


def test_init_centroids_clear_error():
    x = jnp.zeros((10, 8))
    with pytest.raises(ValueError, match="10 points"):
        clustering.init_centroids(jax.random.PRNGKey(0), x, 32)


def test_param_specs_derived_from_bank_metadata():
    """Replicated-vs-sharded layout comes from ClusterBank field metadata:
    the shared LSH bank and scalar bank metadata stay replicated, every
    stacked per-cluster tensor is sharded on its leading axis."""
    from jax import ShapeDtypeStruct as SDS
    from jax.sharding import PartitionSpec as P
    from repro.core import bank as bank_lib
    from repro.core.core_model import CoreModelParams
    from repro.core.distributed import lider_param_specs
    from repro.core.lsh import LSHParams
    from repro.core.rescale import RescaleParams
    from repro.core.rmi import RMIParams

    c, h, lp, d, w = 8, 2, 16, 4, 3
    resc = lambda lead: RescaleParams(
        key_min=SDS(lead, jnp.uint32),
        key_max=SDS(lead, jnp.uint32),
        length=SDS(lead, jnp.float32),
    )
    rmi = lambda lead: RMIParams(
        root_w=SDS(lead, jnp.float32), root_b=SDS(lead, jnp.float32),
        leaf_w=SDS(lead + (w,), jnp.float32), leaf_b=SDS(lead + (w,), jnp.float32),
        length=SDS(lead, jnp.float32), max_err=SDS(lead + (w,), jnp.float32),
        n_leaves=w,
    )
    params = lider.LiderParams(
        centroid_cm=CoreModelParams(
            lsh=LSHParams(projections=SDS((d, 4), jnp.float32), n_arrays=2, key_len=2),
            rescale=resc((h,)), rmi=rmi((h,)),
            sorted_keys=SDS((h, c), jnp.uint32), sorted_ids=SDS((h, c), jnp.int32),
        ),
        centroids=SDS((c, d), jnp.float32),
        bank=bank_lib.ClusterBank(
            lsh=LSHParams(projections=SDS((d, 4), jnp.float32), n_arrays=2, key_len=2),
            rescale=resc((c, h)), rmi=rmi((c, h)),
            sorted_keys=SDS((c, h, lp), jnp.uint32),
            sorted_pos=SDS((c, h, lp), jnp.int32),
            embs=SDS((c, lp, d), jnp.float32),
            gids=SDS((c, lp), jnp.int32),
            sizes=SDS((c,), jnp.int32),
            tombstones=SDS((c,), jnp.int32),
            next_gid=SDS((), jnp.int32),
        ),
    )
    specs = lider_param_specs(params, ("data",))
    # everything outside the bank + the shared LSH + scalar metadata: replicated
    assert specs.centroid_cm.sorted_keys == P()
    assert specs.centroids == P()
    assert specs.bank.lsh.projections == P()
    assert specs.bank.next_gid == P()
    # stacked per-cluster tensors: sharded on the leading (cluster) axis
    assert specs.bank.sorted_keys == P(("data",), None, None)
    assert specs.bank.embs == P(("data",), None, None)
    assert specs.bank.sizes == P(("data",))
    assert specs.bank.tombstones == P(("data",))
    assert specs.bank.rmi.leaf_w == P(("data",), None, None)
