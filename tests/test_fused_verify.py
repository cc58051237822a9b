"""Fused gather-score-reduce verification kernel: parity with the
materialized reference across padding/dtype/blocking edge cases, the
cluster-major grouped kernel and its schedule pre-pass, plus the end-to-end
LIDER regressions (DESIGN.md §Verification-kernel, §Cluster-major
schedule)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lider
from repro.core.utils import l2_normalize
from repro.kernels import fused_verify, fused_verify_grouped, ref
from repro.kernels.fused_verify import (
    _VMEM_CAP,
    _rows_per_step,
    _tile_plan,
    sketch_prefilter,
)
from repro.kernels.quant import quantize_rows, quantize_rows_int4, sketch_rows
from repro.kernels.schedule import build_cluster_schedule


# Batch sizes the parity tests run at: one query (R = 1), a batch under
# one row group, a full sublane group, a ragged batch, the cells' batch (one
# full group of 32), and two groups with padding rows.
BATCHES = (1, 5, 8, 13, 32, 40)


def _case(seed, n, d, b, c, dtype, id_lo=-1):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    embs = jax.random.normal(k1, (n, d), dtype)
    ids = jax.random.randint(k2, (b, c), id_lo, n)
    q = jax.random.normal(k3, (b, d), dtype)
    return embs, ids, q


def _assert_parity(embs, row_ids, q, k, block_c, out_ids=None, rtol=1e-6):
    gi, gs = fused_verify(
        embs, row_ids, q, k=k, out_ids=out_ids, block_c=block_c, interpret=True
    )
    wi, ws = ref.verify_topk_ref(embs, row_ids, q, k=k, out_ids=out_ids)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), rtol=rtol, atol=rtol)
    return np.asarray(gi), np.asarray(gs)


def test_rows_per_step_follows_batch():
    """R is the whole batch up to 32 rows, then 32: B = 1 keeps one query a
    grid step, and no batch of the pow2 ladder is padded."""
    got = [_rows_per_step(b) for b in (1, 2, 5, 8, 13, 32, 40, 64, 256)]
    assert got == [1, 2, 5, 8, 13, 32, 32, 32, 32]


# Every per-query call of the two d=768 cells, (C, table row bytes, bytes a
# row unpacks to in VMEM): route over the f32 centroids, exact rescore of
# k'=40 f32 rows, sketch pre-filter over 24-word sketches, int4 pass over
# the 160 sketch survivors, int8 pass over all 8,000 candidates.
D768_CALLS = {
    "route": (800, 768 * 4, 0),
    "rescore": (40, 768 * 4, 0),
    "sketch": (8000, 24 * 4, 0),
    "int4": (160, 384, 768),
    "int8": (8000, 768, 0),
}


@pytest.mark.parametrize("b", [1, 13, 32, 256])
@pytest.mark.parametrize("call", sorted(D768_CALLS))
def test_tile_plan_at_d768_is_unchanged(call, b):
    """At d=768 every call keeps the R, block and VMEM limit it had before
    the plan followed the row width: R = min(B, 32), the clamped block, and
    the double-buffered tile plus 32 MiB."""
    c, row_bytes, unpacked = D768_CALLS[call]
    r, bc = min(b, 32), max(8, min(256, c) // 8 * 8)
    assert _tile_plan(b, c, 256, row_bytes, unpacked) == (
        r, bc, 2 * r * bc * row_bytes + (32 << 20))


def test_tile_plan_shrinks_the_block_for_wide_rows():
    """d=4096: the f32 route's block shrinks to the widest multiple of 8
    that fits (48), the other calls keep theirs and R; a row too wide for
    one 8-row block is an error."""
    assert _tile_plan(32, 800, 256, 4096 * 4) == (32, 48, _VMEM_CAP)
    assert _tile_plan(32, 40, 256, 4096 * 4)[:2] == (32, 40)
    assert _tile_plan(32, 160, 256, 2048, 4096)[:2] == (32, 160)
    assert _tile_plan(32, 8000, 256, 128 * 4)[:2] == (32, 256)
    with pytest.raises(ValueError, match="too wide"):
        _tile_plan(32, 800, 256, 10**9)
    for b in (1, 32, 256):
        for c, row_bytes, unpacked in [(800, 16384, 0), (40, 16384, 0),
                                       (160, 2048, 4096), (8192, 2048, 4096)]:
            assert _tile_plan(b, c, 256, row_bytes, unpacked)[2] <= _VMEM_CAP


@pytest.mark.parametrize("b", [5, 32])
@pytest.mark.parametrize("kind", ["float", "int4", "sketch"])
def test_parity_at_d4096(kind, b):
    """Interpret-mode parity with the oracle at the LLM embedder's width,
    bit-equal: at B=32 the f32 block shrinks to 48 of its 96 (three
    blocks for C=100), and each row's score is still one sum over d."""
    embs, ids, q = _case(12, 64, 4096, b, 100, jnp.float32)
    ids = ids.at[:, ::7].set(-1)
    if kind == "float":
        gi, gs = fused_verify(embs, ids, q, k=10, interpret=True)
        wi, ws = ref.verify_topk_ref(embs, ids, q, k=10)
    elif kind == "int4":
        table, scales = quantize_rows_int4(embs)
        gi, gs = fused_verify(table, ids, q, k=10, scales=scales,
                              code_dtype="int4", interpret=True)
        wi, ws = ref.verify_topk_ref(table, ids, q, k=10, scales=scales,
                                     code_dtype="int4")
    else:
        table = sketch_rows(embs)
        gi, gs = sketch_prefilter(table, ids, q, k=16, interpret=True)
        wi, ws = ref.sketch_topk_ref(table, ids, q, k=16)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_parity_padded_ids(dtype, b):
    """-1 slots are excluded and never win a top-k slot."""
    embs, ids, q = _case(0, 40, 32, b, 17, dtype)
    ids = ids.at[:, ::3].set(-1)
    _assert_parity(embs, ids, q, k=5, block_c=8)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("c,block_c", [(17, 8), (21, 4), (7, 16), (64, 16)])
def test_parity_c_not_multiple_of_block(c, block_c, b):
    embs, ids, q = _case(c, 50, 16, b, c, jnp.float32)
    _assert_parity(embs, ids, q, k=4, block_c=block_c)


@pytest.mark.parametrize("b", BATCHES)
def test_parity_k_exceeds_valid_candidates(b):
    """k > #unique valid ids: tail slots are (-1, -inf), same as the ref."""
    embs, ids, q = _case(3, 30, 16, b, 6, jnp.float32)
    ids = ids.at[:, 3:].set(-1)  # 3 valid per row, duplicates possible
    gi, gs = _assert_parity(embs, ids, q, k=8, block_c=4)
    assert (gi[:, 3:] == -1).all()
    assert np.isneginf(gs[:, 3:]).all()


@pytest.mark.parametrize("b", BATCHES)
def test_parity_duplicate_ids_deduped(b):
    """Duplicate candidates occupy one top-k slot, not several."""
    embs, ids, q = _case(4, 25, 16, b, 12, jnp.float32, id_lo=0)
    ids = ids.at[:, 6:].set(ids[:, :6])  # every candidate duplicated
    gi, _ = _assert_parity(embs, ids, q, k=6, block_c=4)
    for row in gi:
        v = row[row >= 0]
        assert len(set(v.tolist())) == len(v)


@pytest.mark.parametrize("b", BATCHES)
def test_parity_score_ties_break_by_smallest_id(b):
    """Distinct ids with bit-equal scores (duplicate table rows) must come
    out in the reference order: smallest id first, in every row."""
    k1, k3 = jax.random.split(jax.random.PRNGKey(11), 2)
    embs = jax.random.normal(k1, (20, 16))
    embs = embs.at[7].set(embs[2]).at[13].set(embs[2])  # 3-way score tie
    ids = jnp.tile(jnp.asarray([[13, 2, 0, 7, 5, 13]]), (b, 1))
    q = jax.random.normal(k3, (b, 16))
    gi, _ = _assert_parity(embs, ids, q, k=5, block_c=2)
    for row in gi:
        tied = [i for i in row.tolist() if i in (2, 7, 13)]
        assert tied == [2, 7, 13]


@pytest.mark.parametrize("b", BATCHES)
def test_parity_out_ids_mapping(b):
    """row_ids gather rows; out_ids name/dedup them (the LIDER shape: flat
    (cluster, slot) rows in, global passage ids out)."""
    embs, rows, q = _case(5, 40, 16, b, 10, jnp.float32, id_lo=0)
    out_ids = rows + 100  # distinct id space
    out_ids = out_ids.at[:, 1].set(-1)  # padding marked on out_ids only
    _assert_parity(embs, rows, q, k=4, block_c=4, out_ids=out_ids)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_parity_large_shape_sweep(dtype, b):
    embs, ids, q = _case(6, 200, 64, b, 70, dtype)
    rtol = 1e-6 if dtype == jnp.float32 else 2e-2
    _assert_parity(embs, ids, q, k=10, block_c=16, rtol=rtol)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("code_dtype", ["int8", "int4"])
def test_quantized_parity_block_c_exceeds_c(code_dtype, b):
    """Regression for the lane-aligned clamp ``bc = min(block_c, c)``: a
    block size larger than the candidate count (the kernel default 256 vs a
    tiny provisional list) must clamp, not pad the grid with out-of-range
    reads — and the clamp must stay exact on the quantized paths where the
    table width differs from the logical width (packed int4)."""
    embs_f, ids, q = _case(9, 40, 32, b, 10, jnp.float32)
    quant = quantize_rows if code_dtype == "int8" else quantize_rows_int4
    table, scales = quant(embs_f)
    gi, gs = fused_verify(
        table, ids, q, k=4, scales=scales, block_c=64,
        code_dtype=code_dtype, interpret=True,
    )
    wi, ws = ref.verify_topk_ref(
        table, ids, q, k=4, scales=scales, code_dtype=code_dtype
    )
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


@pytest.mark.parametrize("code_dtype", [None, "int8", "int4"])
def test_row_group_mixes_dead_and_live_rows(code_dtype):
    """Two row groups (B = 40): all-dead rows beside live ones in the same
    group, a block dead in every row of the first group but live in the
    second (skipped there, merged here), and padding rows. Dead rows come
    back all (-1, -inf); every other row matches the reference exactly."""
    embs_f, ids, q = _case(21, 60, 32, 40, 48, jnp.float32, id_lo=0)
    dead_rows = [0, 3, 31, 33]  # in both groups, incl. a group's last row
    ids = ids.at[jnp.asarray(dead_rows)].set(-1)
    ids = ids.at[:32, 16:32].set(-1)  # block 1 dead across group 0 only
    kw = {}
    table = embs_f
    if code_dtype is not None:
        quant = quantize_rows if code_dtype == "int8" else quantize_rows_int4
        table, kw["scales"] = quant(embs_f)
        kw["code_dtype"] = code_dtype
    gi, gs = fused_verify(table, ids, q, k=6, block_c=16, interpret=True, **kw)
    wi, ws = ref.verify_topk_ref(table, ids, q, k=6, **kw)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), rtol=1e-6)
    assert (np.asarray(gi)[dead_rows] == -1).all()
    assert np.isneginf(np.asarray(gs)[dead_rows]).all()
    live = np.setdiff1d(np.arange(40), dead_rows)
    assert (np.asarray(gi)[live] >= 0).all()


@pytest.mark.parametrize("b", [8, 40])
def test_same_id_in_two_queries_does_not_leak(b):
    """Dedup is per query: an id that two rows of one group share is kept by
    both when it wins in both, and never enters a row that lacks it."""
    n, d = 64, 16
    embs = l2_normalize(jax.random.normal(jax.random.PRNGKey(3), (n, d)))
    q = jnp.tile(embs[5][None], (b, 1))  # every query's best row is 5
    rng = np.random.default_rng(4)
    ids = np.stack([rng.choice(np.arange(6, n), 12, replace=False) for _ in range(b)])
    ids[::2, 7] = 5  # even rows hold id 5, odd rows never do
    ids = jnp.asarray(ids, jnp.int32)
    gi, _ = _assert_parity(embs, ids, q, k=4, block_c=8)
    assert (gi[::2, 0] == 5).all()
    assert not (gi[1::2] == 5).any()
    for row, cand in zip(gi, np.asarray(ids)):
        assert set(row[row >= 0].tolist()) <= set(cand.tolist())


# ---------------------------------------------------------------------------
# Cluster-major schedule (DESIGN.md §Cluster-major schedule)
# ---------------------------------------------------------------------------


def _zipf_cids(rng, b, p, n_clusters, a=1.3):
    w = 1.0 / np.arange(1, n_clusters + 1) ** a
    w /= w.sum()
    return np.stack(
        [rng.choice(n_clusters, size=p, replace=False, p=w) for _ in range(b)]
    ).astype(np.int32)


def test_build_cluster_schedule_invariants():
    """The schedule is a bijection over kept pairs: every kept (query,
    probe) pair lands in exactly one (step, slot) that points back at it,
    pruned pairs are excluded, steps stream clusters in ascending order, and
    Zipf-skewed probe lists actually share steps (ratio > 1)."""
    rng = np.random.default_rng(3)
    cids = _zipf_cids(rng, 24, 4, 16)
    pruned = rng.random(cids.shape) < 0.2
    sched = build_cluster_schedule(cids, block_q=8, pruned=pruned)
    keep = ~pruned
    qs, ps = np.nonzero(keep)
    st, sl = sched.pair_step[qs, ps], sched.pair_slot[qs, ps]
    assert (st >= 0).all() and (sl >= 0).all() and (sl < 8).all()
    np.testing.assert_array_equal(sched.sched_cids[st], cids[qs, ps])
    np.testing.assert_array_equal(sched.sched_qids[st, sl], qs)
    assert (sched.pair_step[pruned] == -1).all()
    assert (sched.pair_slot[pruned] == -1).all()
    # each scheduled (step, slot) is used by at most one pair
    assert len(set(zip(st.tolist(), sl.tolist()))) == len(st)
    real = sched.sched_cids[: sched.n_steps]
    assert (np.diff(real) >= 0).all()
    assert sched.n_pairs == int(keep.sum())
    assert sched.sharing_ratio > 1.0
    # padding steps carry empty query tiles
    assert (sched.sched_qids[sched.n_steps :] == -1).all()
    # block_q=1 degenerates to the per-query loop order: one pair per step
    s1 = build_cluster_schedule(cids, block_q=1, pruned=pruned)
    assert s1.n_steps == s1.n_pairs == int(keep.sum())


def _dense_slot_ids(sched, lp):
    """Every scheduled slot scores its cluster's full Lp flat rows."""
    s = sched.sched_cids.shape[0]
    out = np.full((s, sched.block_q, lp), -1, np.int32)
    step, slot = np.nonzero(sched.sched_qids >= 0)
    out[step, slot] = sched.sched_cids[step, None] * lp + np.arange(lp)
    return out


@pytest.mark.parametrize("code_dtype", ["int8", "int4"])
def test_grouped_kernel_matches_ref(code_dtype):
    """fused_verify_grouped (interpret) is bit-exact — ids AND scores —
    against the materialized grouped oracle on a Zipf-skewed schedule, for
    both code dtypes."""
    c, lp, d, b, p, block_q = 6, 16, 32, 5, 3, 4
    k1, k2 = jax.random.split(jax.random.PRNGKey(17), 2)
    embs_f = jax.random.normal(k1, (c, lp, d))
    q = jax.random.normal(k2, (b, d))
    quant = quantize_rows if code_dtype == "int8" else quantize_rows_int4
    codes, scales = quant(embs_f)
    sched = build_cluster_schedule(
        _zipf_cids(np.random.default_rng(5), b, p, c), block_q=block_q
    )
    slot_ids = jnp.asarray(_dense_slot_ids(sched, lp))
    args = (
        codes, scales, q,
        jnp.asarray(sched.sched_cids), jnp.asarray(sched.sched_qids),
        slot_ids,
    )
    gi, gs = fused_verify_grouped(
        *args, kp=6, block_q=block_q, block_c=8, code_dtype=code_dtype,
        interpret=True,
    )
    wi, ws = ref.verify_topk_grouped_ref(*args, kp=6, code_dtype=code_dtype)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


@pytest.fixture(scope="module", params=["int8", "int4"])
def quantized_lider(request):
    rng = jax.random.PRNGKey(7)
    kc, kx, kq, kb = jax.random.split(rng, 4)
    centers = jax.random.normal(kc, (16, 32))
    assign = jax.random.randint(kx, (1500,), 0, 16)
    x = l2_normalize(centers[assign] + 0.3 * jax.random.normal(kq, (1500, 32)))
    q = l2_normalize(x[:8] + 0.05 * jax.random.normal(kb, (8, 32)))
    cfg = lider.LiderConfig(
        n_clusters=16, n_probe=4, n_arrays=2, n_leaves=2, kmeans_iters=5,
        storage_dtype=request.param,
    )
    params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
    return params, q


def test_cluster_major_matches_per_query_schedule(quantized_lider):
    """Acceptance: the cluster-major search is bit-exact — ids AND scores —
    against the per-query schedule; block_q is a pure loop-order change."""
    params, q = quantized_lider
    base = lider.search_lider(params, q, k=10, n_probe=4, r0=8)
    for bq in (1, 4, 8):
        got = lider.search_lider(params, q, k=10, n_probe=4, r0=8, block_q=bq)
        np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(base.ids))
        np.testing.assert_array_equal(
            np.asarray(got.scores), np.asarray(base.scores)
        )


def test_cluster_major_invariant_to_query_order(quantized_lider):
    """Shuffling the batch only permutes the outputs: the schedule's
    determinism contract (cluster asc, query asc, probe asc) means a query's
    results cannot depend on where it sits in the batch or which other
    queries share its steps."""
    params, q = quantized_lider
    base = lider.search_lider(params, q, k=10, n_probe=4, r0=8, block_q=4)
    perm = np.random.default_rng(0).permutation(q.shape[0])
    got = lider.search_lider(
        params, q[jnp.asarray(perm)], k=10, n_probe=4, r0=8, block_q=4
    )
    np.testing.assert_array_equal(
        np.asarray(got.ids), np.asarray(base.ids)[perm]
    )
    np.testing.assert_array_equal(
        np.asarray(got.scores), np.asarray(base.scores)[perm]
    )


def test_cluster_major_parity_under_prune_margin(quantized_lider):
    """Pruned probes drop out of the schedule (pair_step = -1) instead of
    being masked in-kernel; outputs and the pruned-stats mask must still
    match the per-query path exactly."""
    params, q = quantized_lider
    base, pruned_b = lider.search_lider(
        params, q, k=10, n_probe=4, r0=8, prune_margin=0.15, with_stats=True
    )
    got, pruned_g = lider.search_lider(
        params, q, k=10, n_probe=4, r0=8, prune_margin=0.15, with_stats=True,
        block_q=4,
    )
    np.testing.assert_array_equal(np.asarray(pruned_g), np.asarray(pruned_b))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(base.ids))
    np.testing.assert_array_equal(
        np.asarray(got.scores), np.asarray(base.scores)
    )
    assert np.asarray(pruned_g).any()  # the margin actually pruned probes


def test_cluster_major_rejects_float_banks(small_lider):
    params, q = small_lider
    with pytest.raises(ValueError, match="quantized"):
        lider.search_lider(params, q, k=10, n_probe=4, r0=8, block_q=4)


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


def test_search_lider_fused_matches_unfused(small_lider):
    """Regression: the end-to-end fused path returns the exact unfused ids."""
    params, q = small_lider
    unfused = lider.search_lider(params, q, k=10, n_probe=4, r0=8, use_fused=False)
    fused = lider.search_lider(params, q, k=10, n_probe=4, r0=8, use_fused=True)
    np.testing.assert_array_equal(np.asarray(fused.ids), np.asarray(unfused.ids))
    np.testing.assert_allclose(
        np.asarray(fused.scores), np.asarray(unfused.scores), rtol=1e-6
    )


def test_incluster_merge_false_fused_matches_unfused(small_lider):
    """The per-pair (B, P, k) shape the distributed path scatters back."""
    params, q = small_lider
    routed = lider.route_queries(params, q, n_probe=4)
    unfused = lider.incluster_search(
        params, q, routed.ids, k=5, r0=8, merge=False, use_fused=False
    )
    fused = lider.incluster_search(
        params, q, routed.ids, k=5, r0=8, merge=False, use_fused=True
    )
    assert fused.ids.shape == (q.shape[0], 4, 5)
    np.testing.assert_array_equal(np.asarray(fused.ids), np.asarray(unfused.ids))
