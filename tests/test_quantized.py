"""Quantized ClusterBank (DESIGN.md §Quantized bank): int8/int4 round-trip
error bounds, packed-nibble idempotence, kernel-vs-oracle parity across
storage dtypes and dead/mixed blocks, lifecycle (upsert/delete/checkpoint)
consistency of the code + scale + rescore tables, and the quantized+rescore
recall-parity acceptance checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import clustering, lider, update
from repro.core.bank import store_rows
from repro.core.baselines import flat_search
from repro.core.utils import l2_normalize, recall_at_k
from repro.kernels import fused_verify, ref
from repro.kernels.quant import (
    INT4_MAX,
    INT8_MAX,
    dequantize_rows,
    dequantize_rows_int4,
    pack_int4,
    quantize_rows,
    quantize_rows_int4,
    unpack_int4,
)
from repro.serving import RetrievalEngine, make_backend
from repro.training import checkpoint


# ---------------------------------------------------------------------------
# Quantization scheme: round-trip error bound (hypothesis property test)
# ---------------------------------------------------------------------------


def test_int8_roundtrip_score_error_bounded_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(0, 10_000), st.integers(1, 96), st.floats(0.01, 100.0))
    @settings(max_examples=60, deadline=None)
    def check(seed, d, magnitude):
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(4, d)) * magnitude).astype(np.float32)
        q = rng.normal(size=(d,)).astype(np.float32)
        codes, scales = quantize_rows(jnp.asarray(x))
        dq = np.asarray(dequantize_rows(codes, scales))
        # Per-element round-to-nearest error is <= scale/2, so the score
        # error of one quantized row against an exact query is bounded by
        # ||q||_1 * scale/2 — the §Quantized bank error model.
        got = dq @ q
        want = x @ q
        bound = np.abs(q).sum() * (np.asarray(scales) / 2.0) + 1e-4
        assert (np.abs(got - want) <= bound).all()
        # codes stay in the symmetric range (-128 never appears)
        assert np.abs(np.asarray(codes, np.int32)).max() <= INT8_MAX

    check()


def test_int4_roundtrip_score_error_bounded_hypothesis():
    """The 4-bit analogue of the §Quantized bank error model: per-element
    round-to-nearest error is <= scale/2 (with scale = max|x|/7), so a
    quantized row's score error against an exact query is bounded by
    ||q||_1 * scale/2 — identical bound shape, coarser scale."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(0, 10_000), st.integers(1, 48), st.floats(0.01, 100.0))
    @settings(max_examples=60, deadline=None)
    def check(seed, half_d, magnitude):
        d = 2 * half_d  # packing needs an even row width
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(4, d)) * magnitude).astype(np.float32)
        q = rng.normal(size=(d,)).astype(np.float32)
        packed, scales = quantize_rows_int4(jnp.asarray(x))
        assert packed.shape == (4, d // 2) and packed.dtype == jnp.int8
        dq = np.asarray(dequantize_rows_int4(packed, scales))
        bound = np.abs(q).sum() * (np.asarray(scales) / 2.0) + 1e-4
        assert (np.abs(dq @ q - x @ q) <= bound).all()
        # unpacked nibbles stay in the symmetric range (-8 never appears)
        codes = np.asarray(unpack_int4(packed), np.int32)
        assert np.abs(codes).max() <= INT4_MAX

    check()


def test_int4_pack_unpack_idempotent():
    """pack/unpack are exact inverses over the full nibble range [-8, 7]
    (the packed carrier can hold -8 even though the quantizer never emits
    it), across arbitrary leading dims; odd widths are rejected."""
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(-8, 8, size=(5, 3, 24)), jnp.int8)
    packed = pack_int4(codes)
    assert packed.shape == (5, 3, 12) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)), np.asarray(codes))
    np.testing.assert_array_equal(
        np.asarray(pack_int4(unpack_int4(packed))), np.asarray(packed)
    )
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((2, 7), jnp.int8))


def test_int4_zero_rows_pack_to_zero_bytes():
    """All-zero (padded-slot) rows must pack to exact zero bytes, scale 1."""
    packed, scales = quantize_rows_int4(jnp.zeros((3, 16)))
    np.testing.assert_array_equal(np.asarray(packed), 0)
    np.testing.assert_array_equal(np.asarray(scales), 1.0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_rows_int4(packed, scales)), 0.0
    )


def test_quantize_zero_rows_are_exact_padding():
    """All-zero (padded-slot) rows must quantize to exact zeros, scale 1."""
    x = jnp.zeros((3, 16))
    codes, scales = quantize_rows(x)
    np.testing.assert_array_equal(np.asarray(codes), 0)
    np.testing.assert_array_equal(np.asarray(scales), 1.0)
    np.testing.assert_array_equal(np.asarray(dequantize_rows(codes, scales)), 0.0)


# ---------------------------------------------------------------------------
# Kernel vs oracle parity: storage dtypes x block liveness patterns
# ---------------------------------------------------------------------------


def _case(seed, n, d, b, c):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    embs = jax.random.normal(k1, (n, d))
    ids = jax.random.randint(k2, (b, c), 0, n)
    q = jax.random.normal(k3, (b, d))
    return embs, ids, q


def _mask(ids, pattern, block_c):
    """Apply a liveness pattern in units of the kernel's candidate blocks."""
    if pattern == "all_live":
        return ids
    if pattern == "mixed":
        return ids.at[:, ::3].set(-1)
    if pattern == "dead_block":  # one fully-dead block per row
        return ids.at[:, block_c : 2 * block_c].set(-1)
    if pattern == "all_pruned_row":  # row 0 entirely dead
        return ids.at[0, :].set(-1)
    raise ValueError(pattern)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8", "int4"])
@pytest.mark.parametrize(
    "pattern", ["all_live", "mixed", "dead_block", "all_pruned_row"]
)
def test_fused_parity_across_dtypes_and_block_liveness(dtype, pattern):
    block_c = 8
    embs_f, ids, q = _case(11, 64, 32, 3, 4 * block_c)
    ids = _mask(ids, pattern, block_c)
    if dtype in ("int8", "int4"):
        quant = quantize_rows if dtype == "int8" else quantize_rows_int4
        table, scales = quant(embs_f)
    else:
        table = embs_f.astype(jnp.dtype(dtype))
        scales = None
    code_dtype = "int4" if dtype == "int4" else "int8"
    gi, gs = fused_verify(
        table, ids, q, k=6, scales=scales, block_c=block_c,
        code_dtype=code_dtype, interpret=True,
    )
    wi, ws = ref.verify_topk_ref(
        table, ids, q, k=6, scales=scales, code_dtype=code_dtype
    )
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    np.testing.assert_allclose(
        np.asarray(gs), np.asarray(ws), rtol=2e-2 if dtype == "bfloat16" else 1e-6
    )
    if pattern == "all_pruned_row":
        assert (np.asarray(gi)[0] == -1).all()
        assert np.isneginf(np.asarray(gs)[0]).all()


def test_int8_oracle_scores_near_exact():
    """Quantized scoring obeys the §Quantized bank error model against exact
    f32 scoring: |err| <= ||q||_1 s_x/2 + ||x||_1 s_q/2 + d s_x s_q / 4
    (two first-order rounding terms + the second-order cross term)."""
    rng = np.random.default_rng(5)
    d = 48
    x = rng.normal(size=(80, d)).astype(np.float32)
    q = rng.normal(size=(2, d)).astype(np.float32)
    codes, scales = quantize_rows(jnp.asarray(x))
    q_codes, q_scales = quantize_rows(jnp.asarray(q))
    got = (
        np.asarray(codes, np.int32) @ np.asarray(q_codes, np.int32).T
    ).astype(np.float32) * np.asarray(scales)[:, None] * np.asarray(q_scales)
    want = x @ q.T
    sx = np.asarray(scales)[:, None]
    sq = np.asarray(q_scales)[None, :]
    bound = (
        np.abs(q).sum(-1)[None, :] * sx / 2
        + np.abs(x).sum(-1)[:, None] * sq / 2
        + d * sx * sq / 4
        + 1e-4
    )
    assert (np.abs(got - want) <= bound).all()


# ---------------------------------------------------------------------------
# End-to-end LIDER: storage dtypes through build/search
# ---------------------------------------------------------------------------

CFG = lider.LiderConfig(
    n_clusters=32, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10
)


def _cfg(storage_dtype, **kw):
    return dataclasses.replace(CFG, storage_dtype=storage_dtype, **kw)


@pytest.fixture(scope="module")
def built(corpus):
    x, q, gt = corpus
    params = {
        sd: lider.build_lider(jax.random.PRNGKey(0), x, _cfg(sd))
        for sd in ("float32", "bfloat16", "int8", "int4")
    }
    return x, q, gt, params


def test_bank_storage_dtypes(built):
    _, _, _, params = built
    assert params["float32"].bank.embs.dtype == jnp.float32
    assert params["float32"].bank.emb_scales is None
    assert params["bfloat16"].bank.embs.dtype == jnp.bfloat16
    assert params["bfloat16"].bank.rescore_embs is None
    b = params["int8"].bank
    assert b.embs.dtype == jnp.int8 and b.quantized
    assert b.emb_scales.shape == b.gids.shape
    assert b.rescore_embs.shape == b.embs.shape
    assert b.storage_dtype == "int8"
    b4 = params["int4"].bank
    assert b4.embs.dtype == jnp.int8 and b4.quantized
    assert b4.storage_dtype == "int4" and b4.code_dtype == "int4"
    # packed carrier is half the logical width; rescore table stays full
    assert b4.embs.shape[-1] * 2 == b4.rescore_embs.shape[-1]
    assert b4.dim == b.dim
    assert b4.emb_scales.shape == b4.gids.shape


def test_int8_rescore_recall_parity(built):
    """Acceptance: int8+rescore recall@k within eps of the bf16 path."""
    _, q, gt, params = built
    r16 = recall_at_k(
        lider.search_lider(params["bfloat16"], q, k=10, n_probe=8, r0=8).ids, gt
    )
    r8 = recall_at_k(
        lider.search_lider(params["int8"], q, k=10, n_probe=8, r0=8).ids, gt
    )
    assert float(r8) >= float(r16) - 0.02
    # and both stay near the full-precision path
    r32 = recall_at_k(
        lider.search_lider(params["float32"], q, k=10, n_probe=8, r0=8).ids, gt
    )
    assert float(r8) >= float(r32) - 0.03


def test_int4_rescore_recall_parity(built):
    """Acceptance: int4 first pass + exact rescore recall@k within 0.02 of
    the int8 path. The 4-bit codes only pick the rescore candidates, but
    their coarser ordering needs roughly twice the rescore window
    (rescore_factor 8 vs int8's default 4) to surface the same winners —
    still a traffic win: the wider exact gather is B·k'·d while the first
    pass streams half the bytes (DESIGN.md §Quantized bank, int4 column)."""
    _, q, gt, params = built
    r8 = recall_at_k(
        lider.search_lider(params["int8"], q, k=10, n_probe=8, r0=8).ids, gt
    )
    r4 = recall_at_k(
        lider.search_lider(
            params["int4"], q, k=10, n_probe=8, r0=8, rescore_factor=8
        ).ids, gt,
    )
    assert float(r4) >= float(r8) - 0.02


@pytest.mark.parametrize("sd", ["int8", "int4"])
def test_rescore_scores_are_exact(built, sd):
    """Returned scores come from the full-precision side table: every
    (id, score) the quantized path surfaces equals the exact f32 inner
    product."""
    x, q, _, params = built
    out = lider.search_lider(params[sd], q, k=10, n_probe=8, r0=8)
    ids = np.asarray(out.ids)
    scores = np.asarray(out.scores)
    exact = np.asarray(jnp.einsum("nd,bd->bn", jnp.asarray(x), q))
    for b in range(ids.shape[0]):
        for i, s in zip(ids[b], scores[b]):
            if i >= 0:
                np.testing.assert_allclose(s, exact[b, i], rtol=1e-5, atol=1e-5)


def test_rescore_factor_widens_recovery(built):
    """rescore_factor=1 rescores exactly k candidates (order-only recovery);
    larger factors can only help; both run and stay well-formed."""
    _, q, gt, params = built
    r1 = recall_at_k(
        lider.search_lider(
            params["int8"], q, k=10, n_probe=8, r0=8, rescore_factor=1
        ).ids, gt,
    )
    r4 = recall_at_k(
        lider.search_lider(
            params["int8"], q, k=10, n_probe=8, r0=8, rescore_factor=4
        ).ids, gt,
    )
    assert float(r4) >= float(r1) - 1e-6


def test_search_core_model_quantized_two_stage(corpus):
    """The standalone core-model spelling of the quantized search: int8
    first pass + exact rescore from the full-precision table. Returned
    scores must be exact f32 inner products and recall must track the float
    model."""
    from repro.core.core_model import build_core_model, search_core_model

    x, q, gt = corpus
    cm = build_core_model(jax.random.PRNGKey(0), x, n_arrays=6, n_leaves=4)
    base = search_core_model(cm, x, q, k=10, r0=8)
    codes, scales = quantize_rows(x)
    with pytest.raises(ValueError, match="rescore_embs"):
        search_core_model(cm, codes, q, k=10, r0=8, scales=scales)
    got = search_core_model(
        cm, codes, q, k=10, r0=8, scales=scales, rescore_embs=x,
        rescore_factor=4,
    )
    r_base = float(recall_at_k(base.ids, gt))
    r_got = float(recall_at_k(got.ids, gt))
    assert r_got >= r_base - 0.02
    exact = np.asarray(jnp.einsum("nd,bd->bn", x, q))
    ids, scores = np.asarray(got.ids), np.asarray(got.scores)
    for b in range(ids.shape[0]):
        for i, s in zip(ids[b], scores[b]):
            if i >= 0:
                np.testing.assert_allclose(s, exact[b, i], rtol=1e-5, atol=1e-5)


def test_block_c_threading_does_not_change_results(built):
    """block_c is a pure performance knob: any value gives identical ids."""
    _, q, _, params = built
    base = lider.search_lider(params["float32"], q, k=10, n_probe=8, r0=8)
    for bc in (32, 128, 1024):
        got = lider.search_lider(
            params["float32"], q, k=10, n_probe=8, r0=8, block_c=bc,
            use_fused=True,
        )
        np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(base.ids))


# ---------------------------------------------------------------------------
# Lifecycle: upsert / delete / checkpoint keep the quantized tables consistent
# ---------------------------------------------------------------------------


def _assert_bank_consistent(bank):
    """Invariants tying codes, scales, and the rescore side table together."""
    codes = np.asarray(bank.embs, np.int32)
    scales = np.asarray(bank.emb_scales)
    rescore = np.asarray(bank.rescore_embs)
    gids = np.asarray(bank.gids)
    assert (scales > 0).all()
    # dequantized codes approximate the rescore rows to half a step per elem
    dq = np.asarray(bank.float_rows())
    np.testing.assert_allclose(dq, codes * scales[..., None], rtol=1e-6)
    assert (np.abs(dq - rescore) <= scales[..., None] / 2 + 1e-6).all()
    # free/tombstoned slots hold exact zeros in both tables
    dead = gids < 0
    assert (codes[dead] == 0).all()
    assert (rescore[dead] == 0.0).all()
    # stored codes re-quantize to themselves (row-local scheme, no drift)
    c2, s2 = quantize_rows(jnp.asarray(rescore))
    np.testing.assert_array_equal(codes, np.asarray(c2, np.int32))
    np.testing.assert_allclose(scales, np.asarray(s2), rtol=1e-6)


@pytest.mark.parametrize("sd", ["int8", "int4"])
def test_quantized_upsert_matches_full_rebuild(corpus, sd):
    """build(80%) -> upsert(20%) is slot- and byte-identical to build(100%)
    on the quantized tables (quantization is row-local — for int4 the packed
    nibble bytes themselves must match)."""
    x, q, _ = corpus
    n80 = int(x.shape[0] * 0.8)
    km = clustering.kmeans(jax.random.PRNGKey(2), x[:n80], CFG.n_clusters, iters=10)
    assignment, _ = clustering.assign_chunked(x, km.centroids)
    max_size = int(jnp.bincount(assignment, length=CFG.n_clusters).max())
    cfg = _cfg(
        sd,
        capacity=lider.padded_capacity(max_size, None, CFG.pad_multiple),
    )
    full = lider.build_lider(jax.random.PRNGKey(2), x, cfg, centroids=km.centroids)
    base = lider.build_lider(
        jax.random.PRNGKey(2), x[:n80], cfg, centroids=km.centroids
    )
    up, stats = update.upsert(base, x[n80:])
    assert stats.n_added == x.shape[0] - n80
    for name in ("sorted_keys", "sorted_pos", "gids", "embs", "emb_scales",
                 "rescore_embs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(up.bank, name)),
            np.asarray(getattr(full.bank, name)),
            err_msg=name,
        )
    if sd == "int8":
        _assert_bank_consistent(up.bank)
    else:
        # stored packed nibbles re-quantize to themselves from the rescore
        # table (row-local scheme, no drift through the upsert path)
        c2, s2 = quantize_rows_int4(jnp.asarray(up.bank.rescore_embs))
        np.testing.assert_array_equal(
            np.asarray(up.bank.embs), np.asarray(c2)
        )
        np.testing.assert_allclose(
            np.asarray(up.bank.emb_scales), np.asarray(s2), rtol=1e-6
        )
    a = lider.search_lider(up, q, k=10, n_probe=8, r0=8)
    b = lider.search_lider(full, q, k=10, n_probe=8, r0=8)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


@pytest.mark.parametrize("threshold", [1.0, 0.0])
def test_int8_delete_keeps_tables_consistent(corpus, threshold):
    """Tombstoning and (threshold 0) compaction never surface dead ids and
    keep codes/scales/rescore in lockstep."""
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(2), x, _cfg("int8"))
    before = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    dead = np.unique(np.asarray(before.ids)[:, :3].ravel())
    dead = dead[dead >= 0][:50]
    p2, stats = update.delete(
        p, jnp.asarray(dead, jnp.int32), refit_threshold=threshold
    )
    assert stats.n_deleted == len(dead)
    if threshold == 0.0:
        assert stats.n_refit > 0  # compaction actually ran
        _assert_bank_consistent(p2.bank)
    after = lider.search_lider(p2, q, k=10, n_probe=8, r0=8)
    assert not np.isin(np.asarray(after.ids), dead).any()


def test_int8_capacity_growth_preserves_tables(corpus):
    """An upsert that grows Lp pads scales with the zero-row convention and
    keeps every pre-existing slot byte-identical."""
    x, q, _ = corpus
    cfg = _cfg("int8", n_clusters=16, capacity=None)
    p = lider.build_lider(jax.random.PRNGKey(0), x, cfg)
    old = p.bank
    p2, stats = update.upsert(p, x[:300] + 0.01)
    assert stats.capacity_grew
    _assert_bank_consistent(p2.bank)
    lp = old.capacity
    touched = np.unique(
        np.asarray(clustering.assign_chunked(x[:300] + 0.01, p.centroids)[0])
    )
    untouched = np.setdiff1d(np.arange(16), touched)
    np.testing.assert_array_equal(
        np.asarray(p2.bank.embs)[untouched, :lp],
        np.asarray(old.embs)[untouched],
    )
    np.testing.assert_array_equal(
        np.asarray(p2.bank.emb_scales)[untouched, :lp],
        np.asarray(old.emb_scales)[untouched],
    )


@pytest.mark.parametrize("sd", ["int8", "int4"])
def test_quantized_checkpoint_roundtrip(tmp_path, corpus, sd):
    x, q, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg(sd))
    checkpoint.save_index(str(tmp_path), p)
    p2 = checkpoint.load_index(str(tmp_path))
    assert p2.bank.quantized and p2.bank.embs.dtype == jnp.int8
    assert p2.bank.code_dtype == sd
    flat_a = jax.tree_util.tree_leaves(p)
    flat_b = jax.tree_util.tree_leaves(p2)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    a = lider.search_lider(p, q, k=10, n_probe=8, r0=8)
    b = lider.search_lider(p2, q, k=10, n_probe=8, r0=8)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_float_checkpoint_has_no_quantized_leaves(tmp_path, corpus):
    """f32 indexes round-trip without scale/rescore files (format compat)."""
    x, _, _ = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("float32"))
    checkpoint.save_index(str(tmp_path), p)
    p2 = checkpoint.load_index(str(tmp_path))
    assert p2.bank.emb_scales is None and p2.bank.rescore_embs is None


# ---------------------------------------------------------------------------
# Serving + store_rows argument validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage_dtype", ["int8", "int4"])
def test_build_encode_in_chunks_matches_store_rows(storage_dtype):
    """The build's chunked in-place encode (bank._encode_rows) writes the
    same codes, scales and sketches as one store_rows call, across chunk
    boundaries and a ragged last chunk."""
    from repro.core.bank import _encode_rows

    raw = jax.random.normal(jax.random.PRNGKey(3), (10, 16, 40))
    got = _encode_rows(lambda lo, hi: raw[lo:hi], raw.shape, storage_dtype,
                       chunk=4)
    codes, scales, _, sketches = store_rows(raw, storage_dtype)
    for g, w in zip(got, (codes, scales, sketches)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_store_rows_rejects_unknown_dtype():
    with pytest.raises(ValueError, match="storage_dtype"):
        store_rows(jnp.zeros((2, 4, 8)), "float16")


def test_serving_engine_serves_int8_with_rescore(corpus):
    x, q, gt = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("int8"))
    search = make_backend(
        "lider", None, updatable=True, n_probe=8, r0=8, rescore_factor=4,
        block_c=128,
    )
    eng = RetrievalEngine(search, batch_size=16, k=10, dim=x.shape[1], params=p)
    eng.warmup()
    rids = [eng.submit(np.asarray(qq)) for qq in np.asarray(q)[:32]]
    eng.drain()
    got = np.stack([eng.result(r)[0] for r in rids])
    rec = float(recall_at_k(jnp.asarray(got), gt[:32]))
    assert rec > 0.85


def test_serving_engine_serves_int4_cluster_major(corpus):
    """int4 bank + cluster-major schedule threaded through backend kwargs:
    the serving path with ``block_q`` set returns the same ids the direct
    per-query search does, at serving recall."""
    x, q, gt = corpus
    p = lider.build_lider(jax.random.PRNGKey(0), x, _cfg("int4"))
    search = make_backend(
        "lider", None, updatable=True, n_probe=8, r0=8, rescore_factor=4,
        block_c=128, block_q=4,
    )
    eng = RetrievalEngine(search, batch_size=16, k=10, dim=x.shape[1], params=p)
    eng.warmup()
    rids = [eng.submit(np.asarray(qq)) for qq in np.asarray(q)[:32]]
    eng.drain()
    got = np.stack([eng.result(r)[0] for r in rids])
    rec = float(recall_at_k(jnp.asarray(got), gt[:32]))
    assert rec > 0.85
    direct = lider.search_lider(p, q[:16], k=10, n_probe=8, r0=8,
                                rescore_factor=4, block_c=128)
    np.testing.assert_array_equal(got[:16], np.asarray(direct.ids))
