"""LIDER: the clustering-based two-layer learned index (paper Sec. 3).

Layer 1: a *centroids retriever* (one core model over the k-means centroids)
routes each query to ``n_probe`` (= paper c0) clusters. Layer 2: a
:class:`~repro.core.bank.ClusterBank` — the per-cluster retrievers stacked
into dense padded tensors so a (query x probed-cluster) batch is pure gather
+ matmul dataflow (see ``core/bank.py`` for the layout).

Build is staged (paper Sec. 3.3.2): ``assign`` (k-means or nearest-centroid
against precomputed centroids) -> ``pack`` (capacity slots) -> ``hash/sort/
fit`` via ``vmap(bank.refit_cluster)``. The same ``refit_cluster`` unit
powers the incremental upsert/delete path in ``core.update``, so online
maintenance and offline build cannot drift.

``search_lider`` is the single-device reference; ``core.distributed`` wraps
the same ``incluster_search`` math in a shard_map with capacity-based
query->cluster-shard dispatch for the production mesh.

Search stages carry ``jax.named_scope`` names, so every device op (XLA
fusion or Pallas call) of the served jits names its stage in its op-name
metadata and a profiler trace can sum device time per stage:
``lider.route`` (centroid routing + probe pruning), ``lider.candidates``
(hash, rescale, RMI, window, the ``sorted_pos``/``gids`` gathers),
``lider.sketch`` (binary-sketch pre-filter), ``lider.code_pass`` (the
int8/int4 first pass) and ``lider.rescore`` (exact rescore + row->gid map).
Scopes never nest across stages, and none is a kernel's name.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import bank as bank_lib
from . import clustering, lsh as lsh_lib, rescale as rescale_lib, rmi as rmi_lib
from ..kernels.ops import sketch_topk_op, verify_topk_grouped_op, verify_topk_op
from .bank import ClusterBank
from .core_model import CoreModelParams, TopK, build_core_model, search_core_model
from .types import pytree_dataclass
from .utils import dedup_topk

# The stage scopes (module docstring) live in op metadata, which JAX's
# persistent compile cache leaves out of its key by default: an executable
# cached before a scope was added or moved would then profile under stale
# stage names. Keyed on metadata, it compiles again instead.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


@dataclasses.dataclass(frozen=True)
class LiderConfig:
    """Static build/search configuration (paper Sec. 7.2.1 defaults)."""

    n_clusters: int = 1000  # c
    n_probe: int = 20  # c0
    n_arrays: int = 10  # H (in-cluster)
    n_arrays_centroid: int = 10  # H (centroids retriever)
    key_len: int | None = None  # M (in-cluster); None -> ceil(log2 Lp)
    key_len_centroid: int | None = None  # M (centroids); None -> ceil(log2 c)
    n_leaves: int = 5  # RMI width W_i
    n_leaves_centroid: int = 10  # RMI width W_c
    r0: int = 4  # expansion range factor, R = r0 * k
    r0_centroid: int = 4
    kmeans_iters: int = 20
    capacity: int | None = None  # Lp cap; None -> max cluster size (no drops)
    pad_multiple: int = 8
    refine: bool = False  # beyond-paper last-mile searchsorted correction
    # Verification-kernel escape hatch: None -> fused Pallas pass on TPU,
    # materialized reference elsewhere; True/False forces either path.
    # Like n_probe/refine, search entry points take this as a kwarg and
    # launchers feed it from the config (DESIGN.md §Verification-kernel).
    use_fused: bool | None = None
    # Embedding storage dtype (DESIGN.md §Quantized bank): "float32",
    # "bfloat16", "int8", or "int4". int8 cuts the compulsory candidate-row
    # gather 4x vs f32; int4 packs two codes per byte (8x, 0.5 B/elem).
    # Both quantized dtypes add an exact rescore pass over the provisional
    # top-(rescore_factor * k) from the full-precision side table.
    storage_dtype: str = "float32"
    rescore_factor: int = 4  # k' = rescore_factor * k (quantized storage only)
    # Where the full-precision rescore side table lives (quantized storage
    # only; DESIGN.md §Tiered embedding store). "device": a pytree leaf next to
    # the codes (PR-4 layout — costs ~25% more HBM than f32). "host": a
    # process-local pinned host array outside the pytree; search becomes
    # the staged fetch->rescore pipeline and the device-resident index
    # shrinks to codes + scales (~0.25x of f32).
    rescore_tier: str = "device"
    # Verification-kernel candidate block size; None -> kernel default (256).
    # Swept by the Pareto autotuner alongside the quantization knobs.
    block_c: int | None = None
    # Cluster-major multi-query batching (DESIGN.md §Cluster-major schedule;
    # quantized banks only): queries in a batch probing the same cluster are
    # grouped into block_q-wide tiles so the cluster's rows are streamed
    # once per tile instead of once per query — the big first-pass DMA win
    # under Zipf-skewed traffic. None keeps the per-query schedule.
    # Bit-identical results either way; swept by the Pareto autotuner.
    block_q: int | None = None
    # Binary-sketch pre-filter tier (DESIGN.md §Binary sketch tier;
    # quantized banks only): a 1-bit Hamming first pass over the packed
    # sign-sketch table (1/8 the int8 row bytes) keeps the top
    # ``sketch_factor * k'`` survivor rows per query, so the int4/int8 code
    # DMA + MXU pass touches only survivors. None disables the tier; a
    # factor large enough to cover every candidate is bit-identical to the
    # unfiltered pass (tests gate this). Swept by the Pareto autotuner.
    sketch_factor: int | None = None
    # Adaptive probe pruning (DESIGN.md §Adaptive speed-quality control
    # plane): probes whose layer-1 centroid score falls more than this
    # margin below the per-query best are masked to -1 before layer 2.
    # None disables pruning (bit-identical to the fixed-n_probe search).
    prune_margin: float | None = None
    # Capacity overflow policy: when ``capacity`` is below the max cluster
    # size, overflow passages are silently unretrievable unless this is set
    # (bank.build_bank raises CapacityOverflowError otherwise).
    allow_drops: bool = False


@pytree_dataclass
class LiderParams:
    centroid_cm: CoreModelParams
    centroids: jnp.ndarray  # (c, d)
    bank: ClusterBank  # stacked per-cluster state (core/bank.py)

    @property
    def n_clusters(self) -> int:
        return self.bank.n_clusters

    @property
    def capacity(self) -> int:
        return self.bank.capacity

    @property
    def dim(self) -> int:
        return self.bank.dim


# ---------------------------------------------------------------------------
# Build (paper Sec. 3.3.2: Stage 1 clustering, Stage 2 CR, Stage 3 IRs)
# ---------------------------------------------------------------------------


def padded_capacity(max_size: int, cap: int | None, pad_multiple: int) -> int:
    """Slot count per cluster: requested (or max) size, padded for the TPU."""
    cap = cap or max_size
    return max(pad_multiple, math.ceil(cap / pad_multiple) * pad_multiple)


def assign_points(
    rng: jax.Array,
    embs: jnp.ndarray,
    config: LiderConfig,
    *,
    centroids: jnp.ndarray | None = None,
) -> clustering.KMeansResult:
    """Stage 1: k-means, or nearest-centroid against precomputed centroids.

    The ``centroids`` override is the layer-1-frozen rebuild used by the
    update lifecycle (and by multi-stage corpora that share one routing
    layer): assignment is the exact nearest centroid, the same rule the final
    Lloyd step applies — so an index built this way is slot-for-slot
    comparable with one grown by ``core.update.upsert``.
    """
    if centroids is None:
        return clustering.kmeans(rng, embs, config.n_clusters, iters=config.kmeans_iters)
    assignment, _ = clustering.assign_chunked(embs, centroids)
    return clustering.KMeansResult(centroids=centroids, assignment=assignment)


@dataclasses.dataclass(frozen=True)
class BuildStats:
    """Host-side accounting for one offline build."""

    n_indexed: int  # passages that got a slot
    n_dropped: int  # capacity-overflow drops (0 unless allow_drops=True)
    capacity: int  # padded per-cluster slot count Lp


def build_lider(
    rng: jax.Array,
    embs: jnp.ndarray,
    config: LiderConfig,
    *,
    centroids: jnp.ndarray | None = None,
    return_stats: bool = False,
) -> LiderParams | tuple[LiderParams, BuildStats]:
    n, dim = embs.shape
    c = config.n_clusters
    rng_km, rng_cen, rng_in = jax.random.split(rng, 3)

    # Stage 1: clustering (or routing against supplied centroids).
    km = assign_points(rng_km, embs, config, centroids=centroids)
    sizes = jnp.bincount(km.assignment, length=c).astype(jnp.int32)
    max_size = int(jax.device_get(jnp.max(sizes)))
    cap = padded_capacity(max_size, config.capacity, config.pad_multiple)

    # Stage 3: pack -> hash/sort -> fit (vmap of the single-cluster refit).
    # Packing counts capacity-overflow drops; unless the config opts in via
    # allow_drops, a lossy pack raises instead of silently losing passages.
    bank, n_dropped = bank_lib.build_bank(
        rng_in,
        embs,
        km.assignment,
        n_clusters=c,
        capacity=cap,
        n_arrays=config.n_arrays,
        key_len=config.key_len or lsh_lib.suggest_key_len(cap),
        n_leaves=config.n_leaves,
        allow_drops=config.allow_drops,
        storage_dtype=config.storage_dtype,
        rescore_tier=config.rescore_tier,
    )

    # Stage 2: centroids retriever.
    centroid_cm = build_core_model(
        rng_cen,
        km.centroids,
        n_arrays=config.n_arrays_centroid,
        key_len=config.key_len_centroid or lsh_lib.suggest_key_len(c),
        n_leaves=config.n_leaves_centroid,
    )

    params = LiderParams(centroid_cm=centroid_cm, centroids=km.centroids, bank=bank)
    if return_stats:
        return params, BuildStats(
            n_indexed=n - n_dropped, n_dropped=n_dropped, capacity=cap
        )
    return params


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


def prune_probes(
    cids: jnp.ndarray, scores: jnp.ndarray, prune_margin: float | None
) -> jnp.ndarray:
    """Margin rule of the adaptive control plane (DESIGN.md §Adaptive).

    ``cids``/``scores``: (B, P) layer-1 routing output. Probes whose centroid
    score falls more than ``prune_margin`` below the per-query best are
    masked to -1 — shapes stay static (no recompiles per margin value; the
    margin itself is traced), downstream layers treat -1 as an unused probe
    slot. ``None`` returns ``cids`` untouched (bit-identical fixed-probe
    search).
    """
    if prune_margin is None:
        return cids
    valid = cids >= 0
    best = jnp.max(
        jnp.where(valid, scores, -jnp.inf), axis=-1, keepdims=True
    )  # (B, 1)
    keep = scores >= best - prune_margin
    return jnp.where(valid & keep, cids, -1)


def route_queries(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    n_probe: int,
    r0: int = 4,
    use_fused: bool | None = None,
    prune_margin: float | None = None,
    block_c: int | None = None,
) -> TopK:
    """Layer-1: centroids retriever -> (B, n_probe) cluster ids + scores.

    With ``prune_margin`` set, low-confidence probes come back masked to
    (-1, -inf) — the slot count stays ``n_probe`` so downstream shapes are
    static. The centroid table itself always stays full precision (it is
    KB–MB sized; quantizing it would risk routing quality for no traffic
    win).
    """
    routed = search_core_model(
        params.centroid_cm, params.centroids, queries, k=n_probe, r0=r0,
        use_fused=use_fused, block_c=block_c,
    )
    if prune_margin is None:
        return routed
    cids = prune_probes(routed.ids, routed.scores, prune_margin)
    return TopK(
        ids=cids, scores=jnp.where(cids >= 0, routed.scores, -jnp.inf)
    )


def set_rescore_tier(params: LiderParams, tier: str) -> LiderParams:
    """Move the index's rescore table between storage tiers (§Tiered store).

    Search results are bit-identical across the move; only where the
    full-precision rows live — and therefore which search pipeline runs —
    changes (``bank.set_rescore_tier``).
    """
    return dataclasses.replace(
        params, bank=bank_lib.set_rescore_tier(params.bank, tier)
    )


@jax.named_scope("lider.candidates")
def _bank_candidates(
    bank: ClusterBank,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    r0: int,
    refine: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Candidate generation over the probed clusters (hash -> rescale -> RMI
    -> window expansion). Returns ``(flat_emb, gids)``, both (B, P, H, R):
    flat ``(cluster, slot)`` rows into the ``(c*Lp, ...)`` tables and the
    matching global passage ids (-1 at dead/invalid candidates). Shared by
    the single-pass float search, the quantized two-stage search, and the
    tiered first pass (§Tiered embedding store)."""
    c, h, lp = bank.sorted_keys.shape
    b, p = cids.shape
    r = min(r0 * k, lp)

    qkeys = lsh_lib.hash_vectors(bank.lsh, queries)  # (B, H)
    safe_cid = jnp.clip(cids, 0, c - 1)
    cvalid = cids >= 0  # (B, P)

    # Gather per-(query, probe) rescale + RMI models out of the bank, then
    # predict positions with the banked RMI form.
    resc = jax.tree.map(lambda leaf: leaf[safe_cid], bank.rescale)  # (B, P, H)
    scaled = rescale_lib.rescale(resc, qkeys[:, None, :])  # (B, P, H)
    pos = rmi_lib.predict_banked(
        rmi_lib.gather_banked(bank.rmi, safe_cid), scaled
    )  # (B, P, H)

    h_idx = jnp.arange(h, dtype=jnp.int32)[None, None, :, None]
    if refine:
        # Beyond-paper last-mile: gather a 2R key window around the RMI
        # prediction (keys are 4 B vs d*4 B embeddings) and binary-search the
        # exact position inside it, then expand only R around the truth.
        w1 = min(2 * r, lp)
        start1 = jnp.clip(jnp.round(pos).astype(jnp.int32) - w1 // 2, 0, lp - w1)
        idx1 = start1[..., None] + jnp.arange(w1, dtype=jnp.int32)
        flat1 = (safe_cid[:, :, None, None] * h + h_idx) * lp + idx1
        keys_win = jnp.take(bank.sorted_keys.reshape(-1), flat1)  # (B,P,H,W1)
        qk = jnp.broadcast_to(qkeys[:, None, :], (b, p, h)).reshape(-1)
        rows = keys_win.reshape(-1, w1)
        off = jax.vmap(lambda row, q: jnp.searchsorted(row, q))(rows, qk)
        pos = (start1 + off.reshape(b, p, h).astype(jnp.int32)).astype(jnp.float32)

    start = jnp.clip(jnp.round(pos).astype(jnp.int32) - r // 2, 0, lp - r)
    idx = start[..., None] + jnp.arange(r, dtype=jnp.int32)  # (B, P, H, R)
    flat = (safe_cid[:, :, None, None] * h + h_idx) * lp + idx
    local_pos = jnp.take(bank.sorted_pos.reshape(-1), flat)  # (B, P, H, R)

    valid = (local_pos >= 0) & cvalid[:, :, None, None]
    flat_emb = safe_cid[:, :, None, None] * lp + jnp.maximum(local_pos, 0)
    gids = jnp.take(bank.gids.reshape(-1), flat_emb)
    gids = jnp.where(valid, gids, -1)
    return flat_emb, gids


def _verify_bank_rows(
    bank: ClusterBank,
    flat_rows: jnp.ndarray,
    out_gids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    rescore_factor: int,
    block_c: int | None,
    use_pallas: bool | None,
    sketch_factor: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Verify ``(Bq, C)`` flat bank rows -> gid-space top-k ids + scores
    (device-tier rescore table).

    The single verification funnel for both ``incluster_search`` shapes
    (merged and per-pair). On a float bank this is one ``verify_topk_op``
    call deduped by global id. On a quantized bank it is the two-stage pass
    (DESIGN.md §Quantized bank):

    1. int8 first pass over the code table, deduped by *flat row* — exact
       within the bank, since a passage occupies exactly one (cluster, slot)
       — keeping the provisional top-``k' = rescore_factor*k``;
    2. exact rescore of those k' rows from the full-precision side table
       (a gather k'/C the size of the first pass), reusing the same fused
       kernel; final rows map back to global ids through ``bank.gids``.

    On a *host-tier* bank the rescore table is not device-resident, so the
    second stage cannot be traced here; stage 1 is :func:`provisional_rows`
    and the fetch + rescore run between jits (:func:`search_lider` /
    ``serving.RetrievalEngine`` pipeline) — this function is device-tier
    only.

    Score ties between distinct passages break by smallest flat row on the
    quantized path (vs smallest gid on the float path) — both deterministic.
    """
    c, lp = bank.gids.shape
    if not bank.quantized:
        with jax.named_scope("lider.rescore"):
            return verify_topk_op(
                bank.embs.reshape(c * lp, -1),
                flat_rows,
                queries,
                k=k,
                out_ids=out_gids,
                block_c=block_c,
                use_pallas=use_pallas,
            )
    with jax.named_scope("lider.candidates"):
        out_rows = jnp.where(out_gids >= 0, flat_rows, -1)
    kp = min(max(rescore_factor, 1) * k, out_rows.shape[-1])
    if sketch_factor is not None and bank.sketches is not None:
        # Binary-sketch pre-filter (DESIGN.md §Binary sketch tier): 1-bit
        # Hamming pass over the packed sign sketches keeps the top
        # ``sketch_factor * k'`` survivor rows (deduped by flat row, same
        # tie-break as the int pass), so the code-table DMA below streams
        # only survivors. A factor covering every distinct candidate is
        # bit-identical to the unfiltered pass: survivors then hold all
        # valid rows, per-row int scores are unchanged, and dedup collapses
        # the duplicates the sketch pass already collapsed.
        m = min(max(sketch_factor, 1) * kp, out_rows.shape[-1])
        with jax.named_scope("lider.sketch"):
            surv, _ = sketch_topk_op(
                bank.sketches.reshape(c * lp, -1),
                flat_rows,
                queries,
                k=m,
                out_ids=out_rows,
                block_c=block_c,
                use_pallas=use_pallas,
            )
            flat_rows = jnp.maximum(surv, 0)
        out_rows = surv
    with jax.named_scope("lider.code_pass"):
        prov_rows, _ = verify_topk_op(
            bank.embs.reshape(c * lp, -1),
            flat_rows,
            queries,
            k=kp,
            out_ids=out_rows,
            scales=bank.emb_scales.reshape(-1),
            block_c=block_c,
            code_dtype=bank.code_dtype,
            use_pallas=use_pallas,
        )
    with jax.named_scope("lider.rescore"):
        rows, scores = verify_topk_op(
            bank.rescore_embs.reshape(c * lp, -1),
            jnp.maximum(prov_rows, 0),
            queries,
            k=k,
            out_ids=prov_rows,
            block_c=block_c,
            use_pallas=use_pallas,
        )
        ids = jnp.where(
            rows >= 0, bank.gids.reshape(-1)[jnp.maximum(rows, 0)], -1
        )
    return ids, scores


def incluster_search(
    params: LiderParams,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    r0: int = 4,
    refine: bool = False,
    merge: bool = True,
    use_fused: bool | None = None,
    cid_scores: jnp.ndarray | None = None,
    prune_margin: float | None = None,
    rescore_factor: int = 4,
    block_c: int | None = None,
    sketch_factor: int | None = None,
) -> TopK:
    """Layer-2: search the probed clusters for each query.

    ``queries``: (B, d); ``cids``: (B, P) cluster ids (-1 = unused probe slot).
    With ``merge=False`` returns the per-pair top-k (B, P, k) — the shape the
    distributed capacity-dispatch path scatters back before merging.
    With ``cid_scores`` (the layer-1 routing scores) and ``prune_margin``
    both set, probes outside the margin are masked to -1 here instead of by
    the caller — either spelling yields the same candidate mask.

    Verification goes through ``verify_topk_op`` (``use_fused`` as in
    ``LiderConfig``): the fused kernel streams the gathered rows through VMEM
    and emits only the (B, k) result, instead of materializing the
    (B, P, H, R, d) candidate tensor in HBM before the einsum. On an int8
    bank the pass runs in the compressed domain and is followed by an exact
    rescore of the provisional top-``rescore_factor * k`` rows
    (:func:`_verify_bank_rows`); ``block_c`` tunes the kernel's candidate
    block size.
    """
    if prune_margin is not None:
        if cid_scores is None:
            raise ValueError("prune_margin needs cid_scores (layer-1 scores)")
        cids = prune_probes(cids, cid_scores, prune_margin)
    bank = params.bank
    if bank.rescore_tier == "host":
        raise ValueError(
            "incluster_search cannot complete on a host-tier bank — the "
            "rescore table is off-device; use search_lider (staged "
            "fetch->rescore pipeline) or provisional_rows + "
            "rescore_fetched_rows directly (DESIGN.md §Tiered embedding "
            "store)"
        )
    b, p = cids.shape
    flat_emb, gids = _bank_candidates(
        bank, queries, cids, k=k, r0=r0, refine=refine
    )

    # Verification: gather rows from the flat (c*Lp, d) table (row_ids =
    # flat_emb), dedup/report by global passage id (out_ids = gids, -1 where
    # invalid — tombstoned rows carry gid -1 and are suppressed here).
    # Scoring happens in the embedding storage dtype (bf16 stays bf16 on the
    # MXU, int8 runs int8xint8->int32 + exact rescore) with fp32 accumulation
    # for a stable top-k ordering.
    if merge:
        ids, sc = _verify_bank_rows(
            bank,
            flat_emb.reshape(b, -1),
            gids.reshape(b, -1),
            queries,
            k=k,
            rescore_factor=rescore_factor,
            block_c=block_c,
            use_pallas=use_fused,
            sketch_factor=sketch_factor,
        )
        return TopK(ids=ids, scores=sc)
    # Per-pair top-k: flatten (query, probe) pairs into the batch axis so the
    # same kernel covers the shape the distributed path scatters back.
    pair_q = jnp.broadcast_to(queries[:, None, :], (b, p, queries.shape[-1]))
    ids, sc = _verify_bank_rows(
        bank,
        flat_emb.reshape(b * p, -1),
        gids.reshape(b * p, -1),
        pair_q.reshape(b * p, -1),
        k=k,
        rescore_factor=rescore_factor,
        block_c=block_c,
        use_pallas=use_fused,
        sketch_factor=sketch_factor,
    )
    return TopK(ids=ids.reshape(b, p, k), scores=sc.reshape(b, p, k))


@partial(
    jax.jit,
    static_argnames=(
        "k", "n_probe", "r0", "r0_centroid", "refine", "use_fused",
        "with_stats", "rescore_factor", "block_c", "sketch_factor",
    ),
)
def _search_lider_device(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    n_probe: int = 20,
    r0: int = 4,
    r0_centroid: int = 4,
    refine: bool = False,
    use_fused: bool | None = None,
    prune_margin: float | None = None,
    with_stats: bool = False,
    rescore_factor: int = 4,
    block_c: int | None = None,
    sketch_factor: int | None = None,
) -> TopK | tuple[TopK, jnp.ndarray]:
    """Single-jit search for device-tier banks (float, or int8 with the
    rescore table resident next to the codes)."""
    with jax.named_scope("lider.route"):
        routed = route_queries(
            params, queries, n_probe=n_probe, r0=r0_centroid,
            use_fused=use_fused, block_c=block_c,
        )
        cids = prune_probes(routed.ids, routed.scores, prune_margin)
        pruned = (routed.ids >= 0) & (cids < 0)
    out = incluster_search(
        params, queries, cids, k=k, r0=r0, refine=refine,
        use_fused=use_fused, rescore_factor=rescore_factor, block_c=block_c,
        sketch_factor=sketch_factor,
    )
    if with_stats:
        return out, pruned
    return out


# ---------------------------------------------------------------------------
# Tiered (host-resident rescore table) search: three explicit stages
# (DESIGN.md §Tiered embedding store)
# ---------------------------------------------------------------------------


def provisional_rows(
    params: LiderParams,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    r0: int = 4,
    refine: bool = False,
    merge: bool = True,
    use_fused: bool | None = None,
    rescore_factor: int = 4,
    block_c: int | None = None,
    sketch_factor: int | None = None,
) -> TopK:
    """Stage 1 of the tiered search: compressed-domain first pass only.

    Same candidate generation and int8 first pass as the device-tier
    quantized search — deduped by flat bank row, same tie-break — but stops
    at the provisional top-``k' = rescore_factor*k``: ``ids`` are *flat bank
    rows* (-1 padding) and ``scores`` are the compressed-domain scores. The
    caller fetches those rows from the host tier (``bank.store.fetch``) and
    finishes with :func:`rescore_fetched_rows` / :func:`host_rescore`.
    ``merge=False`` keeps the per-(query, probe) pair shape for the
    distributed capacity-dispatch path.
    """
    bank = params.bank
    if not bank.quantized:
        raise ValueError("provisional_rows needs a quantized (int8/int4) bank")
    b, p = cids.shape
    flat_emb, gids = _bank_candidates(
        bank, queries, cids, k=k, r0=r0, refine=refine
    )
    c, lp = bank.gids.shape
    if merge:
        fr = flat_emb.reshape(b, -1)
        og = gids.reshape(b, -1)
        q = queries
    else:
        pair_q = jnp.broadcast_to(queries[:, None, :], (b, p, queries.shape[-1]))
        fr = flat_emb.reshape(b * p, -1)
        og = gids.reshape(b * p, -1)
        q = pair_q.reshape(b * p, -1)
    with jax.named_scope("lider.candidates"):
        out_rows = jnp.where(og >= 0, fr, -1)
    kp = min(max(rescore_factor, 1) * k, fr.shape[-1])
    if sketch_factor is not None and bank.sketches is not None:
        # Sketch pre-filter, same contract as the device-tier funnel
        # (_verify_bank_rows): survivors replace the candidate list so the
        # code pass below streams sketch_factor*k' rows instead of all C.
        m = min(max(sketch_factor, 1) * kp, fr.shape[-1])
        with jax.named_scope("lider.sketch"):
            surv, _ = sketch_topk_op(
                bank.sketches.reshape(c * lp, -1), fr, q, k=m,
                out_ids=out_rows, block_c=block_c, use_pallas=use_fused,
            )
            fr = jnp.maximum(surv, 0)
        out_rows = surv
    with jax.named_scope("lider.code_pass"):
        rows, sc = verify_topk_op(
            bank.embs.reshape(c * lp, -1), fr, q, k=kp, out_ids=out_rows,
            scales=bank.emb_scales.reshape(-1), block_c=block_c,
            code_dtype=bank.code_dtype, use_pallas=use_fused,
        )
    if not merge:
        return TopK(ids=rows.reshape(b, p, kp), scores=sc.reshape(b, p, kp))
    return TopK(ids=rows, scores=sc)


def rescore_fetched_rows(
    fetched: jnp.ndarray,
    out_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    use_fused: bool | None = None,
    block_c: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage 3 of the tiered search: exact rescore over host-fetched rows.

    ``fetched``: (B, k', d) full-precision rows (the H2D payload — only
    ``B*k'*d`` floats); ``out_ids``: (B, k') the ids to dedup/report by
    (flat bank rows on the single-device path — the device-tier tie-break —
    or global ids on the distributed path). Runs the *same* fused kernel as
    the device-tier rescore with the fetched block as its table, so scores
    and tie-breaks are bit-identical to scoring against the resident table.
    """
    b, kp, d = fetched.shape
    table = fetched.reshape(b * kp, d)
    row_ids = jnp.arange(b * kp, dtype=jnp.int32).reshape(b, kp)
    return verify_topk_op(
        table, row_ids, queries, k=k, out_ids=out_ids,
        block_c=block_c, use_pallas=use_fused,
    )


@partial(
    jax.jit,
    static_argnames=(
        "k", "n_probe", "r0", "r0_centroid", "refine", "use_fused",
        "rescore_factor", "block_c", "sketch_factor",
    ),
)
def host_first_pass(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    n_probe: int = 20,
    r0: int = 4,
    r0_centroid: int = 4,
    refine: bool = False,
    use_fused: bool | None = None,
    prune_margin: float | None = None,
    rescore_factor: int = 4,
    block_c: int | None = None,
    sketch_factor: int | None = None,
) -> tuple[TopK, jnp.ndarray]:
    """Jit'd stage 1+2a of the tiered search: route + prune + compressed
    first pass. Returns ``(prov, pruned_mask (B, n_probe))`` where ``prov``
    is the provisional top-k' as ``TopK(ids=flat bank rows (B, k'),
    scores=compressed-domain scores)``; the host fetch and the rescore jit
    complete the query (:func:`search_lider`, or pipelined across batches by
    the serving engine). The provisional scores ride along so a degraded
    engine can answer compressed-only (:func:`compressed_only_topk`) when
    the host fetch is unavailable."""
    with jax.named_scope("lider.route"):
        routed = route_queries(
            params, queries, n_probe=n_probe, r0=r0_centroid,
            use_fused=use_fused, block_c=block_c,
        )
        cids = prune_probes(routed.ids, routed.scores, prune_margin)
        pruned = (routed.ids >= 0) & (cids < 0)
    prov = provisional_rows(
        params, queries, cids, k=k, r0=r0, refine=refine, use_fused=use_fused,
        rescore_factor=rescore_factor, block_c=block_c,
        sketch_factor=sketch_factor,
    )
    return prov, pruned


def host_fetch(params: LiderParams, prov_rows) -> np.ndarray:
    """Stage 2 of the tiered search: host-side exact-row gather.

    A NumPy ``take`` on the process-local host tier — no device involvement;
    the result is the only H2D payload the rescore needs (``B·k'·d``
    floats vs the first pass's ``B·C`` candidate traffic)."""
    return params.bank.store.fetch(np.asarray(prov_rows))


@partial(jax.jit, static_argnames=("k", "use_fused", "block_c"))
@jax.named_scope("lider.rescore")
def host_rescore(
    gids: jnp.ndarray,
    fetched: jnp.ndarray,
    prov_rows: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    use_fused: bool | None = None,
    block_c: int | None = None,
) -> TopK:
    """Jit'd stage 3: exact rescore of the fetched rows + row->gid mapping.

    Dedup/tie-break by flat bank row — identical to the device-tier
    quantized path — then the surviving rows map to global ids through the
    bank's ``gids`` table (a device-resident (c, Lp) int32 leaf)."""
    rows, scores = rescore_fetched_rows(
        fetched, prov_rows, queries, k=k, use_fused=use_fused, block_c=block_c
    )
    ids = jnp.where(rows >= 0, gids.reshape(-1)[jnp.maximum(rows, 0)], -1)
    return TopK(ids=ids, scores=scores)


@partial(jax.jit, static_argnames=("k",))
def compressed_only_topk(
    gids: jnp.ndarray, prov: TopK, *, k: int
) -> TopK:
    """Degraded-mode answer from stage 1 alone: no fetch, no exact rescore.

    The provisional top-k' from :func:`host_first_pass` is already sorted
    descending by compressed-domain score and deduped by flat bank row, so
    the compressed-only answer is its first ``k`` entries mapped through the
    bank's (c, Lp) gid table. Quality is the int8 first pass's — the
    degradation ladder's last rung (DESIGN.md §Failure model)."""
    rows = prov.ids[..., :k]
    scores = prov.scores[..., :k]
    ids = jnp.where(rows >= 0, gids.reshape(-1)[jnp.maximum(rows, 0)], -1)
    return TopK(ids=ids, scores=scores)


# ---------------------------------------------------------------------------
# Cluster-major multi-query search (DESIGN.md §Cluster-major schedule)
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("n_probe", "r0_centroid", "use_fused", "block_c"),
)
def _route_pruned(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    n_probe: int,
    r0_centroid: int = 4,
    use_fused: bool | None = None,
    prune_margin: float | None = None,
    block_c: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Jit'd routing stage of the cluster-major search: layer-1 route +
    margin prune. Returns ``(cids (B, P), pruned_mask (B, P))`` — the probe
    lists the host schedule pre-pass groups by cluster."""
    routed = route_queries(
        params, queries, n_probe=n_probe, r0=r0_centroid, use_fused=use_fused,
        block_c=block_c,
    )
    cids = prune_probes(routed.ids, routed.scores, prune_margin)
    pruned = (routed.ids >= 0) & (cids < 0)
    return cids, pruned


@partial(
    jax.jit,
    static_argnames=(
        "k", "r0", "refine", "use_fused", "rescore_factor", "block_c",
        "block_q", "sketch_factor",
    ),
)
def _cluster_major_first_pass(
    params: LiderParams,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    sched_cids: jnp.ndarray,
    sched_qids: jnp.ndarray,
    pair_step: jnp.ndarray,
    pair_slot: jnp.ndarray,
    *,
    k: int,
    r0: int = 4,
    refine: bool = False,
    use_fused: bool | None = None,
    rescore_factor: int = 4,
    block_c: int | None = None,
    block_q: int = 8,
    sketch_factor: int | None = None,
) -> TopK:
    """Jit'd compressed first pass on the cluster-major schedule.

    Candidate generation is the same ``_bank_candidates`` the per-query path
    runs; its (B, P, H, R) windows are scattered into the dense per-(step,
    query-slot) candidate masks the grouped kernel scores
    (``step_slot_ids``), each (query, probe) pair's per-cluster top-k' is
    gathered back through ``pair_step``/``pair_slot``, and a final
    ``dedup_topk`` merge yields the provisional top-k' — bit-identical ids
    AND scores to the per-query first pass (every global top-k' winner from
    a cluster is inside that pair's per-cluster top-k'; flat rows are unique
    across clusters; the selection order and smallest-id tie-break are
    shared — tests/test_fused_verify.py gates this).
    """
    bank = params.bank
    b, p = cids.shape
    c, lp = bank.gids.shape
    flat_emb, gids = _bank_candidates(
        bank, queries, cids, k=k, r0=r0, refine=refine
    )
    out_rows = jnp.where(gids >= 0, flat_emb, -1)  # (B, P, H, R)
    s_steps = sched_cids.shape[0]
    n_cand = p * flat_emb.shape[2] * flat_emb.shape[3]
    kp = min(max(rescore_factor, 1) * k, n_cand)

    if sketch_factor is not None and bank.sketches is not None:
        # Sketch pre-filter on the cluster-major path: the per-query Hamming
        # pass sees the SAME merged candidate list as the per-query funnel
        # (_verify_bank_rows), so it selects the same survivors — then the
        # per-(step, slot) candidate mask is rebuilt from survivors only.
        # Each survivor maps back to its (query, probe) pair through its
        # cluster id (flat row // Lp; probe lists hold distinct clusters),
        # and from there to the pair's (step, slot) — so the grouped kernel
        # streams the same survivor set the per-query filtered pass scores.
        m = min(max(sketch_factor, 1) * kp, n_cand)
        surv, _ = sketch_topk_op(
            bank.sketches.reshape(c * lp, -1),
            flat_emb.reshape(b, -1),
            queries,
            k=m,
            out_ids=out_rows.reshape(b, -1),
            block_c=block_c,
            use_pallas=use_fused,
        )
        surv_cid = surv // lp  # (B, m); -1 survivors masked below
        match = (cids[:, None, :] == surv_cid[:, :, None]) & (
            surv[:, :, None] >= 0
        )  # (B, m, P)
        has = jnp.any(match, axis=-1)
        pidx = jnp.argmax(match, axis=-1)  # (B, m)
        brow = jnp.arange(b, dtype=jnp.int32)[:, None]
        st_s = jnp.where(has, pair_step[brow, pidx], -1)
        sl_s = jnp.maximum(pair_slot[brow, pidx], 0)
        valid_s = has & (st_s >= 0)
        tgt = jnp.where(
            valid_s,
            (st_s * block_q + sl_s) * lp + surv % lp,
            s_steps * block_q * lp,
        )
        scat_src = surv
    else:
        # Dense per-(step, slot) candidate mask over the step cluster's Lp
        # rows: the union of each pair's H·R window candidates (duplicates
        # collapse). Invalid candidates / unscheduled (pruned) pairs scatter
        # out of range.
        local = flat_emb % lp
        st = pair_step[:, :, None, None]
        sl = pair_slot[:, :, None, None]
        valid = (out_rows >= 0) & (st >= 0)
        tgt = jnp.where(
            valid, (st * block_q + sl) * lp + local, s_steps * block_q * lp
        )
        scat_src = out_rows
    step_slot_ids = (
        jnp.full((s_steps * block_q * lp,), -1, jnp.int32)
        .at[tgt.reshape(-1)]
        .set(scat_src.reshape(-1), mode="drop")
        .reshape(s_steps, block_q, lp)
    )

    kp_pair = min(kp, lp)  # a pair has at most Lp distinct rows
    ids_g, sc_g = verify_topk_grouped_op(
        bank.embs,
        bank.emb_scales,
        queries,
        sched_cids,
        sched_qids,
        step_slot_ids,
        kp=kp_pair,
        block_q=block_q,
        block_c=block_c,
        code_dtype=bank.code_dtype,
        use_pallas=use_fused,
    )

    # Scatter-back: gather each query's pairs' per-cluster top-k' streams
    # and merge. Dead pairs (pruned probes / padding) contribute (-1, -inf).
    safe_st = jnp.maximum(pair_step, 0)
    safe_sl = jnp.maximum(pair_slot, 0)
    pids = ids_g[safe_st, safe_sl]  # (B, P, kp_pair)
    psc = sc_g[safe_st, safe_sl]
    dead = (pair_step < 0)[..., None]
    pids = jnp.where(dead, -1, pids)
    psc = jnp.where(dead, -jnp.inf, psc)
    # dedup_topk pads (-1, -inf) past the candidate count, so degenerate
    # tiny-bank shapes (kp > P·kp_pair) match the per-query pass's padding.
    prov_rows, prov_sc = dedup_topk(
        pids.reshape(b, -1), psc.reshape(b, -1), kp
    )
    return TopK(ids=prov_rows, scores=prov_sc)


@partial(jax.jit, static_argnames=("k", "use_fused", "block_c"))
def _rescore_provisional(
    gids: jnp.ndarray,
    rescore_embs: jnp.ndarray,
    prov_rows: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    use_fused: bool | None = None,
    block_c: int | None = None,
) -> TopK:
    """Device-tier exact rescore of a provisional top-k' (the same stage-2
    math as ``_verify_bank_rows``, split out so the cluster-major first pass
    can feed it between jits)."""
    rescore_table = rescore_embs.reshape(-1, rescore_embs.shape[-1])
    rows, scores = verify_topk_op(
        rescore_table,
        jnp.maximum(prov_rows, 0),
        queries,
        k=k,
        out_ids=prov_rows,
        block_c=block_c,
        use_pallas=use_fused,
    )
    ids = jnp.where(rows >= 0, gids.reshape(-1)[jnp.maximum(rows, 0)], -1)
    return TopK(ids=ids, scores=scores)


def host_first_pass_cluster_major(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    n_probe: int = 20,
    r0: int = 4,
    r0_centroid: int = 4,
    refine: bool = False,
    use_fused: bool | None = None,
    prune_margin: float | None = None,
    rescore_factor: int = 4,
    block_c: int | None = None,
    block_q: int = 8,
    sketch_factor: int | None = None,
    stats_out: dict | None = None,
) -> tuple[TopK, jnp.ndarray]:
    """Cluster-major spelling of :func:`host_first_pass` — same
    ``(prov, pruned)`` contract, so the serving engine's double-buffered
    fetch->rescore pipeline works unchanged with ``block_q`` set.

    Not one jit (the schedule pre-pass is host-side and data-dependent), but
    both device stages inside it are jits, so stage-1 dispatch still returns
    before the device finishes and the pipeline's overlap is preserved.

    ``stats_out`` (the online block_q autotuner's hook) does two things:
    the dict is filled with the drained schedule's measured sharing
    (``n_pairs``/``n_steps``) plus the batch's per-cluster pair counts, AND
    the schedule is padded to the fixed worst case ``_pad_pow2(B·n_probe)``
    instead of the data-dependent power of two — so every batch of the same
    (B, block_q) hits ONE compiled kernel shape and the autotuner can swap
    ``block_q`` between drains with zero query-path retraces (padding steps
    are dead; results unchanged).
    """
    from ..kernels.schedule import _pad_pow2, build_cluster_schedule

    cids, pruned = _route_pruned(
        params, queries, n_probe=n_probe, r0_centroid=r0_centroid,
        use_fused=use_fused, prune_margin=prune_margin, block_c=block_c,
    )
    pad_to = None
    if stats_out is not None:
        pad_to = _pad_pow2(queries.shape[0] * n_probe)
    cids_np = np.asarray(jax.device_get(cids))
    sched = build_cluster_schedule(cids_np, block_q=block_q, pad_to=pad_to)
    if stats_out is not None:
        stats_out["n_pairs"] = sched.n_pairs
        stats_out["n_steps"] = sched.n_steps
        stats_out["cluster_counts"] = np.unique(
            cids_np[cids_np >= 0], return_counts=True
        )[1]
    prov = _cluster_major_first_pass(
        params,
        queries,
        cids,
        jnp.asarray(sched.sched_cids),
        jnp.asarray(sched.sched_qids),
        jnp.asarray(sched.pair_step),
        jnp.asarray(sched.pair_slot),
        k=k,
        r0=r0,
        refine=refine,
        use_fused=use_fused,
        rescore_factor=rescore_factor,
        block_c=block_c,
        block_q=block_q,
        sketch_factor=sketch_factor,
    )
    return prov, pruned


def _search_lider_cluster_major(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    n_probe: int,
    r0: int,
    r0_centroid: int,
    refine: bool,
    use_fused: bool | None,
    prune_margin: float | None,
    with_stats: bool,
    rescore_factor: int,
    block_c: int | None,
    block_q: int,
    sketch_factor: int | None = None,
) -> TopK | tuple[TopK, jnp.ndarray]:
    """Staged cluster-major search: route (jit) -> host schedule pre-pass ->
    grouped first pass (jit) -> exact rescore (tier-appropriate).

    The schedule is data-dependent (it groups the batch's routed probe lists
    by cluster), so it cannot live inside one jit — the same staging pattern
    as the host-tier search. Step counts are padded to powers of two, so the
    grouped kernel's compile count stays O(log batch-pairs).
    """
    bank = params.bank
    if not bank.quantized:
        raise ValueError(
            "block_q (cluster-major schedule) requires a quantized "
            "(int8/int4) bank — the grouped kernel streams code tiles; "
            "use the per-query schedule (block_q=None) for float banks"
        )
    prov, pruned = host_first_pass_cluster_major(
        params, queries, k=k, n_probe=n_probe, r0=r0,
        r0_centroid=r0_centroid, refine=refine, use_fused=use_fused,
        prune_margin=prune_margin, rescore_factor=rescore_factor,
        block_c=block_c, block_q=block_q, sketch_factor=sketch_factor,
    )
    if bank.rescore_tier == "host":
        fetched = host_fetch(params, prov.ids)
        out = host_rescore(
            bank.gids, jnp.asarray(fetched), prov.ids, queries, k=k,
            use_fused=use_fused, block_c=block_c,
        )
    else:
        out = _rescore_provisional(
            bank.gids, bank.rescore_embs, prov.ids, queries, k=k,
            use_fused=use_fused, block_c=block_c,
        )
    return (out, pruned) if with_stats else out


def search_lider(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    n_probe: int = 20,
    r0: int = 4,
    r0_centroid: int = 4,
    refine: bool = False,
    use_fused: bool | None = None,
    prune_margin: float | None = None,
    with_stats: bool = False,
    rescore_factor: int = 4,
    block_c: int | None = None,
    block_q: int | None = None,
    sketch_factor: int | None = None,
) -> TopK | tuple[TopK, jnp.ndarray]:
    """End-to-end LIDER ANN search (paper Sec. 3.3.2), single device.

    ``prune_margin`` enables adaptive probe pruning (see :func:`prune_probes`;
    traced, so sweeping margins does not recompile; ``None`` is bit-identical
    to the fixed-probe search). ``with_stats=True`` additionally returns the
    (B, n_probe) bool mask of probes that were routed but pruned — serving
    aggregates it into the per-batch pruned-probe fraction.

    On an int8 bank (``LiderConfig.storage_dtype="int8"``) layer-2
    verification runs compressed-domain first, then exactly rescores the
    provisional top-``rescore_factor * k``; the knobs are static so each
    (rescore_factor, block_c) pair is one compile.

    Tier dispatch (DESIGN.md §Tiered embedding store): on a device-tier bank
    the whole search is one jit. On a *host-tier* bank it runs as three
    explicit stages — jit'd compressed first pass (:func:`host_first_pass`),
    host-side exact-row fetch (:func:`host_fetch`: ``np.take`` on the
    process-local tier, H2D of only ``B·k'·d`` floats), jit'd fused rescore
    (:func:`host_rescore`) — returning bit-identical (ids, scores) to the
    device tier on the same bank.

    ``block_q`` (quantized banks only) switches the first pass to the
    cluster-major multi-query schedule (§Cluster-major schedule): queries
    probing the same cluster share one DMA of its rows. Results are
    bit-identical to the per-query schedule; only the loop order — and the
    HBM traffic under skewed probe distributions — changes.

    ``sketch_factor`` (quantized banks only) turns on the binary-sketch
    pre-filter (§Binary sketch tier): a 1-bit Hamming pass keeps the top
    ``sketch_factor * k'`` rows, so the code pass streams only survivors. A
    covering factor is bit-identical to the unfiltered search; small
    factors trade recall for ~16x less first-pass traffic than int4.
    """
    if block_q is not None:
        return _search_lider_cluster_major(
            params, queries, k=k, n_probe=n_probe, r0=r0,
            r0_centroid=r0_centroid, refine=refine, use_fused=use_fused,
            prune_margin=prune_margin, with_stats=with_stats,
            rescore_factor=rescore_factor, block_c=block_c, block_q=block_q,
            sketch_factor=sketch_factor,
        )
    if params.bank.rescore_tier == "host":
        prov, pruned = host_first_pass(
            params, queries, k=k, n_probe=n_probe, r0=r0,
            r0_centroid=r0_centroid, refine=refine, use_fused=use_fused,
            prune_margin=prune_margin, rescore_factor=rescore_factor,
            block_c=block_c, sketch_factor=sketch_factor,
        )
        fetched = host_fetch(params, prov.ids)
        out = host_rescore(
            params.bank.gids, jnp.asarray(fetched), prov.ids, queries, k=k,
            use_fused=use_fused, block_c=block_c,
        )
        return (out, pruned) if with_stats else out
    return _search_lider_device(
        params, queries, k=k, n_probe=n_probe, r0=r0,
        r0_centroid=r0_centroid, refine=refine, use_fused=use_fused,
        prune_margin=prune_margin, with_stats=with_stats,
        rescore_factor=rescore_factor, block_c=block_c,
        sketch_factor=sketch_factor,
    )


# Every jit on the serving query path (all tiers + the degraded fallback).
# The cache-size sum below is the recompile detector behind the serving
# front end's zero-retrace gate.
_QUERY_PATH_JITS = (
    "_search_lider_device",
    "host_first_pass",
    "host_rescore",
    "compressed_only_topk",
    "_route_pruned",
    "_cluster_major_first_pass",
    "_rescore_provisional",
)


def query_path_cache_size() -> int:
    """Total compiled-trace count across every jit the serving query path
    can touch. After ``RetrievalEngine.warmup()`` this number must stay
    flat across any mix of batch sizes and ladder rungs — a delta means a
    query ate an XLA re-trace (tests + ``benchmarks.serve_scale`` gate on
    delta == 0)."""
    return sum(globals()[name]._cache_size() for name in _QUERY_PATH_JITS)
