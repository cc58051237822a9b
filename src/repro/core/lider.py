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
the same stages in a shard_map with capacity-based query->cluster-shard
dispatch for the production mesh. Every search-time knob is a field of one
:class:`OperatingPoint` (DESIGN.md §Operating point).

Search stages carry ``jax.named_scope`` names, so every device op (XLA
fusion or Pallas call) of the served jits names its stage in its op-name
metadata and a profiler trace can sum device time per stage:
``lider.route`` (centroid routing + probe pruning), ``lider.candidates``
(hash, rescale, RMI, the contiguous ``sorted_pos`` window reads and the
liveness mask taken from them; a float bank also maps candidates to gids),
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
class OperatingPoint:
    """Every search-time knob, and the only place their defaults live
    (DESIGN.md §Operating point).

    ``n_probe`` (paper c0) clusters are routed with a centroid window of
    ``r0_centroid * n_probe``; each probe reads RMI windows of ``r0 * k``
    slots, with ``refine`` the beyond-paper searchsorted correction;
    ``use_fused`` picks the verification kernel (None -> the fused Pallas
    pass on TPU, the materialized reference elsewhere; True/False force
    either); ``prune_margin`` masks probes scored that far below a query's
    best (None: no pruning); on quantized banks ``rescore_factor * k``
    provisional rows are exactly rescored and ``sketch_factor * k'``
    survivors of the 1-bit pre-filter reach the code pass (None: no
    pre-filter); ``block_c`` is the kernels' candidate block (None: their
    default) and ``block_q`` the cluster-major schedule's query tile (None:
    the per-query schedule).

    A jit takes the point as one static argument, so each distinct point is
    one compile — except ``prune_margin``, which every jit takes traced
    beside a point whose own margin is None: sweeping margins does not
    recompile."""

    n_probe: int = 20
    r0: int = 4
    r0_centroid: int = 4
    refine: bool = False
    use_fused: bool | None = None
    prune_margin: float | None = None
    rescore_factor: int = 4
    block_c: int | None = None
    block_q: int | None = None
    sketch_factor: int | None = None

    @property
    def adaptive(self) -> bool:
        return self.prune_margin is not None

    def only(self, *names: str) -> "OperatingPoint":
        """This point with every field but ``names`` at its default: the
        static argument of a jit that reads only those fields, so points
        that differ elsewhere share its compiled program."""
        return OperatingPoint(**{n: getattr(self, n) for n in names})

    def label(self) -> str:
        tag = f"probe{self.n_probe}/r{self.r0}"
        if self.refine:
            tag += "/refine"
        if self.adaptive:
            tag += f"/margin{self.prune_margin:g}"
        if self.rescore_factor != OperatingPoint.rescore_factor:
            tag += f"/rescore{self.rescore_factor}"
        if self.block_c is not None:
            tag += f"/blk{self.block_c}"
        if self.block_q is not None:
            tag += f"/bq{self.block_q}"
        if self.sketch_factor is not None:
            tag += f"/sk{self.sketch_factor}"
        return tag


@dataclasses.dataclass(frozen=True)
class LiderConfig:
    """Static build configuration (paper Sec. 7.2.1 defaults).

    ``n_probe``, ``r0``, ``r0_centroid`` and ``rescore_factor`` are the
    configuration's stated search settings (defaulting to the operating
    point's): the build reads none of them, but a deployment file states
    them beside the index it describes, and its launcher turns them into
    the served :class:`OperatingPoint`."""

    n_clusters: int = 1000  # c
    n_probe: int = OperatingPoint.n_probe  # c0
    n_arrays: int = 10  # H (in-cluster)
    n_arrays_centroid: int = 10  # H (centroids retriever)
    key_len: int | None = None  # M (in-cluster); None -> ceil(log2 Lp)
    key_len_centroid: int | None = None  # M (centroids); None -> ceil(log2 c)
    n_leaves: int = 5  # RMI width W_i
    n_leaves_centroid: int = 10  # RMI width W_c
    r0: int = OperatingPoint.r0  # expansion range factor, R = r0 * k
    r0_centroid: int = OperatingPoint.r0_centroid
    kmeans_iters: int = 20
    capacity: int | None = None  # Lp cap; None -> max cluster size (no drops)
    pad_multiple: int = 8
    # Embedding storage dtype (DESIGN.md §Quantized bank): "float32",
    # "bfloat16", "int8", or "int4". int8 cuts the compulsory candidate-row
    # gather 4x vs f32; int4 packs two codes per byte (8x, 0.5 B/elem).
    # Both quantized dtypes add an exact rescore pass over the provisional
    # top-(rescore_factor * k) from the full-precision side table.
    storage_dtype: str = "float32"
    # k' = rescore_factor * k (quantized storage only)
    rescore_factor: int = OperatingPoint.rescore_factor
    # Where the full-precision rescore side table lives (quantized storage
    # only; DESIGN.md §Tiered embedding store). "device": a pytree leaf next to
    # the codes (PR-4 layout — costs ~25% more HBM than f32). "host": a
    # process-local pinned host array outside the pytree; search becomes
    # the staged fetch->rescore pipeline and the device-resident index
    # shrinks to codes + scales (~0.25x of f32).
    rescore_tier: str = "device"
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
    params: LiderParams, queries: jnp.ndarray, **point
) -> TopK:
    """Layer-1: centroids retriever -> (B, n_probe) cluster ids + scores.

    Takes :class:`OperatingPoint` fields as keywords and reads ``n_probe``,
    ``r0_centroid``, ``use_fused``, ``block_c`` and ``prune_margin``. With
    a margin set, low-confidence probes come back masked to (-1, -inf) —
    the slot count stays ``n_probe`` so downstream shapes are static. The
    centroid table itself always stays full precision (it is KB–MB sized;
    quantizing it would risk routing quality for no traffic win).
    """
    point = OperatingPoint(**point)
    routed = search_core_model(
        params.centroid_cm, params.centroids, queries, k=point.n_probe,
        r0=point.r0_centroid, use_fused=point.use_fused, block_c=point.block_c,
    )
    if point.prune_margin is None:
        return routed
    cids = prune_probes(routed.ids, routed.scores, point.prune_margin)
    return TopK(
        ids=cids, scores=jnp.where(cids >= 0, routed.scores, -jnp.inf)
    )


def route_probes(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    point: OperatingPoint,
    prune_margin: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The route stage of every search: layer-1 routing at ``point``, the
    margin rule at ``prune_margin`` (passed beside the point so it can be
    traced), and the mask of probes routed but pruned. Returns ``(cids,
    pruned)``, both (B, n_probe)."""
    with jax.named_scope("lider.route"):
        routed = route_queries(
            params, queries, n_probe=point.n_probe,
            r0_centroid=point.r0_centroid, use_fused=point.use_fused,
            block_c=point.block_c,
        )
        cids = prune_probes(routed.ids, routed.scores, prune_margin)
        pruned = (routed.ids >= 0) & (cids < 0)
    return cids, pruned


def set_rescore_tier(params: LiderParams, tier: str) -> LiderParams:
    """Move the index's rescore table between storage tiers (§Tiered store).

    Search results are bit-identical across the move; only where the
    full-precision rows live — and therefore which search pipeline runs —
    changes (``bank.set_rescore_tier``).
    """
    return dataclasses.replace(
        params, bank=bank_lib.set_rescore_tier(params.bank, tier)
    )


def _read_windows(
    table: jnp.ndarray, cids: jnp.ndarray, start: jnp.ndarray, width: int
) -> jnp.ndarray:
    """``table[cids[b, p], j, start[b, p, j] : start[b, p, j] + width]`` for
    every (query, probe, array): (B, P, H, width).

    ``table`` is a bank's (c, H, Lp) sorted-order table. On a TPU it is
    stored H-major in (8, 128) tiles, so its view as a list of those tiles
    is free: no copy of the table is made (unless c or Lp must be padded to
    a whole tile, which no served configuration needs). Each window reads
    the ``nt`` aligned tiles that cover it (B·P·H·nt tile reads of 4 KiB,
    whatever Lp is), keeps its cluster's sublane, and is shifted left by its
    start within the first tile, two bits a step. ``cids`` must be in range
    and ``start`` within ``[0, Lp - width]``."""
    c, h, lp = table.shape
    nt = (width + 126) // 128 + 1  # 128-lane tiles a window can touch
    cp = -(-c // 8) * 8
    n = max(-(-lp // 128), nt)
    if (cp, n * 128) != (c, lp):
        table = jnp.pad(table, ((0, cp - c), (0, 0), (0, n * 128 - lp)))
    tiles = jnp.transpose(table, (1, 0, 2)).reshape(h, cp // 8, 8, n, 128)
    tiles = jnp.transpose(tiles, (0, 1, 3, 2, 4)).reshape(-1, 8, 128)
    s = start.reshape(-1)
    cid = jnp.broadcast_to(cids[:, :, None], start.shape).reshape(-1)
    arr = jnp.broadcast_to(jnp.arange(h, dtype=jnp.int32), start.shape).reshape(-1)
    first = jnp.minimum(s // 128, n - nt)
    tile = (arr * (cp // 8) + cid // 8) * n + first
    x = jnp.take(
        tiles, tile[:, None] + jnp.arange(nt, dtype=jnp.int32), axis=0,
        mode="clip",
    )  # (B·P·H, nt, 8, 128)
    own = (cid % 8)[:, None, None, None] == jnp.arange(8)[None, None, :, None]
    x = jnp.sum(jnp.where(own, x, 0), axis=2, dtype=table.dtype)
    x = x.reshape(-1, nt * 128)
    s = (s - first * 128)[:, None]
    hi = (nt * 128 - width).bit_length()
    while hi > 0:
        # Shift by bits [lo, hi) of the start, two at a time. The window
        # then starts within the first 2**lo lanes and still ends inside x,
        # so the zeros padding a piece are never part of a window.
        lo = max(hi - 2, 0)
        w = width + (1 << lo) - 1
        digit = (s >> lo) & ((1 << (hi - lo)) - 1)
        out = x[:, :w]
        for d in range(1, 1 << (hi - lo)):
            piece = x[:, d << lo:(d << lo) + w]
            piece = jnp.pad(piece, ((0, 0), (0, w - piece.shape[1])))
            out = jnp.where(digit == d, piece, out)
        x, hi = out, lo
    return x.reshape(start.shape + (width,))


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
    -> window read). Returns ``(flat_emb, valid)``, both (B, P, H, R): flat
    ``(cluster, slot)`` rows into the ``(c*Lp, ...)`` tables, and whether
    the candidate is live. Shared by the single-pass float search, the
    quantized two-stage search, and the tiered first pass (§Tiered
    embedding store).

    Validity comes from ``sorted_pos`` alone: it holds -1 at padding, a
    refit sorts only rows whose gid is live, and a delete sets -1 at every
    entry pointing at a row it kills, so ``sorted_pos >= 0`` at a live
    probe means a live row (``tests/test_update.py`` guards that). No gid is
    read here; rows map to global ids only where a path needs them: the
    float funnel (it dedups by gid) and the final top-k rows."""
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

    if refine:
        # Beyond-paper last-mile: read a 2R key window around the RMI
        # prediction (keys are 4 B vs d*4 B embeddings) and binary-search the
        # exact position inside it, then expand only R around the truth.
        w1 = min(2 * r, lp)
        start1 = jnp.clip(jnp.round(pos).astype(jnp.int32) - w1 // 2, 0, lp - w1)
        keys_win = _read_windows(bank.sorted_keys, safe_cid, start1, w1)
        qk = jnp.broadcast_to(qkeys[:, None, :], (b, p, h)).reshape(-1)
        rows = keys_win.reshape(-1, w1)
        off = jax.vmap(lambda row, q: jnp.searchsorted(row, q))(rows, qk)
        pos = (start1 + off.reshape(b, p, h).astype(jnp.int32)).astype(jnp.float32)

    start = jnp.clip(jnp.round(pos).astype(jnp.int32) - r // 2, 0, lp - r)
    local_pos = _read_windows(bank.sorted_pos, safe_cid, start, r)  # (B,P,H,R)
    valid = (local_pos >= 0) & cvalid[:, :, None, None]
    flat_emb = safe_cid[:, :, None, None] * lp + jnp.maximum(local_pos, 0)
    return flat_emb, valid


def _candidate_rows(
    bank: ClusterBank,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    merge: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The probed clusters' candidates as verification rows: ``(rows,
    live, q)``, one row per query over all its probes (``merge``) or one
    per (query, probe) pair — the shape the sharded dispatch scatters back
    — with ``q`` the query of each row. ``rows`` are flat ``(cluster,
    slot)`` rows into the ``(c*Lp, ...)`` tables and ``live`` their
    liveness (:func:`_bank_candidates`)."""
    flat_emb, valid = _bank_candidates(
        bank, queries, cids, k=k, r0=point.r0, refine=point.refine
    )
    b, p = cids.shape
    if merge:
        return flat_emb.reshape(b, -1), valid.reshape(b, -1), queries
    pair_q = jnp.broadcast_to(queries[:, None, :], (b, p, queries.shape[-1]))
    return (
        flat_emb.reshape(b * p, -1),
        valid.reshape(b * p, -1),
        pair_q.reshape(b * p, -1),
    )


def _per_pair(out: TopK, cids: jnp.ndarray, merge: bool) -> TopK:
    """Rows of :func:`_candidate_rows` back to (B, P, ·) when not merged."""
    if merge:
        return out
    b, p = cids.shape
    return TopK(ids=out.ids.reshape(b, p, -1), scores=out.scores.reshape(b, p, -1))


def _sketch_filter(
    bank: ClusterBank,
    rows: jnp.ndarray,
    out_rows: jnp.ndarray,
    q: jnp.ndarray,
    *,
    kp: int,
    point: OperatingPoint,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Binary-sketch pre-filter (DESIGN.md §Binary sketch tier): a 1-bit
    Hamming pass over the packed sign sketches keeps the top
    ``sketch_factor * k'`` rows per query, deduped by ``out_rows`` (the
    code pass's tie-break). Returns ``(survivors, rows to read)``: -1 /
    row 0 where fewer survive. A factor covering every distinct candidate
    is bit-identical to no filter: survivors then hold all valid rows, the
    per-row code scores are unchanged, and dedup collapses the duplicates
    the sketch pass already collapsed."""
    c, lp = bank.gids.shape
    m = min(max(point.sketch_factor, 1) * kp, rows.shape[-1])
    with jax.named_scope("lider.sketch"):
        surv, _ = sketch_topk_op(
            bank.sketches.reshape(c * lp, -1),
            rows,
            q,
            k=m,
            out_ids=out_rows,
            block_c=point.block_c,
            use_pallas=point.use_fused,
        )
        return surv, jnp.maximum(surv, 0)


def _compressed_pass(
    bank: ClusterBank,
    rows: jnp.ndarray,
    live: jnp.ndarray,
    q: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
) -> TopK:
    """The compressed first pass of a quantized bank: candidate mask ->
    sketch pre-filter (with ``sketch_factor``) -> int8/int4 code pass.

    Candidates are reported as ``where(live, row, -1)`` and deduped by
    *flat row* — exact within the bank, since a passage occupies exactly
    one (cluster, slot) — and the pass keeps the provisional top-``k' =
    rescore_factor * k``: ``ids`` flat rows (-1 padding), ``scores`` the
    compressed-domain scores. No gid is read: only the final rows map to
    global ids (:func:`rescore_rows`). Score ties break by smallest flat
    row (the float path breaks them by smallest gid)."""
    c, lp = bank.gids.shape
    with jax.named_scope("lider.candidates"):
        out_rows = jnp.where(live, rows, -1)
    kp = min(max(point.rescore_factor, 1) * k, rows.shape[-1])
    if point.sketch_factor is not None and bank.sketches is not None:
        out_rows, rows = _sketch_filter(
            bank, rows, out_rows, q, kp=kp, point=point
        )
    with jax.named_scope("lider.code_pass"):
        ids, scores = verify_topk_op(
            bank.embs.reshape(c * lp, -1),
            rows,
            q,
            k=kp,
            out_ids=out_rows,
            scales=bank.emb_scales.reshape(-1),
            block_c=point.block_c,
            code_dtype=bank.code_dtype,
            use_pallas=point.use_fused,
        )
    return TopK(ids=ids, scores=scores)


def row_gids(gids: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Flat bank rows -> global ids through the bank's (c, Lp) ``gids``
    table (-1 stays -1)."""
    return jnp.where(rows >= 0, gids.reshape(-1)[jnp.maximum(rows, 0)], -1)


def rescore_rows(
    table: jnp.ndarray,
    row_ids: jnp.ndarray,
    out_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    gids: jnp.ndarray | None = None,
) -> TopK:
    """The exact rescore: score rows ``row_ids`` (B, k') of the
    full-precision ``table`` against the queries and keep the top ``k``,
    deduplicated and reported by ``out_ids`` (-1: dead). With ``gids`` the
    surviving ``out_ids`` are flat bank rows and map to global ids
    (:func:`row_gids`); without, they are global ids already.

    The resident table and a host-fetched block run the same kernel, so
    their scores and tie-breaks are bit-identical: see
    :func:`_rescore_resident` and :func:`rescore_fetched_rows`."""
    ids, scores = verify_topk_op(
        table,
        row_ids,
        queries,
        k=k,
        out_ids=out_ids,
        block_c=point.block_c,
        use_pallas=point.use_fused,
    )
    if gids is not None:
        ids = row_gids(gids, ids)
    return TopK(ids=ids, scores=scores)


def _rescore_resident(
    gids: jnp.ndarray,
    rescore_embs: jnp.ndarray,
    prov_rows: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
) -> TopK:
    """Device tier: exact rescore of provisional flat rows (B, k') from the
    resident rescore table (c, Lp, d), a gather k'/C the size of the first
    pass."""
    with jax.named_scope("lider.rescore"):
        return rescore_rows(
            rescore_embs.reshape(-1, rescore_embs.shape[-1]),
            jnp.maximum(prov_rows, 0),
            prov_rows,
            queries,
            k=k,
            point=point,
            gids=gids,
        )


def _verify(
    bank: ClusterBank,
    rows: jnp.ndarray,
    live: jnp.ndarray,
    q: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
) -> TopK:
    """Candidate rows -> gid-space top-k on a device-tier bank.

    A quantized bank runs the compressed first pass and the exact rescore
    of its top-k' (DESIGN.md §Quantized bank). A float bank is one
    ``verify_topk_op`` call deduped by global id: the only funnel that maps
    every candidate to its gid."""
    if bank.quantized:
        prov = _compressed_pass(bank, rows, live, q, k=k, point=point)
        return _rescore_resident(
            bank.gids, bank.rescore_embs, prov.ids, q, k=k, point=point
        )
    c, lp = bank.gids.shape
    with jax.named_scope("lider.candidates"):
        out_gids = jnp.where(live, bank.gids.reshape(-1)[rows], -1)
    with jax.named_scope("lider.rescore"):
        ids, scores = verify_topk_op(
            bank.embs.reshape(c * lp, -1),
            rows,
            q,
            k=k,
            out_ids=out_gids,
            block_c=point.block_c,
            use_pallas=point.use_fused,
        )
    return TopK(ids=ids, scores=scores)


def _incluster(
    params: LiderParams,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    merge: bool = True,
) -> TopK:
    """Layer 2 at ``point`` on a device-tier bank (:func:`incluster_search`
    without its keyword surface)."""
    if params.bank.rescore_tier == "host":
        raise ValueError(
            "incluster_search cannot complete on a host-tier bank — the "
            "rescore table is off-device; use search_lider (staged "
            "fetch->rescore pipeline) or provisional_rows + "
            "rescore_fetched_rows directly (DESIGN.md §Tiered embedding "
            "store)"
        )
    rows, live, q = _candidate_rows(
        params.bank, queries, cids, k=k, point=point, merge=merge
    )
    return _per_pair(
        _verify(params.bank, rows, live, q, k=k, point=point), cids, merge
    )


def incluster_search(
    params: LiderParams,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    merge: bool = True,
    cid_scores: jnp.ndarray | None = None,
    **point,
) -> TopK:
    """Layer-2: search the probed clusters for each query.

    ``queries``: (B, d); ``cids``: (B, P) cluster ids (-1 = unused probe
    slot); ``point``: :class:`OperatingPoint` fields. With ``merge=False``
    returns the per-pair top-k (B, P, k). With ``cid_scores`` (the layer-1
    routing scores) and ``prune_margin`` both set, probes outside the
    margin are masked to -1 here instead of by the caller — either spelling
    yields the same candidate mask.

    Verification goes through ``verify_topk_op`` (``use_fused``): the fused
    kernel streams the gathered rows through VMEM and emits only the (B, k)
    result, instead of materializing the (B, P, H, R, d) candidate tensor
    in HBM before the einsum. On a quantized bank the pass runs in the
    compressed domain and is followed by an exact rescore of the
    provisional top-``rescore_factor * k`` rows.
    """
    point = OperatingPoint(**point)
    if point.prune_margin is not None:
        if cid_scores is None:
            raise ValueError("prune_margin needs cid_scores (layer-1 scores)")
        cids = prune_probes(cids, cid_scores, point.prune_margin)
    return _incluster(params, queries, cids, k=k, point=point, merge=merge)


@partial(jax.jit, static_argnames=("k", "point", "with_stats"))
def _search_lider_device(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    prune_margin: float | None = None,
    with_stats: bool = False,
) -> TopK | tuple[TopK, jnp.ndarray]:
    """Single-jit search for device-tier banks (float, or quantized with
    the rescore table resident next to the codes)."""
    cids, pruned = route_probes(
        params, queries, point=point, prune_margin=prune_margin
    )
    out = _incluster(params, queries, cids, k=k, point=point)
    if with_stats:
        return out, pruned
    return out


# ---------------------------------------------------------------------------
# Staged search: compressed first pass, then the exact rescore (DESIGN.md
# §Tiered embedding store, §Cluster-major schedule)
# ---------------------------------------------------------------------------


def provisional_rows(
    params: LiderParams,
    queries: jnp.ndarray,
    cids: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    merge: bool = True,
) -> TopK:
    """Stage 1 of the tiered search: the compressed first pass only
    (:func:`_compressed_pass`), stopping at the provisional top-``k'``:
    ``ids`` are *flat bank rows* (-1 padding), ``scores`` the
    compressed-domain scores. The caller fetches those rows from the host
    tier (``bank.store.fetch``) and finishes with
    :func:`rescore_fetched_rows` / :func:`host_rescore`, which map only the
    final rows to gids. ``merge=False`` keeps the per-(query, probe) pair
    shape for the distributed capacity-dispatch path.
    """
    bank = params.bank
    if not bank.quantized:
        raise ValueError("provisional_rows needs a quantized (int8/int4) bank")
    rows, live, q = _candidate_rows(
        bank, queries, cids, k=k, point=point, merge=merge
    )
    return _per_pair(
        _compressed_pass(bank, rows, live, q, k=k, point=point), cids, merge
    )


def rescore_fetched_rows(
    fetched: jnp.ndarray,
    out_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    gids: jnp.ndarray | None = None,
) -> TopK:
    """Stage 3 of the tiered search: exact rescore over host-fetched rows.

    ``fetched``: (B, k', d) full-precision rows (the H2D payload — only
    ``B*k'*d`` floats); ``out_ids``: (B, k') the ids to dedup/report by:
    flat bank rows on the single-device path (the device-tier tie-break,
    mapped through ``gids``) or global ids on the distributed path
    (``gids=None``). The fetched block is the table of
    :func:`rescore_rows`.
    """
    b, kp, d = fetched.shape
    table = fetched.reshape(b * kp, d)
    row_ids = jnp.arange(b * kp, dtype=jnp.int32).reshape(b, kp)
    return rescore_rows(
        table, row_ids, out_ids, queries, k=k, point=point, gids=gids
    )


@partial(jax.jit, static_argnames=("k", "point"))
def host_first_pass(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
    prune_margin: float | None = None,
) -> tuple[TopK, jnp.ndarray]:
    """Jit'd stage 1+2a of the tiered search: route + prune + compressed
    first pass. Returns ``(prov, pruned_mask (B, n_probe))`` where ``prov``
    is the provisional top-k' as ``TopK(ids=flat bank rows (B, k'),
    scores=compressed-domain scores)``; the host fetch and the rescore jit
    complete the query (:func:`search_lider`, or pipelined across batches by
    the serving engine). The provisional scores ride along so a degraded
    engine can answer compressed-only (:func:`compressed_only_topk`) when
    the host fetch is unavailable."""
    cids, pruned = route_probes(
        params, queries, point=point, prune_margin=prune_margin
    )
    return provisional_rows(params, queries, cids, k=k, point=point), pruned


def host_fetch(params: LiderParams, prov_rows) -> np.ndarray:
    """Stage 2 of the tiered search: host-side exact-row gather.

    A NumPy ``take`` on the process-local host tier — no device involvement;
    the result is the only H2D payload the rescore needs (``B·k'·d``
    floats vs the first pass's ``B·C`` candidate traffic)."""
    return params.bank.store.fetch(np.asarray(prov_rows))


@partial(jax.jit, static_argnames=("k", "point"))
@jax.named_scope("lider.rescore")
def host_rescore(
    gids: jnp.ndarray,
    fetched: jnp.ndarray,
    prov_rows: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
) -> TopK:
    """Jit'd stage 3: exact rescore of the fetched rows + row->gid mapping.

    Dedup/tie-break by flat bank row — identical to the device-tier
    quantized path — then the surviving rows map to global ids through the
    bank's ``gids`` table (a device-resident (c, Lp) int32 leaf)."""
    return rescore_fetched_rows(
        fetched, prov_rows, queries, k=k, point=point, gids=gids
    )


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
    return TopK(ids=row_gids(gids, rows), scores=scores)


# The cluster-major schedule's device stages (DESIGN.md §Cluster-major
# schedule): routing and the device-tier rescore as jits of their own, the
# host schedule pre-pass running between them and the grouped first pass.
_route_pruned = jax.jit(route_probes, static_argnames=("point",))
_rescore_provisional = jax.jit(_rescore_resident, static_argnames=("k", "point"))


@partial(jax.jit, static_argnames=("k", "point"))
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
    point: OperatingPoint,
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
    block_q = point.block_q
    b, p = cids.shape
    c, lp = bank.gids.shape
    flat_emb, valid = _bank_candidates(
        bank, queries, cids, k=k, r0=point.r0, refine=point.refine
    )
    out_rows = jnp.where(valid, flat_emb, -1)  # (B, P, H, R)
    s_steps = sched_cids.shape[0]
    n_cand = p * flat_emb.shape[2] * flat_emb.shape[3]
    kp = min(max(point.rescore_factor, 1) * k, n_cand)

    if point.sketch_factor is not None and bank.sketches is not None:
        # The per-query Hamming pass sees the SAME merged candidate list as
        # the per-query first pass, so it selects the same survivors — then
        # the per-(step, slot) candidate mask is rebuilt from survivors
        # only. Each survivor maps back to its (query, probe) pair through
        # its cluster id (flat row // Lp; probe lists hold distinct
        # clusters), and from there to the pair's (step, slot) — so the
        # grouped kernel streams the same survivor set the per-query
        # filtered pass scores.
        surv, _ = _sketch_filter(
            bank, flat_emb.reshape(b, -1), out_rows.reshape(b, -1), queries,
            kp=kp, point=point,
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
        block_c=point.block_c,
        code_dtype=bank.code_dtype,
        use_pallas=point.use_fused,
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


def first_pass(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    point: OperatingPoint,
) -> tuple[TopK, jnp.ndarray]:
    """Stage 1 of the staged search at ``point``: route + prune +
    compressed first pass, returning ``(prov, pruned)`` as
    :func:`host_first_pass` does; the fetch (host tier) and the exact
    rescore finish the query. The margin reaches every jit traced.

    ``block_q`` set (quantized banks only) runs the cluster-major schedule:
    routing jit -> host schedule pre-pass (data-dependent, so it cannot
    live inside one jit) -> grouped first-pass jit. Both device stages are
    jits, so the call returns before the device finishes and the serving
    pipeline's overlap is kept. Every schedule is padded to the batch's
    worst case ``_pad_pow2(B·n_probe)`` steps (padding steps are dead), so
    each batch size compiles one grouped program and no batch re-traces.
    """
    from ..kernels.schedule import _pad_pow2, build_cluster_schedule

    margin = point.prune_margin
    if point.block_q is None:
        return host_first_pass(
            params, queries, k=k,
            point=dataclasses.replace(point, prune_margin=None),
            prune_margin=margin,
        )
    if not params.bank.quantized:
        raise ValueError(
            "block_q (cluster-major schedule) requires a quantized "
            "(int8/int4) bank — the grouped kernel streams code tiles; "
            "use the per-query schedule (block_q=None) for float banks"
        )
    cids, pruned = _route_pruned(
        params, queries, prune_margin=margin,
        point=point.only("n_probe", "r0_centroid", "use_fused", "block_c"),
    )
    sched = build_cluster_schedule(
        np.asarray(jax.device_get(cids)), block_q=point.block_q,
        pad_to=_pad_pow2(queries.shape[0] * point.n_probe),
    )
    prov = _cluster_major_first_pass(
        params,
        queries,
        cids,
        *(jnp.asarray(a) for a in (
            sched.sched_cids, sched.sched_qids, sched.pair_step,
            sched.pair_slot,
        )),
        k=k,
        point=point.only(
            "r0", "refine", "use_fused", "rescore_factor", "block_c",
            "block_q", "sketch_factor",
        ),
    )
    return prov, pruned


def search_lider(
    params: LiderParams,
    queries: jnp.ndarray,
    *,
    k: int,
    with_stats: bool = False,
    **point,
) -> TopK | tuple[TopK, jnp.ndarray]:
    """End-to-end LIDER ANN search (paper Sec. 3.3.2), single device, at
    the operating point given by ``point``'s :class:`OperatingPoint` fields
    (an unknown keyword raises ``TypeError``).

    ``prune_margin`` enables adaptive probe pruning (see :func:`prune_probes`;
    traced, so sweeping margins does not recompile; ``None`` is bit-identical
    to the fixed-probe search). ``with_stats=True`` additionally returns the
    (B, n_probe) bool mask of probes that were routed but pruned — serving
    aggregates it into the per-batch pruned-probe fraction.

    On a quantized bank (``LiderConfig.storage_dtype`` "int8"/"int4")
    layer-2 verification runs compressed-domain first, then exactly
    rescores the provisional top-``rescore_factor * k``.

    Tier dispatch (DESIGN.md §Tiered embedding store): on a device-tier bank
    the whole search is one jit. On a *host-tier* bank it runs as three
    explicit stages — jit'd compressed first pass (:func:`first_pass`),
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
    point = OperatingPoint(**point)
    bank = params.bank
    if point.block_q is None and bank.rescore_tier == "device":
        return _search_lider_device(
            params, queries, k=k,
            point=dataclasses.replace(point, prune_margin=None),
            prune_margin=point.prune_margin, with_stats=with_stats,
        )
    prov, pruned = first_pass(params, queries, k=k, point=point)
    rescore = point.only("use_fused", "block_c")
    if bank.rescore_tier == "host":
        fetched = host_fetch(params, prov.ids)
        out = host_rescore(
            bank.gids, jnp.asarray(fetched), prov.ids, queries, k=k,
            point=rescore,
        )
    else:
        out = _rescore_provisional(
            bank.gids, bank.rescore_embs, prov.ids, queries, k=k,
            point=rescore,
        )
    return (out, pruned) if with_stats else out


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
