"""Distributed LIDER: cluster-parallel sharded search + sharded k-means build.

Sharding layout (DESIGN.md §2):

- **cluster axis** ``c`` of every in-cluster tensor is sharded over
  ``cluster_axes`` (default the ``data`` mesh axis, plus ``pod`` multi-pod) —
  the paper's "parallelise across clusters" mapped onto devices.
- **query batch** is sharded over ``query_axes`` (default ``model``) — each
  (cluster-shard, query-shard) device pair owns a disjoint (clusters ×
  queries) tile, so the full bipartite search is covered exactly once.
- centroids retriever + LSH banks are replicated (they are KB-to-MB sized).

Search dataflow per device:
  1. route local queries on the replicated centroids retriever (redundant
     across cluster shards — cheaper than broadcasting routed ids),
  2. **capacity dispatch**: of the ``B_loc * n_probe`` (query, cluster) pairs,
     keep those owned by this shard, packed to a static capacity — the exact
     MoE expert-capacity trick; overflow drops are counted and psum'd,
  3. per-pair in-cluster search (gather + MXU scoring, static shapes),
  4. scatter pair results back per query, local top-k,
  5. one all-gather of (B_loc, k) id/score pairs over the cluster axes +
     final merge — the only collective in the hot path.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import faults
from .bank import replicated_field_names
from .clustering import update_centroids
from .core_model import TopK
from .lider import (
    LiderParams,
    OperatingPoint,
    _cluster_major_first_pass,
    _incluster,
    _rescore_resident,
    provisional_rows,
    rescore_fetched_rows,
    route_probes,
)
from .utils import dedup_topk


def _path_name(entry) -> str:
    return entry.name if hasattr(entry, "name") else str(entry)


def lider_param_specs(params: LiderParams, cluster_axes: Sequence[str]):
    """PartitionSpec pytree matching ``params``.

    The spec is derived from the :class:`~repro.core.bank.ClusterBank` field
    metadata rather than a hard-coded name list: every leaf under a bank
    field whose ``cluster_axis`` metadata is 0 is sharded
    ``P(cluster_axes, None, ...)``; bank fields marked replicated (the shared
    LSH bank, scalar bank metadata like ``next_gid``) and everything outside
    the bank (centroids + centroids retriever) get ``P()``. New bank fields
    therefore pick the right layout from their own declaration instead of
    silently cluster-sharding.
    """
    caxes = tuple(cluster_axes)
    replicated_bank_fields = set(replicated_field_names())

    def spec_for(path, leaf):
        if _path_name(path[0]) != "bank":
            return P()  # centroid retriever + centroids: replicated
        if len(path) < 2 or _path_name(path[1]) in replicated_bank_fields:
            return P()
        return P(caxes, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_lider_params(
    mesh: jax.sharding.Mesh, params: LiderParams, cluster_axes: Sequence[str]
) -> LiderParams:
    """device_put every leaf onto the mesh with the LIDER layout.

    The host tier (a host-tier bank's off-device rescore table — static
    pytree aux, not a leaf) stays process-local, sharded *by process*
    alongside the device shards: each process keeps the host rows for the
    clusters its devices own (in this single-process codebase that is the
    whole table, exactly like the checkpoint writer's single-process note).
    No device placement and no collectives are involved — the distributed
    search fetches from it between its two device phases.
    """
    specs = lider_param_specs(params, cluster_axes)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def _flat_axis_index(axes: Sequence[str]) -> jnp.ndarray:
    return jax.lax.axis_index(tuple(axes))


def make_sharded_search(
    mesh: jax.sharding.Mesh,
    params_like: LiderParams,
    *,
    k: int,
    capacity_factor: float = 2.0,
    cluster_axes: Sequence[str] = ("data",),
    query_axes: Sequence[str] = ("model",),
    **point,
):
    """Build the jitted multi-device search fn: (params, queries) -> (TopK, drops).

    ``params_like`` supplies the pytree structure/shapes (ShapeDtypeStructs are
    fine — used by the dry-run). Returned fn expects the query batch to be a
    multiple of the query-axis size. ``point`` takes the
    :class:`~repro.core.lider.OperatingPoint` fields, as ``search_lider``
    does.

    ``use_fused`` selects the verification path inside the shard_map body
    (None -> fused Pallas kernel on TPU, materialized reference elsewhere;
    DESIGN.md §Verification-kernel). Both the per-pair in-cluster search and
    the replicated centroid routing honor it.

    ``prune_margin`` applies the adaptive margin rule (DESIGN.md §Adaptive)
    to the routed probes *before* capacity dispatch: pruned pairs never enter
    a shard's pair budget, so pruning additionally shrinks dispatch pressure
    — fewer live pairs means fewer capacity-overflow drops at a given
    ``capacity_factor``.

    Quantized banks (int8 ``embs`` + ``emb_scales``/``rescore_embs``) work
    unchanged: the new bank fields carry ``cluster_axis`` metadata, so their
    PartitionSpecs derive automatically, and the per-pair in-cluster search
    runs the compressed-domain + exact-rescore pass shard-locally
    (``rescore_factor``/``block_c`` tune it) — provisional rows always live
    in the shard that found them, so no extra collective appears.

    **Host-tier banks** (DESIGN.md §Tiered embedding store) split the search
    in two device phases around a host fetch, with *no new collectives*: the
    shard_map phase runs route -> dispatch -> compressed first pass and
    merges per-shard provisional candidates through the *same* single
    all-gather (carrying k' = rescore_factor*k entries instead of k; rows
    offset to global flat ids so the row-dedup stays exact across shards);
    then the front-end fetches the k' exact rows + their gids from the
    process-local host tier and a small top-level jit rescores them
    (dedup/tie-break by gid, the float-path convention). The returned
    ``search`` is therefore a two-phase callable; its jit'd device phase is
    exposed as ``search.stage1`` (what the dry-run lowers).

    ``block_q`` (quantized banks only) runs the shard-local compressed
    first pass on the cluster-major schedule (``fused_verify_grouped``):
    pairs dispatched to a shard that probe the same cluster share one DMA
    of its code rows. The routing + capacity dispatch is replicated on the
    host in NumPy (bit-identical to the device rule — same stable argsort)
    so the per-shard schedules can be built in the host pre-pass; schedule
    arrays ride into the one shard_map as sharded inputs, and the merge
    collective is unchanged — NO new collectives appear. Results are
    bit-identical to the per-query sharded path (tests/test_distributed.py
    gates this in a subprocess). ``sketch_factor`` similarly threads the
    binary-sketch pre-filter into the shard-local first pass, both
    spellings.

    **Degraded mode** (DESIGN.md §Failure model): both tiers accept an
    optional ``shard_health`` bool mask of length ``n_cluster_shards``
    (default: all live). A dead shard's local contribution is masked to
    (-1, -inf) *before* the all-gather, so the merge returns partial
    results over the live shards instead of aborting — and the mask is a
    traced input, so flipping shard health never recompiles. The health of
    the last call is reported as ``search.shard_stats =
    {"shards_live", "shards_total"}``; an active fault plan
    (``faults.SHARD_SEARCH``, mode ``kill_shard``) marks shards dead
    through the same mask.
    """
    point = OperatingPoint(**point)
    caxes = tuple(cluster_axes)
    qaxes = tuple(query_axes)  # may be empty: replicated queries (batch-1)
    n_cluster_shards = math.prod(mesh.shape[a] for a in caxes)
    n_query_shards = math.prod(mesh.shape[a] for a in qaxes) if qaxes else 1
    c_total = params_like.bank.gids.shape[0]
    if c_total % n_cluster_shards:
        raise ValueError(
            f"n_clusters={c_total} must divide cluster shards={n_cluster_shards}"
        )

    param_specs = lider_param_specs(params_like, caxes)
    host_tier = getattr(params_like.bank, "rescore_tier", "device") == "host"

    def _dispatch(local_params, q_loc):
        """Route + prune + capacity dispatch (shared by both tiers)."""
        c_local = local_params.bank.gids.shape[0]
        my = _flat_axis_index(caxes)
        # Adaptive probe pruning before dispatch: a pruned pair is -1, i.e.
        # never "mine" on any shard, so it consumes no capacity slot.
        cids, _ = route_probes(
            local_params, q_loc, point=point, prune_margin=point.prune_margin
        )
        b_loc, p = cids.shape
        n_pairs = b_loc * p
        flat_cids = cids.reshape(-1)
        valid = flat_cids >= 0
        owner = jnp.where(valid, flat_cids // c_local, -1)
        mine = owner == my

        cap = min(
            n_pairs, int(math.ceil(n_pairs / n_cluster_shards * capacity_factor))
        )
        order = jnp.argsort(~mine, stable=True)  # my pairs first
        sel = order[:cap]
        sel_valid = mine[sel]
        sel_b = (sel // p).astype(jnp.int32)
        sel_cid_local = jnp.where(
            sel_valid, flat_cids[sel] - my * c_local, -1
        ).astype(jnp.int32)
        dropped = jnp.sum(mine) - jnp.sum(sel_valid)
        return my, b_loc, p, sel, sel_valid, sel_b, sel_cid_local, dropped

    def _resolve_health(shard_health) -> np.ndarray:
        """Host-side health mask: caller's mask + any injected shard kill."""
        if shard_health is None:
            health = np.ones(n_cluster_shards, np.bool_)
        else:
            health = np.array(shard_health, np.bool_).reshape(-1).copy()
            if health.shape[0] != n_cluster_shards:
                raise ValueError(
                    f"shard_health has {health.shape[0]} entries, expected "
                    f"{n_cluster_shards} cluster shards"
                )
        spec = faults.fire(faults.SHARD_SEARCH)
        if spec is not None and spec.mode == "kill_shard":
            payload = spec.payload or {}
            dead = payload.get("shards")
            if dead is None:
                dead = [payload.get("shard", 0)]
            for s in dead:
                health[int(s) % n_cluster_shards] = False
        return health

    def body(
        local_params: LiderParams, q_loc: jnp.ndarray, shard_health: jnp.ndarray
    ):
        my, b_loc, p, sel, sel_valid, sel_b, sel_cid_local, dropped = _dispatch(
            local_params, q_loc
        )
        n_pairs = b_loc * p

        pair_topk = _incluster(
            local_params, q_loc[sel_b], sel_cid_local[:, None], k=k,
            point=point,
        )  # (cap, k)

        # Scatter per-pair results back to their (query, probe-slot) rows.
        scatter_idx = jnp.where(sel_valid, sel, n_pairs)
        ids_buf = (
            jnp.full((n_pairs + 1, k), -1, dtype=jnp.int32)
            .at[scatter_idx]
            .set(pair_topk.ids)
        )
        sc_buf = (
            jnp.full((n_pairs + 1, k), -jnp.inf, dtype=jnp.float32)
            .at[scatter_idx]
            .set(pair_topk.scores)
        )
        l_ids, l_sc = dedup_topk(
            ids_buf[:-1].reshape(b_loc, -1), sc_buf[:-1].reshape(b_loc, -1), k
        )

        # Degraded mode: a dead shard contributes nothing to the merge (and
        # its capacity drops don't count — that work was never owed).
        alive = shard_health[my]
        l_ids = jnp.where(alive, l_ids, -1)
        l_sc = jnp.where(alive, l_sc, -jnp.inf)
        dropped = jnp.where(alive, dropped, 0)

        # The one hot-path collective: merge (B_loc, k) across cluster shards.
        g_ids = jax.lax.all_gather(l_ids, caxes)  # (S, B_loc, k)
        g_sc = jax.lax.all_gather(l_sc, caxes)
        ids, sc = dedup_topk(
            jnp.moveaxis(g_ids, 0, 1).reshape(b_loc, -1),
            jnp.moveaxis(g_sc, 0, 1).reshape(b_loc, -1),
            k,
        )
        dropped = jax.lax.psum(dropped, caxes + qaxes if qaxes else caxes)
        return ids, sc, dropped

    def body_provisional(
        local_params: LiderParams, q_loc: jnp.ndarray, shard_health: jnp.ndarray
    ):
        """Host-tier device phase: compressed pass + provisional merge.

        Identical dataflow to ``body`` but stops at the provisional
        top-k' *flat bank rows* (offset to global row ids, so the row-level
        dedup of the merges stays exact across shards). The all-gather is
        the same single collective, just k' wide.
        """
        my, b_loc, p, sel, sel_valid, sel_b, sel_cid_local, dropped = _dispatch(
            local_params, q_loc
        )
        n_pairs = b_loc * p
        c_local, lp = local_params.bank.gids.shape

        pair_prov = provisional_rows(
            local_params, q_loc[sel_b], sel_cid_local[:, None], k=k,
            point=point,
        )  # (cap, k') local flat rows + compressed scores
        kp = pair_prov.ids.shape[-1]
        g_rows_pair = jnp.where(
            pair_prov.ids >= 0, pair_prov.ids + my * c_local * lp, -1
        )

        scatter_idx = jnp.where(sel_valid, sel, n_pairs)
        rows_buf = (
            jnp.full((n_pairs + 1, kp), -1, dtype=jnp.int32)
            .at[scatter_idx]
            .set(g_rows_pair)
        )
        sc_buf = (
            jnp.full((n_pairs + 1, kp), -jnp.inf, dtype=jnp.float32)
            .at[scatter_idx]
            .set(pair_prov.scores)
        )
        l_rows, l_sc = dedup_topk(
            rows_buf[:-1].reshape(b_loc, -1), sc_buf[:-1].reshape(b_loc, -1), kp
        )

        alive = shard_health[my]
        l_rows = jnp.where(alive, l_rows, -1)
        l_sc = jnp.where(alive, l_sc, -jnp.inf)
        dropped = jnp.where(alive, dropped, 0)

        g_rows = jax.lax.all_gather(l_rows, caxes)  # (S, B_loc, k')
        g_sc = jax.lax.all_gather(l_sc, caxes)
        rows, sc = dedup_topk(
            jnp.moveaxis(g_rows, 0, 1).reshape(b_loc, -1),
            jnp.moveaxis(g_sc, 0, 1).reshape(b_loc, -1),
            kp,
        )
        dropped = jax.lax.psum(dropped, caxes + qaxes if qaxes else caxes)
        return rows, sc, dropped

    qspec = P(qaxes, None) if qaxes else P(None, None)
    # shard_health is a small replicated (S,) bool vector — a *traced*
    # input, so flipping shard liveness reuses the compiled program.
    sharded = jax.shard_map(
        body_provisional if host_tier else body,
        mesh=mesh,
        in_specs=(param_specs, qspec, P()),
        out_specs=(qspec, qspec, P()),
        check_vma=False,
    )
    run = jax.jit(sharded)

    def _note_health(fn, health: np.ndarray) -> None:
        fn.shard_stats = {
            "shards_live": int(health.sum()),
            "shards_total": n_cluster_shards,
        }

    if point.block_q is not None:
        if not params_like.bank.quantized:
            raise ValueError(
                "block_q (cluster-major schedule) on the sharded path "
                "requires a quantized (int8/int4) bank — use the per-query "
                "spelling (block_q=None) for float banks"
            )
        return _make_grouped_search(
            mesh=mesh,
            param_specs=param_specs,
            qspec=qspec,
            host_tier=host_tier,
            caxes=caxes,
            qaxes=qaxes,
            n_cluster_shards=n_cluster_shards,
            n_query_shards=n_query_shards,
            c_total=c_total,
            k=k,
            point=point,
            capacity_factor=capacity_factor,
            resolve_health=_resolve_health,
            note_health=_note_health,
        )

    if not host_tier:

        def search(params: LiderParams, queries: jnp.ndarray, shard_health=None):
            health = _resolve_health(shard_health)
            _note_health(search, health)
            ids, sc, dropped = run(params, queries, jnp.asarray(health))
            return TopK(ids=ids, scores=sc), dropped

        return search

    def stage1(params: LiderParams, queries: jnp.ndarray, shard_health=None):
        # Plain wrapper (not the raw jit) so the dry-run can lower it with
        # the legacy two-argument signature — the default all-live mask
        # folds to a constant.
        health = _resolve_health(shard_health)
        _note_health(stage1, health)
        return run(params, queries, jnp.asarray(health))

    def search(params: LiderParams, queries: jnp.ndarray, shard_health=None):
        rows, _, dropped = stage1(params, queries, shard_health)
        search.shard_stats = dict(stage1.shard_stats)
        rows_np = np.asarray(rows)
        store = params.bank.store
        fetched = store.fetch(rows_np)  # host np.take on the local shard
        out_gids = store.take_gids(rows_np)  # host row->gid map
        out = _rescore_fetched(
            jnp.asarray(fetched), jnp.asarray(out_gids), queries, k=k,
            point=point.only("use_fused", "block_c"),
        )
        return out, dropped

    search.stage1 = stage1
    return search


# Top-level exact rescore of host-fetched rows (distributed front end). It
# dedups/reports by global id — gids are globally unique, so no cross-shard
# coordination is needed; ties break by smallest gid (the float-path
# convention).
_rescore_fetched = jax.jit(rescore_fetched_rows, static_argnames=("k", "point"))


def _make_grouped_search(
    *,
    mesh,
    param_specs,
    qspec,
    host_tier,
    caxes,
    qaxes,
    n_cluster_shards,
    n_query_shards,
    c_total,
    k,
    point,
    capacity_factor,
    resolve_health,
    note_health,
):
    """Cluster-major spelling of the sharded search (``block_q`` set).

    Same dataflow as the per-query bodies with one structural change: the
    route + capacity dispatch moves OUT of the shard_map into a host
    pre-pass, because the cluster-major schedule is data-dependent host
    bookkeeping (exactly like the single-device staged search). Routing
    runs once in a small top-level jit over the replicated centroids; the
    per-shard capacity selection is replicated in NumPy with the identical
    rule the device body uses (stable argsort, my-pairs-first, same cap
    formula), so the dispatched pair list the schedules describe is
    bit-identical to what the device would have selected. Every
    (cluster shard, query shard) cell's schedule is padded to the common
    worst case ``_pad_pow2(cap)`` so all shards run one kernel shape, and
    the schedule arrays enter the single shard_map as sharded inputs —
    the merge all-gather stays the only hot-path collective.
    """
    from ..kernels.schedule import _pad_pow2, build_cluster_schedule

    c_local = c_total // n_cluster_shards
    block_q, n_probe = point.block_q, point.n_probe

    def _route(local_params, queries):
        cids, _ = route_probes(
            local_params, queries, point=point, prune_margin=point.prune_margin
        )
        return cids

    # Every device routes the whole batch against its replica of the
    # centroid retriever (the bank shards ride along unread). Inside
    # shard_map the routing's Pallas kernels run per device; a plain jit
    # over mesh-placed inputs would ask the TPU compiler to partition them,
    # which it cannot.
    route_jit = jax.jit(
        jax.shard_map(
            _route,
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            check_vma=False,
        )
    )

    _CELL_KEYS = (
        "sel", "sel_valid", "sel_b", "sel_cid_local", "dropped",
        "sched_cids", "sched_qids", "pair_step", "pair_slot",
    )

    def _host_cells(cids_np: np.ndarray) -> dict:
        """Replicated dispatch + per-cell schedules for one routed batch."""
        b, p = cids_np.shape
        if b % n_query_shards:
            raise ValueError(
                f"batch {b} must divide query shards={n_query_shards}"
            )
        b_loc = b // n_query_shards
        n_pairs = b_loc * p
        cap = min(
            n_pairs,
            int(math.ceil(n_pairs / n_cluster_shards * capacity_factor)),
        )
        pad_steps = _pad_pow2(cap)  # n_steps <= cap pairs: always fits
        cs_n, qs_n = n_cluster_shards, n_query_shards
        out = {
            "sel": np.zeros((cs_n, qs_n, cap), np.int32),
            "sel_valid": np.zeros((cs_n, qs_n, cap), bool),
            "sel_b": np.zeros((cs_n, qs_n, cap), np.int32),
            "sel_cid_local": np.zeros((cs_n, qs_n, cap), np.int32),
            "dropped": np.zeros((cs_n, qs_n), np.int32),
            "sched_cids": np.zeros((cs_n, qs_n, pad_steps), np.int32),
            "sched_qids": np.full(
                (cs_n, qs_n, pad_steps, block_q), -1, np.int32
            ),
            "pair_step": np.full((cs_n, qs_n, cap, 1), -1, np.int32),
            "pair_slot": np.full((cs_n, qs_n, cap, 1), -1, np.int32),
        }
        for qs in range(qs_n):
            flat = cids_np[qs * b_loc:(qs + 1) * b_loc].reshape(-1)
            valid = flat >= 0
            owner = np.where(valid, flat // c_local, -1)
            for cs in range(cs_n):
                mine = owner == cs
                # np stable argsort on ~mine == the device dispatch's
                # jnp.argsort(~mine, stable=True): my pairs first, original
                # (query asc, probe asc) order preserved — the replication
                # that keeps schedule and dispatched pair list in lockstep.
                order = np.argsort(~mine, kind="stable")
                sel = order[:cap].astype(np.int32)
                sv = mine[sel]
                scl = np.where(sv, flat[sel] - cs * c_local, -1).astype(
                    np.int32
                )
                out["sel"][cs, qs] = sel
                out["sel_valid"][cs, qs] = sv
                out["sel_b"][cs, qs] = (sel // p).astype(np.int32)
                out["sel_cid_local"][cs, qs] = scl
                out["dropped"][cs, qs] = int(mine.sum()) - int(sv.sum())
                sched = build_cluster_schedule(
                    scl[:, None], block_q=block_q, pad_to=pad_steps
                )
                out["sched_cids"][cs, qs] = sched.sched_cids
                out["sched_qids"][cs, qs] = sched.sched_qids
                out["pair_step"][cs, qs] = sched.pair_step
                out["pair_slot"][cs, qs] = sched.pair_slot
        return out

    def gbody(local_params, q_loc, shard_health, *cells):
        cell = {key: arr[0, 0] for key, arr in zip(_CELL_KEYS, cells)}
        my = _flat_axis_index(caxes)
        b_loc = q_loc.shape[0]
        n_pairs = b_loc * n_probe
        c_loc, lp = local_params.bank.gids.shape
        q_pairs = q_loc[cell["sel_b"]]
        prov = _cluster_major_first_pass(
            local_params,
            q_pairs,
            cell["sel_cid_local"][:, None],
            cell["sched_cids"],
            cell["sched_qids"],
            cell["pair_step"],
            cell["pair_slot"],
            k=k,
            point=point,
        )  # (cap, k') local flat rows + compressed scores
        scatter_idx = jnp.where(cell["sel_valid"], cell["sel"], n_pairs)
        alive = shard_health[my]

        def _merge(l_ids, l_sc, kk):
            g_ids = jax.lax.all_gather(l_ids, caxes)
            g_sc = jax.lax.all_gather(l_sc, caxes)
            return dedup_topk(
                jnp.moveaxis(g_ids, 0, 1).reshape(b_loc, -1),
                jnp.moveaxis(g_sc, 0, 1).reshape(b_loc, -1),
                kk,
            )

        if host_tier:
            # Stop at provisional global rows, exactly as body_provisional.
            kp = prov.ids.shape[-1]
            g_rows_pair = jnp.where(
                prov.ids >= 0, prov.ids + my * c_loc * lp, -1
            )
            rows_buf = (
                jnp.full((n_pairs + 1, kp), -1, dtype=jnp.int32)
                .at[scatter_idx]
                .set(g_rows_pair)
            )
            sc_buf = (
                jnp.full((n_pairs + 1, kp), -jnp.inf, dtype=jnp.float32)
                .at[scatter_idx]
                .set(prov.scores)
            )
            l_rows, l_sc = dedup_topk(
                rows_buf[:-1].reshape(b_loc, -1),
                sc_buf[:-1].reshape(b_loc, -1),
                kp,
            )
            l_rows = jnp.where(alive, l_rows, -1)
            l_sc = jnp.where(alive, l_sc, -jnp.inf)
            out_ids, out_sc = _merge(l_rows, l_sc, kp)
        else:
            # Device tier: exact rescore of each pair's provisional rows,
            # then the per-query scatter + merge of body.
            pair = _rescore_resident(
                local_params.bank.gids, local_params.bank.rescore_embs,
                prov.ids, q_pairs, k=k, point=point,
            )
            ids_buf = (
                jnp.full((n_pairs + 1, k), -1, dtype=jnp.int32)
                .at[scatter_idx]
                .set(pair.ids)
            )
            sc_buf = (
                jnp.full((n_pairs + 1, k), -jnp.inf, dtype=jnp.float32)
                .at[scatter_idx]
                .set(pair.scores)
            )
            l_ids, l_sc = dedup_topk(
                ids_buf[:-1].reshape(b_loc, -1),
                sc_buf[:-1].reshape(b_loc, -1),
                k,
            )
            l_ids = jnp.where(alive, l_ids, -1)
            l_sc = jnp.where(alive, l_sc, -jnp.inf)
            out_ids, out_sc = _merge(l_ids, l_sc, k)

        dropped = jnp.where(alive, cell["dropped"], 0)
        dropped = jax.lax.psum(dropped, caxes + qaxes if qaxes else caxes)
        return out_ids, out_sc, dropped

    cqs = qaxes if qaxes else None
    spec2 = P(caxes, cqs)
    spec3 = P(caxes, cqs, None)
    spec4 = P(caxes, cqs, None, None)
    cell_specs = (
        spec3, spec3, spec3, spec3, spec2, spec3, spec4, spec4, spec4
    )
    run = jax.jit(
        jax.shard_map(
            gbody,
            mesh=mesh,
            in_specs=(param_specs, qspec, P(), *cell_specs),
            out_specs=(qspec, qspec, P()),
            check_vma=False,
        )
    )

    def search(params: LiderParams, queries: jnp.ndarray, shard_health=None):
        health = resolve_health(shard_health)
        note_health(search, health)
        cids_np = np.asarray(jax.device_get(route_jit(params, queries)))
        cells = _host_cells(cids_np)
        cell_args = tuple(jnp.asarray(cells[key]) for key in _CELL_KEYS)
        rows_or_ids, sc, dropped = run(
            params, queries, jnp.asarray(health), *cell_args
        )
        if not host_tier:
            return TopK(ids=rows_or_ids, scores=sc), dropped
        rows_np = np.asarray(rows_or_ids)
        store = params.bank.store
        fetched = store.fetch(rows_np)
        out_gids = store.take_gids(rows_np)
        out = _rescore_fetched(
            jnp.asarray(fetched), jnp.asarray(out_gids), queries, k=k,
            point=point.only("use_fused", "block_c"),
        )
        return out, dropped

    return search


# ---------------------------------------------------------------------------
# Distributed build: sharded Lloyd iterations (Stage 1 at scale)
# ---------------------------------------------------------------------------


def make_sharded_kmeans_step(
    mesh: jax.sharding.Mesh,
    *,
    n_clusters: int,
    data_axes: Sequence[str] = ("data",),
    chunk: int = 4096,
):
    """One Lloyd iteration with points sharded over ``data_axes``; the
    sufficient statistics are psum'd so every shard gets identical centroids
    (gradient-compression hook: stats are cast to fp32 regardless of input)."""
    daxes = tuple(data_axes)

    def body(x_loc, centroids):
        from .clustering import kmeans_step

        sums, counts, _ = kmeans_step(
            x_loc, centroids, n_clusters=n_clusters, chunk=chunk
        )
        sums = jax.lax.psum(sums.astype(jnp.float32), daxes)
        counts = jax.lax.psum(counts.astype(jnp.float32), daxes)
        return update_centroids(centroids, sums, counts)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(daxes, None), P()),
            out_specs=P(),
            check_vma=False,
        )
    )
