"""Cluster-major schedule construction for multi-query batched verification.

The per-query fused-verify grid DMAs every probed cluster's rows once per
(query, probe) pair: a batch of B queries each probing P clusters issues
B·P cluster-tile streams even when the batch concentrates on a handful of
hot clusters — under production (Zipf-skewed) traffic most of that is the
same bytes moved again. The cluster-major schedule fixes the loop order:
group the batch's (query, probe) pairs BY CLUSTER into steps of up to
``block_q`` query slots, stream each cluster's rows once per step, and score
them against the whole query tile on the MXU (DESIGN.md §Cluster-major
schedule). The kernel side is ``fused_verify.fused_verify_grouped``; this
module is the host pre-pass that turns routed probe lists into its schedule
arrays.

The schedule is pure bookkeeping over small host integers (the ``(B, P)``
routed cluster ids — already host-visible in the staged search), so it runs
in NumPy between the routing jit and the verification jit. Step count is
padded to a power of two to bound recompiles of the downstream kernel, the
same policy as ``core.update``'s dirty-cluster batches.

Determinism contract: pairs are ordered by (cluster asc, query asc, probe
asc) and packed greedily into ``block_q``-slot steps, so the schedule — and
therefore the kernel's compiled shape and its bit-exact outputs — depends
only on the routed probe lists, never on query order within a step (scores
are per-slot) or on hash iteration order.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _pad_pow2(m: int, lo: int = 1) -> int:
    """Next power of two >= max(m, lo) — bounds kernel recompiles over
    variable schedule sizes (same policy as core.update's batch padding)."""
    return max(lo, 1 << (max(m, 1) - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class ClusterSchedule:
    """The cluster→query-tile schedule for one routed batch.

    ``sched_cids``: (S,) int32 — the cluster each step streams (padding
    steps carry cluster 0 with an all-empty tile; the kernel skips them).
    ``sched_qids``: (S, block_q) int32 — query index per tile slot (-1 pad).
    ``pair_step`` / ``pair_slot``: (B, P) int32 — where each (query, probe)
    pair landed, -1 for pairs excluded from the schedule (pruned probes);
    the per-query merge gathers its pairs' per-cluster top-k' through these.
    ``n_steps``: real (unpadded) step count.
    ``n_pairs``: scheduled (unpruned) pair count.
    ``cluster_loads``: number of distinct (step, cluster) streams — the
    cluster-tile DMA count the schedule actually issues; the per-query
    schedule issues ``n_pairs`` of them, so ``n_pairs / n_steps`` is the
    DMA-sharing ratio the Zipf benchmark gates on.
    """

    sched_cids: np.ndarray
    sched_qids: np.ndarray
    pair_step: np.ndarray
    pair_slot: np.ndarray
    block_q: int
    n_steps: int
    n_pairs: int

    @property
    def n_padded_steps(self) -> int:
        return int(self.sched_cids.shape[0])

    @property
    def sharing_ratio(self) -> float:
        """Cluster-tile streams saved vs the per-query schedule:
        ``n_pairs / n_steps`` (>= 1; 1.0 means no sharing happened)."""
        return self.n_pairs / max(self.n_steps, 1)


def build_cluster_schedule(
    cids: np.ndarray,
    *,
    block_q: int,
    pruned: np.ndarray | None = None,
    pad_to: int | None = None,
) -> ClusterSchedule:
    """Group a batch's routed (query, probe) pairs by cluster into steps.

    ``cids``: (B, P) int32 routed cluster ids (< 0 = invalid probe).
    ``pruned``: optional (B, P) bool — True excludes the pair (the adaptive
    ``prune_margin`` rule); excluded pairs get ``pair_step = -1`` and their
    candidates never enter the kernel, mirroring the per-query path's
    masked-to--1 candidates.

    Pairs probing the same cluster fill a step's ``block_q`` query slots in
    (query asc, probe asc) order; a cluster with more pairs than ``block_q``
    spans consecutive steps. Steps are ordered by cluster id ascending.

    ``pad_to`` overrides the power-of-two step padding with a FIXED padded
    step count — the searches pass a worst case (``_pad_pow2(B * P)`` on
    one device, n_steps <= n_pairs <= B·P always) so every batch of the
    same (B, block_q) compiles exactly one downstream kernel shape
    regardless of the observed probe distribution (zero query-path
    retraces). Padding steps are dead (empty tiles, ``blk_live = 0``), so
    results are unchanged. Values below the real step count fall back to
    the power-of-two policy.
    """
    cids = np.asarray(cids, np.int32)
    b, p = cids.shape
    keep = cids >= 0
    if pruned is not None:
        keep &= ~np.asarray(pruned, bool)
    qid, pid = np.nonzero(keep)  # row-major: (query asc, probe asc)
    pcid = cids[qid, pid]
    # Stable sort by cluster keeps the (query asc, probe asc) order within
    # each cluster group — the determinism contract.
    order = np.argsort(pcid, kind="stable")
    qid, pid, pcid = qid[order], pid[order], pcid[order]
    n_pairs = int(pcid.shape[0])

    # Slot index within the cluster group, then split groups into
    # block_q-wide steps.
    if n_pairs:
        starts = np.r_[True, pcid[1:] != pcid[:-1]]
        group_start = np.maximum.accumulate(np.where(starts, np.arange(n_pairs), 0))
        within = np.arange(n_pairs) - group_start
        step_of_group = within // block_q
        slot = (within % block_q).astype(np.int32)
        # Global step index: new step whenever the (cluster, step_of_group)
        # pair changes.
        step_key = starts | (np.r_[False, step_of_group[1:] != step_of_group[:-1]])
        step = (np.cumsum(step_key) - 1).astype(np.int32)
        n_steps = int(step[-1]) + 1
    else:
        slot = step = np.zeros((0,), np.int32)
        n_steps = 0

    s_padded = _pad_pow2(n_steps)
    if pad_to is not None and pad_to >= n_steps:
        s_padded = max(int(pad_to), 1)
    sched_cids = np.zeros((s_padded,), np.int32)
    sched_qids = np.full((s_padded, block_q), -1, np.int32)
    if n_pairs:
        sched_cids[step] = pcid
        sched_qids[step, slot] = qid
    pair_step = np.full((b, p), -1, np.int32)
    pair_slot = np.full((b, p), -1, np.int32)
    if n_pairs:
        pair_step[qid, pid] = step
        pair_slot[qid, pid] = slot
    return ClusterSchedule(
        sched_cids=sched_cids,
        sched_qids=sched_qids,
        pair_step=pair_step,
        pair_slot=pair_slot,
        block_q=int(block_q),
        n_steps=n_steps,
        n_pairs=n_pairs,
    )
