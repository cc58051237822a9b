"""ClusterBank: the stacked per-cluster index state + staged build primitives.

LIDER's layer-2 state (one in-cluster retriever per cluster, stacked into
dense padded tensors — DESIGN.md §1/§2) used to live as seven loose fields on
``LiderParams``. This module makes it a first-class pytree so the build, the
incremental-update path (``core.update``), checkpointing, and the distributed
partition-spec derivation all share one structure:

    sorted_keys  (c, H, Lp) uint32   per-cluster sorted hashkey arrays
    sorted_pos   (c, H, Lp) int32    sorted position -> cluster-local row (-1 = pad/dead)
    embs         (c, Lp, d)          embeddings grouped by cluster (zero at pads)
    gids         (c, Lp)    int32    cluster-local row -> global id (-1 = free/tombstone)
    sizes        (c,)       int32    live rows per cluster
    tombstones   (c,)       int32    dead rows awaiting compaction
    next_gid     ()         int32    next global passage id to assign

Each dataclass field carries ``cluster_axis`` metadata: 0 for tensors whose
leading axis is the cluster axis (sharded over the cluster mesh axes by
``core.distributed``), ``None`` for replicated state (the shared LSH bank and
scalar bank metadata). ``core.distributed.lider_param_specs`` derives its
PartitionSpecs from this metadata instead of a hard-coded name list.

Build is staged (paper Sec. 3.3.2 Stage 3, decomposed):

    assign (k-means / nearest-centroid)  ->  pack (capacity slots)
        ->  hash + sort + fit, one cluster at a time: :func:`refit_cluster`

Full build is just ``vmap(refit_cluster)`` over all clusters
(:func:`build_bank`); incremental maintenance (``core.update``) re-runs the
*same* ``refit_cluster`` on only the dirty clusters — there is no separate
"online" fitting code path to drift from the offline one.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import clustering, lsh as lsh_lib, rescale as rescale_lib, rmi as rmi_lib
from .. import faults
from ..kernels.quant import (
    dequantize_codes,
    dequantize_rows,
    quantize_rows,
    quantize_rows_int4,
    sketch_rows,
)
from .types import pytree_dataclass

# dataclasses.field metadata key: leading cluster axis (int) or None for
# replicated leaves. core.distributed reads this to build PartitionSpecs.
CLUSTER_AXIS = "cluster_axis"

# Supported embedding storage dtypes (LiderConfig.storage_dtype). The
# quantized dtypes ("int8", and "int4" — packed two-nibbles-per-byte in an
# int8 carrier of width d//2) additionally populate ``emb_scales`` +
# ``rescore_embs`` (DESIGN.md §Quantized bank).
STORAGE_DTYPES = ("float32", "bfloat16", "int8", "int4")

# The quantized subset: storage dtypes that carry per-row scales + an exact
# rescore table and run the two-stage compressed-first search.
QUANTIZED_DTYPES = ("int8", "int4")

# Where the full-precision rescore side table lives
# (LiderConfig.rescore_tier; DESIGN.md §Tiered embedding store).
RESCORE_TIERS = ("device", "host")


class EmbStore:
    """Tiered store for the full-precision rescore table.

    ``tier="device"``: a shape-only marker — the table is the
    ``ClusterBank.rescore_embs`` pytree leaf and travels through jit/sharding
    like any other device array (the PR-4 layout).

    ``tier="host"``: the table lives HERE, as a process-local contiguous
    ("pinned" in the DMA sense — page-aligned C-contiguous NumPy, the layout
    the runtime can transfer without staging) float32 array of shape
    ``(c, Lp, d)``, *outside* the jit pytree. The jit'd index then carries
    only codes + scales; search fetches the exact rows of the provisional
    top-k' with :meth:`fetch` (a host ``np.take``) and ships ``B·k'·d``
    floats H2D instead of keeping all ``c·Lp·d`` resident (DESIGN.md §Tiered
    embedding store). A synced copy of ``gids`` rides along so the
    distributed front-end can map flat rows to passage ids without touching
    the cluster-sharded device tables.

    The store is **mutable shared state**: the index lifecycle
    (``core.update``) writes both tiers in lockstep — content writes
    (``write_rows`` / ``compact_clusters``) mutate the table in place (like
    any in-place update store, retained pre-update snapshots observe them),
    while capacity growth is copy-on-grow (``grown``) because it changes the
    flat-row arithmetic old snapshots still use. ``version`` bumps on every
    host write so serving can track host-tier generations separately from
    device recompiles. Because
    it rides the ClusterBank pytree as *static* aux data, ``__eq__`` /
    ``__hash__`` key on (tier, shape, dtype) only — content mutation never
    invalidates a compiled search, and two same-shape indexes share one
    compilation (the host data never enters the traced program).

    A store constructed with ``rescore=None`` is *abstract* (shape/dtype
    accounting only — what the dry-run memory model uses); ``fetch`` and the
    write paths require a concrete one.
    """

    def __init__(
        self,
        tier: str,
        *,
        rescore: np.ndarray | None = None,
        shape: tuple[int, ...] | None = None,
        dtype=np.float32,
        gids: np.ndarray | None = None,
    ):
        if tier not in RESCORE_TIERS:
            raise ValueError(f"tier must be one of {RESCORE_TIERS}, got {tier!r}")
        if rescore is not None:
            rescore = np.ascontiguousarray(rescore, dtype=np.float32)
            if not rescore.flags.writeable:  # device_get hands back views
                rescore = rescore.copy()
            shape = rescore.shape
            dtype = rescore.dtype
        if shape is None:
            raise ValueError("EmbStore needs rescore rows or an explicit shape")
        self.tier = tier
        self.rescore = rescore
        self.shape = tuple(int(s) for s in shape)
        self.dtype = np.dtype(dtype)
        self.gids = None if gids is None else np.ascontiguousarray(gids, np.int32)
        self.version = 0  # bumped on every host-tier content write
        self._txn = None  # undo journal while a transaction is open

    # -- pytree aux-data contract: stable across content mutation ----------
    def _key(self):
        return (self.tier, self.shape, str(self.dtype))

    def __eq__(self, other):
        return isinstance(other, EmbStore) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        kind = "abstract" if self.rescore is None else f"v{self.version}"
        return f"EmbStore({self.tier}, {self.shape}, {self.dtype}, {kind})"

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * self.dtype.itemsize

    def _concrete(self) -> np.ndarray:
        if self.rescore is None:
            raise ValueError("abstract EmbStore (shape only) has no rows to access")
        return self.rescore

    # -- host-tier access ---------------------------------------------------
    def fetch(self, rows: np.ndarray) -> np.ndarray:
        """Gather flat bank rows ``(..., )`` -> ``(..., d)`` float32.

        ``rows < 0`` (provisional padding) gather row 0; callers pass the
        row array as ``out_ids`` downstream, so padded gathers are never
        surfaced (same convention as the device-tier rescore gather).
        """
        faults.fire(faults.HOST_FETCH)
        rows = np.asarray(rows)
        table = self._concrete().reshape(-1, self.shape[-1])
        return table.take(np.maximum(rows, 0).reshape(-1), axis=0).reshape(
            rows.shape + (self.shape[-1],)
        )

    def take_gids(self, rows: np.ndarray) -> np.ndarray:
        """Map flat bank rows -> global passage ids via the synced gid copy."""
        rows = np.asarray(rows)
        if self.gids is None:
            raise ValueError("EmbStore has no synced gids (call sync_gids)")
        out = self.gids.reshape(-1).take(np.maximum(rows, 0).reshape(-1))
        return np.where(rows.reshape(-1) < 0, -1, out).reshape(rows.shape)

    # -- transactions -------------------------------------------------------
    # The index lifecycle mutates the host table IN PLACE (write_rows /
    # compact_clusters / sync_gids), so an exception mid-``update_fn`` leaves
    # a mixed-generation store. A transaction keeps an undo journal of
    # first-touch pre-images; ``rollback`` replays it in reverse, restoring
    # table bytes, the synced gid copy, and ``version`` exactly. Growth is
    # already copy-on-grow (``grown`` returns a NEW store), so rolling back
    # a grown update is just discarding the new params — the journal only
    # needs to cover in-place writes to *this* store.

    def begin_txn(self) -> None:
        """Open a transaction; subsequent in-place writes are journaled."""
        if self._txn is not None:
            raise RuntimeError("EmbStore transaction already open")
        self._txn = {
            "log": [],
            "gids": None if self.gids is None else self.gids.copy(),
            "version": self.version,
        }

    def commit(self) -> None:
        """Close the transaction, keeping all writes."""
        if self._txn is None:
            raise RuntimeError("no open EmbStore transaction")
        self._txn = None

    def rollback(self) -> None:
        """Undo every journaled write since ``begin_txn`` (reverse order)."""
        txn = self._txn
        if txn is None:
            raise RuntimeError("no open EmbStore transaction")
        table = None if self.rescore is None else self.rescore.reshape(
            -1, self.shape[-1]
        )
        for kind, key, old in reversed(txn["log"]):
            if kind == "rows":
                table[key] = old
            else:  # "clusters"
                self.rescore[key] = old
        self.gids = txn["gids"]
        self.version = txn["version"]
        self._txn = None

    @property
    def in_txn(self) -> bool:
        return self._txn is not None

    # -- host-tier lifecycle writes (lockstep with the device tier) ---------
    def sync_gids(self, gids: np.ndarray) -> None:
        self.gids = np.ascontiguousarray(gids, np.int32)

    def write_rows(self, flat_slots: np.ndarray, rows: np.ndarray) -> None:
        """Scatter ``rows`` at ``flat_slots``; out-of-range slots drop (the
        same ``mode="drop"`` contract as the device-tier append)."""
        table = self._concrete().reshape(-1, self.shape[-1])
        flat_slots = np.asarray(flat_slots).reshape(-1)
        rows = np.asarray(rows, np.float32).reshape(-1, self.shape[-1])
        keep = (flat_slots >= 0) & (flat_slots < table.shape[0])
        sel = flat_slots[keep]
        if self._txn is not None:
            self._txn["log"].append(("rows", sel.copy(), table[sel].copy()))
        table[sel] = rows[keep]
        self.version += 1
        # Fires AFTER the in-place mutation: models an update_fn crash that
        # leaves the host tier advanced while the device tier is not.
        faults.fire(faults.HOST_WRITE)

    def grown(self, new_capacity: int) -> "EmbStore":
        """A new store with the slot axis ``Lp`` grown (zeros, like the
        device pad). Copy-on-grow, NOT in place: growth changes the flat-row
        arithmetic (``cid*Lp + slot``), so mutating the shared store would
        silently corrupt fetches from any retained pre-growth bank snapshot
        — the new table is a fresh allocation anyway, so returning a new
        store costs nothing and keeps old snapshots consistent."""
        c, lp, d = self.shape
        if new_capacity < lp:
            raise ValueError(f"cannot shrink capacity {lp} -> {new_capacity}")
        if new_capacity == lp:
            return self
        gids = self.gids
        if gids is not None:
            # Pad the synced gid copy like the device pad (-1 = free slot)
            # so take_gids' flat-row arithmetic matches the grown table
            # immediately, not only after the next sync_gids.
            gids = np.pad(
                gids, ((0, 0), (0, new_capacity - lp)), constant_values=-1
            )
        out = EmbStore("host", shape=(c, new_capacity, d), dtype=self.dtype,
                       gids=gids)
        if self.rescore is not None:
            table = np.zeros((c, new_capacity, d), np.float32)
            table[:, :lp] = self.rescore
            out.rescore = table
        out.version = self.version + 1
        return out

    def compact_clusters(self, cids: np.ndarray, gid_rows: np.ndarray) -> None:
        """Mirror of ``update._compact_clusters`` for the host tier: stable
        repack of live rows to the slot prefix. ``gid_rows`` are the
        *pre-compaction* per-cluster gid rows (live = ``gid >= 0``)."""
        table = self._concrete()
        cids = np.asarray(cids)
        if self._txn is not None:
            self._txn["log"].append(("clusters", cids.copy(), table[cids].copy()))
        for cid, g in zip(cids, np.asarray(gid_rows)):
            order = np.argsort(g < 0, kind="stable")
            rows = table[cid][order]
            rows[g[order] < 0] = 0.0
            table[cid] = rows
        self.version += 1


def _f(cluster_axis: int | None, default=dataclasses.MISSING):
    return dataclasses.field(
        metadata={CLUSTER_AXIS: cluster_axis}, default=default
    )


@pytree_dataclass(meta_fields=("store", "code_dtype"))
class ClusterBank:
    lsh: lsh_lib.LSHParams = _f(None)  # shared across clusters (DESIGN.md §2)
    rescale: rescale_lib.RescaleParams = _f(0)  # leaves (c, H)
    rmi: rmi_lib.RMIParams = _f(0)  # leaves (c, H) / (c, H, W)
    sorted_keys: jnp.ndarray = _f(0)  # (c, H, Lp) uint32
    sorted_pos: jnp.ndarray = _f(0)  # (c, H, Lp) int32
    embs: jnp.ndarray = _f(0)  # (c, Lp, d) — storage dtype (d//2 for int4)
    gids: jnp.ndarray = _f(0)  # (c, Lp) int32
    sizes: jnp.ndarray = _f(0)  # (c,) int32 — live rows
    tombstones: jnp.ndarray = _f(0)  # (c,) int32 — dead rows awaiting compaction
    next_gid: jnp.ndarray = _f(None)  # () int32 — bank metadata, replicated
    # Quantized storage only (None otherwise): per-row symmetric scales and
    # the full-precision side table the exact-rescore pass gathers its
    # top-k' rows from (DESIGN.md §Quantized bank).
    emb_scales: jnp.ndarray | None = _f(0, default=None)  # (c, Lp) f32
    rescore_embs: jnp.ndarray | None = _f(0, default=None)  # (c, Lp, d)
    # 1-bit sign-sketch table (quantized storage only; DESIGN.md §Binary
    # sketch tier): per-row sign bits packed 32-per-word. The optional
    # pre-filter pass (LiderConfig.sketch_factor) Hamming-scores these at
    # 1/8 the int8 code bytes before the int4/int8 MXU pass. Built,
    # upserted, and compacted in lockstep with ``embs`` — the sketch is
    # row-local (sign of the raw row), like the quantizers.
    sketches: jnp.ndarray | None = _f(0, default=None)  # (c, Lp, ceil(d/32)) u32
    # Host-tier handle (DESIGN.md §Tiered embedding store). None = device
    # tier. Registered as *static* pytree aux data: the host table never
    # enters traced programs — the staged search fetches from it between its
    # two jit'd stages — and EmbStore hashes by (tier, shape, dtype), so
    # host-content writes never invalidate a compiled search.
    store: EmbStore | None = _f(None, default=None)
    # Code representation of ``embs`` when quantized: "int8" (one code per
    # byte) or "int4" (two nibbles per byte — embs width is d//2). Static
    # pytree aux data like ``store``: it selects a compiled kernel variant,
    # so two banks differing only here must not share a compilation.
    # Ignored (kept at the default) for float banks.
    code_dtype: str = _f(None, default="int8")

    @property
    def n_clusters(self) -> int:
        return self.gids.shape[0]

    @property
    def capacity(self) -> int:
        return self.gids.shape[1]

    @property
    def dim(self) -> int:
        """Embedding dimensionality d (NOT the stored row width — int4 packs
        two elements per stored byte, so ``embs.shape[-1]`` is d//2)."""
        if self.quantized and self.code_dtype == "int4":
            return self.embs.shape[-1] * 2
        return self.embs.shape[-1]

    @property
    def quantized(self) -> bool:
        return self.emb_scales is not None

    @property
    def storage_dtype(self) -> str:
        return self.code_dtype if self.quantized else str(self.embs.dtype)

    @property
    def rescore_tier(self) -> str:
        """Where the full-precision rescore table lives (§Tiered store)."""
        return "host" if self.store is not None else "device"

    def nbytes_by_tier(self) -> dict[str, int]:
        """Index bytes by storage tier: ``device`` (every pytree leaf — what
        must be HBM-resident to search) vs ``host`` (the off-device rescore
        table). The accounting the dry-run memory model and the memory
        benchmarks report; works on abstract (ShapeDtypeStruct) banks too.
        """
        device = sum(
            math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(self)
        )
        host = self.store.nbytes if self.store is not None else 0
        return {"device": int(device), "host": int(host)}

    def float_rows(self) -> jnp.ndarray:
        """(c, Lp, d) rows as first-pass verification scores them —
        dequantized codes for quantized storage, the stored rows otherwise.
        Convenience accessor for consumers/tests; the fit paths apply the
        same ``dequantize_codes`` to their gathered sub-banks (build_bank,
        update._refit_clusters, update._compact_clusters) rather than
        materializing the whole bank through here."""
        if self.quantized:
            return dequantize_codes(self.embs, self.emb_scales, self.code_dtype)
        return self.embs


def replicated_field_names() -> tuple[str, ...]:
    """Bank fields whose leaves are replicated (no cluster axis)."""
    return tuple(
        f.name
        for f in dataclasses.fields(ClusterBank)
        if f.metadata.get(CLUSTER_AXIS) is None
    )


# ---------------------------------------------------------------------------
# Staged build primitives
# ---------------------------------------------------------------------------


def fit_sorted_array(
    sorted_keys: jnp.ndarray, valid: jnp.ndarray, *, n_leaves: int
) -> tuple[rescale_lib.RescaleParams, rmi_lib.RMIParams]:
    """Fit re-scale stats + RMI on one sorted hashkey array ``(L,)``.

    The single learned-fit primitive shared by standalone core models, the
    full bank build, and incremental refits. ``valid`` masks padded slots
    (padding must sort last — the UINT32_PAD sentinel guarantees it).
    """
    resc = rescale_lib.fit_rescale(sorted_keys, valid)
    scaled = rescale_lib.rescale(resc, sorted_keys)
    r = rmi_lib.fit_rmi(scaled, valid.astype(jnp.float32), n_leaves=n_leaves)
    return resc, r


def refit_cluster(
    lsh: lsh_lib.LSHParams,
    row_embs: jnp.ndarray,
    row_valid: jnp.ndarray,
    *,
    n_leaves: int,
):
    """Hash + sort + fit ONE cluster from its packed embedding rows.

    ``row_embs``: (Lp, d); ``row_valid``: (Lp,) bool — live rows. Returns
    ``(sorted_keys (H, Lp), sorted_pos (H, Lp), rescale (H,), rmi (H,))``.
    The unit of both the offline build (``vmap`` over all clusters) and the
    online dirty-cluster refit (``core.update``).
    """
    keys = lsh_lib.hash_vectors(lsh, row_embs)  # (Lp, H)
    keys = lsh_lib.mask_padded(keys, row_valid[:, None]).T  # (H, Lp)
    sorted_keys, order = lsh_lib.sort_hashkeys(keys)
    sorted_pos = jnp.where(
        sorted_keys == jnp.uint32(lsh_lib.UINT32_PAD), -1, order
    ).astype(jnp.int32)
    resc, r = jax.vmap(partial(fit_sorted_array, n_leaves=n_leaves))(
        sorted_keys, sorted_pos >= 0
    )
    return sorted_keys, sorted_pos, resc, r


@partial(jax.jit, static_argnames=("n_leaves", "storage_dtype"))
def _fit_all_clusters(lsh, stored, scales, row_valid, *, n_leaves, storage_dtype):
    """``refit_cluster`` over every cluster, on the storage-effective rows.

    Clusters go through in chunks whose rows are dequantized inside the
    map, so the build never holds a second full ``(c, Lp, d)`` f32 table
    beside the raw rows (at deployment scale that copy alone is most of a
    chip's memory). Per-cluster math is unchanged, so the fit stays
    bit-identical to the online refit in ``core.update``.
    """

    def fit(args):
        rows, scl, valid = args
        if scl is not None:
            rows = dequantize_codes(rows, scl, storage_dtype)
        return refit_cluster(lsh, rows, valid, n_leaves=n_leaves)

    return jax.lax.map(fit, (stored, scales, row_valid), batch_size=64)


@jax.jit
def gather_cluster_rows(embs: jnp.ndarray, gids: jnp.ndarray) -> jnp.ndarray:
    """Pack corpus rows into ``(c, Lp, d)`` per-cluster slots (zero at pads).

    One jit, so the gather and the pad mask fuse into a single output
    buffer instead of two full-size eager temporaries."""
    valid = gids >= 0
    return embs[jnp.maximum(gids, 0)] * valid[..., None]


def store_rows(
    raw_rows: jnp.ndarray, storage_dtype: str
) -> tuple[
    jnp.ndarray, jnp.ndarray | None, jnp.ndarray | None, jnp.ndarray | None
]:
    """Raw packed float rows -> ``(embs, emb_scales, rescore_embs, sketches)``.

    The single conversion point from float rows to bank storage, shared by
    the offline build and the upsert append (so both quantize identically —
    the scheme is row-local, which is what keeps upsert slot-identical to a
    rebuild). For the quantized dtypes the raw rows are also kept as the
    full-precision rescore side table and additionally sign-sketched into the
    packed 1-bit pre-filter table (DESIGN.md §Binary sketch tier); zero
    (padded) rows quantize to exact zeros (int4 rows pack to exact zero
    bytes, sketches to exact zero words).
    """
    if storage_dtype == "int8":
        codes, scales = quantize_rows(raw_rows)
        return codes, scales, raw_rows, sketch_rows(raw_rows)
    if storage_dtype == "int4":
        codes, scales = quantize_rows_int4(raw_rows)
        return codes, scales, raw_rows, sketch_rows(raw_rows)
    if storage_dtype == "bfloat16":
        return raw_rows.astype(jnp.bfloat16), None, None, None
    if storage_dtype == "float32":
        return raw_rows.astype(jnp.float32), None, None, None
    raise ValueError(
        f"storage_dtype must be one of {STORAGE_DTYPES}, got {storage_dtype!r}"
    )


@partial(jax.jit, static_argnames=("storage_dtype",), donate_argnums=(0,))
def _encode_into(out, raw_chunk, start, storage_dtype):
    stored, scales, _, sketches = store_rows(raw_chunk, storage_dtype)
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(o, v, start, axis=0)
        for o, v in zip(out, (stored, scales, sketches))
    )


# Clusters a build step gathers and encodes at once (``_encode_rows``).
ENCODE_CHUNK = 32


def _encode_rows(chunk_rows, shape, storage_dtype, *, chunk: int):
    """Quantized ``store_rows`` without the rescore table: (codes, scales,
    sketches) of ``shape`` ``(c, Lp, d)`` float rows, one jit call per
    ``chunk`` clusters written in place; ``chunk_rows(lo, hi)`` gives the
    rows of clusters ``lo:hi``. The sketch packing makes XLA relayout its
    input; over the whole table (also inside a scan, where the relayout is
    hoisted) that copy alone is the table's size again."""
    shapes = jax.eval_shape(
        lambda r: store_rows(r, storage_dtype),
        jax.ShapeDtypeStruct(shape, jnp.float32),
    )
    out = tuple(
        jnp.zeros(sh.shape, sh.dtype) for sh in (shapes[0], shapes[1], shapes[3])
    )
    for i in range(0, shape[0], chunk):
        out = _encode_into(out, chunk_rows(i, i + chunk), i, storage_dtype)
    return out


def set_rescore_tier(bank: ClusterBank, tier: str) -> ClusterBank:
    """Move the full-precision rescore table between storage tiers.

    ``device -> host``: the ``rescore_embs`` leaf leaves the pytree and
    becomes a process-local host array (the jit'd index shrinks to codes +
    scales). ``host -> device``: the inverse. Search results are
    bit-identical across the move (same rows, same kernel, same tie-break —
    tested in tests/test_tiered.py); only *where* the rows live changes.
    """
    if tier not in RESCORE_TIERS:
        raise ValueError(f"rescore_tier must be one of {RESCORE_TIERS}, got {tier!r}")
    if tier == bank.rescore_tier:
        return bank
    if not bank.quantized:
        raise ValueError(
            "rescore_tier='host' requires quantized (int8/int4) storage — "
            "float banks have no rescore side table to move off-device"
        )
    if tier == "host":
        store = EmbStore(
            "host",
            rescore=np.asarray(jax.device_get(bank.rescore_embs), np.float32),
            gids=np.asarray(jax.device_get(bank.gids)),
        )
        return dataclasses.replace(bank, rescore_embs=None, store=store)
    return dataclasses.replace(
        bank, rescore_embs=jnp.asarray(bank.store._concrete()), store=None
    )


class CapacityOverflowError(ValueError):
    """A pack dropped passages because ``capacity`` < max cluster size.

    Dropped passages never get a slot, so they are permanently unretrievable
    — silent data loss unless the caller explicitly opted in
    (``allow_drops=True``). ``n_dropped`` carries the count.
    """

    def __init__(self, n_dropped: int, capacity: int):
        self.n_dropped = n_dropped
        self.capacity = capacity
        super().__init__(
            f"capacity={capacity} drops {n_dropped} overflow passages "
            "(they become permanently unretrievable); raise capacity or "
            "pass allow_drops=True to accept the recall loss"
        )


def build_bank(
    rng: jax.Array,
    embs: jnp.ndarray,
    assignment: jnp.ndarray,
    *,
    n_clusters: int,
    capacity: int,
    n_arrays: int,
    key_len: int,
    n_leaves: int,
    allow_drops: bool = False,
    storage_dtype: str = "float32",
    rescore_tier: str = "device",
) -> tuple[ClusterBank, int]:
    """Stage-3 build: pack -> store -> hash/sort -> fit, all clusters at once.

    ``assignment`` is the Stage-1 point->cluster map; the fit itself is
    ``vmap(refit_cluster)``, so an incremental refit of a single cluster
    (``core.update``) runs byte-identical math.

    ``storage_dtype`` selects the embedding storage representation; the fit
    runs on the *storage-effective* rows (``ClusterBank.float_rows`` — e.g.
    dequantized int8), so an online refit reading rows back from the bank
    reproduces the offline fit bit-for-bit.

    Returns ``(bank, n_dropped)``. Packing into ``capacity`` slots drops
    per-cluster overflow; a lossy pack raises :class:`CapacityOverflowError`
    unless ``allow_drops=True`` (the count is always returned so callers can
    surface it either way).

    ``rescore_tier="host"`` (quantized only — DESIGN.md §Tiered embedding
    store) builds the full-precision rescore table as a process-local host
    array instead of a device-resident pytree leaf: each chunk of clusters
    is gathered, encoded and copied into that array in turn, so the
    capacity-padded ``(c, Lp, d)`` float table is never whole on the device
    (at d = 4096 it would not fit). The index equals the device-tier build's
    byte for byte.
    """
    if rescore_tier not in RESCORE_TIERS:
        raise ValueError(
            f"rescore_tier must be one of {RESCORE_TIERS}, got {rescore_tier!r}"
        )
    if rescore_tier == "host" and storage_dtype not in QUANTIZED_DTYPES:
        raise ValueError(
            "rescore_tier='host' requires quantized storage "
            f"({QUANTIZED_DTYPES}) — float banks have no rescore side "
            "table to move off-device"
        )
    raw_sizes = jnp.bincount(assignment, length=n_clusters)
    n_dropped = int(
        jax.device_get(jnp.sum(jnp.maximum(raw_sizes - capacity, 0)))
    )
    if n_dropped and not allow_drops:
        raise CapacityOverflowError(n_dropped, capacity)
    gids, sizes = clustering.group_by_cluster(assignment, n_clusters, capacity)
    shape = (n_clusters, capacity, embs.shape[-1])
    store = None
    if rescore_tier == "host":
        table = np.empty(shape, np.float32)

        def host_chunk(lo, hi):
            rows = gather_cluster_rows(embs, gids[lo:hi])
            table[lo:hi] = np.asarray(rows)
            return rows

        stored, emb_scales, sketches = _encode_rows(
            host_chunk, shape, storage_dtype, chunk=ENCODE_CHUNK
        )
        store = EmbStore(
            "host", rescore=table, gids=np.asarray(jax.device_get(gids))
        )
        rescore_embs = None
    elif storage_dtype in QUANTIZED_DTYPES:
        raw_rows = gather_cluster_rows(embs, gids)
        # Encoded chunk by chunk in place (eagerly, each step of the chain
        # would allocate a full-size f32 temporary); the raw rows
        # themselves are the rescore table, so they are not routed through
        # a jit (its output would be a copy).
        stored, emb_scales, sketches = _encode_rows(
            lambda lo, hi: raw_rows[lo:hi], shape, storage_dtype,
            chunk=ENCODE_CHUNK,
        )
        rescore_embs = raw_rows
    else:
        stored, emb_scales, rescore_embs, sketches = store_rows(
            gather_cluster_rows(embs, gids), storage_dtype
        )
    lsh = lsh_lib.make_lsh(rng, embs.shape[-1], n_arrays, key_len)
    sorted_keys, sorted_pos, resc, r = _fit_all_clusters(
        lsh, stored, emb_scales, gids >= 0, n_leaves=n_leaves,
        storage_dtype=storage_dtype,
    )
    bank = ClusterBank(
        lsh=lsh,
        rescale=resc,
        rmi=r,
        sorted_keys=sorted_keys,
        sorted_pos=sorted_pos,
        embs=stored,
        gids=gids,
        sizes=sizes,
        tombstones=jnp.zeros((n_clusters,), jnp.int32),
        next_gid=jnp.int32(embs.shape[0]),
        emb_scales=emb_scales,
        rescore_embs=rescore_embs,
        sketches=sketches,
        store=store,
        code_dtype=storage_dtype if storage_dtype in QUANTIZED_DTYPES else "int8",
    )
    return bank, n_dropped


def grow_bank(bank: ClusterBank, new_capacity: int) -> ClusterBank:
    """Grow the per-cluster slot axis ``Lp`` to ``new_capacity``.

    Pads sorted arrays with the UINT32_PAD sentinel / -1 (padding sorts last,
    so sortedness and every fit statistic are preserved — no refit needed).
    Shapes change, so downstream jits recompile: callers batch growth in
    ``pad_multiple`` steps and serving recompiles only on this event
    (``RetrievalEngine.apply_updates``).
    """
    lp = bank.capacity
    if new_capacity < lp:
        raise ValueError(f"cannot shrink capacity {lp} -> {new_capacity}")
    if new_capacity == lp:
        return bank
    extra = new_capacity - lp
    if bank.store is not None:
        # Host tier grows in lockstep — copy-on-grow, so prior bank
        # snapshots keep a consistent (old-Lp) view of their store.
        bank = dataclasses.replace(bank, store=bank.store.grown(new_capacity))
    return dataclasses.replace(
        bank,
        sorted_keys=jnp.pad(
            bank.sorted_keys,
            ((0, 0), (0, 0), (0, extra)),
            constant_values=jnp.uint32(lsh_lib.UINT32_PAD),
        ),
        sorted_pos=jnp.pad(
            bank.sorted_pos, ((0, 0), (0, 0), (0, extra)), constant_values=-1
        ),
        embs=jnp.pad(bank.embs, ((0, 0), (0, extra), (0, 0))),
        gids=jnp.pad(bank.gids, ((0, 0), (0, extra)), constant_values=-1),
        # Pad scale 1.0, the all-zero-row convention, so grown slots
        # dequantize to exact zeros (same as a fresh pack's padding).
        emb_scales=(
            None
            if bank.emb_scales is None
            else jnp.pad(
                bank.emb_scales, ((0, 0), (0, extra)), constant_values=1.0
            )
        ),
        rescore_embs=(
            None
            if bank.rescore_embs is None
            else jnp.pad(bank.rescore_embs, ((0, 0), (0, extra), (0, 0)))
        ),
        # Zero words: exactly what sketch_rows packs for an all-zero row,
        # so grown slots match a fresh pack's padding byte-for-byte.
        sketches=(
            None
            if bank.sketches is None
            else jnp.pad(bank.sketches, ((0, 0), (0, extra), (0, 0)))
        ),
    )
