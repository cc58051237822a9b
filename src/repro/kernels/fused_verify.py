"""Fused score-reduce verification kernel (the LIDER hot path).

LIDER's end-to-end AQT is dominated by candidate verification (paper
Sec. 3.1/3.3.2): after the RMI predicts positions, each query gathers its
``C = P*H*R`` candidate embeddings and scores them exactly. The materialized
formulation (``ref.verify_topk_ref``) scores the gathered ``(B, C, d)``
candidate tensor with an einsum and round-trips a ``(B, C)`` score matrix
through the dedup/top-k (DESIGN.md §Verification-kernel has the byte
model).

This kernel makes scoring and selection a single VMEM-resident pass over
the batch:

- XLA gathers the candidate rows into a ``(B, n_blocks, block_c, width)``
  block (the TPU's tiled HBM layouts pad rows below 128 lanes and pack 2-4
  narrow rows per 32-bit sublane word, so the compiler refuses single-row
  DMAs from inside the kernel), and the BlockSpec pipeline streams one
  ``block_c`` tile per grid step, block ``j+1`` in flight while ``j`` is
  scored;
- scoring runs on the MXU in the embedding storage dtype (bf16 stays bf16;
  int8 code tables run **int8×int8→int32** with the per-candidate combined
  scale folded in afterwards — DESIGN.md §Quantized bank) with full-width
  accumulation; packed int4 tables (``code_dtype="int4"``) unpack to int8
  **in VMEM** (two arithmetic shifts) before the same int8×int8→int32 pass;
- a masked **streaming top-k accumulator** lives in VMEM and merges each
  block with duplicate suppression (same semantics as
  ``core.utils.dedup_topk``: duplicates of one id carry equal scores, so
  keeping the first-selected occurrence is exact);
- the grid is ``(ceil(B/R), n_blocks)``: each step scores ``R`` queries'
  blocks (one contraction batched over the queries, each against its own
  candidate rows) and merges them as one ``(R, block_c)`` block into an
  ``(R, k)`` accumulator. A selection step of the merge is a chain of
  dependent lane reductions paid in latency, so ``R`` rows cost about what
  one does.
  ``R = min(B, 32)`` follows the batch shape (``_rows_per_step``): B = 1
  keeps one query a step; batches above 32 pad to whole groups with rows
  whose ``out_ids`` are all -1, sliced off after. Results are bit-identical
  for every ``R`` and ``block_c``;
- the call's VMEM is planned from the row width (``_tile_plan``): a wide
  row (d = 4096 f32) shrinks the candidate block so the double-buffered
  tile, and an int4 tile's unpacked copy, fit what a d = 768 call asks.

Neither the score matrix nor the dedup/sort round-trips exist in HBM; only
the ``(B, k)`` result is written.

``row_ids`` index the embedding table (what to gather); ``out_ids`` are the
ids to *report and dedup by* (defaults to ``row_ids``). LIDER passes flat
``(cluster, slot)`` rows as ``row_ids`` and global passage ids as
``out_ids``. ``out_ids < 0`` marks padding (scored ``-inf``).

Per-(row group, block) valid-candidate counts ride the scalar prefetch so
blocks dead in every row of the group (all probes pruned by the adaptive
margin rule, or pure padding) skip their MXU pass and merge under
``pl.when`` (DESIGN.md §Adaptive speed-quality control plane); a dead row
inside a live group merges only -inf and leaves its accumulator as it was.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import resolve_interpret

NEG_INF = float("-inf")  # python float: jnp scalars would init the backend


def _unpack_int4_vmem(rows: jnp.ndarray) -> jnp.ndarray:
    """In-VMEM nibble unpack: ``(..., d//2)`` packed int8 -> ``(..., d)`` int8.

    Emits the *deinterleaved* element order ``[x0, x2, ..., x1, x3, ...]``
    (``concat([low_nibbles, high_nibbles], -1)``) — two arithmetic shifts and
    a concat, no lane-crossing re-interleave. The query side is permuted to
    match outside the kernel (``quant.deinterleave_query_codes``), so the
    dot product over the full width is exact.
    """
    # Shifts run on the sign-extended int32 view: the TPU's vector unit has
    # no 8-bit shifts.
    x = rows.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(x, 28), 28)
    hi = jnp.right_shift(x, 4)
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def _clamp_block_c(block_c: int, c: int) -> int:
    """Effective candidate-block width: ``min(block_c, c)`` rounded down to a
    sublane-aligned multiple of 8 (floor 8). The round-down keeps the
    candidate tiles and the MXU operand shapes aligned when ``c`` is not a
    multiple of the requested ``block_c``; the wrapper pads the candidate
    axis up to a multiple of the result, so a ragged last block is always
    well-formed rather than relying on caller-side padding being exact.
    """
    return max(8, (min(block_c, c) // 8) * 8)


def _merge_topk(acc_sc, acc_ids, sc, ids, k: int):
    """Streaming dedup top-k merge of ``(R, k)`` accumulators with an
    ``(R, n)`` block of scores/ids, row-wise.

    Selects the max ``k`` times from [accumulator ++ block], keeping the two
    halves apart rather than copying them into one array; each selection
    kills every copy of the selected id in both halves (duplicates carry
    equal scores, so this is exact). One helper serves the per-query
    kernels (R = ``_rows_per_step(B)``) and the grouped kernel
    (R = block_q).
    Score ties between distinct ids break toward the smallest id — the order
    ``dedup_topk`` produces (stable top_k over id-sorted candidates). Rows
    with fewer than ``k`` live candidates pad with (-1, -inf).
    """
    r = acc_sc.shape[0]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    big = jnp.int32(2**31 - 1)

    def row_min(x):
        return jnp.min(x, axis=1, keepdims=True)

    def sel_body(i, carry):
        a_sc, b_sc, o_sc, o_id = carry
        m = jnp.maximum(
            jnp.max(a_sc, axis=1, keepdims=True),
            jnp.max(b_sc, axis=1, keepdims=True),
        )  # (R, 1)
        sid = jnp.minimum(
            row_min(jnp.where(a_sc == m, acc_ids, big)),
            row_min(jnp.where(b_sc == m, ids, big)),
        )
        sid = jnp.where(m == NEG_INF, jnp.int32(-1), sid)
        live = sid >= 0
        a_sc = jnp.where((acc_ids == sid) & live, NEG_INF, a_sc)
        b_sc = jnp.where((ids == sid) & live, NEG_INF, b_sc)
        o_sc = jnp.where(iota_k == i, m, o_sc)
        o_id = jnp.where(iota_k == i, sid, o_id)
        return a_sc, b_sc, o_sc, o_id

    init = (
        acc_sc,
        sc,
        jnp.full((r, k), NEG_INF, jnp.float32),
        jnp.full((r, k), -1, jnp.int32),
    )
    _, _, o_sc, o_id = jax.lax.fori_loop(0, k, sel_body, init)
    return o_sc, o_id


def _rows_per_step(b: int) -> int:
    """Queries merged per grid step: the whole batch up to 32 rows (a
    full-dim block), else 32 — four f32 vregs' sublanes, so each serial
    selection step of ``_merge_topk`` serves 32 queries for little more
    than the latency of one. Follows the batch shape and nothing else; B = 1
    keeps one query a step. (R = 8, 16 and 32 were timed on a v5e at
    B = 32: 32 was the fastest for every pass; PERF.md §6.)"""
    return min(b, 32)


# VMEM of one per-query call (``_tile_plan``). The cap is what the d = 768
# route asks for (a double-buffered 32 x 256 x 768 f32 tile plus the
# scratch allowance): every d = 768 call fits it unchanged, and wider rows
# shrink the candidate block to stay inside it, well under a v5e's 128 MiB.
_VMEM_SCRATCH = 32 << 20
_VMEM_CAP = 2 * 32 * 256 * 768 * 4 + _VMEM_SCRATCH


def _vmem_bytes(r: int, bc: int, row_bytes: int, unpacked_row_bytes: int) -> int:
    """VMEM limit of an (R, bc) step: the double-buffered candidate tile,
    plus a scratch allowance for scores, ids, the accumulators and Mosaic's
    own use; a tile unpacked in VMEM (int4 codes widened to int8) gets half
    the allowance on top of its own bytes where that is more."""
    unpacked = r * bc * unpacked_row_bytes
    return 2 * r * bc * row_bytes + max(
        _VMEM_SCRATCH, unpacked + _VMEM_SCRATCH // 2
    )


def _tile_plan(
    b: int, c: int, block_c: int, row_bytes: int, unpacked_row_bytes: int = 0
) -> tuple[int, int, int]:
    """``(R, block_c, vmem_limit_bytes)`` of one per-query call over ``b``
    queries x ``c`` candidates of ``row_bytes`` bytes each.

    ``R = _rows_per_step(b)``; the requested block (``_clamp_block_c``)
    stands while its VMEM fits ``_VMEM_CAP`` — every call at d = 768 does.
    Wider rows shrink the block 8 rows at a time, not the contraction: each
    row's score is still one sum over the whole width, in the oracle's
    order.
    """
    r = _rows_per_step(b)

    def vmem(bc):
        return _vmem_bytes(r, bc, row_bytes, unpacked_row_bytes)

    bc = _clamp_block_c(block_c, c)
    while bc > 8 and vmem(bc) > _VMEM_CAP:
        bc -= 8
    if vmem(bc) > _VMEM_CAP:
        raise ValueError(
            f"a candidate row of {row_bytes} B is too wide for one VMEM block"
        )
    return r, bc, vmem(bc)


def _topk_kernel(
    blk_live_s,  # scalar prefetch: (G * n_blocks,) live candidates per block
    q_ref,  # (R, d_q) queries (codes / sketch) of row group g
    oid_ref,  # (R, block_c) candidate ids (-1 = padding/pruned)
    *rest,
    k: int,
    n_blocks: int,
    n_extra: int,
    score,
):
    """Score -> dedup top-k of R queries over one candidate block each
    (grid = (G, n_blocks), candidate axis innermost; row group g holds
    queries g*R .. g*R+R-1).

    ``rest`` is ``(*extra_refs, cand_ref, ids_out, sc_out)`` with
    ``cand_ref`` the (R, block_c, width) candidate rows of this block, each
    query its own; ``score(rows, q, *extras) -> (R, block_c) f32`` scores
    each query against its own rows and is the only part that differs
    between the float pass, the code pass and the sketch pass.
    """
    extras = rest[:n_extra]
    cand_ref, ids_out, sc_out = rest[n_extra:]
    g = pl.program_id(0)
    cj = pl.program_id(1)

    # The (R, k) output blocks stay resident across the cj axis (same block
    # index), so they are the running top-k accumulator.
    @pl.when(cj == 0)
    def _():
        sc_out[...] = jnp.full_like(sc_out, NEG_INF)
        ids_out[...] = jnp.full_like(ids_out, -1)

    # Block-skip contract (DESIGN.md §Adaptive): a block whose candidates are
    # all invalid in every row of the group — every probe feeding them
    # pruned, or pure padding — would only contribute -inf scores, so its
    # MXU/VPU pass and k-way merge are skipped and the accumulator carries
    # over. A dead row of a live group merges only -inf: no change.
    @pl.when(blk_live_s[g * n_blocks + cj] > 0)
    def _():
        oid = oid_ref[...]
        scores = score(cand_ref[...], q_ref[...], *(e[...] for e in extras))
        scores = jnp.where(oid >= 0, scores, NEG_INF)
        sc, ids = _merge_topk(sc_out[...], ids_out[...], scores, oid, k)
        sc_out[...] = sc
        ids_out[...] = ids


def _per_query_dot(q, rows, **kw):
    """``(R, w)`` queries x ``(R, block_c, w)`` rows -> ``(R, block_c)``: each
    query against its own rows, one contraction batched over the R queries
    (the oracle's ``einsum("bcd,bd->bc")``, so f32 sums keep its order on
    XLA's CPU dot too, where per-row slices of the block would not)."""
    return jax.lax.dot_general(
        q[:, None, :], rows, (((2,), (2,)), ((0,), (0,))), **kw
    )[:, 0, :]


def _score_float(rows, q):
    """Storage-dtype MXU pass, f32 accumulation. An f32 table is scored at
    HIGHEST precision so the rescore is exact f32 math on the TPU too (its
    default f32 matmul is a single bf16 pass); bf16 tables are exact bf16
    products either way."""
    precision = (
        jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    )
    return _per_query_dot(
        q.astype(rows.dtype),
        rows,
        precision=precision,
        preferred_element_type=jnp.float32,
    )


def _score_codes(rows, q, comb, *, code_dtype: str):
    """int8×int8→int32 MXU pass over int8 (or in-VMEM unpacked int4) codes;
    the pre-gathered combined row×query scale is one f32 multiply after."""
    if code_dtype == "int4":
        rows = _unpack_int4_vmem(rows)  # (R, block_c, d) deinterleaved
    acc = _per_query_dot(q, rows, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * comb


def _score_sketch(rows, q):
    """Negated Hamming distance: XOR + popcount on the VPU. The per-row sum
    over words is a ones-vector contraction so the result lands as an
    (R, block_c) block; popcounts <= 32 are exact in bf16 and their sum
    (<= d < 2^24) is exact in the f32 accumulator."""
    # Through int32: the TPU has no uint32 -> float conversion.
    pc = jax.lax.population_count(jnp.bitwise_xor(rows, q[:, None, :]))
    pc = pc.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    ones = jnp.ones((rows.shape[0], rows.shape[2]), jnp.bfloat16)
    return -_per_query_dot(ones, pc, preferred_element_type=jnp.float32)


def _gather_topk(
    table, row_ids, out_ids, q, extra, *, k, block_c, score, name, interpret,
    unpacked_row_bytes=0,
):
    """Shared wrapper of the per-query kernels.

    Pads the candidate axis to whole blocks and the batch to whole row
    groups of ``R`` queries, both from ``_tile_plan`` (padding rows are all
    -1 and sliced off), gathers the candidate rows as a
    ``(B, n_blocks, block_c, width)`` block (an XLA gather: the TPU's tiled HBM layouts pack 2-4 narrow rows
    per 32-bit word and pad rows below 128 lanes, so single-row DMAs from
    inside the kernel do not lower), counts live candidates per (row group,
    block) for the skip path, and lays every per-row input out so each
    block's last two dims equal the array's (the (8, 128) tiling rule).
    Returns ``(B, k)`` ids and scores.
    """
    b, c = row_ids.shape
    n = table.shape[0]
    r, bc, vmem_limit = _tile_plan(
        b, c, block_c, table.shape[-1] * table.dtype.itemsize,
        unpacked_row_bytes,
    )
    pad_c = (-c) % bc
    pad_b = (-b) % r
    if pad_c or pad_b:
        widths = ((0, pad_b), (0, pad_c))
        row_ids = jnp.pad(row_ids, widths)
        out_ids = jnp.pad(out_ids, widths, constant_values=-1)
        q = jnp.pad(q, ((0, pad_b), (0, 0)))
        if extra is not None:
            extra = jnp.pad(extra, widths)
    n_groups = (b + pad_b) // r
    n_blocks = (c + pad_c) // bc
    safe_rows = jnp.clip(row_ids, 0, n - 1).reshape(-1, n_blocks, bc)
    cand = table[safe_rows]  # (B_pad, n_blocks, bc, width)
    out_ids = out_ids.astype(jnp.int32)
    blk_live = jnp.sum(
        (out_ids >= 0).reshape(n_groups, r, n_blocks, bc),
        axis=(1, 3),
        dtype=jnp.int32,
    ).reshape(-1)

    def per_block(x):  # (B_pad, C_pad) -> (G, n_blocks, R, bc)
        return x.reshape(n_groups, r, n_blocks, bc).transpose(0, 2, 1, 3)

    idx_g = lambda g, cj, live: (g, 0, 0)
    idx_blk = lambda g, cj, live: (g, cj, 0, 0)
    blk_spec = pl.BlockSpec((None, None, r, bc), idx_blk)
    in_specs = [pl.BlockSpec((None, r, q.shape[-1]), idx_g), blk_spec]
    inputs = [q.reshape(n_groups, r, -1), per_block(out_ids)]
    if extra is not None:
        in_specs.append(blk_spec)
        inputs.append(per_block(extra))
    in_specs.append(pl.BlockSpec((r, None, bc, cand.shape[-1]), idx_blk))
    inputs.append(cand)

    out_spec = pl.BlockSpec((None, r, k), idx_g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups, n_blocks),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
    )
    ids, scores = pl.pallas_call(
        functools.partial(
            _topk_kernel,
            k=k,
            n_blocks=n_blocks,
            n_extra=0 if extra is None else 1,
            score=score,
        ),
        grid_spec=grid_spec,
        # The candidate tile is double-buffered: 32 rows of a wide f32 table
        # (the route, the device rescore) outgrow the default scoped VMEM.
        # ``_tile_plan`` sizes the block and the limit from the row width.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, r, k), jnp.int32),
            jax.ShapeDtypeStruct((n_groups, r, k), jnp.float32),
        ],
        name=name,
        interpret=interpret,
    )(blk_live, *inputs)
    return ids.reshape(-1, k)[:b], scores.reshape(-1, k)[:b]


@functools.partial(
    jax.jit, static_argnames=("k", "block_c", "code_dtype", "interpret")
)
def fused_verify(
    embs: jnp.ndarray,
    row_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    out_ids: jnp.ndarray | None = None,
    scales: jnp.ndarray | None = None,
    block_c: int = 256,
    code_dtype: str = "int8",
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, d) table, (B, C) rows, (B, d) queries -> ((B, k) ids, (B, k) f32).

    Returns the deduplicated top-k by ``out_ids`` (default ``row_ids``),
    scores descending, padded with (-1, -inf) when fewer than ``k`` unique
    valid candidates exist. ``out_ids < 0`` marks invalid slots.

    With ``scales`` ((N,) f32) set, ``embs`` is an int8 code table
    (DESIGN.md §Quantized bank): queries are quantized per row with the same
    symmetric scheme (``quant.quantize_rows``), the MXU pass runs
    int8×int8→int32, and the combined per-candidate scale (row × query)
    rides a third blocked input so folding it in costs one f32 multiply per
    score inside the merge — candidate row traffic drops to 1 byte/elem
    while dedup/top-k semantics are unchanged.

    With ``code_dtype="int4"`` (requires ``scales``), ``embs`` is a *packed*
    int4 code table of width ``d//2`` (two nibbles per byte —
    ``quant.pack_int4``): candidate tiles move half the bytes again (0.5 B/elem),
    the block is unpacked to int8 in VMEM, and the query codes are
    deinterleaved outside the kernel so the same int8×int8→int32 MXU pass
    applies unchanged.

    Blocks whose candidates are *all* invalid in every row of a row group —
    e.g. every probe feeding them was pruned by the adaptive margin rule, or
    they are pure C-padding — are skipped entirely (no MXU pass, no merge):
    a per-(row group, block) valid count rides the scalar prefetch so the
    kernel knows a block is dead before touching it.
    Output is bit-identical with or without skipping (dead candidates score
    -inf either way); an all-invalid row returns all (-1, -inf).
    """
    from .quant import deinterleave_query_codes, quantize_rows

    if out_ids is None:
        out_ids = row_ids
    if code_dtype not in ("int8", "int4"):
        raise ValueError(f"code_dtype must be 'int8' or 'int4', got {code_dtype!r}")
    if scales is None:
        if code_dtype == "int4":
            raise ValueError("code_dtype='int4' requires scales (a packed code table)")
        return _gather_topk(
            embs, row_ids, out_ids, queries, None, k=k, block_c=block_c,
            score=_score_float, name="fused_verify_float",
            interpret=resolve_interpret(interpret),
        )
    q_codes, q_scales = quantize_rows(queries)
    if code_dtype == "int4":
        # Match the kernel's concat([lo, hi]) unpack order (see
        # _unpack_int4_vmem) — queries stay int8-quantized, only their
        # element order changes, so the int32 dot is still exact.
        q_codes = deinterleave_query_codes(q_codes)
    # Combined per-candidate scale, gathered outside the kernel: O(B·C) f32
    # against the O(B·C·d) row bytes the code pass saves. Invalid slots
    # gather row 0's scale — harmless, their score is masked -inf.
    safe = jnp.clip(row_ids, 0, embs.shape[0] - 1)
    comb = scales[safe].astype(jnp.float32) * q_scales[:, None]
    return _gather_topk(
        embs, row_ids, out_ids, q_codes, comb, k=k, block_c=block_c,
        score=functools.partial(_score_codes, code_dtype=code_dtype),
        name=f"fused_verify_{code_dtype}",
        interpret=resolve_interpret(interpret),
        # The int4 tile is unpacked to int8 in VMEM: d bytes a row.
        unpacked_row_bytes=2 * embs.shape[-1] if code_dtype == "int4" else 0,
    )


# ---------------------------------------------------------------------------
# Binary-sketch pre-filter (DESIGN.md §Binary sketch tier)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "block_c", "interpret"))
def sketch_prefilter(
    sketches: jnp.ndarray,
    row_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    out_ids: jnp.ndarray | None = None,
    block_c: int = 256,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, w) packed sketch table, (B, C) rows, (B, d) queries ->
    ((B, k) ids, (B, k) negated-Hamming f32 scores).

    The 1-bit first pass of the sketch→code→rescore ladder (DESIGN.md
    §Binary sketch tier): queries are sign-sketched outside the kernel
    (``quant.sketch_rows`` — the same packer that built the table), candidate
    sketch rows stream HBM->VMEM at 1/8 the int8 code bytes, and scoring is
    XOR + popcount on the VPU. It runs the same gather/block-skip/merge
    kernel as ``fused_verify`` with a Hamming score, so padding
    (``out_ids < 0`` -> (-1, -inf)), dead-block skipping, and the
    smallest-id tie-break are identical and the surviving top-``k`` rows
    feed the int4/int8 pass as an ordinary ``row_ids``/``out_ids`` pair.
    """
    from .quant import sketch_rows

    if out_ids is None:
        out_ids = row_ids
    return _gather_topk(
        sketches, row_ids, out_ids, sketch_rows(queries), None, k=k,
        block_c=block_c, score=_score_sketch, name="sketch_prefilter",
        interpret=resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# Cluster-major multi-query schedule (DESIGN.md §Cluster-major schedule)
# ---------------------------------------------------------------------------


def _fused_verify_grouped_kernel(
    # scalar prefetch
    sched_cids_s,  # (S,) cluster of each step
    blk_live_s,  # (S * n_blocks,) live candidates per (step, block)
    # blocked inputs
    emb_ref,  # (bc, d_store) — steered to cluster sched_cids[s], block j
    scl_ref,  # (1, bc) per-row scales of the same block
    q_ref,  # (block_q, d_q) query-code tile of step s
    qscl_ref,  # (block_q, 1) query scales of step s
    oid_ref,  # (block_q, bc) per-(slot, row) candidate ids (-1 = not cand)
    # outputs: (block_q, kp), resident across j — the running accumulator
    ids_out,
    sc_out,
    *,
    kp: int,
    n_blocks: int,
    code_dtype: str,
):
    s = pl.program_id(0)
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _():
        sc_out[...] = jnp.full_like(sc_out, NEG_INF)
        ids_out[...] = jnp.full_like(ids_out, -1)

    # Dead step-blocks (no candidate of any query in this tile touches these
    # rows — e.g. pruned probes or schedule padding) skip the MXU pass; the
    # block's rows still stream through the automatic pipeline, but scoring
    # and the k' merge are the dominant per-block cost at block_q > 1.
    @pl.when(blk_live_s[s * n_blocks + cj] > 0)
    def _():
        rows = emb_ref[...]  # (bc, d_store)
        if code_dtype == "int4":
            rows = _unpack_int4_vmem(rows)  # (bc, d) deinterleaved
        # ONE MXU pass scores the whole query tile against the resident
        # cluster block — this is the DMA-sharing win: per-query scheduling
        # would re-stream these rows once per query in the tile.
        int_scores = jax.lax.dot_general(
            q_ref[...],
            rows,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (block_q, bc)
        # Combined scale as an in-kernel outer product (f32 multiply is
        # commutative, so this is bit-identical to the per-query path's
        # pre-gathered row×query scale).
        comb = qscl_ref[...] * scl_ref[...]
        scores = int_scores.astype(jnp.float32) * comb
        oid = oid_ref[...]  # (block_q, bc)
        scores = jnp.where(oid >= 0, scores, NEG_INF)
        # Row-vectorized streaming top-k' merge: same selection order and
        # smallest-id tie-break as the per-query kernel / dedup_topk, applied
        # to all block_q slots at once.
        sc, ids = _merge_topk(sc_out[...], ids_out[...], scores, oid, kp)
        sc_out[...] = sc
        ids_out[...] = ids


def _grouped_block_c(block_c: int, lp: int) -> int:
    """Cluster-row tile width for the grouped kernel: the largest multiple
    of 8 that DIVIDES ``lp`` and is <= min(block_c, lp). ``lp`` (the bank
    slot capacity) is always a multiple of 8 (``pad_multiple``), so a
    sublane-aligned divisor exists and no table padding is ever needed —
    the BlockSpec can slice ``embs[(cid, j)]`` directly. Falls back to any
    divisor for oddly-shaped test tables.
    """
    cap = min(block_c, lp)
    for v in range(cap - cap % 8, 7, -8):
        if lp % v == 0:
            return v
    for v in range(cap, 0, -1):
        if lp % v == 0:
            return v
    return lp


@functools.partial(
    jax.jit,
    static_argnames=("kp", "block_q", "block_c", "code_dtype", "interpret"),
)
def fused_verify_grouped(
    embs: jnp.ndarray,
    row_scales: jnp.ndarray,
    queries: jnp.ndarray,
    sched_cids: jnp.ndarray,
    sched_qids: jnp.ndarray,
    step_slot_ids: jnp.ndarray,
    *,
    kp: int,
    block_q: int,
    block_c: int = 256,
    code_dtype: str = "int8",
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Cluster-major first pass: one cluster DMA serves a whole query tile.

    The per-query ``fused_verify`` grid re-streams a cluster's rows once per
    (query, probe) that touches it. This kernel flips the grid to
    **cluster-major**: a host pre-pass (``schedule.build_cluster_schedule``)
    groups the batch's (query, probe) pairs by cluster into steps of
    ``block_q`` query slots, and each grid step streams one ``block_c`` row
    tile of ONE cluster and scores it against the step's whole query tile on
    the MXU — under skewed (Zipf) probe traffic the same rows serve many
    queries per DMA (DESIGN.md §Cluster-major schedule).

    Quantized banks only (int8 / packed int4 codes + per-row scales):

    - ``embs``: ``(c, Lp, d_store)`` stored codes (``d_store = d//2`` packed
      int4); ``row_scales``: ``(c, Lp)`` f32.
    - ``sched_cids``: ``(S,)`` int32 — the cluster each step scores.
    - ``sched_qids``: ``(S, block_q)`` int32 — query per tile slot (-1 pad).
    - ``step_slot_ids``: ``(S, block_q, Lp)`` int32 — per (step, slot,
      cluster row) the id to report, or -1 where that row is not a candidate
      of that query (the dense union of the pair's H·R window candidates —
      duplicates collapse for free).

    Returns ``(ids, scores)`` of shape ``(S, block_q, kp)``: each (query,
    cluster) pair's dedup-top-k' *within that cluster*, same ordering and
    tie-break as ``fused_verify``. Because every global top-k' winner from a
    cluster is inside its pair's per-cluster top-k', scattering these back
    per query and merging with ``dedup_topk`` reproduces the per-query
    schedule's provisional top-k' bit-exactly (tests/test_fused_verify.py).

    Rows are streamed by BlockSpec index maps steered with the
    scalar-prefetched ``sched_cids`` — cluster rows are contiguous in
    ``embs``, so the automatic pipeline double-buffers tiles with no manual
    DMA loop.
    """
    from .quant import deinterleave_query_codes, quantize_rows

    interpret = resolve_interpret(interpret)
    if code_dtype not in ("int8", "int4"):
        raise ValueError(f"code_dtype must be 'int8' or 'int4', got {code_dtype!r}")
    c, lp, d_store = embs.shape
    s_steps = sched_cids.shape[0]
    d_q = d_store * 2 if code_dtype == "int4" else d_store
    bc = _grouped_block_c(block_c, lp)
    n_blocks = lp // bc

    q_codes, q_scales = quantize_rows(queries)
    if code_dtype == "int4":
        q_codes = deinterleave_query_codes(q_codes)
    safe_q = jnp.maximum(sched_qids, 0)
    q_tiles = q_codes[safe_q]  # (S, block_q, d_q)
    # Pad slots get scale 1.0 (their candidates are all -1 -> -inf anyway).
    qscl_tiles = jnp.where(sched_qids >= 0, q_scales[safe_q], 1.0).astype(
        jnp.float32
    )
    step_slot_ids = step_slot_ids.astype(jnp.int32)
    sched_cids = jnp.clip(sched_cids, 0, c - 1).astype(jnp.int32)
    # Per-(step, block) candidate counts: a block is dead if no query in the
    # tile has a candidate among its rows.
    blk_live = jnp.sum(
        (step_slot_ids >= 0).reshape(s_steps, block_q, n_blocks, bc),
        axis=(1, 3),
        dtype=jnp.int32,
    ).reshape(-1)
    # Layouts whose blocks' last two dims equal the array's (the (8, 128)
    # tiling rule holds for any bc): scales as (c, n_blocks, 1, bc), query
    # scales as a (block_q, 1) column, candidate ids block-major.
    row_scales = row_scales.astype(jnp.float32).reshape(c, n_blocks, 1, bc)
    qscl_tiles = qscl_tiles[:, :, None]
    oid_tiles = step_slot_ids.reshape(s_steps, block_q, n_blocks, bc)
    oid_tiles = oid_tiles.transpose(0, 2, 1, 3)  # (S, n_blocks, bq, bc)

    idx_emb = lambda s, j, cids, live: (cids[s], j, 0)
    idx_scl = lambda s, j, cids, live: (cids[s], j, 0, 0)
    idx_step = lambda s, j, cids, live: (s, 0, 0)
    idx_oid = lambda s, j, cids, live: (s, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_steps, n_blocks),
        in_specs=[
            pl.BlockSpec((None, bc, d_store), idx_emb),
            pl.BlockSpec((None, None, 1, bc), idx_scl),
            pl.BlockSpec((None, block_q, d_q), idx_step),
            pl.BlockSpec((None, block_q, 1), idx_step),
            pl.BlockSpec((None, None, block_q, bc), idx_oid),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, kp), idx_step),
            pl.BlockSpec((None, block_q, kp), idx_step),
        ],
    )
    ids, scores = pl.pallas_call(
        functools.partial(
            _fused_verify_grouped_kernel,
            kp=kp,
            n_blocks=n_blocks,
            code_dtype=code_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s_steps, block_q, kp), jnp.int32),
            jax.ShapeDtypeStruct((s_steps, block_q, kp), jnp.float32),
        ],
        name=f"fused_verify_grouped_{code_dtype}",
        interpret=interpret,
    )(sched_cids, blk_live, embs, row_scales, q_tiles, qscl_tiles, oid_tiles)
    return ids, scores
