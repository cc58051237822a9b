"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each ``*_ref`` is the mathematically transparent version of the kernel with
identical signature and semantics; tests sweep shapes/dtypes and assert the
kernels (interpret mode on CPU, compiled on TPU) match these exactly
(integer outputs) or to fp tolerance (scores).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# f32 oracles run at HIGHEST: the TPU's default f32 matmul is a single bf16
# pass, which would make the oracle, not the kernel, the inexact side.
HIGHEST = jax.lax.Precision.HIGHEST


def lsh_hash_ref(
    x: jnp.ndarray, proj: jnp.ndarray, n_arrays: int, key_len: int
) -> jnp.ndarray:
    """(N, d) x (d, H*M) -> (N, H) packed big-endian uint32 hashkeys."""
    acc = jnp.dot(
        x.astype(jnp.float32), proj.astype(jnp.float32), precision=HIGHEST
    )
    bits = (acc >= 0.0).astype(jnp.uint32)
    bits = bits.reshape(x.shape[0], n_arrays, key_len)
    weights = (jnp.uint32(1) << jnp.arange(key_len - 1, -1, -1, dtype=jnp.uint32))
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)


def kmeans_assign_ref(
    x: jnp.ndarray, centroids: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, d), (c, d) -> (assignment (N,) int32, min squared-L2 (N,) f32)."""
    x = x.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d2 = (
        jnp.sum(x * x, -1, keepdims=True)
        - 2.0 * jnp.dot(x, c.T, precision=HIGHEST)
        + jnp.sum(c * c, -1)[None, :]
    )
    return jnp.argmin(d2, -1).astype(jnp.int32), jnp.min(d2, -1)


def verify_topk_ref(
    embs: jnp.ndarray,
    row_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    out_ids: jnp.ndarray | None = None,
    scales: jnp.ndarray | None = None,
    code_dtype: str = "int8",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize-then-einsum verification: the oracle for ``fused_verify``.

    Gathers a (B, C, d) candidate tensor, scores it (storage-dtype MXU
    inputs, fp32 accumulation — identical math to the fused kernel), then
    dedup-top-ks by ``out_ids`` (default ``row_ids``; < 0 marks padding).
    This is exactly the HBM-materialized path the fused kernel replaces, so
    it doubles as the unfused baseline in benchmarks/kernel_verify.py.

    With ``scales`` set, ``embs`` is an int8 code table with per-row
    symmetric scales (DESIGN.md §Quantized bank): queries are quantized with
    the same ``quant.quantize_rows`` scheme the kernel wrapper uses, scoring
    is exact int8×int8→int32, and the combined per-candidate scale
    (row × query) is folded in as a single f32 multiply — the identical op
    sequence to the fused kernel's quantized path, so ids match exactly.

    ``code_dtype="int4"`` (with ``scales``): ``embs`` is a packed int4 table
    (width d//2); candidates are unpacked to int8 here in natural element
    order — int32 accumulation is exact regardless of summation order, so
    this matches the kernel's deinterleaved in-VMEM unpack bit-for-bit.

    Block-skip semantics mirror: the fused kernel skips blocks whose
    candidates are all invalid (adaptive probe pruning); here they are
    simply scored -inf — the outputs are bit-identical, including the
    all-candidates-invalid row, which returns all (-1, -inf).
    """
    from ..core.utils import NEG_INF, dedup_topk
    from .quant import quantize_rows, unpack_int4

    if out_ids is None:
        out_ids = row_ids
    c = row_ids.shape[-1]
    safe = jnp.maximum(row_ids, 0)
    cand = embs[safe]  # (B, C, d) — the materialization being eliminated
    if scales is not None and code_dtype == "int4":
        cand = unpack_int4(cand)
    if scales is None:
        # Score the same 8-aligned candidate width the kernel's blocks
        # cover: XLA's CPU dot sums a ragged tail of < 8 columns in another
        # order, and the f32 parity with the kernel is bit-exact only when
        # every column takes the same path.
        pad = (-c) % 8
        scores = jnp.einsum(
            "bcd,bd->bc",
            jnp.pad(cand, ((0, 0), (0, pad), (0, 0))),
            queries.astype(cand.dtype),
            precision=HIGHEST,
            preferred_element_type=jnp.float32,
        )[:, :c]
    else:
        q_codes, q_scales = quantize_rows(queries)
        int_scores = jnp.einsum(
            "bcd,bd->bc", cand, q_codes, preferred_element_type=jnp.int32
        )
        comb = scales[safe].astype(jnp.float32) * q_scales[:, None]
        scores = int_scores.astype(jnp.float32) * comb
    scores = jnp.where(out_ids < 0, NEG_INF, scores)
    return dedup_topk(out_ids, scores, k)


def sketch_topk_ref(
    sketches: jnp.ndarray,
    row_ids: jnp.ndarray,
    queries: jnp.ndarray,
    *,
    k: int,
    out_ids: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Natural-order Hamming oracle for the binary-sketch pre-filter
    (``sketch_prefilter``; DESIGN.md §Binary sketch tier).

    ``sketches`` is the packed ``(N, ceil(d/32))`` uint32 sign-sketch table;
    queries are sketched here with the same ``quant.sketch_rows`` packer the
    kernel wrapper uses. The score is the *negated* Hamming distance between
    the row and query sketches — XOR + popcount summed over the words, cast
    to f32 (exact: Hamming <= d < 2^24) so the shared dedup/top-k merge and
    its smallest-id tie-break apply unchanged. Popcount over uint32 words is
    order-independent, so this natural-order sum matches the kernel's
    in-VMEM reduction bit-for-bit.
    """
    from ..core.utils import NEG_INF, dedup_topk
    from .quant import sketch_rows

    if out_ids is None:
        out_ids = row_ids
    safe = jnp.maximum(row_ids, 0)
    cand = sketches[safe]  # (B, C, w)
    q_sk = sketch_rows(queries)  # (B, w)
    x = jnp.bitwise_xor(cand, q_sk[:, None, :])
    ham = jnp.sum(
        jax.lax.population_count(x).astype(jnp.int32), axis=-1
    )  # (B, C)
    scores = jnp.where(out_ids < 0, NEG_INF, -ham.astype(jnp.float32))
    return dedup_topk(out_ids, scores, k)


def verify_topk_grouped_ref(
    embs: jnp.ndarray,
    row_scales: jnp.ndarray,
    queries: jnp.ndarray,
    sched_cids: jnp.ndarray,
    sched_qids: jnp.ndarray,
    step_slot_ids: jnp.ndarray,
    *,
    kp: int,
    code_dtype: str = "int8",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialized oracle for ``fused_verify_grouped`` (identical signature
    semantics; see that docstring for the schedule-array contract).

    Gathers each step's whole cluster ``(S, Lp, d)``, scores it against the
    step's query tile with exact int8×int8→int32 accumulation, folds the
    (query × row) scale product, masks non-candidates via ``step_slot_ids``,
    and dedup-top-k's each (step, slot) stream — the same math in
    materialized form, so ids AND scores match the kernel bit-for-bit.
    """
    from ..core.utils import NEG_INF, dedup_topk
    from .quant import quantize_rows, unpack_int4

    c = embs.shape[0]
    s_steps, block_q, lp = step_slot_ids.shape
    safe_c = jnp.clip(sched_cids, 0, c - 1)
    rows = embs[safe_c]  # (S, Lp, d_store)
    if code_dtype == "int4":
        rows = unpack_int4(rows)
    q_codes, q_scales = quantize_rows(queries)
    safe_q = jnp.maximum(sched_qids, 0)
    qt = q_codes[safe_q]  # (S, block_q, d) — natural order; int32 dot exact
    qscl = jnp.where(sched_qids >= 0, q_scales[safe_q], 1.0).astype(jnp.float32)
    int_scores = jnp.einsum(
        "sqd,sld->sql", qt, rows, preferred_element_type=jnp.int32
    )
    comb = qscl[:, :, None] * row_scales[safe_c][:, None, :].astype(jnp.float32)
    scores = int_scores.astype(jnp.float32) * comb
    scores = jnp.where(step_slot_ids >= 0, scores, NEG_INF)
    ids, scores = dedup_topk(
        step_slot_ids.reshape(s_steps * block_q, lp),
        scores.reshape(s_steps * block_q, lp),
        kp,
    )
    return (
        ids.reshape(s_steps, block_q, kp),
        scores.reshape(s_steps, block_q, kp),
    )
