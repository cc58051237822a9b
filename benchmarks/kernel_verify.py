"""Verification-kernel benchmark: fused vs materialized, bytes + time, per
storage dtype.

Emits ``BENCH_verify.json`` so the perf trajectory of the LIDER hot path is
recorded per commit (CI runs ``--smoke``). Three measurements:

1. **HBM traffic model** (analytic, paper-default shapes B=32, P=20, H=10,
   R=400, d=768 unless overridden) — the byte model from DESIGN.md
   §Verification-kernel, evaluated for every storage dtype
   (f32 / bf16 / int8+rescore), split into:

   - ``emitted_bytes``: HBM write+read traffic the verification stage *emits*
     — intermediates (candidate tensor, score matrix, dedup/sort scratch,
     and on int8 the gathered scale array + provisional top-k') plus the
     final top-k. This is the traffic fusion eliminates.
   - ``total_bytes``: emitted + the compulsory traffic both paths share
     (candidate-row reads at the storage width — the term quantization
     shrinks — plus id/query reads and, on int8, the exact-rescore gather).

2. **Wall time + parity** (measured, smoke shapes) — fused kernel (interpret
   on CPU, compiled on TPU) vs the materialized reference at every storage
   dtype, with an exact top-k id equality check, plus the measured rescore
   overhead of the int8 second stage.

3. **Recall floor** (measured, smoke shapes) — recall@k of the quantized
   (int8 / packed int4) +rescore two-stage verification against exact f32
   over the same candidates, and the same for bf16. CI fails when any
   parity check is false, when int8+rescore recall drops below bf16
   recall − eps, or when int4+rescore drops below int8+rescore − eps.

4. **Cluster-major schedule** (measured, smoke shapes) — bit parity of the
   cluster-major multi-query loop order against the per-query one, plus the
   measured cluster-tile DMA-sharing ratio under Zipf-skewed probe traffic
   (CI gates ratio > 1.5 and the modeled int4 first-pass total ≥ 1.7x
   below int8 at the paper shape).

5. **Binary-sketch tier** (modeled + measured) — the traffic model re-run
   with the 1-bit Hamming pre-filter in front of the quantized pass
   (CI gates the modeled sketch+int4 total ≥ 3x below plain int4 at the
   paper shape), exact (ids, scores) parity of ``sketch_prefilter``
   against the natural-order oracle, and the sketch->int4->rescore
   recall floor vs plain int4+rescore (same eps).

Usage:
    PYTHONPATH=src python -m benchmarks.kernel_verify [--smoke]
        [--out BENCH_verify.json] [--b 32] [--p 20] [--h-arrays 10]
        [--r 400] [--d 768] [--k 100] [--rescore-factor 4]
        [--storage-dtypes float32 bfloat16 int8 int4] [--block-q 8]
        [--sketch-factor 4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

STORAGE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1, "int4": 0.5}
QUANTIZED_DTYPES = ("int8", "int4")
RECALL_EPS = 0.02  # int8+rescore may trail bf16 recall by at most this
# (and int4+rescore may trail int8+rescore by the same eps)
# Modeled int4 first-pass total traffic must be at least this far below int8
# at the paper shape (the sub-int8 floor's acceptance gate).
INT4_VS_INT8_TOTAL_MIN = 1.7
# Measured cluster-tile DMA-sharing ratio of the cluster-major schedule vs
# the per-query schedule under Zipf-skewed probe traffic.
SHARED_DMA_RATIO_MIN = 1.5
# Modeled sketch+int4 first-pass total traffic must be at least this far
# below plain int4 at the paper shape (the 1-bit tier's acceptance gate).
SKETCH_VS_INT4_TOTAL_MIN = 3.0
# int8+host device-resident embedding-store bytes must stay at or below
# this fraction of the f32 store (the tier dimension's CI gate; actual
# ratio at d=768 is (d+4)/(4d) ~ 0.25 — DESIGN.md §Tiered embedding store).
HOST_TIER_DEVICE_BYTES_MAX_VS_F32 = 0.45
MSMARCO_N = 8_847_360  # paper corpus (lider-msmarco arch config)


def storage_tier_model(
    n: int, d: int, storage_dtype: str, rescore_tier: str = "device"
) -> dict[str, float]:
    """Embedding-store bytes by tier for an ``n x d`` corpus.

    Codes at the storage width, plus (quantized dtypes only) the per-row
    f32 scales and the full-precision rescore table — device-resident on
    the "device" tier, host RAM on the "host" tier (DESIGN.md §Tiered
    embedding store). The learned-index arrays (sorted keys/positions, RMI
    fits) are tier-independent and excluded, matching the paper's
    index-memory convention.
    """
    s = STORAGE_BYTES[storage_dtype]
    device = float(n * d * s)
    host = 0.0
    if storage_dtype in QUANTIZED_DTYPES:
        device += n * 4  # per-row symmetric scales
        if rescore_tier == "device":
            device += n * d * 4
        else:
            host = float(n * d * 4)
    return {"device_bytes": device, "host_bytes": host}


def traffic_model(
    b: int,
    c: int,
    d: int,
    k: int,
    storage_dtype: str,
    rescore_factor: int = 4,
    sketch_factor: int | None = None,
) -> dict[str, dict[str, float]]:
    """HBM bytes per batch for both verification paths (DESIGN.md model).

    ``c`` is candidates per query (P*H*R). Id/score words are 4 B; top-k
    rows are 8 B (id + score). ``DEDUP_PASSES`` approximates the argsort +
    take_along_axis + top_k round-trips dedup_topk makes over the (B, C)
    id/score arrays. For quantized dtypes the model adds the per-candidate
    scale array (one gather read + one write + one kernel read), the
    provisional top-k' round-trip, and the exact-rescore gather of k'
    full-precision rows — k'/C (~1% at paper shape) of the first-pass row
    traffic. int4 halves only the candidate-row term (codes are packed two
    per byte; scales, ids, and the f32 rescore gather are width-independent),
    which is exactly why its total-traffic win over int8 lands below 2x.

    Queries are never stored, so the query read is width-INDEPENDENT of the
    storage dtype on the quantized paths: the kernel reads int8 query codes
    at both int8 and int4 table widths (only the table side unpacks
    nibbles) plus one f32 scale per query.

    ``sketch_factor`` (quantized dtypes only; DESIGN.md §Binary sketch
    tier) models the 1-bit pre-filter pass: the packed sketch rows stream
    at ceil(d/32) uint32 words per candidate, the survivor (row, score)
    set round-trips once, and every downstream per-candidate term — the
    code-row gather, the scale array, the score/dedup scratch — shrinks
    from C to ``m = min(sketch_factor*k', C)`` survivors.
    """
    DEDUP_PASSES = 10  # argsort r/w + 3x take_along_axis r/w + top_k read
    s = STORAGE_BYTES[storage_dtype]
    quantized = storage_dtype in QUANTIZED_DTYPES
    bc = b * c

    ids_read = bc * 4
    if quantized:
        # int8 query codes at both quantized widths + one f32 scale per row.
        query_read = b * (d + 4)
    else:
        query_read = b * d * s
    topk_write = b * k * 8

    # The candidate count the code pass actually touches: all C, or the
    # sketch pass's m survivors.
    m = c
    sketch_shared = 0.0
    sketch_emitted = 0.0
    if quantized and sketch_factor is not None:
        kp = min(rescore_factor * k, c)
        m = min(sketch_factor * kp, c)
        w_bytes = -(-d // 32) * 4  # packed words per row
        # 1-bit candidate rows + the query sketches (compulsory reads of
        # the pre-filter pass; it shares the bc id read issued above)
        sketch_shared += bc * w_bytes + b * w_bytes
        # survivor (row, negated-Hamming) round-trip between the passes
        sketch_emitted += 2 * b * m * 8

    bm = b * m
    bmd = b * m * d
    gather_read = bmd * s  # candidate code rows HBM->chip (both paths)

    quant_extra_emitted = 0.0
    quant_extra_shared = 0.0
    if quantized:
        kp = min(rescore_factor * k, c)
        # gathered (B, m) f32 combined-scale array: scale-table read + write
        # + kernel read
        quant_extra_emitted += 3 * bm * 4
        # provisional (B, k') top-k write + read between the passes
        quant_extra_emitted += 2 * b * kp * 8
        # exact-rescore gather: k' full-precision rows + their ids
        quant_extra_shared += b * kp * (d * 4 + 4)

    cand_write = bmd * s  # (B, m, d) materialization ...
    cand_read = bmd * s  # ... re-read by the einsum
    score_write = bm * 4  # (B, m) score matrix ...
    score_read = bm * 4  # ... re-read by dedup/top-k
    dedup_bytes = DEDUP_PASSES * bm * 4

    unfused_emitted = (
        cand_write + cand_read + score_write + score_read + dedup_bytes
        + topk_write + quant_extra_emitted + sketch_emitted
    )
    fused_emitted = topk_write + quant_extra_emitted + sketch_emitted
    shared = (
        gather_read + ids_read + query_read + quant_extra_shared
        + sketch_shared
    )
    return {
        "unfused": {
            "emitted_bytes": unfused_emitted,
            "total_bytes": unfused_emitted + shared,
        },
        "fused": {
            "emitted_bytes": fused_emitted,
            "total_bytes": fused_emitted + shared,
        },
    }


def _time(fn, iters=3):
    import jax

    out = jax.block_until_ready(fn())  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _measure(b, c, n, d, k, dtype_name, block_c, rescore_factor, iters=3):
    """Fused-vs-oracle wall + parity for one storage dtype (+ the quantized
    rescore stage's overhead, measured as its own fused pass)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import fused_verify, ref
    from repro.kernels.quant import quantize_rows, quantize_rows_int4

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    embs_f = jax.random.normal(k1, (n, d))
    ids = jax.random.randint(k2, (b, c), -1, n)
    q = jax.random.normal(k3, (b, d))

    scales = None
    code_dtype = "int8"
    if dtype_name == "int8":
        table, scales = quantize_rows(embs_f)
    elif dtype_name == "int4":
        table, scales = quantize_rows_int4(embs_f)
        code_dtype = "int4"
    else:
        table = embs_f.astype(jnp.dtype(dtype_name))

    def run_fused():
        return fused_verify(table, ids, q, k=k, scales=scales,
                            block_c=block_c, code_dtype=code_dtype)

    def run_unfused():
        return ref.verify_topk_ref(table, ids, q, k=k, scales=scales,
                                   code_dtype=code_dtype)

    out = {}
    ids_by_path = {}
    for name, fn in (("fused", run_fused), ("unfused", run_unfused)):
        out[f"wall_s_{name}"] = _time(fn, iters)
        ids_by_path[name] = np.asarray(fn()[0])
    out["ids_match"] = bool(
        (ids_by_path["fused"] == ids_by_path["unfused"]).all()
    )
    if dtype_name in QUANTIZED_DTYPES:
        # The exact second stage: rescore the provisional top-k' rows from
        # the full-precision table (k'/c the gather of the first pass). The
        # provisional set comes from a k'-deep first pass — the pipeline
        # lider._verify actually runs — not from truncating the
        # k-deep parity run above.
        kp = min(rescore_factor * k, c)

        def run_first_kp():
            return fused_verify(table, ids, q, k=kp, scales=scales,
                                block_c=block_c, code_dtype=code_dtype)

        prov = run_first_kp()[0]

        def run_rescore():
            return fused_verify(
                embs_f, jnp.maximum(prov, 0), q, k=k, out_ids=prov,
                block_c=block_c,
            )

        # Overhead relative to the k'-deep first pass the real pipeline
        # (lider._verify) runs — not the k-deep parity run above,
        # whose smaller top-k accumulator would inflate the fraction.
        wall_first = _time(run_first_kp, iters)
        wall = _time(run_rescore, iters)
        out["wall_s_fused_kp"] = wall_first
        out["wall_s_rescore"] = wall
        out["rescore_overhead_frac"] = wall / max(wall_first, 1e-12)
    out["shape"] = {"B": b, "C": c, "N": n, "d": d, "k": k, "dtype": dtype_name}
    return out


def _measure_host_tier(
    b, c, n, d, k, block_c, rescore_factor, iters=3, code_dtype="int8"
):
    """The tiered search's staged rescore vs the device-resident one: bit
    parity of (ids, scores) plus the measured host fetch (D2H of the
    provisional rows + the np.take) and staged-rescore walls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import verify_topk_op
    from repro.kernels.quant import quantize_rows, quantize_rows_int4

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    embs_f = jax.random.normal(k1, (n, d))
    ids = jax.random.randint(k2, (b, c), -1, n)
    q = jax.random.normal(k3, (b, d))
    if code_dtype == "int4":
        table, scales = quantize_rows_int4(embs_f)
    else:
        table, scales = quantize_rows(embs_f)
    host_table = np.ascontiguousarray(np.asarray(embs_f, np.float32))
    kp = min(rescore_factor * k, c)

    def first_pass():
        return verify_topk_op(table, ids, q, k=kp, scales=scales,
                              block_c=block_c, code_dtype=code_dtype)

    prov = first_pass()[0]

    def device_rescore():
        return verify_topk_op(
            embs_f, jnp.maximum(prov, 0), q, k=k, out_ids=prov,
            block_c=block_c,
        )

    def host_fetch():
        rows = np.asarray(prov)  # D2H of the provisional rows
        return host_table.take(np.maximum(rows, 0).reshape(-1), axis=0
                               ).reshape(b, kp, d)

    fetched = jnp.asarray(host_fetch())  # H2D of only B*k'*d floats
    row_ids = jnp.arange(b * kp, dtype=jnp.int32).reshape(b, kp)

    def host_rescore():
        return verify_topk_op(
            fetched.reshape(b * kp, d), row_ids, q, k=k, out_ids=prov,
            block_c=block_c,
        )

    di, ds = device_rescore()
    hi, hs = host_rescore()
    out = {
        "ids_match": bool((np.asarray(di) == np.asarray(hi)).all()),
        "scores_match": bool((np.asarray(ds) == np.asarray(hs)).all()),
        "wall_s_device_rescore": _time(device_rescore, iters),
        "wall_s_host_rescore": _time(host_rescore, iters),
        "host_fetch_us": _time(host_fetch, iters) * 1e6,
        "h2d_floats": b * kp * d,
        "shape": {"B": b, "C": c, "N": n, "d": d, "k": k, "kp": kp},
    }
    return out


def _measure_sketch(b, c, n, d, k, block_c, iters=3):
    """1-bit Hamming pre-filter kernel vs the natural-order oracle: exact
    (ids, scores) parity plus walls (DESIGN.md §Binary sketch tier)."""
    import jax
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.fused_verify import sketch_prefilter
    from repro.kernels.quant import sketch_rows

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    embs_f = jax.random.normal(k1, (n, d))
    ids = jax.random.randint(k2, (b, c), -1, n)
    q = jax.random.normal(k3, (b, d))
    table = sketch_rows(embs_f)

    def run_kernel():
        return sketch_prefilter(table, ids, q, k=k, block_c=block_c)

    def run_ref():
        return ref.sketch_topk_ref(table, ids, q, k=k)

    gi, gs = run_kernel()
    wi, ws = run_ref()
    return {
        "ids_match": bool((np.asarray(gi) == np.asarray(wi)).all()),
        "scores_match": bool((np.asarray(gs) == np.asarray(ws)).all()),
        "wall_s_kernel": _time(run_kernel, iters),
        "wall_s_ref": _time(run_ref, iters),
        "shape": {"B": b, "C": c, "N": n, "d": d, "k": k},
    }


def _measure_sketch_e2e(n, d, b, k, n_clusters):
    """Covering-sketch end-to-end parity: with ``sketch_factor`` large
    enough that every routed candidate survives the pre-filter, the full
    search must return (ids, scores) bit-identical to the unfiltered int4
    path (the tier's correctness contract, DESIGN.md §Binary sketch tier)."""
    import jax
    import numpy as np

    from repro.core import lider as lider_lib
    from repro.data import synthetic

    corpus = synthetic.retrieval_corpus(3, n, d)
    queries, _ = synthetic.retrieval_queries(4, corpus, b)
    cfg = lider_lib.LiderConfig(
        n_clusters=n_clusters, n_arrays=4, n_leaves=4, kmeans_iters=5,
        storage_dtype="int4",
    )
    params = lider_lib.build_lider(jax.random.PRNGKey(0), corpus, cfg)
    plain = lider_lib.search_lider(params, queries, k=k, n_probe=4)
    filt = lider_lib.search_lider(
        params, queries, k=k, n_probe=4, sketch_factor=10**6
    )
    return {
        "ids_match": bool(
            (np.asarray(plain.ids) == np.asarray(filt.ids)).all()
        ),
        "scores_match": bool(
            (np.asarray(plain.scores) == np.asarray(filt.scores)).all()
        ),
        "shape": {"N": n, "d": d, "B": b, "k": k, "clusters": n_clusters},
    }


def _recall_floor(n, d, b, k, rescore_factor):
    """Recall@k vs exact f32 of one-shot verification over the same
    candidate set, per storage dtype (the quality side of the sweep)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.utils import l2_normalize, recall_at_k
    from repro.kernels.ops import verify_topk_op
    from repro.kernels.quant import quantize_rows, quantize_rows_int4

    k1, k2 = jax.random.split(jax.random.PRNGKey(1), 2)
    x = l2_normalize(jax.random.normal(k1, (n, d)))
    q = l2_normalize(x[:b] + 0.05 * jax.random.normal(k2, (b, d)))
    cand = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
    gt_ids, _ = verify_topk_op(x, cand, q, k=k, use_pallas=False)

    out = {}
    for dtype_name in ("bfloat16", "int8", "int4"):
        if dtype_name in QUANTIZED_DTYPES:
            if dtype_name == "int4":
                codes, scales = quantize_rows_int4(x)
            else:
                codes, scales = quantize_rows(x)
            kp = min(rescore_factor * k, n)
            prov, _ = verify_topk_op(
                codes, cand, q, k=kp, scales=scales, use_pallas=False,
                code_dtype=dtype_name,
            )
            ids, _ = verify_topk_op(
                x, jnp.maximum(prov, 0), q, k=k, out_ids=prov, use_pallas=False
            )
        else:
            ids, _ = verify_topk_op(
                x.astype(jnp.bfloat16), cand, q, k=k, use_pallas=False
            )
        out[dtype_name] = float(np.asarray(recall_at_k(ids, gt_ids)))
    return out


def _recall_floor_sketch(n, d, b, k, rescore_factor, sketch_factor):
    """sketch->int4->rescore recall@k vs plain int4->rescore, same data.

    The corpus plants ``n // b`` genuinely similar rows (cos ~0.8) around
    each query — the neighbor regime dense-retrieval corpora put the true
    top-k in. A pure random-Gaussian corpus would put the "true" top-k at
    cos ~ sqrt(2 ln n / d), which no 1-bit sign sketch can separate from
    the bulk — the failure mode DESIGN.md §Binary sketch tier documents
    under "when the pre-filter loses", not a serving regression — so the
    quality gate is measured where the tier is actually operable.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.utils import l2_normalize, recall_at_k
    from repro.kernels.ops import sketch_topk_op, verify_topk_op
    from repro.kernels.quant import quantize_rows_int4, sketch_rows

    g = n // b
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    base = l2_normalize(jax.random.normal(k1, (b, d)))
    sigma = 0.45 / d**0.5  # noise VECTOR norm ~0.45 vs the unit base
    x = l2_normalize(
        jnp.repeat(base, g, axis=0) + sigma * jax.random.normal(k2, (n, d))
    )
    q = l2_normalize(base + sigma * jax.random.normal(k3, (b, d)))
    cand = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None], (b, n))
    gt_ids, _ = verify_topk_op(x, cand, q, k=k, use_pallas=False)

    codes, scales = quantize_rows_int4(x)
    kp = min(rescore_factor * k, n)

    def two_stage(first_rows):
        prov, _ = verify_topk_op(
            codes, jnp.maximum(first_rows, 0), q, k=kp, out_ids=first_rows,
            scales=scales, use_pallas=False, code_dtype="int4",
        )
        ids, _ = verify_topk_op(
            x, jnp.maximum(prov, 0), q, k=k, out_ids=prov, use_pallas=False
        )
        return float(np.asarray(recall_at_k(ids, gt_ids)))

    m = min(sketch_factor * kp, n)
    surv, _ = sketch_topk_op(sketch_rows(x), cand, q, k=m, use_pallas=False)
    return {
        "int4": two_stage(cand),
        "sketch_int4": two_stage(surv),
        "shape": {"N": n, "d": d, "B": b, "k": k, "group": g,
                  "sketch_factor": sketch_factor},
    }


def _measure_shared_dma(
    b, n_clusters, lp, d, k, n_probe, block_q, zipf_a=1.3, iters=3
):
    """Cluster-major vs per-query schedule under Zipf-skewed probe traffic.

    Routed probe lists are sampled from a Zipf(``zipf_a``) cluster
    popularity (production query traffic concentrates on hot clusters —
    the regime the cluster-major schedule exists for), then BOTH loop
    orders are built from the same lists and run through the same grouped
    kernel: ``block_q=1`` *is* the per-query loop order (one cluster-tile
    stream per (query, probe) pair), so the measured cluster-tile rows of
    the two schedules are directly comparable and the shared-DMA ratio is
    ``pair streams / step streams``. Every pair's per-cluster top-k'
    scatters back through its (step, slot) coordinates and merges per
    query — the final (ids, scores) of the two schedules must match
    bit-for-bit (the ISSUE's schedule-parity acceptance gate, measured
    here on top of the unit tests).
    """
    import jax
    import numpy as np

    from repro.core.utils import dedup_topk
    from repro.kernels.ops import verify_topk_grouped_op
    from repro.kernels.quant import quantize_rows
    from repro.kernels.schedule import build_cluster_schedule

    rng = np.random.default_rng(0)
    weights = 1.0 / np.arange(1, n_clusters + 1) ** zipf_a
    weights /= weights.sum()
    cids = np.stack(
        [
            rng.choice(n_clusters, size=n_probe, replace=False, p=weights)
            for _ in range(b)
        ]
    ).astype(np.int32)

    embs_f = jax.random.normal(jax.random.PRNGKey(2), (n_clusters, lp, d))
    q = jax.random.normal(jax.random.PRNGKey(3), (b, d))
    table, scales = quantize_rows(embs_f)  # codes (c,lp,d), scales (c,lp)
    kp = min(4 * k, lp)

    def dense_step_slot_ids(sched):
        # Every scheduled pair's candidate set = its cluster's full Lp rows
        # (flat ids cid*lp + local), the densest sharing case.
        out = np.full((sched.n_padded_steps, sched.block_q, lp), -1, np.int64)
        qs, ps = np.nonzero(sched.pair_step >= 0)
        st, sl = sched.pair_step[qs, ps], sched.pair_slot[qs, ps]
        out[st, sl, :] = (
            cids[qs, ps][:, None].astype(np.int64) * lp + np.arange(lp)[None]
        )
        return out.astype(np.int32)

    import jax.numpy as jnp

    def run(sched):
        ssi = dense_step_slot_ids(sched)
        ids_g, sc_g = verify_topk_grouped_op(
            table,
            scales,
            q,
            jnp.asarray(sched.sched_cids),
            jnp.asarray(sched.sched_qids),
            jnp.asarray(ssi),
            kp=kp,
            block_q=sched.block_q,
        )
        # Scatter-back + per-query merge, same semantics as the search path.
        safe_st = jnp.maximum(jnp.asarray(sched.pair_step), 0)
        safe_sl = jnp.maximum(jnp.asarray(sched.pair_slot), 0)
        pids = ids_g[safe_st, safe_sl]
        psc = sc_g[safe_st, safe_sl]
        dead = (jnp.asarray(sched.pair_step) < 0)[..., None]
        pids = jnp.where(dead, -1, pids)
        psc = jnp.where(dead, -jnp.inf, psc)
        return dedup_topk(pids.reshape(b, -1), psc.reshape(b, -1), k)

    sched_g = build_cluster_schedule(cids, block_q=block_q)
    sched_1 = build_cluster_schedule(cids, block_q=1)
    gi, gs = run(sched_g)
    pi, ps_ = run(sched_1)
    out = {
        "ids_match": bool((np.asarray(gi) == np.asarray(pi)).all()),
        "scores_match": bool((np.asarray(gs) == np.asarray(ps_)).all()),
        # Cluster-tile rows each schedule streams for the same routed batch.
        "rows_per_query_schedule": sched_1.n_steps * lp,
        "rows_cluster_major": sched_g.n_steps * lp,
        "shared_dma_ratio": sched_1.n_steps / max(sched_g.n_steps, 1),
        "wall_s_cluster_major": _time(lambda: run(sched_g), iters),
        "wall_s_per_query": _time(lambda: run(sched_1), iters),
        "n_pairs": sched_g.n_pairs,
        "n_steps": sched_g.n_steps,
        "shape": {
            "B": b, "clusters": n_clusters, "Lp": lp, "d": d, "k": k,
            "n_probe": n_probe, "block_q": block_q, "zipf_a": zipf_a,
        },
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small measured shapes (CI); model stays at paper defaults")
    ap.add_argument("--out", default="BENCH_verify.json")
    ap.add_argument("--b", type=int, default=32)
    ap.add_argument("--p", type=int, default=20)
    ap.add_argument("--h-arrays", type=int, default=10)
    ap.add_argument("--r", type=int, default=400)
    ap.add_argument("--d", type=int, default=768)
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--rescore-factor", type=int, default=4)
    ap.add_argument(
        "--corpus-n", type=int, default=MSMARCO_N,
        help="corpus rows for the storage-tier byte model (default: the "
        "paper's MS-MARCO scale)",
    )
    ap.add_argument("--dtypes", "--storage-dtypes", nargs="+",
                    default=["float32", "bfloat16", "int8", "int4"],
                    choices=list(STORAGE_BYTES))
    ap.add_argument("--block-q", type=int, default=8,
                    help="query-tile width of the measured cluster-major "
                    "schedule (DESIGN.md §Cluster-major schedule)")
    ap.add_argument("--zipf-a", type=float, default=1.3,
                    help="Zipf exponent of the probe-popularity skew the "
                    "shared-DMA measurement samples")
    ap.add_argument("--sketch-factor", type=int, default=4,
                    help="survivor multiple m = sketch_factor*k' of the "
                    "1-bit pre-filter pass (DESIGN.md §Binary sketch tier)")
    args = ap.parse_args()

    c = args.p * args.h_arrays * args.r
    model = {
        sd: traffic_model(args.b, c, args.d, args.k, sd, args.rescore_factor)
        for sd in args.dtypes
    }
    # Same model with the 1-bit pre-filter in front (quantized dtypes only).
    model_sketch = {
        sd: traffic_model(args.b, c, args.d, args.k, sd, args.rescore_factor,
                          sketch_factor=args.sketch_factor)
        for sd in args.dtypes
        if sd in QUANTIZED_DTYPES
    }
    # Storage-tier dimension (DESIGN.md §Tiered embedding store): where the
    # embedding-store bytes live per (dtype, tier) config at paper scale.
    tier_configs = [(sd, "device") for sd in args.dtypes]
    for sd in QUANTIZED_DTYPES:
        if sd in args.dtypes:
            tier_configs.append((sd, "host"))
    storage_tiers = {
        f"{sd}_{tier}": storage_tier_model(args.corpus_n, args.d, sd, tier)
        for sd, tier in tier_configs
    }
    f32_model = traffic_model(args.b, c, args.d, args.k, "float32",
                              args.rescore_factor)
    ratios = {
        sd: {
            "emitted_vs_unfused": m["unfused"]["emitted_bytes"]
            / m["fused"]["emitted_bytes"],
            "total_vs_unfused": m["unfused"]["total_bytes"]
            / m["fused"]["total_bytes"],
            "fused_total_vs_f32_fused": f32_model["fused"]["total_bytes"]
            / m["fused"]["total_bytes"],
        }
        for sd, m in model.items()
    }

    import jax

    full_measure = not args.smoke and jax.default_backend() == "tpu"
    if not args.smoke and not full_measure:
        print(
            "warning: paper-shape measurement needs a TPU (interpret-mode "
            "Pallas at B=32, C=80000 would take hours on CPU); measuring at "
            "smoke shapes instead — the traffic model above is unaffected",
            file=sys.stderr,
        )
    measured = {}
    for sd in args.dtypes:
        if full_measure:
            measured[sd] = _measure(
                b=args.b, c=c, n=200_000, d=args.d, k=args.k, dtype_name=sd,
                block_c=256, rescore_factor=args.rescore_factor,
            )
        else:
            measured[sd] = _measure(
                b=4, c=608, n=4096, d=64, k=10, dtype_name=sd, block_c=128,
                rescore_factor=args.rescore_factor,
            )
    for sd in QUANTIZED_DTYPES:
        if sd not in args.dtypes:
            continue
        if full_measure:
            measured[f"{sd}_host"] = _measure_host_tier(
                b=args.b, c=c, n=200_000, d=args.d, k=args.k, block_c=256,
                rescore_factor=args.rescore_factor, code_dtype=sd,
            )
        else:
            measured[f"{sd}_host"] = _measure_host_tier(
                b=4, c=608, n=4096, d=64, k=10, block_c=128,
                rescore_factor=args.rescore_factor, code_dtype=sd,
            )
    if full_measure:
        measured["sketch"] = _measure_sketch(
            b=args.b, c=c, n=200_000, d=args.d, k=args.k, block_c=256
        )
    else:
        measured["sketch"] = _measure_sketch(
            b=4, c=608, n=4096, d=64, k=10, block_c=128
        )
    measured["sketch_e2e"] = _measure_sketch_e2e(
        n=4096, d=64, b=16, k=10, n_clusters=16
    )
    recall = _recall_floor(
        n=4096, d=64, b=32, k=10, rescore_factor=args.rescore_factor
    )
    recall_sketch = _recall_floor_sketch(
        n=4096, d=64, b=32, k=10, rescore_factor=args.rescore_factor,
        sketch_factor=args.sketch_factor,
    )
    # Cluster-major schedule: parity + shared-DMA ratio under Zipf probes
    # (shape-independent of the dtype sweep; int8 codes, small bank).
    shared = _measure_shared_dma(
        b=32, n_clusters=64, lp=128, d=64, k=10, n_probe=8,
        block_q=args.block_q, zipf_a=args.zipf_a,
    )

    checks = {
        f"parity_{sd}": measured[sd]["ids_match"] for sd in args.dtypes
    }
    for sd in QUANTIZED_DTYPES:
        if sd in args.dtypes:
            checks[f"parity_{sd}_host_vs_device_rescore"] = (
                measured[f"{sd}_host"]["ids_match"]
                and measured[f"{sd}_host"]["scores_match"]
            )
    if "int8" in args.dtypes and "float32" in args.dtypes:
        checks["int8_host_device_bytes_le_045x_f32"] = (
            storage_tiers["int8_host"]["device_bytes"]
            <= HOST_TIER_DEVICE_BYTES_MAX_VS_F32
            * storage_tiers["float32_device"]["device_bytes"]
        )
    if "int8" in args.dtypes and "bfloat16" in args.dtypes:
        checks["int8_rescore_recall_floor"] = (
            recall["int8"] >= recall["bfloat16"] - RECALL_EPS
        )
    if "int8" in args.dtypes:
        checks["int8_total_traffic_at_least_2x_below_f32"] = (
            ratios["int8"]["fused_total_vs_f32_fused"] >= 2.0
        )
    if "int4" in args.dtypes:
        # int4's quality floor is gated against int8 (both run the exact
        # f32 rescore; only the first pass got narrower).
        checks["int4_rescore_recall_floor_vs_int8"] = (
            recall["int4"] >= recall["int8"] - RECALL_EPS
        )
        if "int8" in args.dtypes:
            checks["int4_total_traffic_at_least_1p7x_below_int8"] = (
                model["int8"]["fused"]["total_bytes"]
                >= INT4_VS_INT8_TOTAL_MIN
                * model["int4"]["fused"]["total_bytes"]
            )
    checks["cluster_major_schedule_parity"] = (
        shared["ids_match"] and shared["scores_match"]
    )
    checks["shared_dma_ratio_above_1p5_zipf"] = (
        shared["shared_dma_ratio"] > SHARED_DMA_RATIO_MIN
    )
    checks["parity_sketch"] = (
        measured["sketch"]["ids_match"] and measured["sketch"]["scores_match"]
    )
    checks["sketch_covering_end_to_end_parity"] = (
        measured["sketch_e2e"]["ids_match"]
        and measured["sketch_e2e"]["scores_match"]
    )
    if "int4" in args.dtypes:
        checks["sketch_int4_recall_floor_vs_int4"] = (
            recall_sketch["sketch_int4"] >= recall_sketch["int4"] - RECALL_EPS
        )
        checks["sketch_int4_total_traffic_at_least_3x_below_int4"] = (
            model["int4"]["fused"]["total_bytes"]
            >= SKETCH_VS_INT4_TOTAL_MIN
            * model_sketch["int4"]["fused"]["total_bytes"]
        )

    report = {
        "paper_shape": {
            "B": args.b, "P": args.p, "H": args.h_arrays, "R": args.r,
            "C": c, "d": args.d, "k": args.k,
            "rescore_factor": args.rescore_factor,
        },
        "traffic_model": model,
        "traffic_ratios": ratios,
        "storage_tiers": {
            "corpus_n": args.corpus_n,
            "d": args.d,
            "max_host_device_ratio_vs_f32": HOST_TIER_DEVICE_BYTES_MAX_VS_F32,
            "configs": storage_tiers,
        },
        "measured": measured,
        "recall_vs_exact": recall,
        "recall_eps": RECALL_EPS,
        "cluster_major": {
            **shared,
            "min_shared_dma_ratio": SHARED_DMA_RATIO_MIN,
        },
        "int4_vs_int8_total_ratio": (
            model["int8"]["fused"]["total_bytes"]
            / model["int4"]["fused"]["total_bytes"]
            if "int8" in model and "int4" in model
            else None
        ),
        "sketch": {
            "sketch_factor": args.sketch_factor,
            "traffic_model": model_sketch,
            "recall_planted_neighbors": recall_sketch,
            "min_total_ratio_vs_int4": SKETCH_VS_INT4_TOTAL_MIN,
            "sketch_int4_vs_int4_total_ratio": (
                model["int4"]["fused"]["total_bytes"]
                / model_sketch["int4"]["fused"]["total_bytes"]
                if "int4" in model_sketch
                else None
            ),
        },
        "checks": checks,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)

    for sd in args.dtypes:
        m, r = model[sd], ratios[sd]
        extra = ""
        if sd in QUANTIZED_DTYPES:
            extra = (
                f" rescore_overhead={measured[sd]['rescore_overhead_frac']:.1%}"
                f" recall={recall[sd]:.4f}"
            )
        print(
            f"[verify] {sd:>8}: fused total {m['fused']['total_bytes']/2**30:7.2f} GiB "
            f"({r['fused_total_vs_f32_fused']:.2f}x below f32), emits "
            f"{m['fused']['emitted_bytes']/2**20:8.2f} MiB "
            f"({r['emitted_vs_unfused']:,.0f}x less than unfused); "
            f"measured fused {measured[sd]['wall_s_fused']*1e3:.2f} ms, "
            f"ids_match={measured[sd]['ids_match']}{extra}"
        )
    f32_dev = storage_tiers.get("float32_device", {}).get("device_bytes")
    for name, tb in storage_tiers.items():
        ratio = (
            f" ({tb['device_bytes'] / f32_dev:.2f}x of f32 device)"
            if f32_dev
            else ""
        )
        print(
            f"[verify] store {name:>15}: device {tb['device_bytes']/2**30:6.2f} GiB"
            f", host {tb['host_bytes']/2**30:6.2f} GiB{ratio}"
        )
    for sd in QUANTIZED_DTYPES:
        if f"{sd}_host" not in measured:
            continue
        mh = measured[f"{sd}_host"]
        print(
            f"[verify] {sd}_host staged rescore: ids_match={mh['ids_match']} "
            f"scores_match={mh['scores_match']} "
            f"fetch={mh['host_fetch_us']:.0f}us "
            f"rescore={mh['wall_s_host_rescore']*1e3:.2f}ms "
            f"(device-resident rescore {mh['wall_s_device_rescore']*1e3:.2f}ms)"
        )
    if "int4" in model_sketch:
        ms = measured["sketch"]
        print(
            f"[verify] sketch+int4 (factor={args.sketch_factor}): fused total "
            f"{model_sketch['int4']['fused']['total_bytes']/2**30:7.2f} GiB "
            f"({model['int4']['fused']['total_bytes'] / model_sketch['int4']['fused']['total_bytes']:.2f}x"
            f" below plain int4), recall={recall_sketch['sketch_int4']:.4f} "
            f"(int4 {recall_sketch['int4']:.4f}, planted neighbors); kernel "
            f"{ms['wall_s_kernel']*1e3:.2f} ms, ids_match={ms['ids_match']} "
            f"scores_match={ms['scores_match']}, covering e2e "
            f"ids_match={measured['sketch_e2e']['ids_match']} "
            f"scores_match={measured['sketch_e2e']['scores_match']}"
        )
    print(
        f"[verify] cluster-major (zipf a={shared['shape']['zipf_a']}, "
        f"block_q={shared['shape']['block_q']}): "
        f"shared-DMA ratio {shared['shared_dma_ratio']:.2f}x "
        f"({shared['n_pairs']} pair streams -> {shared['n_steps']} step "
        f"streams), ids_match={shared['ids_match']} "
        f"scores_match={shared['scores_match']}"
    )
    print(f"[verify] checks: {checks} -> {args.out}")
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SystemExit(f"verification regression, failed checks: {failed}")


if __name__ == "__main__":
    main()
