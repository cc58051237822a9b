#!/usr/bin/env python3
"""Smoke test: the LIDER serving path, compiled, on one TPU chip.

Drives ``build_lider -> make_backend("lider", ...) -> RetrievalEngine`` (the
path ``launch/serve.py`` drives) at the paper's width, d=768, over a seeded
synthetic corpus of 2**20 passages with the ``configs/lider_msmarco.py``
LIDER settings (c=1024, c0=20, H=10), and checks what comes out:

(a) device: the first JAX device is a TPU;
(b) build: an int4 bank with 1-bit sketches and a host-tier rescore table,
    and an int8 bank with a device-tier rescore table;
(c) serve: 512 queries in batches of 32 through the engine, the int4 bank
    both per query and cluster-major (block_q=8, the grouped kernel);
(d) check: recall@10 against exact float64 search on the host; each Pallas
    kernel against its ``kernels/ref.py`` oracle on one real batch; a
    ``tpu_custom_call`` for every kernel each served jit should hold; no
    degraded, shed or retried answer and no query-path retrace.

Timings are one warm run, not a benchmark. The last line of standard output
is ``{"ok": true, "device": {...}}``; a failed phase exits non-zero without
it. Everything is generated from ``--seed``; nothing is read from disk.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # only the sharded-search phase
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--chips 4]
        # CPU rehearsal at a scaled-down size (Pallas kernels interpreted);
        # prints the recall that sets RECALL_FLOOR and exits 3: no chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
DIM = 768  # the paper's embedding width (MS MARCO, 768-d encoders)
K = 10
BATCH = 32
N_QUERIES = 512
BLOCK_Q = 8
SKETCH_FACTOR = 4
# Recall@10 floor against exact search: this script's CPU rehearsal
# (--rehearse, seed 0: 2**17 passages, c=128, c0=2 — the chip run's 1024
# passages per cluster, 4 generator modes per cluster and ~2% of clusters
# probed, scaled down) measured 0.2635 (int4, both schedules) and 0.2826
# (int8), less 0.05 for the gap between that size and the chip's.
RECALL_FLOOR = {"int4_host": 0.2135, "int4_host_bq8": 0.2135,
                "int8_device": 0.2326}
# Expected Pallas kernels in each served jit (by pallas_call name).
ROUTE = {"lsh_hash", "fused_verify_float"}


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# (d) helpers: exact reference, kernel inventory, oracle comparisons
# ---------------------------------------------------------------------------


def exact_topk(corpus: np.ndarray, queries: np.ndarray, k: int) -> np.ndarray:
    """Exact inner-product top-k ids in float64 on the host, chunked."""
    q = queries.astype(np.float64)
    best_s = np.full((len(q), 0), -np.inf)
    best_i = np.zeros((len(q), 0), np.int64)
    for start in range(0, len(corpus), 1 << 17):
        s = corpus[start:start + (1 << 17)].astype(np.float64) @ q.T
        part = np.argpartition(-s, k - 1, axis=0)[:k].T  # (Q, k)
        cand_s = np.concatenate(
            [best_s, np.take_along_axis(s.T, part, axis=1)], axis=1
        )
        cand_i = np.concatenate([best_i, part + start], axis=1)
        top = np.argsort(-cand_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(cand_s, top, axis=1)
        best_i = np.take_along_axis(cand_i, top, axis=1)
    return best_i


def recall(ids: np.ndarray, truth: np.ndarray) -> float:
    return float(
        np.mean([len(set(a) & set(b)) / len(b) for a, b in zip(ids, truth)])
    )


def pallas_kernels(compiled_text: str) -> set[str]:
    """Names of the Pallas kernels compiled into an executable: each is a
    ``tpu_custom_call`` whose op_name ends in ``<name>/pallas_call``."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)
            names.add(m.group(1) if m else "?")
    return names


def assert_same(name, got, want) -> None:
    """Integer-path kernels: ids and scores bit-identical to the oracle."""
    gi, gs = (np.asarray(x) for x in got)
    wi, ws = (np.asarray(x) for x in want)
    check(np.array_equal(gi, wi), f"{name}: ids differ from the oracle at "
          f"{int((gi != wi).sum())} of {gi.size} slots")
    check(np.array_equal(gs, ws), f"{name}: scores differ from the oracle")
    log("check", f"{name}: ids and scores identical to kernels/ref.py "
        f"({gi.shape}, {int((gi >= 0).sum())} live)")


def assert_close_f32(name, got, want, d: int) -> None:
    """f32 kernels: both sides accumulate d products of unit-norm rows in
    f32 at HIGHEST precision, so each score is within d*2^-24 of exact and
    the two within 2*d*2^-24. A position may hold a different id only where
    the two scores are that close (a near-tie ordered the other way)."""
    tol = 2 * d * 2.0**-24
    gi, gs = (np.asarray(x) for x in got)
    wi, ws = (np.asarray(x) for x in want)
    live = np.isfinite(ws)
    check(np.array_equal(live, np.isfinite(gs)), f"{name}: live slots differ")
    err = np.abs(np.where(live, gs - ws, 0.0)).max()
    check(err <= tol, f"{name}: max |score - oracle| {err:.3g} > {tol:.3g}")
    swapped = (gi != wi) & live
    for r, j in zip(*np.nonzero(swapped)):
        # The swapped-in id sits beside an equal score in the oracle's row,
        # or at its cut-off (the oracle ranked it just past the last slot).
        at = np.nonzero(wi[r] == gi[r, j])[0]
        ref_score = ws[r, at[0]] if at.size else ws[r][live[r]][-1]
        check(abs(gs[r, j] - ref_score) <= 2 * tol,
              f"{name}: id {gi[r, j]} in row {r} differs beyond a near-tie")
    log("check", f"{name}: max |score - oracle| {err:.3g} <= {tol:.3g}; "
        f"{int(swapped.sum())} near-tie id swaps of {int(live.sum())}")


def assert_hash_close(name, got, want, x, proj, key_len: int) -> None:
    """LSH keys: a key bit may differ from the oracle only where its
    projection is within f32 accumulation error of zero (the sign test is
    the only non-integer step)."""
    gb = (np.asarray(got)[..., None] >> np.arange(key_len - 1, -1, -1)) & 1
    wb = (np.asarray(want)[..., None] >> np.arange(key_len - 1, -1, -1)) & 1
    x64, p64 = np.asarray(x, np.float64), np.asarray(proj, np.float64)
    proj_v = (x64 @ p64).reshape(gb.shape)
    bound = 2 * x.shape[1] * 2.0**-24 * (np.abs(x64) @ np.abs(p64))
    diff = gb != wb
    check(
        np.all(np.abs(proj_v[diff]) <= bound.reshape(gb.shape)[diff]),
        f"{name}: key bits differ away from a zero projection",
    )
    log("check", f"{name}: {int(diff.sum())} of {diff.size} key bits differ, "
        "all at |projection| within f32 error of 0")


# ---------------------------------------------------------------------------
# One chip: build, serve, check
# ---------------------------------------------------------------------------


def serve(lider, serving, params, cfg, queries, *, sketch_factor, block_q):
    """Serve ``queries`` through RetrievalEngine; returns (ids, timings)."""
    kw = dict(n_probe=cfg.n_probe, r0=cfg.r0, rescore_factor=cfg.rescore_factor)
    if sketch_factor:
        kw["sketch_factor"] = sketch_factor
    # Cluster-major serving goes through the engine's block_q ladder: its
    # schedules are padded to one fixed worst case, so no batch re-traces.
    engine = serving.RetrievalEngine(
        serving.make_backend("lider", None, updatable=True, **kw),
        batch_size=BATCH, k=K, dim=queries.shape[1], params=params,
        block_q_ladder=(block_q,) if block_q else None,
    )
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    traces = lider.query_path_cache_size()
    rids = [engine.submit(v) for v in queries]
    t0 = time.perf_counter()
    engine.drain()
    serve_s = time.perf_counter() - t0
    answers = [engine.result(r) for r in rids]
    st = engine.stats
    bad = [a for a in answers if not isinstance(a, serving.QueryResult)]
    check(not bad, f"{len(bad)} requests unanswered or shed: {bad[:3]}")
    check(not any(a.degraded for a in answers), "degraded answers served")
    check(st.n_shed == 0 and st.n_degraded == 0, "shed or degraded counts")
    check(st.n_fetch_retries == 0 and st.n_fetch_failures == 0,
          "host fetch retried or failed")
    retraces = lider.query_path_cache_size() - traces
    check(retraces == 0, f"{retraces} query-path retraces after warmup")
    ids = np.stack([np.asarray(a.ids) for a in answers])
    return ids, dict(warmup_s=warm_s, serve_s=serve_s,
                     qps=len(queries) / serve_s)


def candidates(jax, jnp, lider, params, cfg, q):
    """One real batch's routed probes and in-cluster candidates, as the
    served search generates them: ``(cids (B, P), flat rows (B, C), rows
    to report (B, C), -1 where dead)``."""
    routed = lider.route_queries(params, q, n_probe=cfg.n_probe,
                                 r0=cfg.r0_centroid)
    cand = jax.jit(lider._bank_candidates, static_argnames=("k", "r0", "refine"))
    flat, gids = cand(params.bank, q, routed.ids, k=K, r0=cfg.r0, refine=False)
    rows = flat.reshape(q.shape[0], -1)
    return routed.ids, rows, jnp.where(gids.reshape(rows.shape) >= 0, rows, -1)


def check_int4_kernels(jax, jnp, lider, ref, schedule, params, cfg, q,
                       rows_sample):
    """Sketch, int4 per-query, int4 grouped, LSH and f32 rescore kernels vs
    their oracles on one real batch of the int4 host-tier bank (and, for the
    LSH hash, on ``rows_sample`` corpus rows as the build hashes them)."""
    from repro.kernels.fused_verify import (
        fused_verify, fused_verify_grouped, sketch_prefilter)
    from repro.kernels.lsh_hash import lsh_hash

    bank = params.bank
    c, lp = bank.gids.shape
    cids, rows, out_rows = candidates(jax, jnp, lider, params, cfg, q)
    kp = cfg.rescore_factor * K
    m = SKETCH_FACTOR * kp

    sk = bank.sketches.reshape(c * lp, -1)
    want = jax.jit(ref.sketch_topk_ref, static_argnames="k")(
        sk, rows, q, k=m, out_ids=out_rows)
    assert_same("sketch_prefilter", sketch_prefilter(
        sk, rows, q, k=m, out_ids=out_rows), want)

    surv = want[0]
    codes = bank.embs.reshape(c * lp, -1)
    scales = bank.emb_scales.reshape(-1)
    args = (codes, jnp.maximum(surv, 0), q)
    kw = dict(k=kp, out_ids=surv, scales=scales, code_dtype="int4")
    prov = jax.jit(ref.verify_topk_ref, static_argnames=("k", "code_dtype"))(
        *args, **kw)
    assert_same("fused_verify int4", fused_verify(*args, **kw), prov)

    sched = schedule.build_cluster_schedule(np.asarray(cids), block_q=BLOCK_Q)
    sc = jnp.asarray(sched.sched_cids)
    sq = jnp.asarray(sched.sched_qids)
    live = (bank.gids[jnp.maximum(sc, 0)] >= 0)[:, None, :] & (sq >= 0)[..., None]
    slot_ids = jnp.where(
        live, jnp.maximum(sc, 0)[:, None, None] * lp + jnp.arange(lp), -1)
    gargs = (bank.embs, bank.emb_scales, q, sc, sq, slot_ids)
    gkw = dict(kp=kp, code_dtype="int4")
    assert_same(
        "fused_verify_grouped int4",
        fused_verify_grouped(*gargs, block_q=BLOCK_Q, **gkw),
        jax.jit(ref.verify_topk_grouped_ref,
                static_argnames=("kp", "code_dtype"))(*gargs, **gkw),
    )

    lsh = bank.lsh
    hk = dict(n_arrays=lsh.n_arrays, key_len=lsh.key_len)
    for name, x in (("lsh_hash (queries)", q),
                    (f"lsh_hash ({rows_sample.shape[0]} corpus rows)",
                     rows_sample)):
        assert_hash_close(
            name, lsh_hash(x, lsh.projections, **hk),
            jax.jit(ref.lsh_hash_ref, static_argnames=("n_arrays", "key_len"))(
                x, lsh.projections, **hk),
            np.asarray(x), np.asarray(lsh.projections), lsh.key_len)

    fetched = jnp.asarray(lider.host_fetch(params, prov[0]))
    table = fetched.reshape(-1, fetched.shape[-1])
    rid = jnp.arange(table.shape[0], dtype=jnp.int32).reshape(prov[0].shape)
    fkw = dict(k=K, out_ids=prov[0])
    assert_close_f32(
        "fused_verify f32 (host-fetched rescore rows)",
        fused_verify(table, rid, q, **fkw),
        jax.jit(ref.verify_topk_ref, static_argnames="k")(table, rid, q, **fkw),
        DIM)
    return cids, sched, prov[0], fetched


def check_int8_kernels(jax, jnp, lider, ref, params, cfg, q):
    """int8 per-query and f32 device-tier rescore kernels vs their oracles
    on one real batch of the int8 device-tier bank."""
    from repro.kernels.fused_verify import fused_verify

    bank = params.bank
    c, lp = bank.gids.shape
    _, rows, out_rows = candidates(jax, jnp, lider, params, cfg, q)
    kp = cfg.rescore_factor * K
    args = (bank.embs.reshape(c * lp, -1), rows, q)
    kw = dict(k=kp, out_ids=out_rows, scales=bank.emb_scales.reshape(-1))
    prov = jax.jit(ref.verify_topk_ref, static_argnames="k")(*args, **kw)
    assert_same("fused_verify int8", fused_verify(*args, **kw), prov)
    table = bank.rescore_embs.reshape(c * lp, -1)
    rargs = (table, jnp.maximum(prov[0], 0), q)
    rkw = dict(k=K, out_ids=prov[0])
    assert_close_f32(
        "fused_verify f32 (device rescore table)",
        fused_verify(*rargs, **rkw),
        jax.jit(ref.verify_topk_ref, static_argnames="k")(*rargs, **rkw), DIM)


def check_served_jits(name: str, expected: dict) -> None:
    """Each served jit holds a compiled Pallas kernel for every kernel it
    should use (so none runs in interpret mode or through kernels/ref.py)."""
    for fn_name, (lowered, want) in expected.items():
        have = pallas_kernels(lowered.compile().as_text())
        check(want <= have, f"{name}: {fn_name} lacks kernels "
              f"{sorted(want - have)} (has {sorted(have)})")
        log("check", f"{name}: {fn_name} compiled with {sorted(have)}")


def run_one_chip(args, jax, on_tpu: bool) -> None:
    import jax.numpy as jnp

    from repro import serving
    from repro.configs.lider_msmarco import ARCH
    from repro.core import lider
    from repro.data import synthetic
    from repro.kernels import ref, schedule

    base = ARCH.config.lider
    n = 1 << 20
    if args.rehearse:
        # Scaled so per-cluster size, modes per cluster and the probed
        # fraction of clusters match the chip run (see RECALL_FLOOR).
        n = 1 << 17
        base = dataclasses.replace(base, n_clusters=128, n_probe=2)

    t0 = time.perf_counter()
    corpus = synthetic.retrieval_corpus(args.seed, n, DIM)
    queries, _ = synthetic.retrieval_queries(args.seed, corpus, N_QUERIES)
    corpus_host = np.asarray(corpus)
    q_host = np.asarray(queries)
    log("build", f"corpus {n} x {DIM} f32 and {N_QUERIES} queries generated "
        f"in {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    truth = exact_topk(corpus_host, q_host, K)
    log("build", f"exact float64 top-{K} on the host in "
        f"{time.perf_counter() - t0:.2f} s")
    q0 = queries[:BATCH]
    results = {}

    # ---- int4 codes + sketch + host-tier rescore ------------------------
    cfg4 = dataclasses.replace(base, storage_dtype="int4", rescore_tier="host")
    t0 = time.perf_counter()
    p4, st = lider.build_lider(jax.random.PRNGKey(args.seed + 1), corpus,
                               cfg4, return_stats=True)
    jax.block_until_ready(p4)
    log("build", f"int4_host: {time.perf_counter() - t0:.2f} s, Lp="
        f"{st.capacity}, bytes {p4.bank.nbytes_by_tier()} (one warm run, "
        "not a benchmark)")
    for label, bq in (("int4_host", None), ("int4_host_bq8", BLOCK_Q)):
        ids, t = serve(lider, serving, p4, cfg4, q_host,
                       sketch_factor=SKETCH_FACTOR, block_q=bq)
        results[label] = recall(ids, truth)
        log("serve", f"{label}: recall@{K} {results[label]:.4f}, warmup "
            f"{t['warmup_s']:.2f} s, {N_QUERIES} queries in "
            f"{t['serve_s']:.3f} s ({t['qps']:.1f} q/s; one warm run, not a "
            "benchmark)")
    cids, sched, prov, fetched = check_int4_kernels(
        jax, jnp, lider, ref, schedule, p4, cfg4, q0, corpus[:4096])
    if on_tpu:
        point = dict(n_probe=cfg4.n_probe, r0=cfg4.r0,
                     rescore_factor=cfg4.rescore_factor)
        check_served_jits("int4_host", {
            "host_first_pass": (lider.host_first_pass.lower(
                p4, q0, k=K, sketch_factor=SKETCH_FACTOR, **point),
                ROUTE | {"sketch_prefilter", "fused_verify_int4"}),
            "host_rescore": (lider.host_rescore.lower(
                p4.bank.gids, fetched, prov, q0, k=K), {"fused_verify_float"}),
            "_route_pruned": (lider._route_pruned.lower(
                p4, q0, n_probe=cfg4.n_probe), ROUTE),
            "_cluster_major_first_pass": (
                lider._cluster_major_first_pass.lower(
                    p4, q0, cids, *(jnp.asarray(a) for a in (
                        sched.sched_cids, sched.sched_qids, sched.pair_step,
                        sched.pair_slot)),
                    k=K, r0=cfg4.r0, rescore_factor=cfg4.rescore_factor,
                    block_q=BLOCK_Q, sketch_factor=SKETCH_FACTOR),
                {"lsh_hash", "sketch_prefilter", "fused_verify_grouped_int4"}),
        })
    del p4
    gc.collect()  # the int4 bank's device leaves go before the int8 build

    # ---- int8 codes + device-tier rescore --------------------------------
    cfg8 = dataclasses.replace(base, storage_dtype="int8", rescore_tier="device")
    n8 = n
    while True:
        t0 = time.perf_counter()
        try:
            p8 = lider.build_lider(jax.random.PRNGKey(args.seed + 2),
                                   corpus[:n8], cfg8)
            jax.block_until_ready(p8)
            break
        except jax.errors.JaxRuntimeError as e:
            # Only running out of device memory halves the corpus, twice
            # at most; anything else propagates.
            if "RESOURCE_EXHAUSTED" not in str(e) or n8 <= n // 4:
                raise
            log("build", f"int8_device does not fit one chip at N={n8}; "
                f"halving its corpus to {n8 // 2}")
            n8 //= 2
        gc.collect()  # the failed attempt's buffers go before the retry
    truth8 = truth if n8 == n else exact_topk(corpus_host[:n8], q_host, K)
    del corpus
    log("build", f"int8_device: N={n8}, {time.perf_counter() - t0:.2f} s, "
        f"bytes {p8.bank.nbytes_by_tier()} (one warm run, not a benchmark)")
    ids, t = serve(lider, serving, p8, cfg8, q_host, sketch_factor=None,
                   block_q=None)
    results["int8_device"] = recall(ids, truth8)
    log("serve", f"int8_device: recall@{K} {results['int8_device']:.4f}, "
        f"warmup {t['warmup_s']:.2f} s, {N_QUERIES} queries in "
        f"{t['serve_s']:.3f} s ({t['qps']:.1f} q/s; one warm run, not a "
        "benchmark)")
    check_int8_kernels(jax, jnp, lider, ref, p8, cfg8, q0)
    if on_tpu:
        check_served_jits("int8_device", {
            "_search_lider_device": (lider._search_lider_device.lower(
                p8, q0, k=K, n_probe=cfg8.n_probe, r0=cfg8.r0,
                rescore_factor=cfg8.rescore_factor),
                ROUTE | {"fused_verify_int8"}),
        })

    for label, r in results.items():
        floor = RECALL_FLOOR[label]
        log("check", f"{label}: recall@{K} {r:.4f} vs floor {floor:.4f}")
        if not args.rehearse:
            check(r >= floor, f"{label}: recall@{K} {r:.4f} below {floor}")


# ---------------------------------------------------------------------------
# Four chips: sharded search vs single device (--chips 4)
# ---------------------------------------------------------------------------


def run_four_chips(args, jax) -> None:
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.configs.lider_msmarco import ARCH
    from repro.core import distributed, lider
    from repro.data import synthetic

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]).reshape(4, 1), ("data", "model"))
    n, base = 1 << 18, ARCH.config.lider
    if args.rehearse:  # four virtual CPU devices: same 1024 rows per cluster
        n, base = 1 << 15, dataclasses.replace(base, n_clusters=32, n_probe=2)
    cfg = dataclasses.replace(base, storage_dtype="int4", rescore_tier="host")
    corpus = synthetic.retrieval_corpus(args.seed, n, DIM)
    queries, _ = synthetic.retrieval_queries(args.seed, corpus, 4 * BATCH)
    t0 = time.perf_counter()
    params = lider.build_lider(jax.random.PRNGKey(args.seed + 1), corpus, cfg)
    jax.block_until_ready(params)
    log("build", f"int4_host N={n}: {time.perf_counter() - t0:.2f} s")
    sp = distributed.shard_lider_params(mesh, params, ("data",))
    placed = {len(x.sharding.device_set) for x in jax.tree.leaves(sp.bank)
              if x.ndim and x.shape[0] == cfg.n_clusters}
    check(placed == {4}, f"cluster-sharded leaves on {placed} devices")
    log("check", "every cluster-sharded bank leaf spans 4 devices")
    kw = dict(k=K, n_probe=cfg.n_probe, r0=cfg.r0,
              rescore_factor=cfg.rescore_factor)
    single = lider.search_lider(params, queries, **kw)
    for label, bq in (("per_query", None), ("block_q=8", BLOCK_Q)):
        search = distributed.make_sharded_search(
            mesh, params, capacity_factor=4.0, block_q=bq, **kw)
        t0 = time.perf_counter()
        out, dropped = search(sp, queries)
        jax.block_until_ready(out.ids)
        secs = time.perf_counter() - t0
        check(int(dropped) == 0, f"{label}: {int(dropped)} pairs dropped")
        same = np.asarray(out.ids) == np.asarray(single.ids)
        log("check", f"sharded {label} vs single-device search_lider: "
            f"{int(same.sum())} of {same.size} ids identical; max |score "
            f"diff| {float(jnp.abs(out.scores - single.scores).max()):.3g}; "
            f"first call {secs:.2f} s")
        check(same.all(), f"sharded {label}: ids differ from single device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a scaled-down size; exits 3")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package (src/repro) is not beside this "
              "script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    import jax

    use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    log("device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print("chip_smoke: no TPU found (JAX sees "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    try:
        if args.chips == 4:
            run_four_chips(args, jax)
        else:
            run_one_chip(args, jax, on_tpu)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    if args.rehearse:
        print("chip_smoke: rehearsal finished; no chip, so no result line",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
