"""Chaos serving benchmark: mixed search/update traffic under injected faults.

Emits ``BENCH_chaos.json`` so the serving fault-tolerance layer (DESIGN.md
§Failure model) is exercised and its guarantees gated per commit (CI runs
``--smoke``). The scenario:

- build an int8 host-tier LIDER index (the tier with the most failure
  surface: host fetch, in-place lifecycle writes, D2H),
- serve batched queries while upserting corpus slices between batches,
- under a **seeded** ``faults.FaultPlan``: host-fetch errors (retry path), a
  retry-exhausting error burst (degraded compressed-only answers), a
  mid-update ``host_write`` fault (transactional rollback), and D2H delay —
  plus a separate checkpoint-integrity scenario (CRC-detected truncation with
  ``restore_latest`` fallback, torn ``save_index`` swap with ``load_index``
  auto-recovery).

Every non-degraded answer is checked **bit-identical** against a direct
``search_lider`` on the engine's served params at the batch's ladder rung —
any mismatch is a *wrong-generation* result (served stale/partially-updated
state) and fails the run. Recall under faults is gated against the
degradation ladder's modeled floor: the measured recall of the worst rung
the engine may serve (including the compressed-only last resort).

Gates (non-zero exit):
- ``wrong_generation == 0``
- availability >= 0.99 (answered, not shed, within deadline)
- recall-under-faults >= ladder floor - tolerance
- rollback bit-identity; checkpoint corruption detected + recovered

Usage:
    PYTHONPATH=src python -m benchmarks.chaos_serve [--smoke]
        [--out BENCH_chaos.json] [--n 20000] [--dim 64] [--k 10]
        [--fault-plan PLAN.json] [--deadline-s 2.0]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

RECALL_TOLERANCE = 0.02  # slack under the measured worst-rung floor


def _default_plan(faults):
    """Seeded schedule hitting every injection site the serve path owns.

    ``times`` index per-site *calls inside the engine's activation window*
    (warmup and reference searches run outside it), so the schedule replays
    identically run-to-run. host_fetch call indices count retries too:
    [2, 3] is one batch retried twice then succeeding; [8, 9, 10] exhausts
    fetch_retries=2 and degrades that batch to compressed-only.
    """
    return faults.FaultPlan(
        [
            faults.FaultSpec("host_fetch", mode="error", times=(2, 3)),
            faults.FaultSpec("host_fetch", mode="error", times=(8, 9, 10)),
            faults.FaultSpec(
                "host_fetch", mode="delay", delay_s=0.005, times=(11,)
            ),
            faults.FaultSpec("host_write", mode="error", times=(0,)),
            faults.FaultSpec("d2h", mode="delay", delay_s=0.002, times=(1,)),
        ],
        seed=7,
    )


def _point_kwargs(point):
    """Ladder-rung dict -> search_lider kwargs (drop report metadata)."""
    keys = (
        "n_probe", "r0", "prune_margin", "refine", "rescore_factor", "block_c"
    )
    return {k: point[k] for k in keys if k in point}


def _reference_ids(lider, params, q, k, base_kw, point):
    """Direct (unfaulted, serial-path) answer at one operating point."""
    eff = dict(base_kw)
    if point:
        eff.update(point)
    out = lider.search_lider(params, q, k=k, **_point_kwargs(eff))
    # TopK is a NamedTuple; with_stats searches return (TopK, pruned mask).
    return out.ids if hasattr(out, "ids") else out[0].ids


def _measure_floor(lider, np, params, queries, gt_ids, k, base_kw, ladder):
    """Measured recall of every servable mode; the min is the modeled floor.

    Modes: nominal, each ladder rung, and the compressed-only last resort at
    the cheapest rung (what a retry-exhausted batch is answered with)."""
    from repro.core.utils import recall_at_k

    per_mode = {}
    for name, point in [("nominal", None)] + [
        (f"rung{i + 1}", r) for i, r in enumerate(ladder)
    ]:
        ids = _reference_ids(lider, params, queries, k, base_kw, point)
        per_mode[name] = float(recall_at_k(ids[:, :k], gt_ids[:, :k]))
    worst = dict(base_kw)
    if ladder:
        worst.update(_point_kwargs(ladder[-1]))
    prov, _ = lider.first_pass(
        params, queries, k=k,
        point=lider.OperatingPoint(**_point_kwargs(worst)),
    )
    comp = lider.compressed_only_topk(params.bank.gids, prov, k=k)
    per_mode["compressed_only"] = float(
        recall_at_k(comp.ids[:, :k], gt_ids[:, :k])
    )
    return per_mode, min(per_mode.values())


def _run_workload(
    *, build, queries, slices, k, batch, base_kw, ladder, deadline_s, plan
):
    """Serve ``queries`` one batch per drain, upserting ``slices`` at evenly
    spaced points; verify every non-degraded batch bit-matches the direct
    search on the engine's current params. Returns (report, answered_ids)."""
    import jax.numpy as jnp
    import numpy as np

    from repro import faults
    from repro.core import lider
    from repro.core import update as update_lib
    from repro.serving import DegradePolicy, RetrievalEngine, make_backend

    params = build()
    policy = DegradePolicy(
        ladder=tuple(ladder), deadline_s=deadline_s, fetch_retries=2,
        fetch_backoff_s=0.001,
    )
    search = make_backend("lider", None, updatable=True, **base_kw)
    engine = RetrievalEngine(
        search, batch_size=batch, k=k, dim=queries.shape[1], params=params,
        policy=policy, fault_plan=plan,
    )
    engine.warmup()  # pre-compiles every rung: no re-trace on the hot path

    n_batches = (len(queries) + batch - 1) // batch
    update_at = {
        (i + 1) * n_batches // (len(slices) + 1) for i in range(len(slices))
    }
    slices = list(slices)
    wrong_generation = 0
    rollback_identical = True
    n_update_failures = 0
    answered = np.full((len(queries), k), -1, np.int64)
    degraded_rows = np.zeros(len(queries), bool)
    probe_q = jnp.asarray(queries[:batch])  # rollback bit-identity probe

    for b in range(n_batches):
        if b in update_at and slices:
            s = slices.pop(0)
            before = np.asarray(
                _reference_ids(lider, engine.params, probe_q, k, base_kw, None)
            )
            try:
                engine.apply_updates(lambda p: update_lib.upsert(p, s))
            except faults.InjectedFault:
                # Transaction rolled the host tier back; serving must be
                # bit-identical to the pre-update generation, and the retry
                # (fault schedule has moved on) must land cleanly.
                n_update_failures += 1
                after = np.asarray(
                    _reference_ids(
                        lider, engine.params, probe_q, k, base_kw, None
                    )
                )
                rollback_identical &= bool(np.array_equal(before, after))
                engine.apply_updates(lambda p: update_lib.upsert(p, s))
        lo, hi = b * batch, min((b + 1) * batch, len(queries))
        rids = [engine.submit(q) for q in queries[lo:hi]]
        engine.drain()
        results = [engine.result(r) for r in rids]
        got = np.stack([np.asarray(r.ids) for r in results])
        answered[lo:hi] = got
        if all(r.degraded for r in results):
            degraded_rows[lo:hi] = True
            continue  # compressed-only answers are exempt from the bit-check
        # Wrong-generation check: the engine's answer must bit-match the
        # direct serial search on the params it claims to have served, at
        # the rung it claims to have served them (one batch -> one rung).
        qpad = np.zeros((batch, queries.shape[1]), np.float32)
        qpad[: hi - lo] = queries[lo:hi]
        point = (
            ladder[min(results[0].rung, len(ladder)) - 1]
            if results[0].rung > 0 and ladder
            else None
        )
        ref = np.asarray(
            _reference_ids(
                lider, engine.params, jnp.asarray(qpad), k, base_kw, point
            )
        )[: hi - lo]
        wrong_generation += int((got != ref).any(axis=1).sum())

    s = engine.stats
    submitted = s.n_queries + s.n_shed
    availability = (
        (submitted - s.n_shed - s.n_deadline_misses) / max(submitted, 1)
    )
    report = {
        "availability": availability,
        "wrong_generation": wrong_generation,
        "rollback_bit_identical": rollback_identical,
        "n_update_failures_injected": n_update_failures,
        "n_degraded": s.n_degraded,
        "n_fetch_retries": s.n_fetch_retries,
        "n_fetch_failures": s.n_fetch_failures,
        "n_update_rollbacks": s.n_update_rollbacks,
        "n_shed": s.n_shed,
        "n_deadline_misses": s.n_deadline_misses,
        "n_rung_steps": s.n_rung_steps,
        "n_faults_fired": plan.n_fired if plan is not None else 0,
        "aqt_s": s.aqt,
        "generation": engine.generation,
    }
    return report, engine.params, answered, degraded_rows


def _checkpoint_scenario(tmp):
    """Checkpoint-integrity leg: CRC detection + both recovery paths."""
    import jax
    import numpy as np

    from repro import faults
    from repro.core import lider
    from repro.core.utils import l2_normalize
    from repro.training import checkpoint

    x = l2_normalize(jax.random.normal(jax.random.PRNGKey(3), (512, 16)))
    params = lider.build_lider(
        jax.random.PRNGKey(0), x, lider.LiderConfig(n_clusters=4, n_probe=2)
    )

    # (a) Step checkpoints: truncate one leaf mid-save; restore_latest must
    # name the corrupt leaf on direct restore and fall back to the newest
    # *verified* step.
    mgr_dir = os.path.join(tmp, "steps")
    mgr = checkpoint.CheckpointManager(mgr_dir, keep=4)
    state = {"w": np.arange(32, dtype=np.float32)}
    mgr.save(1, state)
    plan = faults.FaultPlan(
        [faults.FaultSpec("checkpoint_write", mode="truncate", times=(0,))]
    )
    with faults.activate(plan):
        mgr.save(2, {"w": state["w"] + 1})
    try:
        checkpoint.restore(mgr_dir, 2, {"w": np.zeros(32, np.float32)})
        detected, leaf = False, None
    except checkpoint.CheckpointCorruptError as e:
        detected, leaf = True, e.leaf
    step, rec = mgr.restore_latest({"w": np.zeros(32, np.float32)})
    fallback_ok = step == 1 and np.array_equal(rec["w"], state["w"])

    # (b) Index checkpoint: crash inside the index.old swap window (leaf
    # truncated + process dies before cleanup); load_index must auto-recover
    # the previous generation.
    idx_dir = os.path.join(tmp, "index")
    checkpoint.save_index(idx_dir, params)
    plan2 = faults.FaultPlan(
        [faults.FaultSpec("checkpoint_write", mode="torn_write", times=(0,))]
    )
    torn = False
    try:
        with faults.activate(plan2):
            checkpoint.save_index(idx_dir, params)
    except faults.InjectedFault:
        torn = True
    loaded = checkpoint.load_index(idx_dir)
    out_a = lider.search_lider(params, x[:8], k=5, n_probe=2)
    out_b = lider.search_lider(loaded, x[:8], k=5, n_probe=2)
    torn_recovered = torn and bool(
        np.array_equal(np.asarray(out_a.ids), np.asarray(out_b.ids))
    )
    return {
        "corrupt_detected": detected,
        "corrupt_leaf": leaf,
        "restore_fallback_ok": bool(fallback_ok),
        "torn_write_recovered": torn_recovered,
    }


def _bench(n, dim, k, n_clusters, queries, batch, deadline_s, plan_path,
           sweep_ladder):
    import jax
    import numpy as np

    from repro import faults
    from repro.core import clustering, lider
    from repro.core.baselines import flat_search
    from repro.core.utils import l2_normalize, recall_at_k

    rng = jax.random.PRNGKey(0)
    kc, kx, kn, kq = jax.random.split(rng, 4)
    centers = jax.random.normal(kc, (n_clusters, dim))
    assign = jax.random.randint(kx, (n,), 0, n_clusters)
    x = l2_normalize(centers[assign] + 0.3 * jax.random.normal(kn, (n, dim)))
    q = np.asarray(
        l2_normalize(x[:queries] + 0.05 * jax.random.normal(kq, (queries, dim)))
    )

    n_base = int(n * 0.9)  # 10% held out for the mid-traffic upserts
    base_x, new_x = x[:n_base], x[n_base:]
    cfg = lider.LiderConfig(
        n_clusters=n_clusters, n_probe=8, storage_dtype="int8",
        rescore_tier="host", rescore_factor=4,
    )
    base_kw = dict(n_probe=8, rescore_factor=4)
    build = lambda: lider.build_lider(jax.random.PRNGKey(2), base_x, cfg)

    # Degradation ladder: from a Pareto sweep (full mode) or hand-built
    # (smoke); either way each rung's recall floor is MEASURED below, so the
    # gate never trusts a stale model.
    if sweep_ladder:
        from repro.tuning import pareto as pareto_lib

        ref = build()
        gt0 = flat_search(base_x, jax.numpy.asarray(q[:128]), k=k)
        grid = pareto_lib.default_grid(
            n_probes=tuple(p for p in (2, 4, 8) if p <= n_clusters),
            margins=(0.1,), rescore_factors=(4,),
        )
        results = pareto_lib.sweep(
            ref, jax.numpy.asarray(q[:128]), gt0.ids, grid, k=k, repeats=2
        )
        ladder = pareto_lib.degradation_ladder(results, max_rungs=2)
    else:
        ladder = [
            {"n_probe": 4},
            {"n_probe": 2, "rescore_factor": 2},
        ]

    plan = (
        faults.FaultPlan.from_json(plan_path)
        if plan_path
        else _default_plan(faults)
    )

    # Fault-free reference pass: same workload, same ladder, no plan.
    n_slices = 2
    slices = np.array_split(np.asarray(jax.device_get(new_x)), n_slices)
    clean, clean_params, clean_ids, _ = _run_workload(
        build=build, queries=q, slices=slices, k=k, batch=batch,
        base_kw=base_kw, ladder=ladder, deadline_s=deadline_s, plan=None,
    )
    faulted, f_params, f_ids, f_degraded = _run_workload(
        build=build, queries=q, slices=slices, k=k, batch=batch,
        base_kw=base_kw, ladder=ladder, deadline_s=deadline_s, plan=plan,
    )

    # Recall vs the exact search over the FINAL corpus (everything upserted).
    gt = flat_search(x, jax.numpy.asarray(q), k=k)
    gt_ids = np.asarray(gt.ids)
    rec_clean = float(
        recall_at_k(jax.numpy.asarray(clean_ids), gt.ids[:, :k])
    )
    rec_fault = float(recall_at_k(jax.numpy.asarray(f_ids), gt.ids[:, :k]))
    per_mode, floor = _measure_floor(
        lider, np, f_params, jax.numpy.asarray(q), gt_ids, k, base_kw, ladder
    )

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = _checkpoint_scenario(tmp)

    report = {
        "shape": {
            "n": n, "dim": dim, "k": k, "n_clusters": n_clusters,
            "queries": queries, "batch": batch, "deadline_s": deadline_s,
            "ladder": ladder, "plan_seed": plan.seed,
            "n_plan_specs": len(plan.specs),
        },
        "fault_free": clean,
        "faulted": faulted,
        "recall_fault_free": rec_clean,
        "recall_under_faults": rec_fault,
        "recall_floor_by_mode": per_mode,
        "recall_floor": floor,
        "degraded_fraction": float(f_degraded.mean()),
        "checkpoint": ckpt,
    }

    failures = []
    if faulted["wrong_generation"]:
        failures.append(
            f"{faulted['wrong_generation']} wrong-generation results"
        )
    if clean["wrong_generation"]:
        failures.append(
            f"{clean['wrong_generation']} wrong-generation results (fault-free)"
        )
    if faulted["availability"] < 0.99:
        failures.append(f"availability {faulted['availability']:.4f} < 0.99")
    if rec_fault < floor - RECALL_TOLERANCE:
        failures.append(
            f"recall under faults {rec_fault:.4f} < ladder floor "
            f"{floor:.4f} - {RECALL_TOLERANCE}"
        )
    if not faulted["rollback_bit_identical"]:
        failures.append("post-rollback serving not bit-identical")
    if faulted["n_update_rollbacks"] < 1:
        failures.append("fault plan never exercised the update rollback")
    if faulted["n_fetch_retries"] < 1:
        failures.append("fault plan never exercised the fetch retry")
    if not all(
        ckpt[f] for f in
        ("corrupt_detected", "restore_fallback_ok", "torn_write_recovered")
    ):
        failures.append(f"checkpoint integrity scenario failed: {ckpt}")
    report["failures"] = failures
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small shapes (CI)")
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--n-clusters", type=int, default=32)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument(
        "--deadline-s", type=float, default=2.0,
        help="per-request deadline (generous: CPU CI must not miss on jit "
        "jitter — warmup pre-compiles every rung)",
    )
    ap.add_argument(
        "--fault-plan", default=None,
        help="FaultPlan JSON path/object (default: built-in seeded schedule)",
    )
    args = ap.parse_args()

    if args.smoke:
        report = _bench(
            n=4000, dim=32, k=10, n_clusters=16, queries=256,
            batch=args.batch_size, deadline_s=args.deadline_s,
            plan_path=args.fault_plan, sweep_ladder=False,
        )
    else:
        report = _bench(
            n=args.n, dim=args.dim, k=args.k, n_clusters=args.n_clusters,
            queries=args.queries, batch=args.batch_size,
            deadline_s=args.deadline_s, plan_path=args.fault_plan,
            sweep_ladder=True,
        )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)

    fl = report["faulted"]
    print(
        f"chaos serve @ n={report['shape']['n']} "
        f"({report['shape']['n_plan_specs']} fault specs, "
        f"seed={report['shape']['plan_seed']})\n"
        f"  availability {fl['availability']:.4f} | "
        f"wrong-generation {fl['wrong_generation']} | "
        f"rollbacks {fl['n_update_rollbacks']} | "
        f"retries {fl['n_fetch_retries']} | "
        f"degraded batches->queries {fl['n_degraded']} | "
        f"shed {fl['n_shed']}\n"
        f"  recall: fault-free {report['recall_fault_free']:.4f}, "
        f"under faults {report['recall_under_faults']:.4f} "
        f"(ladder floor {report['recall_floor']:.4f})\n"
        f"  checkpoint: corrupt leaf {report['checkpoint']['corrupt_leaf']!r} "
        f"detected={report['checkpoint']['corrupt_detected']} "
        f"fallback={report['checkpoint']['restore_fallback_ok']} "
        f"torn-write-recovered="
        f"{report['checkpoint']['torn_write_recovered']}\n"
        f"-> {args.out}"
    )
    if report["failures"]:
        raise SystemExit("chaos gates failed: " + "; ".join(report["failures"]))


if __name__ == "__main__":
    main()
