"""Pareto autotuner: sweep the speed-quality knobs, pick an operating point.

LIDER's headline claim is a better speed-quality trade-off, but a fixed
``n_probe`` pays the worst-case candidate cost for every query. The adaptive
control plane (DESIGN.md §Adaptive speed-quality control plane) adds a
``prune_margin`` whose block-skipping verification kernel turns per-query
routing confidence into wall-clock savings. This module closes the loop:

1. **sweep** ``(n_probe, r0, prune_margin, refine, rescore_factor,
   block_c, block_q, sketch_factor)`` on held-out queries over a built
   index, measuring AQT,
   recall@k, MRR@10, and the pruned-probe fraction per operating point; the
   CLI additionally sweeps ``--storage-dtypes`` (one built index per dtype,
   DESIGN.md §Quantized bank) and tags every point with the bank storage it
   ran against;
2. **pareto_frontier** keeps the non-dominated points (min AQT, max recall)
   across *all* storage dtypes — a quantized bank earns frontier spots only
   by actually beating the full-precision points;
3. **select_operating_point** returns the cheapest point meeting a recall
   target — what ``launch.serve --recall-target`` feeds into the engine.

The CLI emits ``BENCH_tradeoff.json`` and exits non-zero when the frontier
contains a point strictly dominated by a fixed-``n_probe`` baseline (CI runs
``--smoke``) — the regression guard that adaptivity keeps paying for itself.

AQT accounting: on TPU the fused block-skip kernel realizes pruning savings
directly, so ``aqt_s`` is the measured wall AQT. On CPU/GPU the materialized
reference path cannot skip statically-shaped work, so ``aqt_s`` is the
device-cost model ``route + (full - route) * live_fraction`` built from two
measured walls (routing-only and full unpruned search at the same
``n_probe``) — the savings the kernel contract guarantees on the target
hardware. Both walls and the model inputs land in the JSON (``aqt_metric``
says which convention a run used), so nothing is silently extrapolated.

Usage:
    PYTHONPATH=src python -m repro.tuning.pareto [--smoke]
        [--out BENCH_tradeoff.json] [--recall-target 0.95] ...
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import lider as lider_lib
from ..core.lider import OperatingPoint
from ..core.utils import mrr_at_10, recall_at_k


@dataclasses.dataclass(frozen=True)
class SweepResult:
    point: OperatingPoint
    aqt_s: float  # frontier metric (measured on TPU, modeled on CPU/GPU)
    wall_aqt_s: float  # wall AQT measured on this host, pruning applied
    wall_route_s: float  # routing-only wall AQT (model input)
    wall_full_s: float  # unpruned wall AQT at the same n_probe (model input)
    recall: float
    mrr10: float
    pruned_fraction: float
    storage_dtype: str = "float32"  # bank storage the point ran against
    # Tier axis (DESIGN.md §Tiered embedding store): which tier held the
    # rescore table, and the measured host fetch overhead per query (D2H of
    # the provisional rows + the np.take; 0.0 on the device tier).
    rescore_tier: str = "device"
    host_fetch_s: float = 0.0

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(d.pop("point"))
        d["adaptive"] = self.point.adaptive
        return d


def default_grid(
    n_probes: Sequence[int] = (2, 5, 10, 20, 40),
    margins: Sequence[float] = (0.02, 0.05, 0.1, 0.2),
    rescore_factors: Sequence[int] = (4,),
    block_cs: Sequence[int | None] = (None,),
    block_qs: Sequence[int | None] = (None,),
    sketch_factors: Sequence[int | None] = (None,),
    **held,
) -> list[OperatingPoint]:
    """Fixed baselines (margin=None) plus adaptive variants per n_probe.

    ``rescore_factors``/``block_cs``/``block_qs``/``sketch_factors`` extend
    the sweep over the quantized bank's rescore depth, the kernel block
    size, the cluster-major query-tile width, and the 1-bit pre-filter's
    survivor multiple (defaults keep the grid size unchanged); every
    (n_probe, margin) combo is crossed with them. ``held`` holds further
    :class:`OperatingPoint` fields at one value over the whole grid (e.g.
    ``r0``, ``refine``, ``use_fused``).
    """
    def point(p, m, rf, bc, bq, sf):
        return OperatingPoint(
            n_probe=p, prune_margin=m, rescore_factor=rf, block_c=bc,
            block_q=bq, sketch_factor=sf, **held,
        )

    fixed = [
        point(p, None, rf, bc, bq, sf)
        for p in n_probes
        for rf in rescore_factors
        for bc in block_cs
        for bq in block_qs
        for sf in sketch_factors
    ]
    adaptive = [
        point(p, m, rf, bc, bq, sf)
        for p in n_probes
        if p > 1  # pruning a single probe can only be a no-op
        for m in margins
        for rf in rescore_factors
        for bc in block_cs
        for bq in block_qs
        for sf in sketch_factors
    ]
    return fixed + adaptive


def _time_fn(fn, queries, repeats: int) -> float:
    """Wall seconds per query of a jitted callable (compile excluded).

    ``fn`` must return every device output it is accountable for —
    ``block_until_ready`` walks the whole pytree, and timing a search by its
    ids alone under-counts when scores finish later (the same bug the
    serving engine's AQT window guards against).
    """
    jax.block_until_ready(fn(queries))
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(queries)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (repeats * queries.shape[0])


def sweep(
    params,
    queries: jnp.ndarray,
    gt_ids: jnp.ndarray,
    grid: Sequence[OperatingPoint],
    *,
    k: int,
    relevant: jnp.ndarray | None = None,
    repeats: int = 3,
) -> list[SweepResult]:
    """Measure every operating point on the held-out ``queries``.

    ``gt_ids``: exact top-k ids (Flat search) for recall@k; ``relevant``:
    optional (B,) known-relevant ids for MRR@10. Routing-only and unpruned
    walls are measured once per (n_probe, r0, refine) combo and shared by
    that combo's margin variants.
    """
    on_tpu = jax.default_backend() == "tpu"
    storage_dtype = params.bank.storage_dtype
    rescore_tier = getattr(params.bank, "rescore_tier", "device")
    base_walls: dict[tuple, tuple[float, float]] = {}
    host_fetch_walls: dict[tuple, float] = {}
    results = []
    for point in grid:
        base_key = (
            point.n_probe, point.r0, point.refine,
            point.rescore_factor, point.block_c, point.block_q,
            point.sketch_factor,
        )
        if base_key not in base_walls:
            route = jax.jit(
                lambda q, p=point: lider_lib.route_queries(
                    params, q, n_probe=p.n_probe, use_fused=p.use_fused,
                    block_c=p.block_c,
                )
            )
            full = lambda q, p=point: lider_lib.search_lider(
                params, q, k=k,
                **dataclasses.asdict(dataclasses.replace(p, prune_margin=None)),
            )
            base_walls[base_key] = (
                _time_fn(route, queries, repeats),
                _time_fn(full, queries, repeats),
            )
        wall_route, wall_full = base_walls[base_key]

        def run(q, p=point):
            return lider_lib.search_lider(
                params, q, k=k, with_stats=True, **dataclasses.asdict(p)
            )
        out, pruned = run(queries)
        pruned_frac = float(np.asarray(pruned).mean())
        # A fixed point's pruned search IS the base full search (margin=None
        # masks nothing) — reuse its wall instead of timing it twice.
        wall = (
            _time_fn(lambda q: run(q)[0], queries, repeats)
            if point.adaptive
            else wall_full
        )
        if on_tpu:
            aqt = wall  # block-skip kernel realizes the savings in silicon
        else:
            live = 1.0 - pruned_frac
            aqt = wall_route + max(wall_full - wall_route, 0.0) * live
        host_fetch_s = 0.0
        if rescore_tier == "host":
            # Measured fetch overhead of the tiered pipeline at this point:
            # D2H of the provisional rows + the host-side np.take (shared
            # across margin variants — pruning doesn't change k').
            fetch_key = (
                point.n_probe, point.rescore_factor, point.block_c,
                point.block_q, point.sketch_factor,
            )
            if fetch_key not in host_fetch_walls:
                prov, _ = lider_lib.first_pass(
                    params, queries, k=k,
                    point=dataclasses.replace(point, prune_margin=None),
                )
                t0 = time.perf_counter()
                for _ in range(repeats):
                    lider_lib.host_fetch(params, prov.ids)
                host_fetch_walls[fetch_key] = (
                    time.perf_counter() - t0
                ) / (repeats * queries.shape[0])
            host_fetch_s = host_fetch_walls[fetch_key]
        ids = np.asarray(out.ids)
        results.append(
            SweepResult(
                point=point,
                aqt_s=aqt,
                wall_aqt_s=wall,
                wall_route_s=wall_route,
                wall_full_s=wall_full,
                recall=float(recall_at_k(out.ids, jnp.asarray(gt_ids))),
                mrr10=mrr_at_10(ids, relevant) if relevant is not None else -1.0,
                pruned_fraction=pruned_frac,
                storage_dtype=storage_dtype,
                rescore_tier=rescore_tier,
                host_fetch_s=host_fetch_s,
            )
        )
    return results


def _dominates(a: SweepResult, b: SweepResult) -> bool:
    """a weakly better on both axes, strictly better on at least one."""
    ge = a.recall >= b.recall and a.aqt_s <= b.aqt_s
    return ge and (a.recall > b.recall or a.aqt_s < b.aqt_s)


def pareto_frontier(results: Sequence[SweepResult]) -> list[SweepResult]:
    """Non-dominated subset (min AQT, max recall), sorted by AQT.

    Computed over ALL swept points — fixed baselines included — so a frontier
    point can never be strictly dominated by a fixed-``n_probe`` config; the
    CLI re-checks that invariant explicitly as a regression guard.
    """
    front = [
        r
        for r in results
        if not any(_dominates(o, r) for o in results if o is not r)
    ]
    return sorted(front, key=lambda r: r.aqt_s)


def select_operating_point(
    results: Sequence[SweepResult],
    recall_target: float,
    load_signal: float | None = None,
) -> SweepResult:
    """Pick the operating point for one dispatch.

    Offline spelling (``load_signal=None``, the PR 3 behavior): cheapest
    point meeting the recall target; highest-recall point if none does.

    Online spelling (``load_signal`` in [0, 1], from
    ``serving.Scheduler.load_signal``): navigate the measured frontier
    instead of holding one point. Load 0 is the nominal (recall-target)
    point; rising load walks toward cheaper frontier points, reaching the
    cheapest at load 1 — the engine trades recall for latency exactly when
    queue pressure says the SLO is at risk, and every point on the walk is
    a frontier point (never a dominated config). This is the 1-D rung
    controller generalized: the ladder was "step down one rung under
    deadline pressure"; this maps a continuous load signal onto the whole
    frontier in one shot.
    """
    meeting = [r for r in results if r.recall >= recall_target]
    nominal = (
        min(meeting, key=lambda r: r.aqt_s)
        if meeting
        else max(results, key=lambda r: (r.recall, -r.aqt_s))
    )
    if load_signal is None:
        return nominal
    load = min(max(float(load_signal), 0.0), 1.0)
    # Walk: nominal first, then strictly-cheaper frontier points ordered
    # best-recall first (the same chain degradation_ladder materializes).
    chain = [nominal] + sorted(
        (r for r in pareto_frontier(results) if r.aqt_s < nominal.aqt_s),
        key=lambda r: -r.recall,
    )
    return chain[int(round(load * (len(chain) - 1)))]


def degradation_ladder(
    results: Sequence[SweepResult],
    *,
    nominal: SweepResult | None = None,
    max_rungs: int = 3,
) -> list[dict]:
    """Operating-point rungs for the serving degradation ladder
    (``serving.DegradePolicy.ladder`` — DESIGN.md §Failure model).

    Walks the Pareto frontier *downward* from the nominal point: each rung
    is strictly cheaper (lower AQT) than the last, ordered best-recall
    first, capped at ``max_rungs``. Each rung dict carries the search-knob
    overrides the engine applies (``n_probe`` / ``prune_margin`` /
    ``rescore_factor`` / ...) plus the swept ``expected_recall`` — the
    *modeled floor* chaos benchmarks gate recall-under-faults against. The
    engine itself ignores non-knob keys.
    """
    front = pareto_frontier(results)
    if nominal is None:
        nominal = front[-1] if front else None
    if nominal is None:
        return []
    cheaper = [r for r in front if r.aqt_s < nominal.aqt_s]
    cheaper.sort(key=lambda r: -r.recall)  # step down quality gradually
    if len(cheaper) > max_rungs:
        # Evenly spaced picks keep the full quality range with few rungs.
        idx = np.linspace(0, len(cheaper) - 1, max_rungs).round().astype(int)
        cheaper = [cheaper[i] for i in dict.fromkeys(idx.tolist())]
    rungs = []
    for r in cheaper:
        # Only the swept fields: the engine's base point keeps its own
        # verification kernel and centroid window.
        rung = dataclasses.asdict(r.point)
        del rung["use_fused"], rung["r0_centroid"]
        rung["expected_recall"] = r.recall
        rungs.append(rung)
    return rungs


def dominated_frontier_points(
    frontier: Sequence[SweepResult], results: Sequence[SweepResult]
) -> list[tuple[SweepResult, SweepResult]]:
    """(frontier point, fixed baseline that strictly dominates it) pairs.

    Non-empty means the adaptive machinery made the trade-off *worse*
    somewhere — the CI failure condition.
    """
    fixed = [r for r in results if not r.point.adaptive]
    bad = []
    for p in frontier:
        for f in fixed:
            if f.recall >= p.recall and f.aqt_s < p.aqt_s:
                bad.append((p, f))
                break
    return bad


def adaptive_beats_fixed(results: Sequence[SweepResult]) -> bool:
    """Is there an adaptive point cheaper than every fixed config of
    equal-or-better recall? (The PR's acceptance condition.)"""
    fixed = [r for r in results if not r.point.adaptive]
    for a in results:
        if not a.point.adaptive:
            continue
        rivals = [f for f in fixed if f.recall >= a.recall]
        if all(a.aqt_s < f.aqt_s for f in rivals):
            return True
    return False


def make_report(
    results: Sequence[SweepResult],
    *,
    k: int,
    n_queries: int,
    recall_target: float | None = None,
) -> dict:
    """Frontier + checks + selection over already-swept results.

    ``results`` may span several built indexes (e.g. one per storage dtype
    — the CLI's ``--storage-dtypes`` sweep); the frontier is computed over
    all of them, so a quantized bank earns its place only by actually
    beating the full-precision points somewhere on the curve.
    """
    results = list(results)
    frontier = pareto_frontier(results)
    frontier_set = {id(r) for r in frontier}
    report = {
        "backend": jax.default_backend(),
        "aqt_metric": (
            "measured_wall"
            if jax.default_backend() == "tpu"
            else "modeled_from_measured_walls"
        ),
        "k": k,
        "n_queries": n_queries,
        "storage_dtypes": sorted({r.storage_dtype for r in results}),
        "rescore_tiers": sorted({r.rescore_tier for r in results}),
        "points": [
            {**r.to_json(), "on_frontier": id(r) in frontier_set}
            for r in results
        ],
        "frontier": [r.to_json() for r in frontier],
        "checks": {
            "frontier_not_dominated_by_fixed": not dominated_frontier_points(
                frontier, results
            ),
            "adaptive_beats_fixed_at_equal_or_better_recall":
                adaptive_beats_fixed(results),
        },
    }
    if recall_target is not None:
        sel = select_operating_point(results, recall_target)
        report["recall_target"] = recall_target
        report["selected"] = {
            **sel.to_json(),
            "meets_target": sel.recall >= recall_target,
        }
    return report


def tune(
    params,
    queries,
    gt_ids,
    *,
    k: int,
    grid: Sequence[OperatingPoint] | None = None,
    recall_target: float | None = None,
    relevant=None,
    repeats: int = 3,
) -> dict:
    """Sweep + frontier + selection, as one JSON-ready report dict."""
    grid = list(grid) if grid is not None else default_grid()
    results = sweep(
        params, queries, gt_ids, grid, k=k, relevant=relevant,
        repeats=repeats,
    )
    return make_report(
        results, k=k, n_queries=int(queries.shape[0]),
        recall_target=recall_target,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus + coarse grid (CI)")
    ap.add_argument("--out", default="BENCH_tradeoff.json")
    ap.add_argument("--corpus-size", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--n-clusters", type=int, default=None,
                    help="default: corpus_size // 1000 (>= 16)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--recall-target", type=float, default=0.9)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--n-probes", type=int, nargs="+", default=None)
    ap.add_argument("--margins", type=float, nargs="+", default=None)
    ap.add_argument(
        "--storage-dtypes", nargs="+", default=["float32"],
        choices=["float32", "bfloat16", "int8", "int4"],
        help="build + sweep one index per storage dtype; the frontier spans "
        "all of them (DESIGN.md §Quantized bank)",
    )
    ap.add_argument(
        "--rescore-factors", type=int, nargs="+", default=None,
        help="k' = factor*k exact-rescore depths to sweep (int8 banks)",
    )
    ap.add_argument(
        "--rescore-tiers", nargs="+", default=["device"],
        choices=["device", "host"],
        help="storage tiers for the int8 rescore table (DESIGN.md §Tiered "
        "embedding store): every int8 point is swept per tier, tagged with "
        "the tier and its measured host fetch overhead (host is skipped "
        "for float banks, which have no rescore table)",
    )
    ap.add_argument(
        "--block-cs", type=int, nargs="+", default=None,
        help="verification-kernel candidate block sizes to sweep",
    )
    ap.add_argument(
        "--block-qs", type=int, nargs="+", default=None,
        help="cluster-major query-tile widths to sweep IN ADDITION to the "
        "per-query schedule (quantized banks only; float banks always run "
        "per-query — DESIGN.md §Cluster-major schedule), so a cluster-major "
        "point must beat its per-query twin to reach the frontier",
    )
    ap.add_argument(
        "--sketch-factors", type=int, nargs="+", default=None,
        help="1-bit pre-filter survivor multiples (m = factor*k') to sweep "
        "IN ADDITION to the unfiltered pass (quantized banks only; float "
        "banks carry no sketches — DESIGN.md §Binary sketch tier), so a "
        "sketch point must beat its unfiltered twin to reach the frontier",
    )
    ap.add_argument("--no-check", action="store_true",
                    help="report only; do not exit non-zero when a check "
                    "fails (dominated frontier, or no adaptive point beating "
                    "the fixed baselines)")
    args = ap.parse_args()
    if args.smoke:
        args.corpus_size = min(args.corpus_size, 8_000)
        args.dim = min(args.dim, 32)
        args.queries = min(args.queries, 64)
        args.repeats = min(args.repeats, 2)

    from ..core.baselines import flat_search
    from ..data import synthetic

    corpus = synthetic.retrieval_corpus(0, args.corpus_size, args.dim)
    queries, relevant = synthetic.retrieval_queries(1, corpus, args.queries)
    gt = flat_search(corpus, queries, k=args.k)

    n_clusters = args.n_clusters or max(16, args.corpus_size // 1000)
    n_probes = tuple(args.n_probes) if args.n_probes else (
        (2, 4, 8, 16) if args.smoke else (2, 5, 10, 20, 40)
    )
    n_probes = tuple(p for p in n_probes if p <= n_clusters)
    margins = tuple(args.margins) if args.margins else (
        (0.05, 0.1, 0.2) if args.smoke else (0.02, 0.05, 0.1, 0.2)
    )
    block_cs = tuple(args.block_cs) if args.block_cs else (None,)
    block_qs = (None, *args.block_qs) if args.block_qs else (None,)
    sketch_factors = (
        (None, *args.sketch_factors) if args.sketch_factors else (None,)
    )

    # One built index per storage dtype; the frontier spans all of them
    # (and, for int8, every requested rescore tier — the tier move is a
    # pure conversion of the same bank, so points differ only in where the
    # rescore rows live).
    results = []
    for sd in args.storage_dtypes:
        cfg = lider_lib.LiderConfig(
            n_clusters=n_clusters, n_arrays=4, n_leaves=4, kmeans_iters=10,
            storage_dtype=sd,
        )
        t0 = time.time()
        params = lider_lib.build_lider(jax.random.PRNGKey(0), corpus, cfg)
        print(f"[pareto] built n={args.corpus_size} c={n_clusters} "
              f"storage={sd} in {time.time() - t0:.1f}s")
        # rescore_factor and block_q are no-ops (resp. errors) on float
        # banks — crossing them in would only duplicate identical points.
        quantized = sd in ("int8", "int4")
        if quantized:
            rescore_factors = (
                tuple(args.rescore_factors) if args.rescore_factors else (2, 4)
            )
        else:
            rescore_factors = (4,)
        grid = default_grid(
            n_probes=n_probes, margins=margins,
            rescore_factors=rescore_factors, block_cs=block_cs,
            block_qs=block_qs if quantized else (None,),
            sketch_factors=sketch_factors if quantized else (None,),
        )
        for tier in args.rescore_tiers:
            if tier == "host" and not quantized:
                continue  # float banks have no rescore table to move
            p_t = (
                params if tier == "device"
                else lider_lib.set_rescore_tier(params, "host")
            )
            results.extend(
                sweep(p_t, queries, gt.ids, grid, k=args.k,
                      relevant=relevant, repeats=args.repeats)
            )

    report = make_report(
        results, k=args.k, n_queries=int(queries.shape[0]),
        recall_target=args.recall_target,
    )
    report["build"] = {
        "corpus_size": args.corpus_size, "dim": args.dim,
        "n_clusters": n_clusters, "storage_dtypes": args.storage_dtypes,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)

    for p in report["points"]:
        star = "*" if p["on_frontier"] else " "
        kind = "adapt" if p["adaptive"] else "fixed"
        fetch = (
            f" fetch={p['host_fetch_s'] * 1e6:.1f}us"
            if p["rescore_tier"] == "host"
            else ""
        )
        print(
            f"[pareto]{star} {kind} {p['storage_dtype']:>8}"
            f"/{p['rescore_tier']} "
            f"probe={p['n_probe']:3d} "
            f"margin={p['prune_margin'] if p['prune_margin'] is not None else '-':>5} "
            f"rescore={p['rescore_factor']} "
            f"sketch={p['sketch_factor'] if p.get('sketch_factor') is not None else '-':>2} "
            f"aqt={p['aqt_s'] * 1e6:9.1f}us recall@{args.k}={p['recall']:.4f} "
            f"mrr10={p['mrr10']:.4f} pruned={p['pruned_fraction']:.2%}{fetch}"
        )
    sel = report.get("selected")
    if sel:
        sel_point = OperatingPoint(**{
            f.name: sel[f.name] for f in dataclasses.fields(OperatingPoint)
        })
        print(
            f"[pareto] operating point for recall>={args.recall_target}: "
            f"{sel['storage_dtype']}/{sel_point.label()} "
            f"(aqt={sel['aqt_s'] * 1e6:.1f}us recall={sel['recall']:.4f}, "
            f"meets_target={sel['meets_target']})"
        )
    checks = report["checks"]
    print(f"[pareto] checks: {checks} -> {args.out}")
    # Both checks gate CI. The frontier-domination check is a structural
    # invariant of pareto_frontier (it can only fail if the frontier code
    # regresses); the adaptive-beats-fixed check is the payoff condition —
    # without it, adaptivity regressing to "never cheaper than a fixed
    # n_probe" would still pass.
    failed = [name for name, ok in checks.items() if not ok]
    if failed and not args.no_check:
        raise SystemExit(f"speed-quality regression, failed checks: {failed}")


if __name__ == "__main__":
    main()
