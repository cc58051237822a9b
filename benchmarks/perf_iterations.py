import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimbing (deliverable g): hypothesis -> change -> re-lower ->
measure, on the three chosen cells (single-pod production mesh).

Cells (chosen per the assignment criteria):
  A. lider-msmarco:serve_bulk + two-tower-retrieval:retrieval_cand — most
     representative of the paper's technique (LIDER serving itself).
  B. qwen2-72b:prefill_32k — most collective-bound baseline cell.
  C. qwen2-72b:train_4k — worst roofline fraction among the train cells.

Each variant is re-lowered on the 16x16 mesh and its roofline terms
recomputed; results land in experiments/perf_iterations.json and are
narrated (hypothesis / predicted delta / measured delta / verdict) in
EXPERIMENTS.md §Perf.

Usage: PYTHONPATH=src python -m benchmarks.perf_iterations
"""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.base import ArchSpec, ShapeSpec
from repro.configs.lider_msmarco import RetrievalArchConfig
from repro.core.lider import LiderConfig
from repro.launch.dryrun import collective_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    make_lm_bundle,
    make_recsys_bundle,
    make_retrieval_bundle,
)

PEAK_FLOPS, HBM_BW, LINK_BW = 197e12, 819e9, 50e9


def measure(bundle, mesh, loop_factor=None) -> dict:
    lf = loop_factor if loop_factor is not None else bundle.loop_factor
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        jf = jax.jit(
            bundle.fn,
            in_shardings=bundle.in_shardings,
            out_shardings=bundle.out_shardings,
            donate_argnums=bundle.donate_argnums,
        )
        compiled = jf.lower(*bundle.args).compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_stats(compiled.as_text())
    coll_bytes = sum(v["bytes"] for v in coll.values()) * lf
    flops = float(cost.get("flops", 0)) * lf
    byts = float(cost.get("bytes accessed", 0)) * lf
    return {
        "compile_s": round(time.time() - t0, 1),
        "loop_factor": lf,
        "flops_per_chip": flops,
        "bytes_per_chip": byts,
        "coll_bytes_per_chip": coll_bytes,
        "t_compute_s": flops / PEAK_FLOPS,
        "t_memory_s": byts / HBM_BW,
        "t_collective_s": coll_bytes / LINK_BW,
        "hbm_gib": (mem.temp_size_in_bytes + mem.argument_size_in_bytes) / 2**30,
        "collectives": {k: v["bytes"] for k, v in coll.items()},
    }


def two_tower_lider_arch() -> ArchSpec:
    """LIDER over the 1M-item two-tower embedding space (d=256)."""
    return ArchSpec(
        arch_id="two-tower-lider",
        family="retrieval",
        config=RetrievalArchConfig(
            lider=LiderConfig(
                n_clusters=512, n_probe=20, n_arrays=10, key_len=12,
                key_len_centroid=9, n_leaves=5, n_leaves_centroid=10, r0=4,
            ),
            corpus_size=1_000_000,
            dim=256,
            capacity=2752,
            k=100,
        ),
        shapes=(ShapeSpec("retrieval_cand", "retrieval_serve", {"batch": 1}),),
    )


def main() -> None:
    mesh = make_production_mesh(multi_pod=False)
    results: dict[str, dict] = {}

    def record(cell, variant, m):
        results[f"{cell}/{variant}"] = m
        print(
            f"[perf] {cell}/{variant}: comp={m['t_compute_s']:.3g}s "
            f"mem={m['t_memory_s']:.3g}s coll={m['t_collective_s']:.3g}s "
            f"hbm={m['hbm_gib']:.1f}GiB (compile {m['compile_s']}s)",
            flush=True,
        )

    # ---------------- Cell A: the paper's technique --------------------
    lider_arch = get_arch("lider-msmarco")
    sb = lider_arch.shape("serve_bulk")
    record("A.lider_serve_bulk", "baseline_f32_r04",
           measure(make_retrieval_bundle(lider_arch, sb, mesh), mesh))
    record("A.lider_serve_bulk", "A1_bf16_embs",
           measure(make_retrieval_bundle(lider_arch, sb, mesh,
                                         emb_dtype=jnp.bfloat16), mesh))
    record("A.lider_serve_bulk", "A2_bf16_r02_refine",
           measure(make_retrieval_bundle(lider_arch, sb, mesh,
                                         emb_dtype=jnp.bfloat16, r0=2,
                                         refine=True), mesh))

    tt = get_arch("two-tower-retrieval")
    rc = tt.shape("retrieval_cand")
    record("A.two_tower_retrieval_cand", "baseline_flat",
           measure(make_recsys_bundle(tt, rc, mesh), mesh))
    la = two_tower_lider_arch()
    record("A.two_tower_retrieval_cand", "A3_lider_index",
           measure(make_retrieval_bundle(la, la.shapes[0], mesh,
                                         emb_dtype=jnp.bfloat16,
                                         capacity_factor=40.0), mesh))

    # ---------------- Cell B: collective-bound prefill ------------------
    q72 = get_arch("qwen2-72b")
    pf = q72.shape("prefill_32k")
    seq_cfg_b = dataclasses.replace(q72.config, seq_shard_activations=True)
    record("B.qwen2_72b_prefill", "baseline_fsdp",
           measure(make_lm_bundle(q72, pf, mesh), mesh))
    record("B.qwen2_72b_prefill", "B1_tp_only_serving_params",
           measure(make_lm_bundle(q72, pf, mesh, fsdp=False), mesh))
    record("B.qwen2_72b_prefill", "B2_seqparallel_activations",
           measure(make_lm_bundle(q72, pf, mesh, cfg_override=seq_cfg_b), mesh))

    # ---------------- Cell C: worst-roofline train ----------------------
    tr = q72.shape("train_4k")
    record("C.qwen2_72b_train", "baseline_ga16",
           measure(make_lm_bundle(q72, tr, mesh), mesh))
    record("C.qwen2_72b_train", "C1_ga8",
           measure(make_lm_bundle(q72, tr, mesh, grad_accum=8), mesh,
                   loop_factor=80 * 8))
    seq_cfg = dataclasses.replace(q72.config, seq_shard_activations=True)
    record("C.qwen2_72b_train", "C2_seqparallel_ga4",
           measure(make_lm_bundle(q72, tr, mesh, grad_accum=4,
                                  cfg_override=seq_cfg), mesh,
                   loop_factor=80 * 4))
    record("C.qwen2_72b_train", "C3_seqparallel_ga1",
           measure(make_lm_bundle(q72, tr, mesh, grad_accum=1,
                                  cfg_override=seq_cfg), mesh,
                   loop_factor=80 * 1))
    record("C.qwen2_72b_train", "C4_seqparallel_ga8",
           measure(make_lm_bundle(q72, tr, mesh, grad_accum=8,
                                  cfg_override=seq_cfg), mesh,
                   loop_factor=80 * 8))

    with open("experiments/perf_iterations.json", "w") as f:
        json.dump(results, f, indent=1)
    print(f"[perf] wrote experiments/perf_iterations.json ({len(results)} rows)")


if __name__ == "__main__":
    main()
