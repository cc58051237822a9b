import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell: ``jax.jit(step).lower(**abstract inputs).compile()`` on the
production mesh — success proves the sharding config is coherent (no
sharding mismatches, no OOM at compile, supported collectives). The compiled
artifact yields ``memory_analysis()`` (fits-per-device proof),
``cost_analysis()`` (FLOPs/bytes) and the optimized HLO text from which
per-device collective traffic is parsed — the three roofline inputs
(EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--mesh single|multi|both]
        [--arch ID] [--shape NAME] [--out experiments/dryrun.json]
"""
import argparse
import json
import re
import time
import traceback

import jax

from ..configs import ARCHS, get_arch
from .mesh import make_production_mesh
from .steps import make_bundle

COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
# e.g.:  %ag = bf16[2,128,512]{2,1,0} all-gather(%x), replica_groups=...
_OP_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+(" + "|".join(COLLECTIVES) + r")[\(-]"
)
# tuple-result collectives:  (bf16[...], bf16[...]) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+(" + "|".join(COLLECTIVES) + r")[\(-]"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo_text: str) -> dict:
    """Per-device collective byte counts by op kind, from optimized HLO."""
    out: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        shapes: list[tuple[str, str]] = []
        kind = None
        if m:
            kind = m.group(3)
            shapes = [(m.group(1), m.group(2))]
        else:
            mt = _TUPLE_RE.search(line)
            if mt:
                kind = mt.group(2)
                shapes = _SHAPE_RE.findall(mt.group(1))
        if kind is None:
            continue
        b = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        ent = out.setdefault(kind, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += b
    return out


def run_cell(arch_id: str, shape_name: str, mesh, mesh_name: str) -> dict:
    arch = get_arch(arch_id)
    shape = arch.shape(shape_name)
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": int(mesh.devices.size),
    }
    if shape_name in arch.skip_shapes:
        rec["status"] = "skipped"
        rec["reason"] = arch.notes
        return rec
    t0 = time.time()
    try:
        with jax.sharding.set_mesh(mesh):
            bundle = make_bundle(arch, shape, mesh)
            jf = jax.jit(
                bundle.fn,
                in_shardings=bundle.in_shardings,
                out_shardings=bundle.out_shardings,
                donate_argnums=bundle.donate_argnums,
            )
            lowered = jf.lower(*bundle.args)
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 1)
            try:
                mem = compiled.memory_analysis()
                rec["memory"] = {
                    "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                    "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                    "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                    "generated_code_bytes": int(
                        getattr(mem, "generated_code_size_in_bytes", 0)
                    ),
                }
            except Exception as e:  # noqa: BLE001 — backend-dependent
                rec["memory"] = {"error": str(e)}
            try:
                cost = compiled.cost_analysis()
                if isinstance(cost, (list, tuple)):
                    cost = cost[0]
                rec["cost"] = {
                    "flops": float(cost.get("flops", -1)),
                    "bytes_accessed": float(cost.get("bytes accessed", -1)),
                }
            except Exception as e:  # noqa: BLE001
                rec["cost"] = {"error": str(e)}
            rec["collectives"] = collective_stats(compiled.as_text())
            rec["model_flops"] = bundle.model_flops
            if bundle.tier_memory is not None:
                # Retrieval cells: index bytes by storage tier (device HBM
                # vs host RAM) per storage config, so memory_analysis above
                # is read against the true device-resident footprint of an
                # int8+host index (DESIGN.md §Tiered embedding store). The
                # bundle asserts int8+host device bytes < int8-device (and
                # < f32) before this record is written.
                rec["tier_memory"] = bundle.tier_memory
            rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def iter_cells(arch_filter=None, shape_filter=None):
    for arch_id, arch in ARCHS.items():
        if arch_filter and arch_id != arch_filter:
            continue
        for shape in arch.shapes:
            if shape_filter and shape.name != shape_filter:
                continue
            yield arch_id, shape.name


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="experiments/dryrun.json")
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    results = []
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            prior = json.load(f)
        # keep ok/skipped records; failed cells re-run after fixes
        results = [r for r in prior if r["status"] in ("ok", "skipped")]
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for mesh_name, mesh in meshes:
        for arch_id, shape_name in iter_cells(args.arch, args.shape):
            if (arch_id, shape_name, mesh_name) in done:
                continue
            print(f"[dryrun] {arch_id} x {shape_name} x {mesh_name} ...", flush=True)
            rec = run_cell(arch_id, shape_name, mesh, mesh_name)
            status = rec["status"]
            extra = (
                f" compile={rec.get('compile_s')}s"
                if status == "ok"
                else f" ({rec.get('error', rec.get('reason', ''))[:120]})"
            )
            print(f"[dryrun]   -> {status}{extra}", flush=True)
            if status == "ok":
                print(
                    f"[dryrun]   mem(temp)={rec['memory'].get('temp_bytes', 0)/2**30:.2f}GiB/dev "
                    f"flops={rec['cost'].get('flops', -1):.3g} "
                    f"coll={ {k: round(v['bytes']/2**20, 1) for k, v in rec['collectives'].items()} }MiB",
                    flush=True,
                )
            results.append(rec)
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_fail = sum(r["status"] == "failed" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed -> {args.out}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
