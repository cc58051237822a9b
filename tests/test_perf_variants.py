"""Guards for the §Perf optimization variants: quality of the bf16 index,
the last-mile refine trade-off, and the roofline analytics plumbing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lider
from repro.core.baselines import flat_search
from repro.core.utils import recall_at_k


def _setup(corpus):
    x, q, gt = corpus
    cfg = lider.LiderConfig(
        n_clusters=64, n_probe=12, n_arrays=6, n_leaves=4, kmeans_iters=10
    )
    return x, q, gt, lider.build_lider(jax.random.PRNGKey(0), x, cfg)


def test_bf16_index_recall_close_to_f32(corpus):
    x, q, gt, params = _setup(corpus)
    base = recall_at_k(
        lider.search_lider(params, q, k=10, n_probe=12, r0=8).ids, gt
    )
    p16 = dataclasses.replace(
        params,
        bank=dataclasses.replace(
            params.bank, embs=params.bank.embs.astype(jnp.bfloat16)
        ),
    )
    got = recall_at_k(lider.search_lider(p16, q, k=10, n_probe=12, r0=8).ids, gt)
    assert float(got) >= float(base) - 0.03  # A1 quality guard


def test_refine_halves_window_at_small_recall_cost(corpus):
    x, q, gt, params = _setup(corpus)
    wide = recall_at_k(lider.search_lider(params, q, k=10, n_probe=12, r0=8).ids, gt)
    narrow_refined = recall_at_k(
        lider.search_lider(params, q, k=10, n_probe=12, r0=4, refine=True).ids, gt
    )
    narrow_plain = recall_at_k(
        lider.search_lider(params, q, k=10, n_probe=12, r0=4).ids, gt
    )
    # A2: refine at half width must not be (meaningfully) worse than plain
    # half width, and stay near the full-width recall.
    assert float(narrow_refined) >= float(narrow_plain) - 0.02
    assert float(narrow_refined) >= float(wide) - 0.08


def test_model_flops_analytics():
    from repro.configs import ARCHS, get_arch
    from repro.launch.flops import model_flops

    for arch_id, arch in ARCHS.items():
        for shape in arch.shapes:
            if shape.name in arch.skip_shapes:
                continue
            f = model_flops(arch, shape)
            assert f > 0, (arch_id, shape.name)
    # 6*N*D sanity for a dense LM train cell
    arch = get_arch("qwen2.5-3b")
    f = model_flops(arch, arch.shape("train_4k"))
    n = arch.config.flops_params()
    d = 256 * 4096
    assert f >= 6 * n * d  # matmuls + attention


def test_roofline_analyze_roundtrip():
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "roofline",
        pathlib.Path(__file__).parent.parent / "benchmarks" / "roofline.py",
    )
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    rec = {
        "status": "ok",
        "arch": "qwen2.5-3b",
        "shape": "decode_32k",
        "mesh": "single_pod_16x16",
        "n_devices": 256,
        "cost": {"flops": 1e9, "bytes_accessed": 1e10},
        "collectives": {"all-gather": {"count": 2, "bytes": 1e8}},
        "memory": {"temp_bytes": 2**30},
        "model_flops": 1e12,
    }
    out = roofline.analyze(rec)
    assert out["bottleneck"] in ("compute", "memory", "collective")
    assert out["loop_factor"] == 36.0  # qwen2.5-3b layer count
    assert out["t_memory_s"] > 0 and out["t_collective_s"] > 0
    assert roofline.analyze({"status": "failed"}) is None
