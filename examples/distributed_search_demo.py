"""Distributed LIDER demo on 8 simulated devices: cluster-parallel sharding,
capacity dispatch, and the single all-gather merge — the exact program the
multi-pod dry-run lowers at 512 chips, executed end-to-end here.

    PYTHONPATH=src python examples/distributed_search_demo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import distributed, lider  # noqa: E402
from repro.core.baselines import flat_search  # noqa: E402
from repro.core.utils import l2_normalize, recall_at_k  # noqa: E402
from repro.data import synthetic  # noqa: E402


def main():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(4, 2), ("data", "model")
    )
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"(clusters shard over 'data', queries over 'model')")

    corpus = synthetic.retrieval_corpus(0, 20_000, 64)
    queries, _ = synthetic.retrieval_queries(1, corpus, 128)
    cfg = lider.LiderConfig(n_clusters=64, n_probe=12, n_arrays=6, n_leaves=4,
                            kmeans_iters=10)
    params = lider.build_lider(jax.random.PRNGKey(0), corpus, cfg)

    sharded = distributed.shard_lider_params(mesh, params, ("data",))
    search = distributed.make_sharded_search(
        mesh, params, k=10, n_probe=12, r0=4, capacity_factor=2.0
    )
    out, dropped = search(sharded, queries)
    jax.block_until_ready(out.ids)
    t0 = time.time()
    out, dropped = search(sharded, queries)
    jax.block_until_ready(out.ids)
    dt = time.time() - t0

    ref = lider.search_lider(params, queries, k=10, n_probe=12, r0=4)
    gt = flat_search(corpus, queries, k=10)
    print(f"distributed search: {dt*1e3/128:.3f} ms/query, "
          f"capacity drops={int(dropped)}")
    print(f"recall@10 vs Flat: distributed={float(recall_at_k(out.ids, gt.ids)):.4f} "
          f"single-device={float(recall_at_k(ref.ids, gt.ids)):.4f}")
    overlap = np.mean([
        len(set(a[a >= 0]) & set(b[b >= 0])) / max(len(set(a[a >= 0])), 1)
        for a, b in zip(np.asarray(ref.ids), np.asarray(out.ids))
    ])
    print(f"distributed == single-device result overlap: {overlap:.4f}")


if __name__ == "__main__":
    main()
