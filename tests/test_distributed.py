"""Multi-device correctness (8 fake CPU devices via subprocess — the unit
test process keeps its single real device)."""
import subprocess
import sys
import textwrap

import pytest


def _run(body: str) -> str:
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        """
    ) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)),
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_sharded_search_equals_single_device():
    out = _run(
        """
        from repro.core import lider, distributed
        from repro.core.utils import l2_normalize
        rng = jax.random.PRNGKey(0)
        kc, kx, kq, kb = jax.random.split(rng, 4)
        centers = jax.random.normal(kc, (32, 64))
        assign = jax.random.randint(kx, (4000,), 0, 32)
        x = l2_normalize(centers[assign] + 0.3*jax.random.normal(kq, (4000, 64)))
        q = l2_normalize(x[:64] + 0.05*jax.random.normal(kb, (64, 64)))
        cfg = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10)
        params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
        ref = lider.search_lider(params, q, k=10, n_probe=8, r0=8)
        sp = distributed.shard_lider_params(mesh, params, ("data",))
        search = distributed.make_sharded_search(mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0)
        out, dropped = search(sp, q)
        assert int(dropped) == 0, f"dropped {dropped}"
        rs = np.sort(np.asarray(ref.scores)); os_ = np.sort(np.asarray(out.scores))
        assert np.allclose(rs, os_, atol=1e-5), np.abs(rs-os_).max()
        ov = np.mean([len(set(a[a>=0]) & set(b[b>=0]))/max(len(set(a[a>=0])),1)
                      for a, b in zip(np.asarray(ref.ids), np.asarray(out.ids))])
        assert ov == 1.0, ov
        print("EQUIV_OK")
        """
    )
    assert "EQUIV_OK" in out


def test_sharded_search_quantized_bank_matches_single_device():
    """int8 bank (DESIGN.md §Quantized bank): the new emb_scales /
    rescore_embs fields derive cluster-sharded specs from their metadata and
    the compressed-domain + exact-rescore pass runs shard-locally."""
    out = _run(
        """
        from repro.core import lider, distributed
        from repro.core.utils import l2_normalize
        rng = jax.random.PRNGKey(0)
        kc, kx, kq, kb = jax.random.split(rng, 4)
        centers = jax.random.normal(kc, (32, 64))
        assign = jax.random.randint(kx, (4000,), 0, 32)
        x = l2_normalize(centers[assign] + 0.3*jax.random.normal(kq, (4000, 64)))
        q = l2_normalize(x[:64] + 0.05*jax.random.normal(kb, (64, 64)))
        cfg = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4,
                                n_leaves=4, kmeans_iters=10,
                                storage_dtype="int8")
        params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
        assert params.bank.quantized
        ref = lider.search_lider(params, q, k=10, n_probe=8, r0=8)
        sp = distributed.shard_lider_params(mesh, params, ("data",))
        specs = distributed.lider_param_specs(params, ("data",))
        assert specs.bank.emb_scales == P(("data",), None)
        assert specs.bank.rescore_embs == P(("data",), None, None)
        search = distributed.make_sharded_search(
            mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0)
        out, dropped = search(sp, q)
        assert int(dropped) == 0, f"dropped {dropped}"
        rs = np.sort(np.asarray(ref.scores)); os_ = np.sort(np.asarray(out.scores))
        assert np.allclose(rs, os_, atol=1e-5), np.abs(rs-os_).max()
        ov = np.mean([len(set(a[a>=0]) & set(b[b>=0]))/max(len(set(a[a>=0])),1)
                      for a, b in zip(np.asarray(ref.ids), np.asarray(out.ids))])
        assert ov == 1.0, ov
        print("INT8_EQUIV_OK")
        """
    )
    assert "INT8_EQUIV_OK" in out


def test_sharded_search_host_tier_matches_single_device():
    """Host-tier bank (DESIGN.md §Tiered embedding store): the two-phase
    sharded search — compressed shard_map pass + host fetch + top-level
    rescore — matches the single-device staged search, with the rescore
    table never device-resident and no change to the collective set."""
    out = _run(
        """
        from repro.core import lider, distributed
        from repro.core.utils import l2_normalize
        rng = jax.random.PRNGKey(0)
        kc, kx, kq, kb = jax.random.split(rng, 4)
        centers = jax.random.normal(kc, (32, 64))
        assign = jax.random.randint(kx, (4000,), 0, 32)
        x = l2_normalize(centers[assign] + 0.3*jax.random.normal(kq, (4000, 64)))
        q = l2_normalize(x[:64] + 0.05*jax.random.normal(kb, (64, 64)))
        cfg = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4,
                                n_leaves=4, kmeans_iters=10,
                                storage_dtype="int8", rescore_tier="host")
        params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
        assert params.bank.rescore_tier == "host"
        assert params.bank.rescore_embs is None  # never a device leaf
        ref = lider.search_lider(params, q, k=10, n_probe=8, r0=8)
        sp = distributed.shard_lider_params(mesh, params, ("data",))
        search = distributed.make_sharded_search(
            mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0)
        assert hasattr(search, "stage1")  # the lowerable device phase
        out, dropped = search(sp, q)
        assert int(dropped) == 0, f"dropped {dropped}"
        rs = np.sort(np.asarray(ref.scores)); os_ = np.sort(np.asarray(out.scores))
        assert np.allclose(rs, os_, atol=1e-5), np.abs(rs-os_).max()
        ov = np.mean([len(set(a[a>=0]) & set(b[b>=0]))/max(len(set(a[a>=0])),1)
                      for a, b in zip(np.asarray(ref.ids), np.asarray(out.ids))])
        assert ov == 1.0, ov
        print("HOST_TIER_EQUIV_OK")
        """
    )
    assert "HOST_TIER_EQUIV_OK" in out


def test_sharded_search_grouped_matches_per_query_sharded():
    """Cluster-major grouped spelling on the distributed path (``block_q``):
    the host-replicated dispatch + per-cell schedules feed the grouped
    kernel inside the same shard_map, and results — ids AND scores — are
    bit-identical to the per-query sharded path on both tiers, with and
    without the binary-sketch pre-filter (covering factor)."""
    out = _run(
        """
        from repro.core import lider, distributed
        from repro.core.utils import l2_normalize
        rng = jax.random.PRNGKey(0)
        kc, kx, kq, kb = jax.random.split(rng, 4)
        centers = jax.random.normal(kc, (32, 64))
        assign = jax.random.randint(kx, (4000,), 0, 32)
        x = l2_normalize(centers[assign] + 0.3*jax.random.normal(kq, (4000, 64)))
        q = l2_normalize(x[:64] + 0.05*jax.random.normal(kb, (64, 64)))
        cfg = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4,
                                n_leaves=4, kmeans_iters=10,
                                storage_dtype="int8")
        params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
        sp = distributed.shard_lider_params(mesh, params, ("data",))
        base = distributed.make_sharded_search(
            mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0)
        ref, d0 = base(sp, q)
        grouped = distributed.make_sharded_search(
            mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0,
            block_q=8)
        out, d1 = grouped(sp, q)
        assert int(d0) == int(d1) == 0, (int(d0), int(d1))
        assert np.array_equal(np.asarray(ref.ids), np.asarray(out.ids))
        assert np.array_equal(np.asarray(ref.scores), np.asarray(out.scores))
        sk = distributed.make_sharded_search(
            mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0,
            block_q=8, sketch_factor=64)
        outs, _ = sk(sp, q)
        assert np.array_equal(np.asarray(ref.ids), np.asarray(outs.ids))

        # Host tier: grouped first pass + the same fetch->rescore pipeline.
        cfg_h = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4,
                                  n_leaves=4, kmeans_iters=10,
                                  storage_dtype="int8", rescore_tier="host")
        ph = lider.build_lider(jax.random.PRNGKey(2), x, cfg_h)
        sph = distributed.shard_lider_params(mesh, ph, ("data",))
        base_h = distributed.make_sharded_search(
            mesh, ph, k=10, n_probe=8, r0=8, capacity_factor=3.0)
        ref_h, _ = base_h(sph, q)
        grp_h = distributed.make_sharded_search(
            mesh, ph, k=10, n_probe=8, r0=8, capacity_factor=3.0,
            block_q=8, sketch_factor=64)
        out_h, _ = grp_h(sph, q)
        assert np.array_equal(np.asarray(ref_h.ids), np.asarray(out_h.ids))
        assert np.array_equal(np.asarray(ref_h.scores), np.asarray(out_h.scores))

        # Float banks cannot take the grouped path.
        cfg_f = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4,
                                  n_leaves=4, kmeans_iters=10)
        pf = lider.build_lider(jax.random.PRNGKey(2), x, cfg_f)
        try:
            distributed.make_sharded_search(
                mesh, pf, k=10, n_probe=8, r0=8, block_q=8)
            raise AssertionError("float bank should reject block_q")
        except ValueError:
            pass
        print("GROUPED_SHARDED_OK")
        """
    )
    assert "GROUPED_SHARDED_OK" in out


def test_capacity_drops_reduce_recall_gracefully():
    out = _run(
        """
        from repro.core import lider, distributed
        from repro.core.utils import l2_normalize, recall_at_k
        rng = jax.random.PRNGKey(1)
        x = l2_normalize(jax.random.normal(rng, (2000, 32)))
        q = l2_normalize(x[:32] + 0.01)
        cfg = lider.LiderConfig(n_clusters=32, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=5)
        params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
        sp = distributed.shard_lider_params(mesh, params, ("data",))
        tight = distributed.make_sharded_search(mesh, params, k=10, n_probe=8, r0=4, capacity_factor=0.5)
        out, dropped = tight(sp, q)
        assert int(dropped) > 0  # tight capacity must drop pairs...
        ids = np.asarray(out.ids)
        assert (ids[ids >= 0] < 2000).all()  # ...but results stay well-formed
        print("DROPS_OK", int(dropped))
        """
    )
    assert "DROPS_OK" in out


def test_sharded_kmeans_step_equals_reference():
    out = _run(
        """
        from repro.core import clustering, distributed
        x = jax.random.normal(jax.random.PRNGKey(0), (1024, 16))
        cen = clustering.init_centroids(jax.random.PRNGKey(1), x, 16)
        step = distributed.make_sharded_kmeans_step(mesh, n_clusters=16)
        got = step(jax.device_put(x, NamedSharding(mesh, P(("data",), None))), cen)
        sums, counts, _ = clustering.kmeans_step(x, cen, n_clusters=16)
        want = clustering.update_centroids(cen, sums, counts)
        assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
        print("KMEANS_OK")
        """
    )
    assert "KMEANS_OK" in out


def test_sharded_embedding_lookup_equals_take():
    out = _run(
        """
        from repro.models.recsys import embedding_lookup
        table = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
        ids = jax.random.randint(jax.random.PRNGKey(1), (16, 3), 0, 64)
        plain = table[ids]
        with jax.sharding.set_mesh(mesh):
            sharded = jax.jit(embedding_lookup)(table, ids)
        assert np.allclose(np.asarray(plain), np.asarray(sharded), atol=1e-6)
        # gradient path through the shard_map lookup
        g_plain = jax.grad(lambda t: jnp.sum(t[ids] ** 2))(table)
        with jax.sharding.set_mesh(mesh):
            g_shard = jax.jit(
                jax.grad(lambda t: jnp.sum(embedding_lookup(t, ids) ** 2))
            )(table)
        assert np.allclose(np.asarray(g_plain), np.asarray(g_shard), atol=1e-5)
        print("EMB_OK")
        """
    )
    assert "EMB_OK" in out


def test_lm_train_step_runs_sharded():
    """A reduced LM train step executes (not just compiles) on the mesh and
    matches the single-device loss."""
    out = _run(
        """
        from repro.models import transformer as T
        from repro.data import synthetic
        cfg = T.LMConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                         d_ff=128, vocab=256, dtype=jnp.float32)
        params = T.init(jax.random.PRNGKey(0), cfg)
        batch = synthetic.lm_batch(0, 0, batch=8, seq=32, vocab=256)
        ref = float(T.train_loss(params, cfg, batch))
        pspec = T.param_specs(cfg, mesh.axis_names)
        ns = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                          is_leaf=lambda x: isinstance(x, P))
        sp = jax.tree.map(lambda x, s: jax.device_put(x, s), params, ns)
        sb = jax.tree.map(lambda x: jax.device_put(x, NamedSharding(mesh, P(("data",), None))), batch)
        with jax.sharding.set_mesh(mesh):
            got = float(jax.jit(lambda p, b: T.train_loss(p, cfg, b))(sp, sb))
        assert abs(ref - got) < 1e-3, (ref, got)
        print("LM_SHARD_OK")
        """
    )
    assert "LM_SHARD_OK" in out


def test_sharded_search_degraded_shard_serves_partial_results():
    """Shard-health degraded mode (DESIGN.md §Failure model): a dead shard's
    contribution is masked before the all-gather, so the merge returns
    partial results over the live shards — no abort, no fabricated ids, and
    every full-search answer not owned by the dead shard survives. The
    injected ``kill_shard`` fault drives the exact same mask."""
    out = _run(
        """
        from repro import faults
        from repro.core import lider, distributed
        from repro.core.utils import l2_normalize
        rng = jax.random.PRNGKey(0)
        kc, kx, kq, kb = jax.random.split(rng, 4)
        centers = jax.random.normal(kc, (32, 64))
        assign = jax.random.randint(kx, (4000,), 0, 32)
        x = l2_normalize(centers[assign] + 0.3*jax.random.normal(kq, (4000, 64)))
        q = l2_normalize(x[:64] + 0.05*jax.random.normal(kb, (64, 64)))
        cfg = lider.LiderConfig(n_clusters=64, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10)
        params = lider.build_lider(jax.random.PRNGKey(2), x, cfg)
        sp = distributed.shard_lider_params(mesh, params, ("data",))
        search = distributed.make_sharded_search(mesh, params, k=10, n_probe=8, r0=8, capacity_factor=3.0)
        full, _ = search(sp, q)
        assert search.shard_stats == {"shards_live": 4, "shards_total": 4}

        health = np.array([True, False, True, True])
        part, _ = search(sp, q, shard_health=health)
        assert search.shard_stats == {"shards_live": 3, "shards_total": 4}
        # Shard 1 owns clusters [16, 32): its gids must never be served...
        dead_gids = set(np.asarray(params.bank.gids)[16:32].ravel().tolist()) - {-1}
        fids, pids = np.asarray(full.ids), np.asarray(part.ids)
        assert not (set(pids.ravel().tolist()) & dead_gids)
        assert set(fids.ravel().tolist()) & dead_gids  # ...and were in the full answer
        # ...while every live-shard answer from the full search survives the merge.
        for f, p in zip(fids, pids):
            assert set(f[f >= 0]) - dead_gids <= set(p[p >= 0])

        # The injected kill drives the same mask -> bit-identical answers.
        plan = faults.FaultPlan([faults.FaultSpec(
            "shard_search", mode="kill_shard", payload={"shard": 1}, times=(0,))])
        with faults.activate(plan):
            killed, _ = search(sp, q)
        assert search.shard_stats == {"shards_live": 3, "shards_total": 4}
        assert np.array_equal(np.asarray(killed.ids), pids)
        print("DEGRADED_OK")
        """
    )
    assert "DEGRADED_OK" in out
