"""End-to-end system behaviour: the paper's full pipeline on CPU-sized data
(encode -> build LIDER -> serve), plus structural checks that every assigned
(arch x shape) cell constructs a lowerable step bundle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ASSIGNED, get_arch
from repro.core import lider
from repro.core.baselines import flat_search
from repro.core.utils import l2_normalize, recall_at_k
from repro.data import synthetic
from repro.models import recsys as recsys_lib
from repro.training import optimizer as opt_lib


def test_end_to_end_retrieval_pipeline(corpus):
    """Build LIDER over the corpus and verify the serving path beats the
    required quality bar at paper-style settings."""
    x, q, gt = corpus
    cfg = lider.LiderConfig(
        n_clusters=64, n_probe=12, n_arrays=6, n_leaves=4, kmeans_iters=10
    )
    params = lider.build_lider(jax.random.PRNGKey(0), x, cfg)
    out = lider.search_lider(params, q, k=10, n_probe=12, r0=8)
    assert float(recall_at_k(out.ids, gt)) > 0.9


def test_trained_encoder_plus_lider_end_to_end():
    """The paper's deployment: a two-tower encoder produces embeddings, LIDER
    indexes them, retrieval returns the trained-relevant items."""
    cfg = recsys_lib.RecsysConfig(
        name="tt", kind="two_tower", embed_dim=16, item_vocab=512,
        field_vocab=64, tower_dims=(64, 32), n_user_fields=4, n_item_fields=2,
    )
    params = recsys_lib.two_tower_init(jax.random.PRNGKey(0), cfg)
    ocfg = opt_lib.OptimizerConfig(peak_lr=3e-3, warmup_steps=5, decay_steps=60)
    state = opt_lib.init_state(params)

    @jax.jit
    def step(p, s, b):
        loss, g = jax.value_and_grad(recsys_lib.two_tower_loss)(p, cfg, b)
        p, s, m = opt_lib.apply_updates(p, g, s, ocfg)
        return p, s, loss

    losses = []
    for i in range(60):
        batch = synthetic.recsys_batch(0, i, kind="two_tower", batch=64, cfg=cfg)
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3  # encoder actually trained

    # Index all items through the item tower.
    all_items = jnp.stack(
        [jnp.arange(512, dtype=jnp.int32), jnp.zeros((512,), jnp.int32)], axis=1
    )
    item_embs = recsys_lib.item_embed(params, cfg, all_items)
    item_embs = l2_normalize(item_embs)
    idx_cfg = lider.LiderConfig(n_clusters=16, n_probe=6, n_arrays=4, n_leaves=2, kmeans_iters=8)
    index = lider.build_lider(jax.random.PRNGKey(1), item_embs, idx_cfg)
    users = synthetic.recsys_batch(0, 999, kind="two_tower", batch=16, cfg=cfg)["user_fields"]
    u = l2_normalize(recsys_lib.user_embed(params, cfg, users))
    got = lider.search_lider(index, u, k=10, n_probe=6, r0=8)
    gt = flat_search(item_embs, u, k=10)
    assert float(recall_at_k(got.ids, gt.ids)) > 0.85


@pytest.mark.parametrize("arch_id", ASSIGNED)
def test_every_cell_constructs_a_bundle(arch_id):
    """All 40 (arch x shape) cells produce a StepBundle whose abstract args,
    shardings and flops are well-formed (full lower/compile happens in the
    dry-run; this guards the construction path in unit tests)."""
    import numpy as np

    from repro.launch.steps import make_bundle

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()).reshape(1, 1), ("data", "model")
    )
    arch = get_arch(arch_id)
    for shape in arch.shapes:
        with jax.sharding.set_mesh(mesh):
            b = make_bundle(arch, shape, mesh)
        assert b.model_flops > 0
        flat_args = jax.tree.leaves(b.args)
        flat_sh = jax.tree.leaves(
            b.in_shardings, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding)
        )
        assert len(flat_args) == len(flat_sh)
        assert all(isinstance(s, jax.sharding.NamedSharding) for s in flat_sh)


def test_lider_msmarco_bundle_dims():
    from repro.launch.steps import lider_param_structs

    arch = get_arch("lider-msmarco")
    s = lider_param_structs(arch.config)
    assert s.bank.embs.shape == (1024, 12288, 768)
    assert s.bank.sorted_keys.shape == (1024, 10, 12288)
    # corpus fits the padded grid
    assert arch.config.corpus_size <= 1024 * 12288
