"""RecSys model zoo: SASRec, two-tower retrieval, DIN, xDeepFM.

The shared substrate is the **sharded embedding layer**: JAX has no
EmbeddingBag, so lookups are ``jnp.take`` + ``jax.ops.segment_sum`` and the
huge tables are row(vocab)-sharded over the ``model`` mesh axis. Under a mesh
the lookup runs as an explicit shard_map (local masked take + psum) — the
classic model-parallel embedding — so the table is never all-gathered; on a
single device it degrades to a plain take.

The two-tower ``retrieval_cand`` path is the paper's own workload (score one
query against ~1e6 candidates): it is served either brute-force (one matmul)
or through a LIDER index over the item-tower embeddings (``--index lider``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers
from .sharding import ALL, DP, TP, maybe_shard

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Sharded embedding substrate
# ---------------------------------------------------------------------------


def embedding_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Row-sharded embedding lookup.

    Under an ambient mesh with a ``model`` axis: shard_map over the vocab
    rows — each shard takes its local rows (masked) and the partials are
    psum'd. Otherwise a plain take. Differentiable (scatter-add transpose).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return table[ids]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if ids.shape[0] % max(dp_size, 1):
        dp = ()  # batch-1 / ragged leading dim: replicate the ids instead

    def local_lookup(tab, idx):
        shard = jax.lax.axis_index("model")
        rows = tab.shape[0]  # local rows
        local = idx - rows * shard
        inside = (local >= 0) & (local < rows)
        got = tab[jnp.clip(local, 0, rows - 1)]
        got = jnp.where(inside[..., None], got, 0.0)
        return jax.lax.psum(got, "model")

    id_spec = P(dp if dp else None, *([None] * (ids.ndim - 1)))
    out_spec = P(dp if dp else None, *([None] * ids.ndim))
    return jax.shard_map(
        local_lookup,
        mesh=mesh,
        in_specs=(P("model", None), id_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids)


def embedding_bag(
    table: jnp.ndarray, ids: jnp.ndarray, segment_ids: jnp.ndarray, n_bags: int
) -> jnp.ndarray:
    """EmbeddingBag(sum): multi-hot ids reduced per bag (JAX-native)."""
    rows = embedding_lookup(table, ids)
    return jax.ops.segment_sum(rows, segment_ids, num_segments=n_bags)


def _dense(key, shape, dtype=jnp.float32, scale=None):
    scale = scale or (1.0 / (shape[0] ** 0.5))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _mlp_init(key, dims, dtype=jnp.float32):
    ks = jax.random.split(key, len(dims) - 1)
    return {
        f"w{i}": _dense(ks[i], (dims[i], dims[i + 1]), dtype)
        for i in range(len(dims) - 1)
    } | {
        f"b{i}": jnp.zeros((dims[i + 1],), dtype) for i in range(len(dims) - 1)
    }


def _mlp_apply(p, x, n, act=jax.nn.relu, final_act=False):
    for i in range(n):
        x = x @ p[f"w{i}"] + p[f"b{i}"]
        if i < n - 1 or final_act:
            x = act(x)
    return x


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str  # sasrec | two_tower | din | xdeepfm
    embed_dim: int
    item_vocab: int = 1_048_576
    seq_len: int = 50
    # two-tower
    n_user_fields: int = 4
    n_item_fields: int = 2
    field_vocab: int = 131_072
    tower_dims: tuple[int, ...] = (1024, 512, 256)
    # din
    attn_dims: tuple[int, ...] = (80, 40)
    mlp_dims: tuple[int, ...] = (200, 80)
    # xdeepfm
    n_sparse: int = 39
    cin_dims: tuple[int, ...] = (200, 200, 200)
    dnn_dims: tuple[int, ...] = (400, 400)
    # sasrec
    n_blocks: int = 2
    n_heads: int = 1
    dtype: Any = jnp.float32


# ---------------------------------------------------------------------------
# SASRec (Kang & McAuley 2018)
# ---------------------------------------------------------------------------


def sasrec_init(rng: jax.Array, cfg: RecsysConfig) -> Params:
    d = cfg.embed_dim
    ks = jax.random.split(rng, 3 + cfg.n_blocks)
    blocks = []
    for i in range(cfg.n_blocks):
        kb = jax.random.split(ks[3 + i], 6)
        blocks.append(
            {
                "wq": _dense(kb[0], (d, d)),
                "wk": _dense(kb[1], (d, d)),
                "wv": _dense(kb[2], (d, d)),
                "wo": _dense(kb[3], (d, d)),
                "w1": _dense(kb[4], (d, d)),
                "w2": _dense(kb[5], (d, d)),
                "ln1": jnp.ones((d,)),
                "ln2": jnp.ones((d,)),
            }
        )
    return {
        "item_emb": _dense(ks[0], (cfg.item_vocab, d), scale=0.02),
        "pos_emb": _dense(ks[1], (cfg.seq_len, d), scale=0.02),
        "ln_f": jnp.ones((d,)),
        "blocks": blocks,
    }


def sasrec_forward(params: Params, cfg: RecsysConfig, seq: jnp.ndarray) -> jnp.ndarray:
    """seq (B, S) item ids (0 = padding) -> hidden states (B, S, d)."""
    b, s = seq.shape
    d = cfg.embed_dim
    h = embedding_lookup(params["item_emb"], seq) + params["pos_emb"][None, :s]
    h = maybe_shard(h, DP, None, None)
    nh = cfg.n_heads
    for blk in params["blocks"]:
        x = layers.rms_norm(h, blk["ln1"])
        q = (x @ blk["wq"]).reshape(b, s, nh, d // nh)
        k = (x @ blk["wk"]).reshape(b, s, nh, d // nh)
        v = (x @ blk["wv"]).reshape(b, s, nh, d // nh)
        o = layers.flash_attention(q, k, v, causal=True, q_chunk=s, kv_chunk=s)
        h = h + o.reshape(b, s, d) @ blk["wo"]
        x = layers.rms_norm(h, blk["ln2"])
        h = h + jax.nn.relu(x @ blk["w1"]) @ blk["w2"]
    return layers.rms_norm(h, params["ln_f"])


def sasrec_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """BCE with one positive (next item) and one sampled negative per step."""
    h = sasrec_forward(params, cfg, batch["seq"])  # (B, S, d)
    pos = embedding_lookup(params["item_emb"], batch["pos"])  # (B, S, d)
    neg = embedding_lookup(params["item_emb"], batch["neg"])
    pos_s = jnp.sum(h * pos, -1)
    neg_s = jnp.sum(h * neg, -1)
    mask = (batch["pos"] > 0).astype(jnp.float32)
    loss = -jax.nn.log_sigmoid(pos_s) - jax.nn.log_sigmoid(-neg_s)
    return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Two-tower retrieval (Yi et al., RecSys'19)
# ---------------------------------------------------------------------------


def two_tower_init(rng: jax.Array, cfg: RecsysConfig) -> Params:
    d = cfg.embed_dim
    ks = jax.random.split(rng, 4)
    user_in = cfg.n_user_fields * d
    item_in = cfg.n_item_fields * d
    return {
        "user_emb": _dense(ks[0], (cfg.field_vocab * cfg.n_user_fields, d), scale=0.02),
        "item_emb": _dense(ks[1], (cfg.item_vocab, d), scale=0.02),
        "user_tower": _mlp_init(ks[2], (user_in,) + cfg.tower_dims),
        "item_tower": _mlp_init(ks[3], (item_in,) + cfg.tower_dims),
    }


def user_embed(params: Params, cfg: RecsysConfig, user_fields: jnp.ndarray):
    """user_fields (B, n_user_fields) int32 -> (B, d_out) normalised."""
    b, f = user_fields.shape
    offset = jnp.arange(f, dtype=user_fields.dtype) * cfg.field_vocab
    rows = embedding_lookup(params["user_emb"], user_fields + offset)  # (B,F,d)
    x = rows.reshape(b, -1)
    x = _mlp_apply(params["user_tower"], x, len(cfg.tower_dims))
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def item_embed(params: Params, cfg: RecsysConfig, item_fields: jnp.ndarray):
    """item_fields (B, n_item_fields): column 0 = item id, rest categorical."""
    b, f = item_fields.shape
    rows0 = embedding_lookup(params["item_emb"], item_fields[:, 0])
    rest = embedding_lookup(
        params["user_emb"],
        item_fields[:, 1:] + jnp.arange(1, f, dtype=item_fields.dtype) * cfg.field_vocab,
    ).reshape(b, -1)
    x = jnp.concatenate([rows0, rest], axis=-1)
    x = _mlp_apply(params["item_tower"], x, len(cfg.tower_dims))
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def two_tower_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """In-batch sampled softmax with logQ correction."""
    u = user_embed(params, cfg, batch["user_fields"])  # (B, dout)
    i = item_embed(params, cfg, batch["item_fields"])  # (B, dout)
    logits = (u @ i.T) / 0.05  # temperature
    logq = batch.get("sampling_logq")
    if logq is not None:
        logits = logits - logq[None, :]
    labels = jnp.arange(u.shape[0])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def two_tower_score_candidates(
    params: Params, cfg: RecsysConfig, user_fields: jnp.ndarray, cand_embs: jnp.ndarray, k: int
):
    """retrieval_cand: (B, F) users x (N_cand, dout) precomputed item
    embeddings -> top-k. This is the LIDER-served workload; the brute-force
    path here is the Flat baseline."""
    u = user_embed(params, cfg, user_fields)
    scores = u @ cand_embs.T  # (B, N_cand)
    return jax.lax.top_k(scores, k)


# ---------------------------------------------------------------------------
# DIN (Zhou et al. 2018)
# ---------------------------------------------------------------------------


def din_init(rng: jax.Array, cfg: RecsysConfig) -> Params:
    d = cfg.embed_dim
    ks = jax.random.split(rng, 4)
    return {
        "item_emb": _dense(ks[0], (cfg.item_vocab, d), scale=0.02),
        "attn": _mlp_init(ks[1], (4 * d,) + cfg.attn_dims + (1,)),
        "mlp": _mlp_init(ks[2], (3 * d,) + cfg.mlp_dims + (1,)),
    }


def din_forward(params: Params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """history (B, S), target (B,) -> CTR logits (B,)."""
    hist = embedding_lookup(params["item_emb"], batch["history"])  # (B, S, d)
    tgt = embedding_lookup(params["item_emb"], batch["target"])  # (B, d)
    t = jnp.broadcast_to(tgt[:, None, :], hist.shape)
    a_in = jnp.concatenate([hist, t, hist - t, hist * t], axis=-1)
    w = _mlp_apply(params["attn"], a_in, len(cfg.attn_dims) + 1)[..., 0]  # (B, S)
    mask = (batch["history"] > 0).astype(w.dtype)
    w = w * mask  # DIN: no softmax, preserve intensity
    pooled = jnp.einsum("bs,bsd->bd", w, hist) / jnp.maximum(
        jnp.sum(mask, -1, keepdims=True), 1.0
    )
    x = jnp.concatenate([pooled, tgt, pooled * tgt], axis=-1)
    return _mlp_apply(params["mlp"], x, len(cfg.mlp_dims) + 1)[..., 0]


def din_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    logits = din_forward(params, cfg, batch)
    y = batch["label"].astype(jnp.float32)
    return -jnp.mean(
        y * jax.nn.log_sigmoid(logits) + (1 - y) * jax.nn.log_sigmoid(-logits)
    )


# ---------------------------------------------------------------------------
# xDeepFM (Lian et al. 2018)
# ---------------------------------------------------------------------------


def xdeepfm_init(rng: jax.Array, cfg: RecsysConfig) -> Params:
    d, m = cfg.embed_dim, cfg.n_sparse
    ks = jax.random.split(rng, 6)
    cin = []
    h_prev = m
    for i, h in enumerate(cfg.cin_dims):
        cin.append(_dense(jax.random.fold_in(ks[2], i), (h_prev * m, h)))
        h_prev = h
    return {
        "emb": _dense(ks[0], (cfg.field_vocab * m, d), scale=0.02),
        "linear": _dense(ks[1], (cfg.field_vocab * m, 1), scale=0.01),
        "cin": cin,
        "cin_out": _dense(ks[3], (sum(cfg.cin_dims), 1)),
        "dnn": _mlp_init(ks[4], (m * d,) + cfg.dnn_dims + (1,)),
    }


def xdeepfm_forward(params: Params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    """fields (B, n_sparse) int32 per-field ids -> CTR logits (B,)."""
    fields = batch["fields"]
    b, m = fields.shape
    offset = jnp.arange(m, dtype=fields.dtype) * cfg.field_vocab
    flat_ids = fields + offset
    x0 = embedding_lookup(params["emb"], flat_ids)  # (B, m, d)
    # Re-shard the batch over every axis after the (model-sharded) lookup:
    # the CIN outer-product tensor (B, H_k*m, d) is the footprint driver for
    # huge offline/retrieval batches.
    x0 = maybe_shard(x0, ALL, None, None)
    linear = jnp.sum(embedding_lookup(params["linear"], flat_ids), axis=(1, 2))

    # CIN: x^{k+1}_h = sum_{i,j} W^k_{h,ij} (x^k_i * x^0_j)
    xk = x0
    pools = []
    for w in params["cin"]:
        z = jnp.einsum("bhd,bmd->bhmd", xk, x0)  # (B, Hk, m, d)
        z = z.reshape(b, -1, cfg.embed_dim)  # (B, Hk*m, d)
        xk = jnp.einsum("bzd,zh->bhd", z, w)  # (B, Hk+1, d)
        pools.append(jnp.sum(xk, axis=-1))  # (B, Hk+1)
    cin_logit = (jnp.concatenate(pools, axis=-1) @ params["cin_out"])[:, 0]

    dnn_logit = _mlp_apply(params["dnn"], x0.reshape(b, -1), len(cfg.dnn_dims) + 1)[
        :, 0
    ]
    return linear + cin_logit + dnn_logit


def xdeepfm_loss(params: Params, cfg: RecsysConfig, batch: dict) -> jnp.ndarray:
    logits = xdeepfm_forward(params, cfg, batch)
    y = batch["label"].astype(jnp.float32)
    return -jnp.mean(
        y * jax.nn.log_sigmoid(logits) + (1 - y) * jax.nn.log_sigmoid(-logits)
    )


# ---------------------------------------------------------------------------
# Shared entry points
# ---------------------------------------------------------------------------

INIT = {
    "sasrec": sasrec_init,
    "two_tower": two_tower_init,
    "din": din_init,
    "xdeepfm": xdeepfm_init,
}

LOSS = {
    "sasrec": sasrec_loss,
    "two_tower": two_tower_loss,
    "din": din_loss,
    "xdeepfm": xdeepfm_loss,
}


def param_specs(params: Params) -> Params:
    """Vocab-sharded tables over 'model'; everything else replicated."""
    def spec_for(path, leaf):
        names = [getattr(p, "name", getattr(p, "key", None)) for p in path]
        if any(n in ("item_emb", "user_emb", "emb", "linear") for n in names):
            return P("model", *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_for, params)
