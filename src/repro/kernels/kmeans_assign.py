"""Fused k-means assignment kernel: tiled distances + running argmin.

Stage-1 of the LIDER build runs Lloyd iterations over the full corpus; the
assignment step naively writes an (N, c) distance matrix to HBM (MS-8.8M at
c=1000: 35 GB per iteration). This kernel streams centroid tiles against a
VMEM-resident point tile and keeps only the running (best distance, best id)
pair — HBM traffic drops to reading X and C once plus writing 8 bytes/point.

Grid is (N tiles, c tiles) with the c axis innermost ("arbitrary" semantics:
the output block for row-tile i is revisited across j, accumulating the
running min — the standard Pallas reduction idiom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import resolve_interpret, row_block

_F32_MAX = 3.4e38  # python float: jnp scalars would be captured consts


def _kmeans_assign_kernel(x_ref, c_ref, best_d_ref, best_i_ref, *, block_c: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_d_ref[...] = jnp.full(best_d_ref.shape, _F32_MAX, jnp.float32)
        best_i_ref[...] = jnp.zeros(best_i_ref.shape, jnp.int32)

    x = x_ref[...].astype(jnp.float32)  # (block_n, d)
    c = c_ref[...].astype(jnp.float32)  # (block_c, d)
    x_sq = jnp.sum(x * x, axis=-1, keepdims=True)  # (block_n, 1)
    c_sq = jnp.sum(c * c, axis=-1)  # (block_c,)
    d2 = x_sq - 2.0 * jnp.dot(x, c.T, preferred_element_type=jnp.float32) + c_sq

    local_i = jnp.argmin(d2, axis=-1).astype(jnp.int32)  # (block_n,)
    local_d = jnp.min(d2, axis=-1)
    global_i = local_i + j * block_c

    prev_d = best_d_ref[...][:, 0]
    prev_i = best_i_ref[...][:, 0]
    better = local_d < prev_d
    best_d_ref[...] = jnp.where(better, local_d, prev_d)[:, None]
    best_i_ref[...] = jnp.where(better, global_i, prev_i)[:, None]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_c", "interpret")
)
def kmeans_assign(
    x: jnp.ndarray,
    centroids: jnp.ndarray,
    *,
    block_n: int = 512,
    block_c: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, d), (c, d) -> (assignment (N,) int32, min squared-L2 (N,) f32).

    ``interpret=None`` resolves to "not on TPU" (matching ``kernels/ops.py``)
    so direct calls compile on TPU instead of silently interpreting.
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    c = centroids.shape[0]
    block_n = row_block(block_n, n, d * x.dtype.itemsize)
    block_c = min(block_c, max(8, c))
    pad_n = (-n) % block_n
    pad_c = (-c) % block_c
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
    if pad_c:
        # Padded centroids at +inf distance: fill with a huge coordinate.
        centroids = jnp.pad(
            centroids, ((0, pad_c), (0, 0)), constant_values=1e18
        )
    grid = (x.shape[0] // block_n, centroids.shape[0] // block_c)

    best_d, best_i = pl.pallas_call(
        functools.partial(_kmeans_assign_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((x.shape[0], 1), jnp.int32),
        ],
        name="kmeans_assign",
        interpret=interpret,
    )(x, centroids)
    return best_i[:n, 0], best_d[:n, 0]
