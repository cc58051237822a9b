"""Fused LSH hashing kernel: matmul + sign + bit-pack in one VMEM pass.

Hashing the corpus is LIDER's build-time hot spot and the first step of every
query: ``bits = sign(X @ P)`` packed big-endian into uint32. Done naively XLA
materialises the (N, H*M) float projection tensor in HBM (for MS-8.8M at
H=10, M=24: 8.4 GB written + re-read). This kernel tiles N into VMEM-resident
blocks, keeps the projection bank resident (d*H*M*4 B — ~1 MB at paper
scales), and writes only the (N, H) uint32 keys back: a ~(32*M)x reduction in
HBM write traffic for the pack stage.

TPU notes: the matmul tile (block_n x d)@(d x HM) feeds the MXU; pick
``block_n`` a multiple of 8 (f32 sublane) and pad HM to a lane multiple for
peak efficiency — correctness does not depend on it (compiler pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import resolve_interpret, row_block


def _lsh_hash_kernel(x_ref, proj_ref, w_hi_ref, w_lo_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)  # (block_n, d)
    acc = jnp.dot(
        x,
        proj_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (block_n, H*M)
    bits = jnp.where(acc >= 0.0, 1.0, 0.0).astype(jnp.bfloat16)
    # Bit-pack as two exact MXU passes: each key's big-endian bit weights
    # are split at 2**16, so every weight is a power of two that bf16 holds
    # exactly and each partial sum (< 2**16) is exact in the f32
    # accumulator. (Packing by reshape + sum would split the lane dim and
    # reduce over unsigned ints, neither of which lowers on the TPU.)
    hi = jnp.dot(bits, w_hi_ref[...], preferred_element_type=jnp.float32)
    lo = jnp.dot(bits, w_lo_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)


def _pack_weights(n_arrays: int, key_len: int) -> tuple[np.ndarray, np.ndarray]:
    """(H*M, H) bf16 matrices: column h holds key h's big-endian bit
    weights 2**(M-1-j), split into the bits at or above 2**16 (stored
    divided by 2**16) and those below."""
    exps = np.arange(key_len - 1, -1, -1)
    w_hi = np.zeros((n_arrays * key_len, n_arrays), np.float32)
    w_lo = np.zeros_like(w_hi)
    for h in range(n_arrays):
        rows = slice(h * key_len, (h + 1) * key_len)
        w_hi[rows, h] = np.where(exps >= 16, 2.0 ** (exps - 16), 0.0)
        w_lo[rows, h] = np.where(exps < 16, 2.0 ** exps, 0.0)
    return w_hi, w_lo


@functools.partial(
    jax.jit, static_argnames=("n_arrays", "key_len", "block_n", "interpret")
)
def lsh_hash(
    x: jnp.ndarray,
    proj: jnp.ndarray,
    *,
    n_arrays: int,
    key_len: int,
    block_n: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(N, d) float x (d, H*M) float -> (N, H) uint32 packed hashkeys.

    ``interpret=None`` resolves to "not on TPU" (matching ``kernels/ops.py``)
    so direct calls compile on TPU instead of silently interpreting.
    """
    interpret = resolve_interpret(interpret)
    n, d = x.shape
    hm = proj.shape[1]
    assert hm == n_arrays * key_len
    block_n = row_block(block_n, n, d * x.dtype.itemsize)
    # A ragged last block is masked by Pallas, so x is never padded (a
    # padded copy would double the build's largest buffer).
    grid = (pl.cdiv(n, block_n),)

    w_hi, w_lo = (
        jnp.asarray(w, jnp.bfloat16) for w in _pack_weights(n_arrays, key_len)
    )
    out = pl.pallas_call(
        _lsh_hash_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((d, hm), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((hm, n_arrays), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((hm, n_arrays), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_n, n_arrays), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n, n_arrays), jnp.int32),
        name="lsh_hash",
        interpret=interpret,
    )(x, proj, w_hi, w_lo)
    # key_len <= 31, so every key is a non-negative int32.
    return out.astype(jnp.uint32)
