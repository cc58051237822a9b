"""Backend-dispatch policy shared by every kernel and the op wrappers."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compile on TPU, interpret elsewhere (the kernels are TPU
    targets; off-TPU they only run for validation)."""
    if interpret is None:
        return not on_tpu()
    return interpret


# Input bytes a grid step of a row-blocked build kernel (``lsh_hash``,
# ``kmeans_assign``) takes. Above both kernels' default blocks at d = 768, so
# their d = 768 programs are unchanged; at d = 4096 it keeps their scoped
# VMEM inside the default limit.
ROW_BLOCK_BYTES = 2 << 20


def row_block(block_n: int, n: int, row_bytes: int) -> int:
    """Rows a grid step: ``block_n``, fewer for a short input (floor 8), and
    no more than fit ``ROW_BLOCK_BYTES`` (a multiple of 8). Each row's
    result is its own, so the block changes no output."""
    fit = max(8, ROW_BLOCK_BYTES // row_bytes // 8 * 8)
    return min(block_n, max(8, n), fit)
