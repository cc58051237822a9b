"""Pallas TPU kernels for LIDER's compute hot spots.

- ``lsh_hash``      — fused projection + sign + bit-pack (build & query hash)
- ``kmeans_assign`` — tiled distance + running argmin (Stage-1 Lloyd)
- ``fused_verify``  — score-reduce candidate verification over XLA-gathered
  candidate tiles: scores stay in VMEM and a streaming dedup top-k is the
  only HBM output (DESIGN.md §Verification-kernel); ``sketch_prefilter``
  and ``fused_verify_grouped`` share its merge

``ops`` holds the jit'd dispatchers (TPU -> kernel, CPU -> ``ref`` oracle);
``ref`` holds the pure-jnp oracles the tests sweep against.
"""
from .lsh_hash import lsh_hash
from .kmeans_assign import kmeans_assign
from .fused_verify import fused_verify, fused_verify_grouped
from . import ops, ref, schedule

__all__ = [
    "lsh_hash",
    "kmeans_assign",
    "fused_verify",
    "fused_verify_grouped",
    "ops",
    "ref",
    "schedule",
]
