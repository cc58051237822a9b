"""ESK-LSH: extended SortingKeys-LSH for cosine similarity (paper Sec. 4).

A hashkey is ``M`` sign bits of random hyperplane projections (Charikar
random-projection LSH), packed big-endian into a ``uint32`` — the first
hash bit is the most significant bit, so *numeric order of the packed key ==
the SK-LSH lexicographic linear order*. A core model keeps ``H`` independent
sorted arrays (one per compound hash function).

The extended hashkey distance (paper Eq. 6/7)::

    dist_e(K1, K2) = KL(K1, K2) + KD_e(K1, K2) / 2**B

with ``KL`` the non-prefix length and ``KD_e`` the absolute difference of the
``B``-bit windows immediately after the common prefix, fixes the "low
resolution problem" of binary alphabets while preserving the linear order
(paper Lemmas 4.3/4.4 — property-tested in ``tests/test_lsh.py``).

TPU adaptation: hashing a corpus is a single fused ``X @ P`` matmul + sign +
bit-pack; the Pallas kernel ``repro.kernels.lsh_hash`` streams this without
materialising the ``(N, H*M)`` float tensor, and :func:`hash_vectors` runs it
on TPU (its pure-jnp reference elsewhere).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.ops import lsh_hash_op
from .types import pytree_dataclass

# Sentinel key for padded slots (requires M <= 31). Python int, not a jnp
# scalar: module-level device constants would init the backend at import
# time and break the dry-run's XLA_FLAGS device-count override.
UINT32_PAD = 0xFFFFFFFF
MAX_KEY_LEN = 31


@pytree_dataclass(meta_fields=("n_arrays", "key_len"))
class LSHParams:
    """Bank of ``n_arrays`` compound hash functions of ``key_len`` bits each."""

    projections: jnp.ndarray  # (dim, n_arrays * key_len) float32
    n_arrays: int
    key_len: int


def make_lsh(key: jax.Array, dim: int, n_arrays: int, key_len: int) -> LSHParams:
    if not (1 <= key_len <= MAX_KEY_LEN):
        raise ValueError(f"key_len must be in [1, {MAX_KEY_LEN}], got {key_len}")
    proj = jax.random.normal(key, (dim, n_arrays * key_len), dtype=jnp.float32)
    return LSHParams(projections=proj, n_arrays=n_arrays, key_len=key_len)


def suggest_key_len(n_points: int) -> int:
    """Paper setting ``M = ceil(log2 N)``, clamped to the packable range."""
    import math

    return max(4, min(MAX_KEY_LEN, math.ceil(math.log2(max(2, n_points)))))


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """Pack (..., M) {0,1} bits big-endian into uint32 compact keys."""
    m = bits.shape[-1]
    weights = (jnp.uint32(1) << jnp.arange(m - 1, -1, -1, dtype=jnp.uint32)).astype(
        jnp.uint32
    )
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(keys: jnp.ndarray, key_len: int) -> jnp.ndarray:
    """Inverse of :func:`pack_bits`: (...,) uint32 -> (..., M) uint32 bits."""
    shifts = jnp.arange(key_len - 1, -1, -1, dtype=jnp.uint32)
    return (keys[..., None] >> shifts) & jnp.uint32(1)


def hash_vectors(params: LSHParams, x: jnp.ndarray) -> jnp.ndarray:
    """Hash (..., dim) vectors into (..., H) packed uint32 hashkeys.

    One function for build and query, so both sides of every sorted array
    hash alike: the ``lsh_hash`` Pallas kernel on TPU, its reference
    (``kernels.ref.lsh_hash_ref``, the same projection + sign + big-endian
    pack) elsewhere.
    """
    keys = lsh_hash_op(
        x.reshape(-1, x.shape[-1]),
        params.projections,
        n_arrays=params.n_arrays,
        key_len=params.key_len,
    )
    return keys.reshape(*x.shape[:-1], params.n_arrays)


def mask_padded(keys: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Replace keys of padded/dead slots with the UINT32_PAD sentinel.

    The sentinel is the largest uint32, so masked slots sort to the end of
    every array — the invariant the bank build/refit and the rescale fit
    rely on (padding sorts last).
    """
    return jnp.where(valid, keys.astype(jnp.uint32), jnp.uint32(UINT32_PAD))


def _clz32(x: jnp.ndarray) -> jnp.ndarray:
    """Count leading zeros of uint32 (branchless smear + popcount)."""
    x = x.astype(jnp.uint32)
    x = x | (x >> 1)
    x = x | (x >> 2)
    x = x | (x >> 4)
    x = x | (x >> 8)
    x = x | (x >> 16)
    return jnp.uint32(32) - jax.lax.population_count(x)


def common_prefix_len(k1: jnp.ndarray, k2: jnp.ndarray, key_len: int) -> jnp.ndarray:
    """Length of the common bit prefix of two compact keys (0..key_len)."""
    a1 = k1.astype(jnp.uint32) << (32 - key_len)
    a2 = k2.astype(jnp.uint32) << (32 - key_len)
    lead = _clz32(a1 ^ a2)
    return jnp.minimum(lead, jnp.uint32(key_len)).astype(jnp.int32)


def dist_e(
    k1: jnp.ndarray, k2: jnp.ndarray, key_len: int, window_bits: int = 8
) -> jnp.ndarray:
    """Extended hashkey distance (paper Eq. 7). Broadcasting elementwise.

    ``dist_e = KL + KD_e / 2**B`` where ``KD_e`` reads the ``B``-bit window
    right after the common prefix (zero-padded past the key end, matching the
    sub-sequence definition in Eq. 6 with C = 2**B).
    """
    b = int(window_bits)
    m = int(key_len)
    l = common_prefix_len(k1, k2, m)  # (..., ) int32
    kl = (m - l).astype(jnp.float32)
    a1 = k1.astype(jnp.uint32) << (32 - m)
    a2 = k2.astype(jnp.uint32) << (32 - m)
    shift = jnp.minimum(l, 31).astype(jnp.uint32)
    s1 = ((a1 << shift) >> jnp.uint32(32 - b)).astype(jnp.int32)
    s2 = ((a2 << shift) >> jnp.uint32(32 - b)).astype(jnp.int32)
    kd = jnp.where(l >= m, 0, jnp.abs(s1 - s2)).astype(jnp.float32)
    return kl + kd / float(2**b)


def sort_hashkeys(keys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort one array of compact keys by the SK-LSH linear order.

    Returns ``(sorted_keys, order)`` where ``order[i]`` is the original index
    of the i-th sorted key. For packed big-endian binary keys the linear order
    is plain numeric order.
    """
    order = jnp.argsort(keys, axis=-1)
    return jnp.take_along_axis(keys, order, axis=-1), order


def query_position(sorted_keys: jnp.ndarray, qkey: jnp.ndarray) -> jnp.ndarray:
    """Exact insertion position of qkey in a sorted key array (binary search).

    Used by the SK-LSH baseline and by LIDER's optional "last-mile refine"
    (beyond-paper optimisation) — the paper's RMI replaces this lookup with a
    prediction.
    """
    return jnp.searchsorted(sorted_keys, qkey, side="left").astype(jnp.int32)
