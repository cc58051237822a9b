"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at the paper's width (d=768) and the serving shapes, and at
a 7B LLM embedder's width (d=4096) at the shapes of the benchmark's
``msmarco4096-e5mistral-int4-host`` configuration.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, unaligned DMA slices, ops
Mosaic has no lowering for, more VMEM than a kernel may use. These tests
compile each kernel with ``interpret=False`` for one chip of a ``v5e:2x2``
topology that is described, not attached, and check the executable holds
the kernel as a ``tpu_custom_call``. Nothing runs.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_verify import (
    fused_verify,
    fused_verify_grouped,
    sketch_prefilter,
)
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.lsh_hash import lsh_hash

D = 768  # the paper's width
N = 1 << 20  # rows of the searched table
B, C = 32, 8192  # batch x candidates: c0=20 probes x H=10 arrays x R=40
CLUSTERS, LP = 1024, 2560  # the 2**20 bank's clusters and slot capacity


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def aot(one_chip):
    """``compile(fn, *shapes)`` -> compiled text, with the persistent cache
    off: an entry written for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
        ]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernels(text: str) -> list[str]:
    return re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*/(\w+)/pallas_call"',
        text,
    )


# Batches the per-query kernels compile at: one query (one row a grid
# step), a ragged batch (one full-dim row group), the cells' batch, and the
# bulk batch (eight row groups of 32).
BATCHES = (1, 13, 32, 256)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dtype", ["float32", "int8", "int4"])
def test_fused_verify_compiles(aot, dtype, b):
    quantized = dtype != "float32"
    width = D // 2 if dtype == "int4" else D
    table = (N, width), jnp.float32 if dtype == "float32" else jnp.int8
    shapes = [table, ((b, C), jnp.int32), ((b, D), jnp.float32)]
    if quantized:
        shapes.append(((N,), jnp.float32))

        def fn(embs, rows, q, scales):
            return fused_verify(
                embs, rows, q, k=40, scales=scales, code_dtype=dtype,
                interpret=False,
            )
    else:

        def fn(embs, rows, q):
            return fused_verify(embs, rows, q, k=10, interpret=False)

    want = f"fused_verify_{dtype}" if quantized else "fused_verify_float"
    assert _kernels(aot(fn, *shapes)) == [want]


@pytest.mark.parametrize("b", BATCHES)
def test_sketch_prefilter_compiles(aot, b):
    text = aot(
        lambda sk, rows, q: sketch_prefilter(sk, rows, q, k=160, interpret=False),
        ((N, D // 32), jnp.uint32),
        ((b, C), jnp.int32),
        ((b, D), jnp.float32),
    )
    assert _kernels(text) == ["sketch_prefilter"]


@pytest.mark.parametrize("code_dtype", ["int8", "int4"])
def test_fused_verify_grouped_compiles(aot, code_dtype):
    steps, block_q = 1024, 8
    width = D // 2 if code_dtype == "int4" else D
    text = aot(
        lambda e, s, q, cids, qids, slots: fused_verify_grouped(
            e, s, q, cids, qids, slots, kp=40, block_q=block_q,
            code_dtype=code_dtype, interpret=False,
        ),
        ((CLUSTERS, LP, width), jnp.int8),
        ((CLUSTERS, LP), jnp.float32),
        ((B, D), jnp.float32),
        ((steps,), jnp.int32),
        ((steps, block_q), jnp.int32),
        ((steps, block_q, LP), jnp.int32),
    )
    assert _kernels(text) == [f"fused_verify_grouped_{code_dtype}"]


def test_lsh_hash_compiles(aot):
    text = aot(
        lambda x, p: lsh_hash(x, p, n_arrays=10, key_len=16, interpret=False),
        ((N, D), jnp.float32),
        ((D, 160), jnp.float32),
    )
    assert _kernels(text) == ["lsh_hash"]


def test_kmeans_assign_compiles(aot):
    text = aot(
        lambda x, c: kmeans_assign(x, c, interpret=False),
        ((N, D), jnp.float32),
        ((CLUSTERS, D), jnp.float32),
    )
    assert _kernels(text) == ["kmeans_assign"]


# d=4096 (E5-Mistral-7B): the int4 + host-tier index at the benchmark
# configuration's shapes. The tables are the served ones (codes, sketches,
# the centroids the route scores, the fetched rescore rows), so each fits in
# HBM; a 2**20 x 4096 f32 table would not.
D_WIDE = 4096
ROWS_WIDE = CLUSTERS * 1280  # clusters x slot capacity of the int4 bank
ROUTE_C, CODE_C = 800, 160  # H*r0*c0 centroid candidates; sketch survivors


def _wide_calls(b):
    """(name, fn, shapes) of each per-query kernel call of one d=4096 batch."""
    q = ((b, D_WIDE), jnp.float32)

    def route(embs, rows, q):
        return fused_verify(embs, rows, q, k=20, interpret=False)

    def sketch(sk, rows, q):
        return sketch_prefilter(sk, rows, q, k=160, interpret=False)

    def code_pass(embs, rows, q, scales):
        return fused_verify(embs, rows, q, k=40, scales=scales,
                            code_dtype="int4", interpret=False)

    def rescore(embs, rows, q):
        return fused_verify(embs, rows, q, k=10, interpret=False)

    return {
        "route": ("fused_verify_float", route, [
            ((CLUSTERS, D_WIDE), jnp.float32), ((b, ROUTE_C), jnp.int32), q]),
        "sketch": ("sketch_prefilter", sketch, [
            ((ROWS_WIDE, D_WIDE // 32), jnp.uint32), ((b, 8000), jnp.int32),
            q]),
        "code_pass": ("fused_verify_int4", code_pass, [
            ((ROWS_WIDE, D_WIDE // 2), jnp.int8), ((b, CODE_C), jnp.int32), q,
            ((ROWS_WIDE,), jnp.float32)]),
        "rescore": ("fused_verify_float", rescore, [
            ((b * 40, D_WIDE), jnp.float32), ((b, 40), jnp.int32), q]),
    }


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("call", ["route", "sketch", "code_pass", "rescore"])
def test_wide_rows_compile(aot, call, b):
    kernel, fn, shapes = _wide_calls(b)[call]
    assert _kernels(aot(fn, *shapes)) == [kernel]


@pytest.mark.parametrize("kernel", ["lsh_hash", "kmeans_assign"])
def test_wide_build_kernels_compile(aot, kernel):
    x = ((1 << 18, D_WIDE), jnp.float32)
    if kernel == "lsh_hash":
        text = aot(
            lambda x, p: lsh_hash(x, p, n_arrays=10, key_len=16,
                                  interpret=False),
            x, ((D_WIDE, 160), jnp.float32),
        )
    else:
        text = aot(lambda x, c: kmeans_assign(x, c, interpret=False),
                   x, ((CLUSTERS, D_WIDE), jnp.float32))
    assert _kernels(text) == [kernel]
